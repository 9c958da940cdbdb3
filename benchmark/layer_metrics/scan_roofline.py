"""Step body: the least time HBM could move the scan's algorithmic bytes,
over the scan module's device time in the traced wave. Bound: HBM
bandwidth (the step does integer compares and adds, no matrix product).
The bytes are those of the window's own stream: where the sweep records
count deletions (`delete_share`), a deletion reads no node row
(`lib/roofline.stream_bytes_per_lane_event`, PR 48); where they count
none, or the program's record has no such counter, every event is a
creation's."""

from benchmark.layer_metrics import delete_share
from benchmark.lib import device, roofline


def read(run):
    traced = run.get("traced")
    if not traced or run.get("rehearsal") or not traced["scan_device_s"]:
        return None
    shape = run["shape"]
    moved = (roofline.stream_bytes_per_lane_event(
        shape["nodes"], shape["pod_types"], shape["policies"],
        delete_share.read(run) or 0.0)
        * shape["lanes"] * shape["events"])
    peak = device.peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return roofline.roofline_share_pct(moved, traced["scan_device_s"], peak)
