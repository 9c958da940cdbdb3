"""Step body: the least time HBM could move the scan's algorithmic bytes,
over the scan module's device time in the traced wave. Bound: HBM
bandwidth (the step does integer compares and adds, no matrix product)."""

from benchmark.lib import device, roofline


def read(run):
    traced = run.get("traced")
    if not traced or run.get("rehearsal") or not traced["scan_device_s"]:
        return None
    shape = run["shape"]
    moved = (roofline.scan_bytes_per_lane_event(
        shape["nodes"], shape["pod_types"], shape["policies"])
        * shape["lanes"] * shape["events"])
    peak = device.peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return roofline.roofline_share_pct(moved, traced["scan_device_s"], peak)
