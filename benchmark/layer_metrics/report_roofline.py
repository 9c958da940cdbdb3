"""Post-pass: the least time HBM could move the report program's
algorithmic bytes (`lib/roofline_report.py`), over that program's device
time in the traced wave (the driver's `report_device_s`: the profiler's
module line, by the program's name). Bound: HBM bandwidth (sorts, integer
sums and float32 adds; no matrix product)."""

from benchmark.lib import device, roofline, roofline_report


def read(run):
    traced = run.get("traced")
    if not traced or run.get("rehearsal") or not traced.get("report_device_s"):
        return None
    shape = run["shape"]
    moved = roofline_report.report_bytes(
        shape["nodes"], shape["lanes"], run["real_events"])
    peak = device.peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return roofline.roofline_share_pct(
        moved, traced["report_device_s"], peak)
