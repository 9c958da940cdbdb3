"""Lane slicing: the `slice_lanes` span (the fetched result cut into one
SweepLane a lane, host only), median over the window's waves."""

from benchmark.lib import sweep_log


def read(run):
    return sweep_log.median_span_seconds(run, "slice_lanes")
