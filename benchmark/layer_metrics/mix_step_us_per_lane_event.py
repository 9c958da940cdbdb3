"""Step body: microseconds of the vmapped scan a lane-event where the
program scores under TWO policies, one of them normalized in the scan (a
second raw-score table, the feasible extrema, the scale and the weighted
total every event, a weight row a lane), from the sweep record alone: the
`scan` span's block time over the record's lanes x events, median over the
window's waves. The same quotient as `flat_step_us_per_lane_event` and
`trace_step_us_per_lane_event`, which read one-policy programs; kept apart
because the program is another one."""

from benchmark.layer_metrics.flat_step_us_per_lane_event import read  # noqa: F401
