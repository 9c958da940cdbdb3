"""Step body: `load_step_us_per_lane_event` in the clustering cell, whose
program keeps the commit's add into `NodeState.aff_cnt` inside the event
loop (its kernel reads the counts every event): microseconds of the vmapped
scan a REAL lane-event, median over the window's waves. Beside the load
cell's it prices the branch PR 42 forked. The same reader under a name of
its own: the accepted tests pin that metric's list to its cell (PERF.md
section 7)."""

from benchmark.layer_metrics.load_step_us_per_lane_event import read  # noqa: F401
