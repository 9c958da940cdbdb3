"""Lane slicing and readback: `host_tail_s` in the clock cell: the part of
its wave that moves from run to run (PERF.md section 6), and a lane here
carries its record of every event (`event_node`, `event_dev`). The same
reader under a name of its own: the accepted tests pin that metric's list
to its cell (PERF.md section 7)."""

from benchmark.layer_metrics.host_tail_s import read  # noqa: F401
