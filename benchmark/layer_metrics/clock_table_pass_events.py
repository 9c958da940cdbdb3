"""Step body: `table_pass_events` in the clock cell: the flat step's group
with releases among its events (a node given back dirties its column as a
bind does), 16. The same reader under a name of its own: the accepted tests
pin that metric's list to its cell (PERF.md section 7)."""

from benchmark.layer_metrics.table_pass_events import read  # noqa: F401
