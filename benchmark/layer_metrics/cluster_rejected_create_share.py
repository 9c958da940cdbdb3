"""Step body: `rejected_create_share` in the clustering cell: the share of
the window's real lane-events that are creates the lanes REJECTED. A
baseline fills the cluster sooner than FGD does (it strands more GPU), so
it rejects more of the same traces. The same reader under a name of its
own: the accepted tests pin that metric's list to its cell (PERF.md section
7)."""

from benchmark.layer_metrics.rejected_create_share import read  # noqa: F401
