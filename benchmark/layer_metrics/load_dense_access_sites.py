"""Step body: `dense_access_sites` in the load cell, where the POD axis of a
whole tuned trace (10,9xx rows) is over `lane_write`'s line for a short
leaf: which form the three pod-axis writes an event took shows here at
once. The same reader under a name of its own: the accepted tests pin that
metric's list to its cell (PERF.md section 7)."""

from benchmark.layer_metrics.dense_access_sites import read  # noqa: F401
