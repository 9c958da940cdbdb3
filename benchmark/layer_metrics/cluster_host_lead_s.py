"""Host prep: `host_lead_s` in the clustering cell, where the `specs` span
pads and stacks ten whole traces, as in the load cell. The same reader
under a name of its own: the accepted tests pin that metric's list to its
cell (PERF.md section 7)."""

from benchmark.layer_metrics.host_lead_s import read  # noqa: F401
