"""Readback: `fetch_copy_s` in the clustering cell, where the per-event
leaves (the series and the lane's record of its events) are most of the
packed buffer, as in the load cell. The same reader under a name of its
own: the accepted tests pin that metric's list to its cell (PERF.md section
7)."""

from benchmark.layer_metrics.fetch_copy_s import read  # noqa: F401
