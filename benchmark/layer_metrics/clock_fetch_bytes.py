"""Readback: `fetch_bytes` in the clock cell (the pod axis 274 on its 512
bucket, the event axis 512). The same reader under a name of its own: the
accepted tests pin that metric's list to its cell (PERF.md section 7)."""

from benchmark.layer_metrics.fetch_bytes import read  # noqa: F401
