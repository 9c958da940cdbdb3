"""Readback: `fetch_copy_s` in the clock cell (the control's 331 MB: pod and
event axes are 512 both). The same reader under a name of its own: the
accepted tests pin that metric's list to its cell (PERF.md section 7)."""

from benchmark.layer_metrics.fetch_copy_s import read  # noqa: F401
