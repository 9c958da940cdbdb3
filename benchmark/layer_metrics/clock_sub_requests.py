"""Step body: `sub_requests` in the clock cell: the distinct (gpu_milli,
gpu_num) requests of the window's 52 pod types on their bucket, 8 as in the
control. The same reader under a name of its own: the accepted tests pin
that metric's list to its cell (PERF.md section 7)."""

from benchmark.layer_metrics.sub_requests import read  # noqa: F401
