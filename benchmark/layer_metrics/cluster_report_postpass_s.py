"""Post-pass: `report_postpass_s` in the clustering cell: the
`event_metrics` span, the policy-free report program that rebuilds every
lane's per-event series (the load cell's own compiled program). The same
reader under a name of its own: the accepted tests pin that metric's list
to its cell (PERF.md section 7)."""

from benchmark.layer_metrics.report_postpass_s import read  # noqa: F401
