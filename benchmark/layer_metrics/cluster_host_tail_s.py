"""Lane slicing and readback: `host_tail_s` in the clustering cell: the
copy of a buffer that is mostly per-event leaves, its unpacking, and a
lane's nine series cut to its own length, as in the load cell. The same
reader under a name of its own: the accepted tests pin that metric's list
to its cell (PERF.md section 7)."""

from benchmark.layer_metrics.host_tail_s import read  # noqa: F401
