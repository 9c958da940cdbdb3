"""Step body: `table_reuse_share` in the clock cell. A stream by timestamp
ends every wave on another state than it began (36 pods alive) and the
build reads the INITIAL state, so the proof of what the build read has to
hold from wave to wave: 1.0. The same reader under a name of its own: the
accepted tests pin that metric's list to its cell (PERF.md section 7)."""

from benchmark.layer_metrics.table_reuse_share import read  # noqa: F401
