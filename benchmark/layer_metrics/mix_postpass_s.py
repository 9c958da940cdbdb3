"""Post-pass: the `frag_postpass` span where it holds the power reduction
too (one vmapped program a wave: every lane's frag amounts and its cluster
CPU and GPU watts over its final state, between scan and fetch; its
dispatch is the program traced again in every wave, its block the device),
median over the window's waves. The same span as `frag_postpass_s`, which
lists the 100k cell."""

from benchmark.layer_metrics.frag_postpass_s import read  # noqa: F401
