"""Step body: the `init_tables` span (the one table build a wave shares;
dispatch + block, so the build's device time), median over the window's
waves."""

from benchmark.lib import sweep_log


def read(run):
    return sweep_log.median_span_seconds(run, "init_tables")
