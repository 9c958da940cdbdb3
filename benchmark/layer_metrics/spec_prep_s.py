"""Host prep: the `specs` span (pod specs and events built, padded and
uploaded; dispatch + block), median over the window's waves."""

from benchmark.lib import sweep_log


def read(run):
    return sweep_log.median_span_seconds(run, "specs")
