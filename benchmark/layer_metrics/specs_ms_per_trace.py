"""Host prep: milliseconds of the `specs` span (pod specs and events built,
padded and uploaded, the typical-pod sets stacked; dispatch + block) a
DISTINCT trace the span prepared (`SweepRecord.traces`: 50 of 600 lanes in
the family wave), median over the window's waves. What host prep costs
where it goes with the traces and not with the lanes. A program without
the counter (the parent of the PR that brought it) has nothing to read,
and the metric is left out."""

import statistics

from benchmark.lib import sweep_log


def read(run):
    found = sweep_log.records(run)
    if found is None or not all(
            getattr(rec, "traces", 0) for rec in found[1]):
        return None
    return statistics.median(
        1e3 * sweep_log.span_seconds(rec, ("specs",)) / rec.traces
        for rec in found[1])
