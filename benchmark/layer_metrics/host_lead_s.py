"""Host prep: from a wave's start to the dispatch of its scan, as the sweep
record derives it from its spans (`SweepRecord.host_lead_s`: specs, keys,
ranks, the tables' hand-over, the sweep wrapper's dispatch and the gaps
between), median over the window's waves. The device has no scan to run
yet, so none of it can hide behind one. A program without the field (the
parent of the PR that brought it) has nothing to read, and the metric is
left out."""

from benchmark.lib import sweep_log


def derived_seconds(run, field: str):
    """Median over the window's sweep records of their derived `field`;
    None where the records are not there, or a record lacks the field or
    the spans and marks it is derived from."""
    found = sweep_log.records(run)
    if found is None or any(
            getattr(rec, field, None) is None for rec in found[1]):
        return None
    return sweep_log.window_median(run, lambda rec: getattr(rec, field))


def read(run):
    return derived_seconds(run, "host_lead_s")
