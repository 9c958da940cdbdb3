"""Step body: distinct weight rows among the lanes of the window's sweeps,
as the sweep record says (`SweepRecord.weight_rows`, set at run time from
the `weights` the caller gave: 1 for a seed sweep, 3 where the lanes carry
the three PWR+FGD rows); median over the window's waves. A program without
the counter (the parent of the PR that brought it) has nothing to read, and
the metric is left out."""

import statistics

from benchmark.lib import sweep_log


def record_counter(run, field: str):
    """Median over the window's sweep records of their counter `field`;
    None where the records, or a record's counter, are not there."""
    found = sweep_log.records(run)
    if found is None or not all(hasattr(rec, field) for rec in found[1]):
        return None
    return statistics.median(getattr(rec, field) for rec in found[1])


def read(run):
    return record_counter(run, "weight_rows")
