"""Step body: typical-pod sets, and so score-table sets, the window's
sweeps carried, as the sweep record says (`SweepRecord.typical_sets`: one a
workload family of the wave's lanes, 1 where every lane is scored against
one set); median over the window's waves. A program without the counter
(the parent of the PR that brought it) has nothing to read, and the metric
is left out."""

import statistics

from benchmark.lib import sweep_log


def read(run):
    found = sweep_log.records(run)
    if found is None or not all(
            hasattr(rec, "typical_sets") for rec in found[1]):
        return None
    return statistics.median(rec.typical_sets for rec in found[1])
