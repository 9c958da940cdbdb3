"""Step body: the share of the window's waves whose sweep read the score
tables an earlier sweep of the Simulator left on the device instead of
building them, as the sweep record says (`SweepRecord.tables_reused`, 0 or
1 a wave; its `init_tables` span is then the proof's cost and not a build).
1.0 where the cluster and the type set stay what they were from wave to
wave, as in both cells. A program without the field (the parent of the PR
that brought it) has nothing to read, and the metric is left out."""

import statistics

from benchmark.lib import sweep_log


def read(run):
    found = sweep_log.records(run)
    if found is None or not all(
            hasattr(rec, "tables_reused") for rec in found[1]):
        return None
    return statistics.fmean(rec.tables_reused for rec in found[1])
