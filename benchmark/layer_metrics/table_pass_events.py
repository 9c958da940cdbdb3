"""Step body: events whose dirty columns ONE whole-table pass of a dense
table write puts down in the sweep's program, as the sweep record counts
them at trace time (`SweepRecord.table_pass_events`: the flat step's group;
1 where a column is written every event; 0 where no table is written
densely); median over the window's waves. A program without the field (the
parent of the PR that brought it) has nothing to read, and the metric is
left out."""

import statistics

from benchmark.lib import sweep_log


def read(run):
    found = sweep_log.records(run)
    if found is None or not all(
            hasattr(rec, "table_pass_events") for rec in found[1]):
        return None
    return statistics.median(rec.table_pass_events for rec in found[1])
