"""Step body: the share of the window's lane-events that are deletions, as
the sweep record says (`SweepRecord.delete_events`: deletions among the
record's real events, summed over its lanes, counted on the host from the
streams the sweep built) over the record's lanes x events; median over the
window's waves. 238 / 512 in the clock cell; a cell that stops sending
deletes reads 0. A program without the counter (the parent of the PR that
brought it) has nothing to read, and the metric is left out."""

import statistics

from benchmark.lib import sweep_log


def read(run):
    found = sweep_log.records(run)
    if found is None or not all(
            hasattr(rec, "delete_events") for rec in found[1]):
        return None
    return statistics.median(
        rec.delete_events / (rec.lanes * rec.events) for rec in found[1])
