"""Step body: the share of the window's real lane-events that are creates
the lanes REJECTED, as the sweep record counts them from the fetched
failure flags (`SweepRecord.rejected_creates`) over the real events of the
wave's lanes; median over the window's waves. About 0.225 where every lane
runs to 130 % of the cluster's capacity; a cell that stops short of a full
cluster reads 0. A program without the counter (the parent of the PR that
brought it) has nothing to read, and the metric is left out."""

import statistics

from benchmark.lib import sweep_log


def read(run):
    found, events = sweep_log.records(run), run.get("real_events")
    if found is None or not events or not all(
            hasattr(rec, "rejected_creates") for rec in found[1]):
        return None
    return statistics.median(
        rec.rejected_creates / events for rec in found[1])
