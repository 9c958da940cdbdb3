"""Step body: access sites (reads and writes) of the sweep's program that
sim/lane_write.py's batching rule lowered in the dense form, as the sweep
record counts them at trace time (`SweepRecord.dense_accesses`); median
over the window's waves. A program without the field (the parent of the PR
that brought it) has nothing to read, and the metric is left out."""

import statistics

from benchmark.lib import sweep_log


def read(run):
    found = sweep_log.records(run)
    if found is None or not all(
            hasattr(rec, "dense_accesses") for rec in found[1]):
        return None
    return statistics.median(rec.dense_accesses for rec in found[1])
