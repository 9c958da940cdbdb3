"""Readback: bytes of the packed buffer that are per-event report series,
as the sweep record says (`SweepRecord.series_bytes`: the nine
EventMetrics leaves over the padded event axis), median over the window's
waves. A program without the field (the parent of the PR that brought it)
has nothing to read, and the metric is left out."""

from benchmark.layer_metrics.weight_rows import record_counter


def read(run):
    return record_counter(run, "series_bytes")
