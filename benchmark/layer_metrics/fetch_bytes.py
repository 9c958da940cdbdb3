"""Readback: bytes of the one packed buffer a wave's `fetch` span moved to
the host, as the sweep record says (`SweepRecord.fetch_bytes`, the
buffer's `nbytes`), median over the window's waves. A program without the
field (the parent of the PR that brought it) has nothing to read, and the
metric is left out."""

from benchmark.layer_metrics.weight_rows import record_counter


def read(run):
    return record_counter(run, "fetch_bytes")
