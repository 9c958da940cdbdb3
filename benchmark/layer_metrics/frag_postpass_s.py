"""Post-pass: the `frag_postpass` span (per-lane frag reduction between
scan and fetch: its dispatch is the program traced again in every wave,
its block the device), median over the window's waves."""

from benchmark.lib import sweep_log


def read(run):
    return sweep_log.median_span_seconds(run, "frag_postpass")
