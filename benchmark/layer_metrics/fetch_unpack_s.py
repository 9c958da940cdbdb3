"""Readback: the host's unpacking of the copied buffer (one view a leaf,
an `astype(bool)` copy for every bool leaf), from the `fetch` span's mark
`copied` to the span's end, median over the window's waves. A program
whose spans carry no marks (the parent of the PR that brought them) has
nothing to read, and the metric is left out."""

from benchmark.layer_metrics.fetch_copy_s import window_median_of_fetch


def read(run):
    return window_median_of_fetch(
        run, lambda sp: sp.total_s - sp.marks["copied"])
