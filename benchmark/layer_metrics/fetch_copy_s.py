"""Readback: the copy of the packed buffer to the host, from the `fetch`
span's mark `ready` (the device has finished everything it owed) to its
mark `copied` (the bytes are on the host), median over the window's waves.
A program whose spans carry no marks (the parent of the PR that brought
them) has nothing to read, and the metric is left out."""

from benchmark.lib import sweep_log


def marked_fetch(rec):
    """The record's `fetch` span if it carries both marks, or None."""
    for sp in rec.spans:
        marks = getattr(sp, "marks", None) or {}
        if sp.name == "fetch" and "ready" in marks and "copied" in marks:
            return sp
    return None


def window_median_of_fetch(run, of):
    """Median over the window's records of `of(marked fetch span)`, or
    None where a record has none."""
    found = sweep_log.records(run)
    if found is None or not all(marked_fetch(rec) for rec in found[1]):
        return None
    return sweep_log.window_median(run, lambda rec: of(marked_fetch(rec)))


def read(run):
    return window_median_of_fetch(
        run, lambda sp: sp.marks["copied"] - sp.marks["ready"])
