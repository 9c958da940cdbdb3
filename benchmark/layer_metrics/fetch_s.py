"""Readback: the `fetch` span (one packed transfer of every lane's
result), median over the window's waves."""

import statistics


def read(run):
    if not run.get("waves"):
        return None
    return statistics.median(w["fetch_s"] for w in run["waves"])
