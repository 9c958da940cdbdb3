"""Step body: seconds the host waited on the vmapped scan (the `scan`
span's block time), median over the window's waves."""

import statistics


def read(run):
    if not run.get("spans_blocked") or not run.get("waves"):
        return None
    return statistics.median(w["scan_block_s"] for w in run["waves"])
