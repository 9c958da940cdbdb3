"""Host prep and lane slicing: a wave's wall less the time the host
waited on the scan and less the fetch, median over the window's waves.
Needs the spans' block split, so only a traced (profile=True) run."""

import statistics


def read(run):
    if not run.get("spans_blocked") or not run.get("waves"):
        return None
    return statistics.median(
        w["wall_s"] - w["scan_block_s"] - w["fetch_s"] for w in run["waves"])
