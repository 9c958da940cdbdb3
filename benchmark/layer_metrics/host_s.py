"""Host prep and lane slicing: a wave's wall less the time the host
waited on the scan, less the fetch and, since PR 48, less the table build
(`init_tables`) and the post-passes (`frag_postpass`, and `event_metrics`
where the per-event report is on): what is left is the host's own work,
specs, keys, ranks, lane slicing and the gaps between the spans. Median
over the window's waves. Needs the spans' block split, so only a traced
(profile=True) run; a driver kind whose waves carry no table build or
post-pass seconds has nothing taken off for them."""

import statistics


def read(run):
    if not run.get("spans_blocked") or not run.get("waves"):
        return None
    return statistics.median(
        w["wall_s"] - w["scan_block_s"] - w["fetch_s"]
        - w.get("table_build_s", 0.0) - w.get("postpass_s", 0.0)
        for w in run["waves"])
