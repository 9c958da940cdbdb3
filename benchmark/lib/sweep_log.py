"""The program's own sweep records (tpusim/obs/spans.py: one per
`schedule_pods_sweep` call, eight flat phase spans and the compile counts
over the call), found again after the driver has dropped the Simulator.

The wave drivers run W = run["warm_waves"] warm waves (1 where the run
does not say), N = run["attempted"] window waves and, in a traced run, one
more wave under the profiler; the oracle replays through `run_events` and
adds no record. So the log's last N + 1 records are the window's and the
traced wave's, and the W before them are the warm waves', the FIRST of
which loaded or compiled the window's programs (not the first of the
process: tests run several cells in one). That reading is checked against
what the driver itself timed, and anything that does not line up reads as
nothing: a program without the log (the parent of the PR that brought it),
an untraced run whose spans did not block, a log too short, records that are not consecutive, or a
record whose wall is not just under the wall the driver measured around
the same call.
"""

import statistics

WALL_TOLERANCE = 0.01  # a record's wall is inside the driver's, within 1 %


def records(run):
    """(the first warm wave's record, [the window's records]) or None."""
    try:
        from tpusim.obs.spans import sweep_log
    except ImportError:
        return None
    waves = run.get("waves")
    if not run.get("spans_blocked") or not waves:
        return None
    n, warm_waves = len(waves), int(run.get("warm_waves", 1))
    tail = sweep_log()[-(warm_waves + n + 1):]
    if len(tail) != warm_waves + n + 1:
        return None
    if [r.id for r in tail] != list(range(tail[0].id, tail[0].id + len(tail))):
        return None
    if not all(r.blocked for r in tail):
        return None
    warm, window = tail[0], tail[warm_waves:-1]
    for rec, wave in zip(window, waves):
        outer = wave["wall_s"]
        if not (1.0 - WALL_TOLERANCE) * outer <= rec.wall_s <= outer:
            return None
    return warm, window


def span_seconds(rec, names) -> float:
    """Dispatch + block seconds of the record's spans of those names."""
    return sum(sp.total_s for sp in rec.spans if sp.name in names)


def window_median(run, of):
    """Median over the window's records of `of(record)`, or None."""
    found = records(run)
    if found is None:
        return None
    return statistics.median(of(rec) for rec in found[1])


def median_span_seconds(run, *names):
    return window_median(run, lambda rec: span_seconds(rec, names))
