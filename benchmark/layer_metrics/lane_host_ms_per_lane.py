"""Host prep and lane slicing: milliseconds of per-lane host work a lane
(the `lane_keys`, `lane_ranks` and `slice_lanes` spans: one PRNG key, one
tie-break permutation and one SweepLane a lane) over the record's lanes,
median over the window's waves."""

from benchmark.lib import sweep_log

SPANS = ("lane_keys", "lane_ranks", "slice_lanes")


def read(run):
    return sweep_log.window_median(
        run, lambda rec: 1e3 * sweep_log.span_seconds(rec, SPANS) / rec.lanes)
