"""Host prep: the `lane_keys` and `lane_ranks` spans (one PRNG key and one
tie-break permutation of the nodes a lane, each its own transfer), median
over the window's waves."""

from benchmark.lib import sweep_log


def read(run):
    return sweep_log.median_span_seconds(run, "lane_keys", "lane_ranks")
