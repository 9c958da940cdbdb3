"""Entry points: host time the first warm wave spent dispatching programs
(the sum of its spans' dispatch halves: tracing, lowering and loading or
compiling every program of the window), from the sweep record of the
first warm wave. Since PR 48 without the `fetch` and `slice_lanes` spans:
neither blocks apart, so their dispatch halves are the wait for the
device, the copy and the host's slicing, whole, and no dispatch."""

from benchmark.lib import sweep_log

NOT_DISPATCH = ("fetch", "slice_lanes")


def read(run):
    found = sweep_log.records(run)
    if found is None:
        return None
    return sum(sp.dispatch_s for sp in found[0].spans
               if sp.name not in NOT_DISPATCH)
