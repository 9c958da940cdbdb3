"""Entry points: host time the warm wave spent dispatching (the sum of its
spans' dispatch halves: tracing, lowering and loading or compiling every
program of the window), from the sweep record before the window's."""

from benchmark.lib import sweep_log


def read(run):
    found = sweep_log.records(run)
    if found is None:
        return None
    return sum(sp.dispatch_s for sp in found[0].spans)
