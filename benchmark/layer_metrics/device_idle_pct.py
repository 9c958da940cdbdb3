"""Device: share of the traced window in which no program ran on the
device (1 - union of the device's program intervals over the window). The
window is one whole wave, from the call to the returned lanes."""

from benchmark.lib import trace_reduce


def read(run):
    traced = run.get("traced")
    if not traced or run.get("rehearsal"):
        return None
    return trace_reduce.idle_share_pct(traced["busy_s"], traced["window_s"])
