"""Post-pass: the dispatch half of the `frag_postpass` span, median over
the window's waves: the eager gather of each lane's typical pods (to the
span's mark `gathered`) and then the trace, the lowering and the cache
load of a `jax.jit` that wraps a new function object in every wave. The
host work an unblocked wave can hide behind the scan; the span's block
half is the device's. A record without the span reads as nothing."""

from benchmark.lib import sweep_log


def dispatch_s(rec):
    return next((sp.dispatch_s for sp in rec.spans
                 if sp.name == "frag_postpass"), None)


def read(run):
    found = sweep_log.records(run)
    if found is None or any(dispatch_s(rec) is None for rec in found[1]):
        return None
    return sweep_log.window_median(run, dispatch_s)
