"""Post-pass: programs that reached the backend during one wave (each was
traced and lowered again, then compiled or loaded from the persistent
cache), as the sweep record counts them through jax.monitoring; median
over the window's waves."""

from benchmark.lib import sweep_log


def read(run):
    return sweep_log.window_median(run, lambda rec: rec.programs_requested)
