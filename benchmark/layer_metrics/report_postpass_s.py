"""Post-pass: the `event_metrics` span, the report program that rebuilds
every lane's per-event series from its telemetry (its dispatch the host's
hand-over, its block the device in a blocked wave), median over the
window's waves. A program that dispatches the report inside another span
(the parent of the PR that brought this one) has nothing to read, and the
metric is left out."""

from benchmark.lib import sweep_log


def read(run):
    found = sweep_log.records(run)
    if found is None or not all(
            any(sp.name == "event_metrics" for sp in rec.spans)
            for rec in found[1]):
        return None
    return sweep_log.median_span_seconds(run, "event_metrics")
