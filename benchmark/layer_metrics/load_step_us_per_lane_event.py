"""Step body: microseconds of the vmapped scan a REAL lane-event where every
lane replays its whole tuned trace on a cluster that fills up: the `scan`
span's block time over the real events of the wave's lanes (the driver's
count, each lane's own: the traces differ in length and the bucket is
longer than any), median over the window's waves. Kept apart from
`flat_step_us_per_lane_event`, which divides by lanes x the record's
longest lane."""

from benchmark.lib import sweep_log


def read(run):
    events = run.get("real_events")
    if not events:
        return None
    return sweep_log.window_median(
        run, lambda rec: 1e6 * sum(
            sp.block_s for sp in rec.spans if sp.name == "scan") / events)
