"""Step body: microseconds of the vmapped scan a lane-event, from the sweep
record alone: the `scan` span's block time over the record's lanes x events,
median over the window's waves. What the flat step costs at sweep width,
whatever the wave's depth."""

from benchmark.lib import sweep_log


def per_lane_event_us(rec):
    block_s = sum(sp.block_s for sp in rec.spans if sp.name == "scan")
    return 1e6 * block_s / (rec.lanes * rec.events)


def read(run):
    return sweep_log.window_median(run, per_lane_event_us)
