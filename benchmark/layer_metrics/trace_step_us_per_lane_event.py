"""Step body: microseconds of the vmapped scan a lane-event where every
lane replays its own trace (a type id, a pod row and an event a lane; the
grouped flat body since PR 33, one table pass every 16 events with the
row gathered a lane: `table_pass_events` 16; the plain body, a dense column
write an event, before), from the sweep record alone: the `scan` span's
block time over the record's lanes x events, median over the window's
waves. The same quotient as `flat_step_us_per_lane_event`, which reads the
grouped body of one SHARED trace; kept apart because a trace a lane and a
shared trace are different programs."""

from benchmark.layer_metrics.flat_step_us_per_lane_event import read  # noqa: F401
