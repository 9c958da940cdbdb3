"""Step body: microseconds of the vmapped scan a lane-event where every
lane replays its own trace (the plain flat body: a type id, a pod row and
an event a lane, one dense column write an event), from the sweep record
alone: the `scan` span's block time over the record's lanes x events,
median over the window's waves. The same quotient as
`flat_step_us_per_lane_event`, which reads the grouped body of one shared
trace; kept apart because the two bodies are different programs."""

from benchmark.layer_metrics.flat_step_us_per_lane_event import read  # noqa: F401
