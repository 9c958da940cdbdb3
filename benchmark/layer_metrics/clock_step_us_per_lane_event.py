"""Step body: microseconds of the vmapped scan a lane-event where the stream
is the trace's own clock: creations and deletions interleaved, so nearly
half of the events take the event switch's delete branch (no Filter, no
select, no device pick; the commit gives the node back and the column
refresh runs either way), from the sweep record alone: the `scan` span's
block time over the record's lanes x events, median over the window's
waves. The same quotient as `flat_step_us_per_lane_event`, whose cell sends
creations only on the same cluster, policy, lanes and depth; kept apart
because the stream is another one."""

from benchmark.layer_metrics.flat_step_us_per_lane_event import read  # noqa: F401
