"""The comparison that decides `correct` (chip_smoke.assert_same_replay,
returning counts instead of raising, so a run can print every number
beside its limit). Exact i32/bool arithmetic: every limit is 0."""

from __future__ import annotations

import numpy as np


def lane_differences(got, want) -> list[tuple[str, int]]:
    """[(what, entries that differ)] between two replays of one lane:
    placed_node, dev_mask, ever_failed and every field of the final
    NodeState. A shape or dtype mismatch counts every entry as differing."""
    if got.state._fields != want.state._fields:
        return [("state fields", len(want.state._fields))]
    pairs = [(name, getattr(got, name), getattr(want, name))
             for name in ("placed_node", "dev_mask", "ever_failed")]
    pairs += [(f"state.{f}", getattr(got.state, f), getattr(want.state, f))
              for f in want.state._fields]
    out = []
    for name, x, y in pairs:
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            differing = int(max(x.size, y.size, 1))
        else:
            differing = int((x != y).sum())
        out.append((name, differing))
    return out


def counter_differences(lane, events: int) -> list[tuple[str, int]]:
    """The in-scan counter identities of one SweepLane, as absolute gaps:
    creates + deletes + skips = real events (the program has already taken
    its bucket padding out of skips), binds + rejected = creates, and the
    pods the arrays hold placed = binds less the deletes (a delete of a
    pod that was never placed makes that a lower bound)."""
    if lane.counters is None:
        return [("counters present", 1)]
    creates, binds, fails, deletes, skips = (int(v) for v in lane.counters[:5])
    placed = int((np.asarray(lane.placed_node) >= 0).sum())
    short = binds - deletes - placed
    return [
        ("creates+deletes+skips-events", abs(creates + deletes + skips - events)),
        ("binds+rejected-creates", abs(binds + fails - creates)),
        ("binds-deletes-placed", abs(short) if deletes == 0 else max(short, 0)),
    ]
