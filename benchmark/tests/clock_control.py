"""The control of `correct` for the clock wave: one guarantee of the
configuration `openb-clock` ("a deletion gives back exactly the node, the
devices and the CPU / memory / GPU-milli its creation bound") checked from
the other side. The timed path is left as it is; the walk beside the plain
reference (`lib/reference_follow_clock.walk`) is handed the window's stream
with every deletion made an event of no effect, so the reference never
releases. A comparison that could not tell a cluster that empties from one
that only fills would still read correct; this one has to read not correct
(the reference's cluster ends fuller than the lane's, and its scores drift
from the lane's as it fills), the cell as it is correct.

By hand through the chip tool at the cell's own size,
`python benchmark/tests/clock_control.py --seeds 11 12 13`, or on a CPU with
`--rehearse`; the same control runs at a tiny size in test_clock_cell.py.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

CELL = "openb-clock.fgd-seeds"
EV_NOTHING = 2  # a kind the reference passes over


def hand_the_reference_a_stream_without_deletions():
    """Patch the walk the driver calls so that the reference sees every
    deletion as an event of no effect; returns the undo."""
    import numpy as np

    from benchmark.lib import reference_clock, reference_follow_clock

    real = reference_follow_clock.walk

    def deaf(cluster, pods, events, *rest, **kw):
        kind, pod = events
        kind = np.where(kind == reference_clock.EV_DELETE, EV_NOTHING, kind)
        return real(cluster, pods, (kind, pod), *rest, **kw)

    reference_follow_clock.walk = deaf
    return lambda: setattr(reference_follow_clock, "walk", real)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    rows, ok = [], True
    for seed in args.seeds:
        argv = ["--workload", CELL, "--seed", str(seed), "--seconds",
                str(args.seconds), "--trace", "0"] + (
            ["--rehearse"] * args.rehearse)
        sound = bench_run.execute(bench_run.parse(argv))
        undo = hand_the_reference_a_stream_without_deletions()
        try:
            control = bench_run.execute(bench_run.parse(argv))
        finally:
            undo()
        rows.append({"seed": seed, "sound_correct": sound["correct"],
                     "control_correct": control["correct"],
                     "sound_metrics": sound["metrics"],
                     "memory_peak_bytes": sound["device"]["memory_peak_bytes"]})
        ok = ok and sound["correct"] and not control["correct"]
    print(json.dumps({"workload": CELL, "control_fails_every_time": ok,
                      "rows": rows}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
