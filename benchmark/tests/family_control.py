"""The control of `correct` for the family wave: one guarantee of the
configuration `openb-families` broken underneath the timed path. Every lane
is scored against ANOTHER family's typical pods (the sets handed on one
family along) and handed back as if nothing had happened; that run has to
come out not correct, the cell as it is correct.

By hand through the chip tool at the cell's own size,
`python benchmark/tests/family_control.py --seeds 11`, or on a CPU with
`--rehearse`; the same control runs at a tiny size in test_family_cell.py.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

CELL = "openb-families.fgd-seeds"


def hand_on_the_typical_pods(driver_module):
    """Patch the program's sweep so each lane gets the typical pods of the
    next distinct set in the wave's order; returns the undo."""
    real = driver_module.schedule_pods_sweep

    def other_family(sim, pods, weights, seeds=None, *, lane_typical, **kw):
        sets = list({id(tp): tp for tp in lane_typical}.values())
        after = {id(tp): sets[(i + 1) % len(sets)]
                 for i, tp in enumerate(sets)}
        return real(sim, pods, weights, seeds,
                    lane_typical=[after[id(tp)] for tp in lane_typical], **kw)

    driver_module.schedule_pods_sweep = other_family
    return lambda: setattr(driver_module, "schedule_pods_sweep", real)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from tpusim.sim import driver

    rows, ok = [], True
    for seed in args.seeds:
        argv = ["--workload", CELL, "--seed", str(seed), "--seconds",
                str(args.seconds), "--trace", "0"] + (
            ["--rehearse"] * args.rehearse)
        sound = bench_run.execute(bench_run.parse(argv))
        undo = hand_on_the_typical_pods(driver)
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
