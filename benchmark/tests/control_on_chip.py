"""The control of `correct`, at a cell's own size on the chip (not run by
the benchmark's own runs, nor by pytest: start it by hand through the chip
tool): `python benchmark/tests/control_on_chip.py --workload <cell>
--seeds 11 12 13`.

For each seed it makes one short run of the cell as it is (which has to
come out correct) and one with a guarantee of the configuration broken
underneath the timed path: every lane is computed with ONE tie-break
seed instead of its own, the shortcut that would save a sweep its
per-lane rank transfers, and handed back under the seed it was asked for
("no lane is approximated"). That run has to come out not correct.
The same control runs at a tiny size in test_harness.py.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402


def share_one_tie_break(driver_module):
    """Patch the program's sweep so all lanes use one seed that is none
    of theirs (the last lane's plus one); returns the undo."""
    real = driver_module.schedule_pods_sweep

    def shared(sim, pods, weights, seeds=None, **kw):
        lanes = real(sim, pods, weights, [seeds[-1] + 1] * len(seeds), **kw)
        for lane, seed in zip(lanes, seeds):
            lane.seed = seed
        return lanes

    driver_module.schedule_pods_sweep = shared
    return lambda: setattr(driver_module, "schedule_pods_sweep", real)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from tpusim.sim import driver

    rows, ok = [], True
    for seed in args.seeds:
        argv = ["--workload", args.workload, "--seed", str(seed), "--seconds",
                str(args.seconds), "--trace", "0"] + (["--rehearse"] * args.rehearse)
        sound = bench_run.execute(bench_run.parse(argv))
        undo = share_one_tie_break(driver)
        try:
            control = bench_run.execute(bench_run.parse(argv))
        finally:
            undo()
        rows.append({"seed": seed, "sound_correct": sound["correct"],
                     "control_correct": control["correct"],
                     "sound_metrics": sound["metrics"],
                     "memory_peak_bytes": sound["device"]["memory_peak_bytes"]})
        ok = ok and sound["correct"] and not control["correct"]
    print(json.dumps({"workload": args.workload, "control_fails_every_time": ok,
                      "rows": rows}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
