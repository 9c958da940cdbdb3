"""The control of `correct` for the mix wave: one guarantee of the
configuration `openb-pwrfgd` ("every lane is scored under ITS weight row")
checked from the other side. The timed path is left as it is; the
sequential oracle is given the NEXT weight row of the configuration (500/500
-> 100/900 -> 50/950 -> 500/500) for every lane it replays. A comparison
that could not tell one row's placements from another's would still read
correct; this one has to read not correct, the cell as it is correct.

By hand through the chip tool at the cell's own size,
`python benchmark/tests/mix_control.py --seeds 11 12 13`, or on a CPU with
`--rehearse`; the same control runs at a tiny size in test_mix_cell.py.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

CELL = "openb-pwrfgd.mix-seeds"


def hand_the_oracle_the_next_row(rows):
    """Patch the benchmark's oracle helper so that a lane of weight row r
    is replayed under row r + 1 of `rows`; returns the undo."""
    from benchmark.drivers import wave

    real = wave.oracle_lane
    after = {tuple(row): rows[(i + 1) % len(rows)]
             for i, row in enumerate(rows)}

    def next_row(nodes, pods, sim_cfg, seed, trace, weights, lane_seed):
        return real(nodes, pods, sim_cfg, seed, trace,
                    after[tuple(int(w) for w in weights)], lane_seed)

    wave.oracle_lane = next_row
    return lambda: setattr(wave, "oracle_lane", real)


def configured_rows():
    bench = bench_run.load_json(os.path.join(bench_run.REPO, "BENCHMARK.json"))
    cell = bench_run.by_name(bench["workloads"], CELL, "workload")
    entry = bench_run.by_name(bench["configs"], cell["config"], "config")
    config = bench_run.load_json(os.path.join(bench_run.REPO, entry["file"]))
    return config["simulator"]["weight_rows"]


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
        undo = hand_the_oracle_the_next_row(configured_rows())
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
