"""The controls of `correct` for the load wave, and the whole walk of one
lane. The timed path is left as it is; what the driver hands the plain
reference is bent, one way at a time, and the cell has to read not correct:

- `another_shuffle`: the checked lane is walked beside the reference's
  replay of ANOTHER tuning seed's trace (the next one of the
  configuration's). A comparison that could not tell one shuffle of the
  pod list from another would still read correct.
- `dropped_delta`: the checked lane's frag series is handed over with ONE
  event's delta left out (a create the lane placed on GPUs, drawn from the
  lane's seed; every later row is short of that event's change). The
  limit on the float series has to be tight enough to see it.

`--whole` runs, beside the controls, the cell with EVERY create of the
checked lane held to the full FGD scoring rule (`scored_creates` past the
trace's length): the whole 10,8xx-event reference walk, about two minutes
of the host for the one lane, which the cell itself scores a tenth of.

By hand through the chip tool at the cell's own size,
`python benchmark/tests/load_control.py --seeds 11 12 [--whole]`, or on a
CPU with `--rehearse`; the same controls run at a tiny size in
test_load_cell.py.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

CELL = "openb-load130.report-seeds"


def _bend_walk(bend):
    """Patch the walk the driver calls so that `bend(cluster, pods, lane)`
    gives the pods the reference replays (and may bend the lane it is
    handed); returns the undo."""
    from benchmark.lib import reference_follow_load

    real = reference_follow_load.walk

    def bent(cluster, pods, typical, rank, lane, weight, scored, keep):
        pods = bend(cluster, pods, lane)
        last = len(pods["cpu"]) - 1
        return real(cluster, pods, typical, rank, lane, weight,
                    [e for e in scored if e <= last],
                    [min(e, last) for e in keep])

    reference_follow_load.walk = bent
    return lambda: setattr(reference_follow_load, "walk", real)


def another_shuffle(tuning_seeds, ratio: float):
    """The reference replays the NEXT tuning seed's trace: its own shuffle
    and tuning of the CSV's rows, found among the configuration's by the
    requests the driver handed over."""
    import numpy as np

    from benchmark.drivers import load_wave
    from benchmark.lib import (
        inputs,
        reference_fgd,
        reference_follow_load,
        reference_inputs,
    )
    from tpusim import constants

    def bend(cluster, pods, lane):
        requests = reference_inputs.pods(
            inputs.POD_CSV, constants.GPU_MODEL_IDS)
        names = load_wave.pod_names()
        capacity = (int(np.asarray(cluster["gpu_cnt"]).sum())
                    * reference_fgd.MILLI)
        traces = [{k: v[reference_follow_load.tuned_order(
            names, requests["gpu_milli"], requests["gpu_num"], capacity,
            ratio, s)] for k, v in requests.items()} for s in tuning_seeds]
        (at,) = [i for i, t in enumerate(traces) if all(
            np.array_equal(t[k], pods[k]) for k in pods)]
        return traces[(at + 1) % len(traces)]

    return _bend_walk(bend)


def dropped_delta():
    """The lane's frag series with one placed GPU create's delta left out:
    the lane the walk is handed is the one the driver reads the series of
    afterwards, so its series are bent in place."""
    import numpy as np

    def bend(cluster, pods, lane):
        on_gpus = (np.asarray(lane.placed_node) >= 0) & (
            pods["gpu_milli"] * pods["gpu_num"] > 0)
        pool = np.flatnonzero(on_gpus[1:-1]) + 1  # a row before, a row after
        e = int(np.random.default_rng(lane.seed).choice(pool))
        frag = np.array(lane.metrics.frag_amounts)
        frag[e:] -= frag[e] - frag[e - 1]
        lane.metrics = lane.metrics._replace(frag_amounts=frag)
        return pods

    return _bend_walk(bend)


def score_every_create():
    """The cell with `scored_creates` past any trace's length."""
    real = bench_run.load_json

    def whole(path):
        out = real(path)
        if out.get("driver") == "load_wave":
            out["scored_creates"] = out["tiny"]["scored_creates"] = 10**9
        return out

    bench_run.load_json = whole
    return lambda: setattr(bench_run, "load_json", real)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--whole", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    config = bench_run.load_json(os.path.join(
        BENCH, "configs", "openb-load130.json"))
    if args.rehearse:
        config = {**config, **config["tiny"]}
    seeds = config["workload"]["tuning_seeds"]
    ratio = float(config["simulator"]["tuning_ratio"])
    rows, ok = [], True
    for seed in args.seeds:
        argv = ["--workload", CELL, "--seed", str(seed), "--seconds",
                str(args.seconds), "--trace", "0"] + (
            ["--rehearse"] * args.rehearse)
        row = {"seed": seed}
        bends = {"another_shuffle": lambda: another_shuffle(seeds, ratio),
                 "dropped_delta": dropped_delta}
        if args.whole:
            bends["whole_walk"] = score_every_create
        for name, bend in bends.items():
            undo = bend()
            try:
                got = bench_run.execute(bench_run.parse(argv))
            finally:
                undo()
            row[f"{name}_correct"] = got["correct"]
            ok = ok and got["correct"] == (name == "whole_walk")
        rows.append(row)
    print(json.dumps({"workload": CELL, "controls_read_as_they_must": ok,
                      "rows": rows}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
