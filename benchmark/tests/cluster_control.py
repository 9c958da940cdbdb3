"""The controls of `correct` for the cluster wave. The timed path is left as
it is; the plain reference the driver holds the checked lane to is bent, one
way at a time, and the cell has to read not correct:

- `dropped_add`: the reference's Bind never adds into its affinity counts
  (`reference_clustering.replay(count_affinity=False)`), so every node looks
  idle to its score for ever: what a program that LOST the commit's add
  into `aff_cnt` would compute. A comparison that cannot tell this from the
  lane does not see what the cell exists for.
- `late_add`: the reference scores against counts that are a chunk of 128
  events old (the deferred form's timing, `table_engine.chunk_affinity`'s
  block, which only a program whose kernels do NOT read the counts may
  take); the counts themselves come out whole, only the reads are stale.

By hand through the chip tool at the cell's own size,
`python benchmark/tests/cluster_control.py --seeds 11 12`, or on a CPU with
`--rehearse`; the first control runs at a tiny size in
tests/test_clustering_cell.py, both in tests/test_clustering_reference.py.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

CELL = "openb-clustering.report-seeds"
CHUNK = 128


def dropped_add():
    from benchmark.lib import reference_clustering as ref

    real = ref.replay
    ref.replay = lambda *a, **kw: real(*a, **kw, count_affinity=False)
    return lambda: setattr(ref, "replay", real)


def late_add():
    from benchmark.lib import reference_clustering as ref

    real_replay, real_score = ref.replay, ref.score_nodes

    def replay(*a, **kw):
        seen = {"event": 0, "aff": None}

        def stale(gpu_left, aff_cnt, pod):
            if seen["event"] % CHUNK == 0:
                seen["aff"] = aff_cnt.copy()
            seen["event"] += 1
            return real_score(gpu_left, seen["aff"], pod)

        ref.score_nodes = stale
        try:
            return real_replay(*a, **kw)
        finally:
            ref.score_nodes = real_score

    ref.replay = replay
    return lambda: setattr(ref, "replay", real_replay)


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
        row = {"seed": seed}
        for name, bend in (("dropped_add", dropped_add),
                           ("late_add", late_add)):
            undo = bend()
            try:
                got = bench_run.execute(bench_run.parse(argv))
            finally:
                undo()
            row[f"{name}_correct"] = got["correct"]
            ok = ok and not got["correct"]
        rows.append(row)
    print(json.dumps({"workload": CELL, "controls_read_as_they_must": ok,
                      "rows": rows}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
