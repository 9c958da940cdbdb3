"""The sweep records of waves that did NOT block, at a cell's own size on
the chip (not run by the benchmark's own runs, nor by pytest: start it by
hand through the chip tool): `python benchmark/tests/unblocked_on_chip.py
--workload <cell> --seeds 11 12 13`.

Every per-layer metric is read in the `--trace 1` run, where the program
blocks after each phase; the measured wave (`--trace 0`, and every
caller's) does not, and `benchmark/lib/sweep_log.records` passes its
records over. This script runs the cell through `run.py`'s `execute` with
`--trace 0`, once a seed and all in this process, and reads the program's
`sweep_log()` itself: the window's records, `blocked` false. For every wave
it prints the eight spans' walls, the marks inside them, the record's five
derived fields (host lead, covered, device block, device wait, host tail)
and the compile counts; for every run the medians. A wave over STALL x the
run's median wall is printed whole, beside the garbage collections a
`gc.callbacks` hook of THIS script saw during it (the program counts none).
`--until-stall` stops after the first run that held one. Everything goes to
`chiprun_out/unblocked_<cell>.json` too.
"""

import argparse
import gc
import json
import os
import statistics
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

STALL = 1.5
DERIVED = ("host_lead_s", "covered_s", "device_block_s", "device_wait_s",
           "host_tail_s")


class Collections:
    """Every garbage collection of the process, on the clock the sweep
    records use: [generation, start, seconds, objects collected]."""

    def __init__(self):
        self.seen = []
        self._start = None

    def __call__(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.seen.append([info["generation"], self._start,
                              time.perf_counter() - self._start,
                              info["collected"]])

    def during(self, t0: float, t1: float) -> list:
        return [c for c in self.seen if c[1] < t1 and c[1] + c[2] > t0]


def window_records(attempted: int):
    """The last `attempted` sweep records: the window's, since the oracle
    replays through `run_events` and leaves none."""
    from tpusim.obs.spans import sweep_log

    recs = sweep_log()[-attempted:]
    if len(recs) != attempted or any(r.blocked for r in recs):
        raise RuntimeError(f"the log's last {attempted} records are not an "
                           "unblocked window's")
    return recs


def account(rec) -> dict:
    """One wave: walls, marks, derived fields and compile counts."""
    out = {"id": rec.id, "wall_s": rec.wall_s,
           "spans": {sp.name: sp.total_s for sp in rec.spans},
           "dispatch": {sp.name: sp.dispatch_s for sp in rec.spans},
           "marks": {f"{sp.name}.{k}": v for sp in rec.spans
                     for k, v in getattr(sp, "marks", {}).items()},
           "programs_requested": rec.programs_requested,
           "cache_loads": rec.cache_loads, "compiled": rec.compiled,
           "fetch_bytes": getattr(rec, "fetch_bytes", None)}
    out.update({name: getattr(rec, name, None) for name in DERIVED})
    return out


def line(acc: dict) -> str:
    spans = " ".join(f"{k} {v:.4f}" for k, v in acc["spans"].items())
    marks = " ".join(f"{k} {v:.4f}" for k, v in acc["marks"].items())
    derived = " ".join(
        f"{k} {acc[k]:.4f}" for k in DERIVED if acc[k] is not None)
    return (f"wave {acc['id']}: wall {acc['wall_s']:.4f} | {spans} | marks "
            f"{marks} | {derived} | postpass dispatch "
            f"{acc['dispatch'].get('frag_postpass', 0.0):.4f} | programs "
            f"{acc['programs_requested']} loads {acc['cache_loads']} "
            f"compiled {acc['compiled']}")


def medians(accounts: list) -> dict:
    """Median over the run's waves of every number of `account`."""
    out = {"wall_s": statistics.median(a["wall_s"] for a in accounts)}
    for group in ("spans", "dispatch", "marks"):
        for key in accounts[0][group]:
            out[f"{group}.{key}"] = statistics.median(
                a[group][key] for a in accounts)
    for key in DERIVED + ("fetch_bytes",):
        if all(a[key] is not None for a in accounts):
            out[key] = statistics.median(a[key] for a in accounts)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window; default: BENCHMARK.json's run_seconds")
    ap.add_argument("--until-stall", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend: the code path only")
    args = ap.parse_args()
    seconds = args.seconds or bench_run.load_json(
        os.path.join(REPO, "BENCHMARK.json"))["run_seconds"]

    collections = Collections()
    gc.callbacks.append(collections)
    runs, stalled = [], 0
    try:
        for seed in args.seeds:
            result = bench_run.execute(bench_run.parse(
                ["--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", "0"]
                + ["--rehearse"] * args.rehearse))
            recs = window_records(result["attempted"])
            accounts = [account(rec) for rec in recs]
            mid = medians(accounts)
            print(f"run seed {seed}: {len(recs)} waves, correct "
                  f"{result['correct']}, metrics "
                  f"{ {k: v['value'] for k, v in result['metrics'].items()} }")
            for acc in accounts:
                print(line(acc))
            print("medians: " + json.dumps(mid))
            stalls = []
            for rec, acc in zip(recs, accounts):
                if acc["wall_s"] > STALL * mid["wall_s"]:
                    held = collections.during(
                        rec.start_s, rec.start_s + rec.wall_s)
                    stalls.append({"record": rec.to_dict(),
                                   "collections": held})
                    print(f"STALLED wave {rec.id}: {acc['wall_s']:.4f} s "
                          f"against a median of {mid['wall_s']:.4f}: "
                          + json.dumps(rec.to_dict()))
                    print("  collections during it [generation, start, "
                          f"seconds, collected]: {held}")
            stalled += len(stalls)
            runs.append({"seed": seed, "correct": result["correct"],
                         "metrics": result["metrics"], "waves": accounts,
                         "medians": mid, "stalls": stalls})
            if stalls and args.until_stall:
                break
    finally:
        gc.callbacks.remove(collections)
    gen2 = [c for c in collections.seen if c[0] == 2]
    out = {"workload": args.workload, "rehearsal": args.rehearse,
           "runs": runs, "waves": sum(len(r["waves"]) for r in runs),
           "stalled_waves": stalled,
           "gen2_collections": len(gen2),
           "gen2_seconds": sum(c[2] for c in gen2)}
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           f"unblocked_{args.workload}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: v for k, v in out.items() if k != "runs"}
                     | {"correct": all(r["correct"] for r in runs)}))
    return 0 if all(r["correct"] for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
