"""Every wave's wall of one untraced run, at a cell's own size on the chip
(not run by the benchmark's own runs, nor by pytest: start it by hand
through the chip tool):

    python3 benchmark/tests/walls_on_chip.py --workload <cell> --seed <n> \
        --seconds 40 --out chiprun_out/walls/<cell>.<n>.json

One run of `run.py`'s `execute` with `--trace 0` in this process, so what
it times is what the driver's check times; then the file holds the result
line, the window's walls as the cell's driver returned them and, from the
program's `sweep_log()`, EVERY sweep record of the process: the warm waves
first, then the window's (wall, host lead, device wait, host tail, compile
counts, `landing_reused`). `--summarize <dir>...` reads such files back
and prints, a cell, the end-to-end metrics' medians and spreads and what
the rate would read with the window's first waves or its stalled waves
(over STALL x the median wall) left out: where a spread comes from.
"""

import argparse
import glob
import json
import os
import statistics
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

STALL = 1.5
RECORD_FIELDS = ("id", "wall_s", "host_lead_s", "covered_s", "device_wait_s",
                 "host_tail_s", "programs_requested", "cache_loads",
                 "compiled", "landing_reused", "fetch_pieces", "blocked")


def one(args) -> int:
    sys.path.insert(0, REPO)
    sys.path.insert(0, BENCH)
    import run as bench_run

    kept = {}
    load_module = bench_run.load_module

    def keeping(kind, name):
        module = load_module(kind, name)
        if kind == "drivers":
            inner = module.run

            def run(ctx):
                kept.update(inner(ctx))
                return kept

            module.run = run
        return module

    bench_run.load_module = keeping
    result = bench_run.execute(bench_run.parse(
        ["--workload", args.workload, "--seed", str(args.seed), "--seconds",
         str(args.seconds), "--trace", "0"] + ["--rehearse"] * args.rehearse))
    from tpusim.obs.spans import sweep_log

    out = {"workload": args.workload, "seed": args.seed,
           "seconds": args.seconds, "result": result,
           "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
           "walls": [w["wall_s"] for w in kept["waves"]],
           "setup_parts": kept.get("setup_parts"),
           "records": [{k: getattr(rec, k, None) for k in RECORD_FIELDS}
                       for rec in sweep_log()]}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


def quartile_spread(values) -> float:
    """The distance between the first and third quartile over the median,
    as `statistics.quantiles(values, n=4)` gives them (the driver's)."""
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def range_spread(values) -> float:
    return (max(values) - min(values)) / statistics.median(values)


def rate(walls) -> float:
    """Waves a second: the cell's rate up to its constant lanes x events."""
    return len(walls) / sum(walls)


FORMS = {
    "all waves": lambda w: w,
    "less the first": lambda w: w[1:],
    "less the first two": lambda w: w[2:],
    "less stalled": lambda w: [
        x for x in w if x <= STALL * statistics.median(w)],
    "less the first two and stalled": lambda w: [
        x for x in w[2:] if x <= STALL * statistics.median(w)],
}


def summarize(dirs) -> int:
    runs = {}
    for d in dirs:
        for path in sorted(glob.glob(os.path.join(d, "*.json"))):
            with open(path) as f:
                r = json.load(f)
            runs.setdefault((d, r["workload"]), []).append(r)
    for (d, cell), rs in sorted(runs.items()):
        print(f"== {d} {cell}: {len(rs)} runs, seeds "
              f"{[r['seed'] for r in rs]}, correct "
              f"{sum(1 for r in rs if r['result']['correct'])}")
        for name in ("lane_events_per_s", "wave_s", "setup_s"):
            vals = [r["result"]["metrics"][name]["value"] for r in rs]
            print(f"  {name}: median {statistics.median(vals):.6f} quartile "
                  f"{100 * quartile_spread(vals):.3f} % range "
                  f"{100 * range_spread(vals):.3f} % | "
                  + " ".join(f"{v:.6g}" for v in vals))
        for what, form in FORMS.items():
            vals = [rate(form(r["walls"])) for r in rs]
            print(f"  rate, {what}: quartile "
                  f"{100 * quartile_spread(vals):.3f} % range "
                  f"{100 * range_spread(vals):.3f} % of the median "
                  f"{statistics.median(vals):.6f} waves/s")
        for r in rs:
            w, mid = r["walls"], statistics.median(r["walls"])
            warm = [rec for rec in r["records"]
                    if rec["id"] is not None][:-len(w)]
            print(f"  seed {r['seed']}: {len(w)} waves, median {mid:.6f}, "
                  f"first three {[round(x, 4) for x in w[:3]]}, over "
                  f"{STALL} x the median {[round(x, 4) for x in w if x > STALL * mid]}, "
                  f"over 1.05 x {sum(1 for x in w if x > 1.05 * mid)}; warm "
                  f"waves {[(round(rec['wall_s'], 3), rec['compiled']) for rec in warm]}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--summarize", nargs="+")
    args = ap.parse_args()
    if args.summarize:
        return summarize(args.summarize)
    return one(args)


if __name__ == "__main__":
    sys.exit(main())
