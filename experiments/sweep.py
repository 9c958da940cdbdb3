#!/usr/bin/env python
"""Single-process experiment sweep (replaces the reference's
`run_scripts.sh | xargs --max-procs=128` fleet, experiments/README.md
step 2: 1020 experiments, ~10 h on a 256-vCPU machine).

Runs the (trace × policy × seed) grid in ONE process so every experiment
after the first reuses the compiled replay engines (tpusim.sim.engine /
table_engine caches + the driver's shape bucketing over pod/event/typical
axes). Bellman memos stay scoped per experiment — sharing them would make
report values depend on sweep order (see tpusim/sim/driver.py).

    python experiments/sweep.py --traces openb_pod_list_default \
        --methods 06-FGD 01-Random --seeds 3
    python experiments/sweep.py            # full 10-method × 21 × 10 grid
    python experiments/sweep.py --fast     # skip per-event report lines

Each experiment writes the same per-directory outputs as experiments/run.py
(simon.log + analysis CSVs) under --out-root/<trace>/<method>/<tune>/<seed>,
so experiments/merge.py and the plot scripts work unchanged.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "experiments"))

from generate_run_scripts import METHODS, TRACES  # noqa: E402

import run as runner  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-root", default="experiments/data")
    ap.add_argument("--tune", type=float, default=1.3)
    ap.add_argument("--seeds", type=int, default=10, help="seeds 42..42+n-1")
    ap.add_argument("--traces", nargs="*", default=None)
    ap.add_argument("--methods", nargs="*", default=None, help="method ids")
    ap.add_argument("--fast", action="store_true", help="no per-event report")
    ap.add_argument(
        "--no-batch", action="store_true",
        help="run seeds one-by-one instead of one vmapped replay per group",
    )
    args = ap.parse_args(argv)

    traces = args.traces or TRACES
    methods = [m for m in METHODS if args.methods is None or m[0] in args.methods]
    groups = [(trace, m) for trace in traces for m in methods]
    seeds = list(range(42, 42 + args.seeds))
    total = len(groups) * len(seeds)
    t_all = time.perf_counter()
    done = 0
    for trace, (mid, flags, gpusel, dimext, norm) in groups:
        # one group = the same experiment across seeds; uncached seeds run
        # as ONE vmapped device replay (driver.run_batch) unless --no-batch
        pending = []
        for seed in seeds:
            outdir = f"{args.out_root}/{trace}/{mid}/{args.tune}/{seed}"
            argv_exp = (
                ["-d", outdir, "-f", trace]
                + flags.split()
                + ["-gpusel", gpusel, "-dimext", dimext, "-norm", norm,
                   "-tune", str(args.tune), "-tuneseed", str(seed),
                   "--shuffle-pod", "true"]
                + (["--no-per-event-report"] if args.fast else [])
            )
            # resume marker: written only after a fully-finished experiment,
            # keyed on the exact argv so --fast and full runs never alias
            marker = Path(outdir) / ".sweep_done"
            if marker.exists() and marker.read_text() == " ".join(argv_exp):
                done += 1
                print(
                    f"[sweep {done}/{total}] {trace} {mid} seed={seed} "
                    f"cached, skipping",
                    flush=True,
                )
                continue
            pending.append((seed, argv_exp, marker))
        if not pending:
            continue
        t0 = time.perf_counter()
        if len(pending) > 1 and not args.no_batch:
            runner.run_experiment_batch(
                [runner.get_args(a) for _, a, _ in pending]
            )
            for _, argv_exp, marker in pending:
                marker.write_text(" ".join(argv_exp))
        else:
            # per-seed markers: a failure on a late seed must not discard
            # earlier seeds' completion records
            for _, argv_exp, marker in pending:
                runner.run_experiment(runner.get_args(argv_exp))
                marker.write_text(" ".join(argv_exp))
        done += len(pending)
        print(
            f"[sweep {done}/{total}] {trace} {mid} "
            f"seeds={[s for s, _, _ in pending]} "
            f"{time.perf_counter() - t0:.1f}s "
            f"(total {time.perf_counter() - t_all:.0f}s)",
            flush=True,
        )
    print(f"[sweep] {total} experiments in {time.perf_counter() - t_all:.0f}s")


if __name__ == "__main__":
    from tpusim.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
