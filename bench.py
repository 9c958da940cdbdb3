#!/usr/bin/env python
"""Headline benchmark: full openb production-trace replay under FGD.

Mirrors the reference's flagship experiment (openb_pod_list_default,
FGD policy, workload tuning ratio 1.3 — experiments/README.md): 1523 nodes /
6212 GPUs, ~10.6k pod placements after tuning. The reference takes ~10 min on
2 vCPU for this replay (≈13.6 placements/sec, BASELINE.md); here the whole
event loop is one compiled lax.scan on the TPU.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "placements/sec", "vs_baseline": N,
   "engine": "...", "platform": "...", "device_kind": "...",
   "device_count": N}
plus auxiliary quality numbers (GPU allocation ratio) on stderr. The
device fields come from tpusim.obs.bench.device_stamp(): a backend that is
not a TPU is an error unless the caller set JAX_PLATFORMS=cpu.

Methodology (pinned round 5): minimum over WARM_RUNS (6) warm replays
after one compile run; raw samples ship alongside (wall_samples_s).

`--all` additionally measures every sweep policy (the 6 reference-cached
methods + PWR), pinning the sequential path's throughput (RandomScore /
gpu_sel=random cannot use the table engine), writing the rows to
BENCH_DETAILS.json (stderr shows them too).
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

from tpusim.obs import bench as obs_bench  # noqa: E402 (path insert above)
from tpusim.obs.bench import WARM_RUNS  # noqa: E402  timing protocol home

# Implied reference throughput: 8152 placements / ~10 min on 2 vCPU
# (BASELINE.md "Implied placement throughput").
BASELINE_PLACEMENTS_PER_SEC = 13.59

# (name, policies, gpu_sel, dim_ext, norm) — the sweep's method configs
# (experiments/generate_run_scripts.py METHODS)
POLICY_ROWS = [
    ("Random", (("RandomScore", 1000),), "random", "merge", "max"),
    ("DotProd", (("DotProductScore", 1000),), "best", "merge", "max"),
    ("GpuClustering", (("GpuClusteringScore", 1000),), "best", "share", "max"),
    ("GpuPacking", (("GpuPackingScore", 1000),), "best", "share", "max"),
    ("BestFit", (("BestFitScore", 1000),), "best", "share", "max"),
    ("FGD", (("FGDScore", 1000),), "FGDScore", "share", "max"),
    ("PWR", (("PWRScore", 1000),), "PWRScore", "share", "max"),
]


# the headline: exact flags of the reference's 1020-experiment protocol
# (FGD row): -FGD 1000 -gpusel FGD -dimext share -norm max -tune 1.3
# -tuneseed 42 --shuffle-pod=true
HEADLINE_ROW = next(r for r in POLICY_ROWS if r[0] == "FGD")


def load_trace():
    from tpusim.io.trace import load_node_csv, load_pod_csv

    node_csv = os.path.join(REPO, "data/csv/openb_node_list_gpu_node.csv")
    pod_csv = os.path.join(REPO, "data/csv/openb_pod_list_default.csv")
    return load_node_csv(node_csv), load_pod_csv(pod_csv)


def gpu_alloc_pct(state) -> float:
    import numpy as np

    from tpusim.constants import MILLI

    slot = np.arange(state.gpu_left.shape[1])[None, :] < state.gpu_cnt[:, None]
    milli_used = int(np.where(slot, MILLI - state.gpu_left, 0).sum())
    return 100.0 * milli_used / (int(state.gpu_cnt.sum()) * MILLI)


def replay_summary(result) -> dict:
    """The backend-independent outcome of one replay: event count,
    placements and end-state GPU allocation (bench rows, chip_smoke.py)."""
    import jax
    import numpy as np

    events = int(result.event_node.shape[0])
    state = jax.tree.map(np.asarray, result.state)
    return {
        "events": events,
        "placements": events - int(np.asarray(result.ever_failed).sum()),
        "gpu_alloc_pct": round(gpu_alloc_pct(state), 2),
    }


def prepare_replay(nodes, pods, policies, gpu_sel, dim_ext, norm,
                   engine="auto", profile=False):
    """The headline replay as (sim, run): the reference protocol's tuned,
    shuffled openb workload under one policy row, and a nullary `run()`
    that replays it once through Simulator.run_events, blocks on the
    result and returns it. Shared by measure_policy and chip_smoke.py so
    the smoke drives exactly the configuration the benchmark times."""
    import jax
    import jax.numpy as jnp

    from tpusim.io.trace import build_events, pods_to_specs
    from tpusim.sim.driver import Simulator, SimulatorConfig
    from tpusim.sim.typical import TypicalPodsConfig

    cfg = SimulatorConfig(
        policies=policies,
        gpu_sel_method=gpu_sel,
        dim_ext_method=dim_ext,
        norm_method=norm,
        tuning_ratio=1.3,
        tuning_seed=42,
        seed=42,
        shuffle_pod=True,
        report_per_event=False,
        engine=engine,
        profile=profile,
        typical_pods=TypicalPodsConfig(pod_popularity_threshold=95),
    )
    sim = Simulator(nodes, cfg)
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    trace = sim.prepare_pods()
    specs = pods_to_specs(trace)
    ev_kind, ev_pod = build_events(trace)
    ev_kind, ev_pod = jnp.asarray(ev_kind), jnp.asarray(ev_pod)
    key = jax.random.PRNGKey(cfg.seed)

    def run():
        res = sim.run_events(sim.init_state, specs, ev_kind, ev_pod, key, bucket=1)
        jax.block_until_ready(res.state)
        return res

    return sim, run


def measure_policy(nodes, pods, name, policies, gpu_sel, dim_ext, norm,
                   warm_runs=WARM_RUNS, profile=False):
    """One policy's replay throughput + end-state quality (both engines
    where the config allows; the table engine rejects per-event
    randomness). Timing = the shared cold + warm-minimum protocol
    (tpusim.obs.bench.measure). profile=True runs under obs profiling and
    returns the RunTelemetry in the row's `_telemetry` key (the bench
    gate's smoke profile)."""
    sim, replay = prepare_replay(
        nodes, pods, policies, gpu_sel, dim_ext, norm, profile=profile
    )
    box = {}

    def run():
        box["result"] = replay()

    m = obs_bench.measure(run, warm_runs)
    wall = m["min_s"]
    outcome = replay_summary(box["result"])
    row = obs_bench.round_row({
        "policy": name,
        "engine": sim._last_engine,
        "events": outcome["events"],
        "placements": outcome["placements"],
        "wall_s": wall,
        "wall_samples_s": m["samples_s"],
        "placements_per_sec": round(outcome["placements"] / wall, 1),
        "gpu_alloc_pct": outcome["gpu_alloc_pct"],
        "compile_first_s": round(m["first_s"], 1),
        **obs_bench.device_stamp(),
    })
    if profile:
        row["_telemetry"] = sim.run_telemetry()
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--all", action="store_true",
        help="per-policy rows -> BENCH_DETAILS.json",
    )
    args = ap.parse_args()
    from tpusim.compile_cache import enable_compile_cache

    enable_compile_cache()
    obs_bench.device_stamp()  # no chip and no JAX_PLATFORMS=cpu: stop here
    nodes, pods = load_trace()

    head = measure_policy(nodes, pods, *HEADLINE_ROW)
    print(
        f"[bench] events={head['events']} placed={head['placements']} "
        f"wall={head['wall_s']:.2f}s "
        f"(first incl. compile {head['compile_first_s']:.1f}s) "
        f"gpu_alloc={head['gpu_alloc_pct']:.2f}% "
        f"engine={head['engine']} platform={head['platform']} "
        f"device_kind={head['device_kind']!r} x{head['device_count']}",
        file=sys.stderr,
    )

    if args.all:
        rows = []
        for name, policies, gpu_sel, dim_ext, norm in POLICY_ROWS:
            row = (
                head
                if name == "FGD"
                else measure_policy(
                    nodes, pods, name, policies, gpu_sel, dim_ext, norm
                )
            )
            rows.append(row)
            print(f"[bench-all] {json.dumps(row)}", file=sys.stderr)
        obs_bench.write_json(
            os.path.join(REPO, "BENCH_DETAILS.json"),
            {
                "config": "openb_pod_list_default, tune 1.3, seed 42, "
                "warm steady-state",
                **obs_bench.device_stamp(),
                "baseline_placements_per_sec": BASELINE_PLACEMENTS_PER_SEC,
                "rows": rows,
            },
        )

    print(
        json.dumps(
            {
                "metric": "openb default-trace FGD replay throughput (tune 1.3)",
                "value": head["placements_per_sec"],
                "unit": "placements/sec",
                "vs_baseline": round(
                    head["placements_per_sec"] / BASELINE_PLACEMENTS_PER_SEC, 1
                ),
                "engine": head["engine"],
                **obs_bench.device_stamp(),
            }
        )
    )


if __name__ == "__main__":
    main()
