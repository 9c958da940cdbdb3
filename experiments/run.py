#!/usr/bin/env python
"""One-experiment runner (ref: scripts/generate_config_and_run.py).

Mirrors the reference harness's flag surface — trace file, score-policy
weights, tuning/inflation/deschedule knobs, typical-pod knobs, snapshot
export prefixes — but drives the TPU simulator in-process from the CSV
trace instead of generating YAML configs and shelling out to a Go binary.
With --emit-configs it additionally writes the equivalent cluster-config
and scheduler-config YAML (md5-suffixed, like the reference), so the same
experiment can be reproduced through `python -m tpusim apply`.

Writes <exp-dir>/simon.log (reference-format log lines) and then runs
experiments/analysis.py over it, producing analysis{,_frag,_allo,_cdol,
_pwr}.csv in the same directory.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from hashlib import md5
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


SCORE_POLICY_ABBR = {
    "Simon": "Simon",
    "RandomScore": "Random",
    "DotProductScore": "DotProd",
    "GpuClusteringScore": "GpuClustering",
    "GpuPackingScore": "GpuPacking",
    "BestFitScore": "BestFit",
    "FGDScore": "FGD",
    "PWRScore": "PWR",
}


def get_args(argv=None):
    p = argparse.ArgumentParser(description="run one simulator experiment")
    p.add_argument("-d", "--experiment-dir", type=str, default="./")
    p.add_argument(
        "-f",
        "--trace",
        type=str,
        default="data/csv/openb_pod_list_default.csv",
        help="pod-trace CSV (or a name like openb_pod_list_default)",
    )
    p.add_argument(
        "--node-trace",
        type=str,
        default="data/csv/openb_node_list_gpu_node.csv",
        help="node-trace CSV",
    )
    p.add_argument("-r", "--deschedule-ratio", type=float, default=0.0)
    p.add_argument("-p", "--deschedule-policy", type=str, default="")
    p.add_argument("-y", "--export-pod-snapshot-yaml-file-prefix", default=None)
    p.add_argument("-z", "--export-node-snapshot-csv-file-prefix", default=None)
    p.add_argument("--is-involved-cpu-pods", type=str, default="true")
    p.add_argument("--pod-popularity-threshold", type=int, default=95)
    p.add_argument("--pod-increase-step", type=int, default=1)
    p.add_argument("--gpu-res-weight", type=float, default=0)
    p.add_argument("--shuffle-pod", type=str, default="false")
    p.add_argument("--workload-inflation-ratio", type=float, default=1)
    p.add_argument("-seed", "--workload-inflation-seed", type=int, default=233)
    p.add_argument("-tune", "--workload-tuning-ratio", type=float, default=0)
    p.add_argument("-tuneseed", "--workload-tuning-seed", type=int, default=233)
    for abbr in SCORE_POLICY_ABBR.values():
        p.add_argument(f"-{abbr}", type=int, default=0, help="score weight")
    p.add_argument("-gpusel", "--gpu-sel-method", type=str, default="best")
    p.add_argument("-dimext", "--dim-ext-method", type=str, default="share")
    p.add_argument("-norm", "--norm-method", type=str, default="max")
    p.add_argument(
        "--use-timestamps",
        action="store_true",
        help="annotation-driven create+delete replay: expand each pod into "
        "creation (+deletion, when deletion_time is set) events stable-"
        "sorted by timestamp (ref: simulator.go:672-717)",
    )
    p.add_argument(
        "--no-per-event-report",
        action="store_true",
        help="skip per-event [Report]/[Alloc]/[Power] lines (faster, "
        "summary analysis only)",
    )
    p.add_argument(
        "--emit-configs",
        action="store_true",
        help="also write the equivalent cluster/scheduler YAML configs",
    )
    p.add_argument(
        "--engine", type=str, default="auto",
        help="replay engine: auto | sequential | table | pallas (ENGINES.md)",
    )
    p.add_argument(
        "--mesh", type=int, default=0,
        help="shard the node axis over an N-device mesh (shard_map "
        "engine, MULTICHIP.md); placements and merged CSVs are identical "
        "to single-device runs",
    )
    p.add_argument(
        "--analysis-from-log",
        action="store_true",
        help="build the analysis CSVs by re-parsing simon.log (the "
        "reference's log_to_csv lane) instead of directly from the "
        "driver's arrays; outputs are byte-identical either way "
        "(tests/test_experiments.py pins it)",
    )
    return p.parse_args(argv)


def resolve_trace(path_or_name: str, default_dir: Path) -> str:
    if os.path.isfile(path_or_name):
        return path_or_name
    name = os.path.basename(path_or_name).replace(".csv", "")
    cand = default_dir / f"{name}.csv"
    if cand.is_file():
        return str(cand)
    raise FileNotFoundError(f"trace not found: {path_or_name}")


def selected_policies(args):
    pol = []
    for name, abbr in SCORE_POLICY_ABBR.items():
        w = getattr(args, abbr, 0)
        if w > 0:
            pol.append((name, w))
    return pol or [("FGDScore", 1000)]


def emit_configs(args, policies, outdir: Path):
    """Write the reference-shape YAML pair with md5-suffixed names
    (generate_config_and_run.py cc_/sc_ naming)."""
    import yaml

    cc = {
        "apiVersion": "simon/v1alpha1",
        "kind": "Config",
        "metadata": {"name": "tpusim-experiment"},
        "spec": {
            "cluster": {"customConfig": str(args.trace)},
            "customConfig": {
                "shufflePod": args.shuffle_pod.lower() == "true",
                "useTimestamps": args.use_timestamps,
                "workloadInflationConfig": {
                    "ratio": args.workload_inflation_ratio,
                    "seed": args.workload_inflation_seed,
                },
                "workloadTuningConfig": {
                    "ratio": args.workload_tuning_ratio,
                    "seed": args.workload_tuning_seed,
                },
                "descheduleConfig": {
                    "ratio": args.deschedule_ratio,
                    "policy": args.deschedule_policy,
                },
                "typicalPodsConfig": {
                    "isInvolvedCpuPods": args.is_involved_cpu_pods.lower()
                    == "true",
                    "podPopularityThreshold": args.pod_popularity_threshold,
                    "podIncreaseStep": args.pod_increase_step,
                    "gpuResWeight": args.gpu_res_weight,
                },
            },
        },
    }
    sc = {
        "apiVersion": "kubescheduler.config.k8s.io/v1beta1",
        "kind": "KubeSchedulerConfiguration",
        "percentageOfNodesToScore": 100,
        "profiles": [
            {
                "schedulerName": "simon-scheduler",
                "plugins": {
                    "score": {
                        "enabled": [
                            {"name": n, "weight": w} for n, w in policies
                        ]
                    }
                },
                "pluginConfig": [
                    {
                        "name": "Open-Gpu-Share",
                        "args": {
                            "dimExtMethod": args.dim_ext_method,
                            "normMethod": args.norm_method,
                            "gpuSelMethod": args.gpu_sel_method,
                        },
                    }
                ],
            }
        ],
    }
    for prefix, doc in (("cc", cc), ("sc", sc)):
        content = yaml.dump(doc)
        suffix = md5(content.encode()).hexdigest()[:4]
        (outdir / f"{prefix}_md{suffix}.yaml").write_text(content)


_TRACE_CACHE = {}


def _load_trace_cached(path: str, loader):
    """Trace CSVs are immutable inputs shared by every experiment of a
    sweep (rows are never mutated — clones go through dataclasses.replace);
    one parse per (path, mtime) saves ~0.15 s × 2100 experiments."""
    key = (loader.__name__, path, os.path.getmtime(path))
    if key not in _TRACE_CACHE:
        _TRACE_CACHE[key] = loader(path)
    return list(_TRACE_CACHE[key])


def _build_sim(args):
    """Construct the configured Simulator + outdir/paths for one experiment
    (the setup half of run_experiment)."""
    if getattr(args, "mesh", 0) and args.mesh > 1:
        # JAX_PLATFORMS=cpu + --mesh N: the mesh is N virtual CPU devices
        # (nothing happens on an accelerator host); must come from the
        # leaf module BEFORE anything initializes the backend
        from tpusim.virtual_mesh import virtual_cpu_devices

        virtual_cpu_devices(args.mesh)
    from tpusim.io.trace import load_node_csv, load_pod_csv
    from tpusim.sim.driver import Simulator, SimulatorConfig
    from tpusim.sim.typical import TypicalPodsConfig

    outdir = Path(args.experiment_dir)
    outdir.mkdir(parents=True, exist_ok=True)
    pod_csv = resolve_trace(args.trace, REPO / "data/csv")
    node_csv = resolve_trace(args.node_trace, REPO / "data/csv")
    policies = selected_policies(args)
    if args.emit_configs:
        emit_configs(args, policies, outdir)

    cfg = SimulatorConfig(
        policies=tuple(policies),
        gpu_sel_method=args.gpu_sel_method,
        dim_ext_method=args.dim_ext_method,
        norm_method=args.norm_method,
        shuffle_pod=args.shuffle_pod.lower() == "true",
        tuning_ratio=args.workload_tuning_ratio,
        tuning_seed=args.workload_tuning_seed,
        inflation_ratio=args.workload_inflation_ratio,
        inflation_seed=args.workload_inflation_seed,
        deschedule_ratio=args.deschedule_ratio,
        deschedule_policy=args.deschedule_policy,
        seed=args.workload_tuning_seed,
        report_per_event=not args.no_per_event_report,
        use_timestamps=args.use_timestamps,
        engine=args.engine,
        mesh=args.mesh,
        typical_pods=TypicalPodsConfig(
            is_involved_cpu_pods=args.is_involved_cpu_pods.lower() == "true",
            pod_popularity_threshold=args.pod_popularity_threshold,
            pod_increase_step=args.pod_increase_step,
            gpu_res_weight=args.gpu_res_weight,
        ),
    )
    sim = Simulator(_load_trace_cached(node_csv, load_node_csv), cfg)
    sim.set_workload_pods(_load_trace_cached(pod_csv, load_pod_csv))
    return sim, outdir, pod_csv, policies


def _post_run(sim, args, outdir, pod_csv, policies, t0) -> dict:
    """Everything after the main schedule: inflation/deschedule stages,
    exports, log write, analysis CSVs (the tail half of run_experiment)."""
    if args.workload_inflation_ratio > 1:
        sim.run_workload_inflation_evaluation("ScheduleInflation")
    if args.deschedule_ratio > 0 and args.deschedule_policy:
        sim.deschedule_cluster()
        sim.cluster_analysis("PostDeschedule")
        if args.workload_inflation_ratio > 1:
            sim.run_workload_inflation_evaluation("DescheduleInflation")
    if args.export_pod_snapshot_yaml_file_prefix:
        path = f"{args.export_pod_snapshot_yaml_file_prefix}.yaml"
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        sim.export_pod_snapshot_yaml(path)
    if args.export_node_snapshot_csv_file_prefix:
        path = f"{args.export_node_snapshot_csv_file_prefix}.csv"
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        sim.export_node_snapshot_csv(path)
    sim.finish()
    wall = time.perf_counter() - t0

    log_path = outdir / "simon.log"
    with open(log_path, "w") as f:
        f.write(sim.log.dump())
    print(f"[run] {log_path} ({wall:.1f}s, {sim.last_result.events} events)")

    sys.path.insert(0, str(Path(__file__).parent))
    from analysis import analyze_dir, analyze_sim

    meta = {
        "workload": Path(pod_csv).stem,
        "policy": "_".join(f"{SCORE_POLICY_ABBR[n]}{w}" for n, w in policies),
        "tune": args.workload_tuning_ratio,
        "tune_seed": args.workload_tuning_seed,
        "de": args.dim_ext_method,
        "gs": args.gpu_sel_method,
        "dr": args.deschedule_ratio,
        "dp": args.deschedule_policy,
    }
    if args.analysis_from_log:
        return analyze_dir(str(outdir), meta)
    return analyze_sim(sim, str(outdir), meta)


def run_experiment(args) -> dict:
    sim, outdir, pod_csv, policies = _build_sim(args)
    t0 = time.perf_counter()
    sim.run()
    return _post_run(sim, args, outdir, pod_csv, policies, t0)


def run_experiment_batch(args_list) -> list:
    """Run a seed group (same trace/policy/knobs, different seeds) as ONE
    vmapped replay, the sweep's (driver.run_batch). Produces per-experiment
    outputs identical to run_experiment: the group only changes how the
    main schedules execute on the chip."""
    from tpusim.sim.driver import run_batch

    t0 = time.perf_counter()
    built = [_build_sim(a) for a in args_list]
    run_batch([b[0] for b in built])
    # each experiment reports its fair share of the group's phase plus its
    # own post-run stages, not the whole group's elapsed time
    shared = (time.perf_counter() - t0) / len(built)
    return [
        _post_run(
            sim, args, outdir, pod_csv, policies,
            time.perf_counter() - shared,
        )
        for args, (sim, outdir, pod_csv, policies) in zip(args_list, built)
    ]


if __name__ == "__main__":
    from tpusim.compile_cache import enable_compile_cache

    enable_compile_cache()
    run_experiment(get_args())
