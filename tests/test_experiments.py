"""Experiment-harness tests: run.py → log → analysis CSVs → merge → plots
(ref: scripts/generate_config_and_run.py + scripts/analysis.py +
experiments/analysis/merge_*.py, exercised on a tiny synthetic trace)."""

import csv
import importlib.util
import os
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
EXP = REPO / "experiments"


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _write_tiny_trace(dirpath: Path):
    node_csv = dirpath / "nodes.csv"
    pod_csv = dirpath / "tiny_trace.csv"
    with open(node_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["sn", "cpu_milli", "memory_mib", "gpu", "model"])
        w.writerow(["n-0", 32000, 65536, 2, "V100M16"])
        w.writerow(["n-1", 64000, 131072, 4, "A100"])
    with open(pod_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(
            [
                "name",
                "cpu_milli",
                "memory_mib",
                "num_gpu",
                "gpu_milli",
                "gpu_spec",
                "qos",
                "pod_phase",
                "creation_time",
                "deletion_time",
                "scheduled_time",
            ]
        )
        for i in range(8):
            w.writerow(
                [f"pod-{i}", 2000, 4096, 1, 500 if i % 2 else 1000, "", "LS", "Running", 0, 0, 0]
            )
    return node_csv, pod_csv


def test_run_analysis_merge_plot(tmp_path):
    import jax

    cache_before = jax.config.jax_compilation_cache_dir
    run = _load("exp_run", EXP / "run.py")
    # loading the runner as a library re-points nothing process-wide: the
    # compile cache is placed by entry points (tpusim.compile_cache)
    assert jax.config.jax_compilation_cache_dir == cache_before
    node_csv, pod_csv = _write_tiny_trace(tmp_path)
    outdir = tmp_path / "data" / "tiny_trace" / "06-FGD" / "1.0" / "42"
    args = run.get_args(
        [
            "-d",
            str(outdir),
            "-f",
            str(pod_csv),
            "--node-trace",
            str(node_csv),
            "-FGD",
            "1000",
            "-gpusel",
            "FGDScore",
            "--emit-configs",
        ]
    )
    result = run.run_experiment(args)
    assert result["summary"]["unscheduled"] == 0
    assert (outdir / "simon.log").is_file()

    # a second policy for the cross-policy power deliverable below
    outdir2 = tmp_path / "data" / "tiny_trace" / "05-BestFit" / "1.0" / "42"
    args2 = run.get_args(
        [
            "-d", str(outdir2), "-f", str(pod_csv),
            "--node-trace", str(node_csv), "-BestFit", "1000",
        ]
    )
    run.run_experiment(args2)
    # per-event series parsed back out of the log
    assert len(result["allo"]["used_gpu_milli"]) == 8
    assert result["allo"]["used_gpu_milli"][-1] == 6000  # 4×1000 + 4×500
    assert result["cdol"]["event"] == ["create"] * 8
    assert result["cdol"]["cum_pod"][-1] == 8
    # the cluster-analysis block made it into the summary row
    assert result["summary"]["milli_gpu_init_schedule"] == 100.0
    # emit-configs wrote the reproducible YAML pair
    assert list(outdir.glob("cc_md*.yaml")) and list(outdir.glob("sc_md*.yaml"))

    # merge into discrete tables
    merge = _load("exp_merge", EXP / "merge.py")
    results_dir = tmp_path / "results"
    merge.merge(tmp_path / "data", results_dir)
    with open(results_dir / "analysis_allo_discrete.csv", newline="") as f:
        rows = list(csv.DictReader(f))
    assert rows[0]["workload"] == "tiny_trace"
    assert rows[0]["sc_policy"] == "05-BestFit"
    assert float(rows[0]["100"]) == 100.0  # fully allocated at 100% load

    # power/usage/failed merges (the fork's notebook-1 parse, round 4)
    with open(results_dir / "analysis_pwr_discrete.csv", newline="") as f:
        pwr_rows = list(csv.DictReader(f))
    # one row per experiment per series, cluster = cpu + gpu at each sample
    by_series = {
        r["series"]: r for r in pwr_rows if r["sc_policy"] == "06-FGD"
    }
    assert set(by_series) == {"cluster", "cpu", "gpu"}
    assert float(by_series["cluster"]["100"]) == pytest.approx(
        float(by_series["cpu"]["100"]) + float(by_series["gpu"]["100"]), abs=0.05
    )
    with open(results_dir / "analysis_usage_discrete.csv", newline="") as f:
        usage_rows = list(csv.DictReader(f))
    # all 8 tiny pods schedule -> used == arrived at 100% load
    assert float(usage_rows[0]["100"]) == pytest.approx(1.0, abs=0.01)
    assert (results_dir / "analysis_failed_discrete.csv").is_file()

    # power deliverable: figures + tables from the merged artifact alone
    power = _load("exp_power", EXP / "power.py")
    power_dir = tmp_path / "power"
    sys.argv = [
        "power.py", "--merged", str(results_dir), "--out", str(power_dir)
    ]
    power.main()
    assert (power_dir / "power_savings_tiny_trace.png").is_file()
    assert (power_dir / "usage_efficiency_tiny_trace.png").is_file()
    assert (power_dir / "failed_relative_tiny_trace.png").is_file()
    md = (power_dir / "power_tables.md").read_text()
    assert "GRAR" in md and "06-FGD" in md and "05-BestFit" in md
    tex = (power_dir / "power_tables.tex").read_text()
    assert "\\begin{tabular}" in tex and "Savings" in tex

    # trace families with percentage suffixes must emit LaTeX-safe headers
    # (a raw % would comment out the rest of the header row)
    power.emit_tables(
        {"openb_pod_list_cpu": {"06-FGD": {"050": 0.95, "100": 0.97}}},
        {},
        power_dir,
    )
    tex2 = (power_dir / "power_tables.tex").read_text()
    assert "GRAR (050\\%)" in tex2
    assert "(050%)" not in tex2

    # plots render from the merged tables
    plot = _load("exp_plot", EXP / "plot" / "plot_openb.py")
    figdir = tmp_path / "figures"
    sys.argv = [
        "plot_openb.py",
        "--results",
        str(results_dir),
        "--out-dir",
        str(figdir),
        "--workload",
        "tiny_trace",
    ]
    plot.main()
    assert (figdir / "openb_alloc.png").is_file()

    # compare tool runs over the merged tables (no reference rows for the
    # tiny trace — prints ours-only cells and says so)
    import contextlib
    import io

    cmp_mod = _load("exp_compare", EXP / "compare.py")
    # the tiny workload tops out at 100% arrived load, so compare at 100
    sys.argv = ["compare.py", "--merged", str(results_dir), "--at", "100"]
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cmp_mod.main()
    out = buf.getvalue()
    assert "tiny_trace" in out and "FGD" in out
    assert "100.00" in out  # the fully-allocated @100 cell
    assert "no overlapping reference cells" in out


def test_analysis_lanes_byte_identical(tmp_path):
    """The direct array->CSV lane (default) and the log-reparse lane
    (--analysis-from-log) must write byte-identical CSV families — on a
    trace exercising failures (an unfittable pod), deletions
    (deletion_time + --use-timestamps), and the failed-create rollback
    calculus (the --engine knob also gets a forced-table pass here)."""
    run = _load("exp_run2", EXP / "run.py")
    node_csv, _ = _write_tiny_trace(tmp_path)
    pod_csv = tmp_path / "mix_trace.csv"
    with open(pod_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(
            ["name", "cpu_milli", "memory_mib", "num_gpu", "gpu_milli",
             "gpu_spec", "qos", "pod_phase", "creation_time",
             "deletion_time", "scheduled_time"]
        )
        for i in range(10):
            w.writerow([f"pod-{i}", 2000, 4096, 1, 500, "", "LS",
                        "Running", i, i + 20 if i % 3 == 0 else 0, 0])
        # unfittable: more CPU than any node has
        w.writerow(["pod-big", 99000000, 4096, 0, 0, "", "LS", "Running",
                    5, 0, 0])

    outs = {}
    for lane, extra in (("direct", ()), ("log", ("--analysis-from-log",))):
        outdir = tmp_path / lane
        run.run_experiment(run.get_args(
            ["-d", str(outdir), "-f", str(pod_csv), "--node-trace",
             str(node_csv), "-FGD", "1000", "-gpusel", "FGDScore",
             "--use-timestamps", "--engine", "table", *extra]
        ))
        outs[lane] = outdir
    files = sorted(
        p.name for p in outs["direct"].iterdir()
        if p.name.startswith("analysis")
    )
    assert "analysis_fail.csv" in files  # the unfittable pod failed
    for name in files:
        a = (outs["direct"] / name).read_bytes()
        b = (outs["log"] / name).read_bytes()
        assert a == b, f"{name} differs between analysis lanes"


def test_seed_group_writes_what_single_runs_write(tmp_path):
    """The artifact's batched route (experiments/sweep.py's unit:
    run_experiment_batch, the seeds of one trace and method as ONE sweep)
    writes a simon.log and analysis CSVs byte-identical to one
    run_experiment a seed, on a shuffled trace tuned past what the cluster
    holds (rejected creates in every seed)."""
    run = _load("exp_run_group", EXP / "run.py")
    node_csv, pod_csv = _write_tiny_trace(tmp_path)

    def argv(root, seed):
        return run.get_args(
            ["-d", str(tmp_path / root / str(seed)), "-f", str(pod_csv),
             "--node-trace", str(node_csv), "-FGD", "1000", "-gpusel",
             "FGDScore", "-tune", "1.3", "-tuneseed", str(seed),
             "--shuffle-pod", "true"]
        )

    seeds = (42, 43)
    for seed in seeds:
        run.run_experiment(argv("single", seed))
    results = run.run_experiment_batch([argv("group", s) for s in seeds])
    assert len(results) == 2
    assert all(r["summary"]["unscheduled"] > 0 for r in results)
    for seed in seeds:
        single = tmp_path / "single" / str(seed)
        group = tmp_path / "group" / str(seed)
        files = sorted(p.name for p in single.iterdir())
        assert files == sorted(p.name for p in group.iterdir())
        assert "simon.log" in files and "analysis_frag.csv" in files
        for name in files:
            assert (single / name).read_bytes() == (
                group / name).read_bytes(), f"{name} differs for seed {seed}"


def test_reused_simulator_lanes_stay_identical(tmp_path):
    """Calling run() twice on one Simulator must not double-count the
    direct-CSV stashes vs the log lane (ADVICE r4): both lanes reflect the
    LAST run only, byte-identically."""
    import sys

    sys.path.insert(0, str(EXP))
    from analysis import build_result_from_sim, parse_log

    from tpusim.io.trace import load_node_csv, load_pod_csv
    from tpusim.sim.driver import Simulator, SimulatorConfig

    node_csv, pod_csv = _write_tiny_trace(tmp_path)
    sim = Simulator(
        load_node_csv(str(node_csv)),
        SimulatorConfig(policies=(("FGDScore", 1000),), seed=1),
    )
    sim.set_workload_pods(load_pod_csv(str(pod_csv)))
    sim.run()
    sim.finish()
    sim.run()  # reuse: stashes and log must reset
    sim.finish()
    assert len(sim.event_reports) == 1
    log_path = tmp_path / "simon.log"
    log_path.write_text(sim.log.dump())
    direct = build_result_from_sim(sim)
    parsed = parse_log(str(log_path))
    assert direct["frag"] == parsed["frag"]
    assert direct["allo"] == parsed["allo"]
    assert direct["summary"]["unscheduled"] == parsed["summary"]["unscheduled"]


def test_generate_run_scripts(capsys):
    gen = _load("exp_gen", EXP / "generate_run_scripts.py")
    sys.argv = [
        "generate_run_scripts.py",
        "--seeds",
        "2",
        "--traces",
        "openb_pod_list_default",
        "--methods",
        "06-FGD",
        "01-Random",
    ]
    gen.main()
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    assert len(lines) == 4  # 1 trace × 2 methods × 2 seeds
    assert all("experiments/run.py" in l for l in lines)
    assert any("-FGD 1000" in l and "-tuneseed 43" in l for l in lines)


def test_analysis_stop_marker(tmp_path):
    """Lines after `there are N unscheduled pods` are ignored, matching the
    reference parser's break (scripts/analysis.py log_to_csv)."""
    ana = _load("exp_ana", EXP / "analysis.py")
    log = tmp_path / "x.log"
    log.write_text(
        'time="t" level=info msg="[Report]; Frag amount: 10.00; Frag ratio: 5.00%; Q124 ratio: 1.00%; (origin)\\n"\n'
        'time="t" level=info msg="there are 3 unscheduled pods\\n"\n'
        'time="t" level=info msg="[Report]; Frag amount: 99.00; Frag ratio: 9.00%; Q124 ratio: 9.00%; (origin)\\n"\n'
    )
    out = ana.parse_log(str(log))
    assert out["summary"]["unscheduled"] == 3
    assert out["frag"]["origin_milli"] == [10.0]


def test_bellman_series_cache_identical(tmp_path, monkeypatch):
    """The persistent Bellman-series cache (content-keyed, like the XLA
    compile cache) must reproduce uncached results byte-identically — incl.
    multi-stage experiments, where a first-call cache hit replays its
    inputs before any later stage evaluates (memo-order dependence)."""
    run = _load("exp_run_bc", EXP / "run.py")
    node_csv, pod_csv = _write_tiny_trace(tmp_path)
    base = ["-f", str(pod_csv), "--node-trace", str(node_csv),
            "-FGD", "1000", "-gpusel", "FGDScore",
            "--workload-inflation-ratio", "1.6"]  # second bellman stage

    outs = {}
    # warm2 exercises the second-warm-run ordering hazard: a first-call
    # hit must not let LATER stages read/write the cache (their values
    # embed the warmed memo's evaluation order)
    for label, cache in (("nocache", ""), ("cold", str(tmp_path / "bc")),
                         ("warm", str(tmp_path / "bc")),
                         ("warm2", str(tmp_path / "bc"))):
        monkeypatch.setenv("TPUSIM_BELLMAN_CACHE", cache)
        outdir = tmp_path / label
        run.run_experiment(run.get_args(["-d", str(outdir)] + base))
        outs[label] = outdir
    entries = list((tmp_path / "bc").glob("*.npy"))
    assert len(entries) == 1, "only the FIRST stage's series may be cached"
    for name in ("analysis.csv", "analysis_frag.csv", "analysis_allo.csv"):
        ref = (outs["nocache"] / name).read_bytes()
        for label in ("cold", "warm", "warm2"):
            assert (outs[label] / name).read_bytes() == ref, f"{name} ({label})"
