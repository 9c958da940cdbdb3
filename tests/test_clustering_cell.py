"""The clustering cell (`openb-clustering.report-seeds`, ISSUE 45): the FGD
artifact's row 03-GpuClustering (gpusel best) at the headline protocol's
own depth, whose program keeps the commit's add into `aff_cnt` in its event
loop.

(a) The configuration, the traffic mix and the BENCHMARK.json entries name
what the issue names. (b) The tiny cell runs through the `cluster_wave`
driver on the CPU and is `correct`, its records read `affinity_deferred` 0
and `affinity_readers` 1, its eight readers read; a reference that drops
the affinity add reads not correct. (c) The readers on records with and
without the fields. (d) The artifact's anchor on the whole cluster. Here
and not under benchmark/tests: the tier-1 lane runs it.
"""

import csv
import json
import os
import types

import numpy as np
import pytest

from benchmark.drivers import cluster_wave, load_wave, wave
from benchmark.lib import inputs, reference_clustering, sweep_log
from tests.test_table_reuse_metric import (  # noqa: F401  (fixtures)
    bench_run,
    compile_cache_put_back,
)
from tpusim.obs import sweep_log as program_log
from tpusim.sim import driver

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "openb-clustering.report-seeds"
NEW = ["cluster_step_us_per_lane_event", "affinity_in_scan",
       "affinity_readers", "cluster_rejected_create_share",
       "cluster_report_postpass_s", "cluster_fetch_copy_s",
       "cluster_host_lead_s", "cluster_host_tail_s"]
# name -> (the accepted entry it copies but for name and cell, the module of
# the reader it shares)
SHARED = {
    "cluster_step_us_per_lane_event": ("load_step_us_per_lane_event",) * 2,
    "cluster_rejected_create_share": ("rejected_create_share",) * 2,
    "cluster_report_postpass_s": ("report_postpass_s",) * 2,
    "cluster_fetch_copy_s": ("load_fetch_copy_s", "fetch_copy_s"),
    "cluster_host_lead_s": ("load_host_lead_s", "host_lead_s"),
    "cluster_host_tail_s": ("load_host_tail_s", "host_tail_s")}


def _json(*path) -> dict:
    with open(os.path.join(REPO, "benchmark", *path)) as f:
        return json.load(f)


# ---------------------------------------------------------------- (a)
def test_the_configuration_is_the_artifacts_row_03_at_the_load_cells_depth():
    config, load = _json("configs", "openb-clustering.json"), _json(
        "configs", "openb-load130.json")
    assert config["simulator"] == dict(
        load["simulator"], policies=[["GpuClusteringScore", 1000]],
        gpu_sel_method="best")
    assert config["simulator"]["report_per_event"] is True
    assert (config["simulator"]["dim_ext_method"],
            config["simulator"]["norm_method"],
            config["simulator"]["tuning_ratio"]) == ("share", "max", 1.3)
    # the policy row and nothing else: cluster, pod list, tuning seeds,
    # energy tables and the tiny deployment are the load configuration's
    for shared in ("cluster", "workload", "energy_model", "tiny",
                   "not_reduced", "assumed"):
        assert config[shared] == load[shared], shared
    assert config["reduced"] == ["families", "policies"]
    assert sorted(config["reduced_why"]) == config["reduced"]
    assert "depth" in config["not_reduced"]
    assert "03-GpuClustering" in config["source"] and len(
        config["source"]) <= 200 and config["source"] != load["source"]
    fourth = config["guarantees"][3]
    assert "GpuClusteringScore 1000" in fourth and "REPORTED" in fourth
    assert [g for i, g in enumerate(config["guarantees"]) if i != 3] == [
        g for i, g in enumerate(load["guarantees"]) if i != 3]
    # the row of the artifact's own method list
    from experiments.generate_run_scripts import METHODS

    assert ("03-GpuClustering", "-GpuClustering 1000", "best", "share",
            "max") in METHODS


def test_the_traffic_is_the_load_cells_under_the_new_driver():
    traffic, load = _json("traffic", "cluster-report-seeds.json"), _json(
        "traffic", "report-seeds-320.json")
    assert (traffic["driver"], traffic["lanes"], traffic["seeds_per_shuffle"],
            traffic["check_lanes"]) == ("cluster_wave", 320, 32, 1)
    for shared in ("events_by_shuffle", "lane_seeds", "lane_order", "report"):
        assert traffic[shared] == load[shared], shared
    assert sum(traffic["events_by_shuffle"]) == 108_313
    assert 32 * sum(traffic["events_by_shuffle"]) == 3_466_016
    assert traffic["record_must_read"] == {
        "affinity_deferred": 0, "affinity_readers": 1, "table_pass_events": 16}
    assert "scored_creates" not in traffic  # ALL of them are
    assert "TO BE FILLED" not in json.dumps(traffic)


def test_the_cell_and_its_eight_metrics_stand_as_entered(bench_run):
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    at = cells.index(CELL)
    assert cells[at - 1] == "openb-load130.report-seeds"
    assert bench["workloads"][at] == {
        "name": CELL, "config": "openb-clustering",
        "traffic": "cluster-report-seeds", "chips": 1,
        "why": bench["workloads"][at]["why"]}
    entry = next(c for c in bench["configs"] if c["name"] == "openb-clustering")
    assert (entry["file"], entry["reduced"], entry["source"]) == (
        "benchmark/configs/openb-clustering.json", ["families", "policies"],
        _json("configs", "openb-clustering.json")["source"])
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])
    assert names[first - 1] == "load_host_tail_s"
    assert names[first:first + len(NEW)] == NEW
    by_name = dict(zip(names, bench["per_layer"]))
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert hasattr(bench_run.load_module("layer_metrics", name), "read")
    for name, (control, module) in SHARED.items():
        assert by_name[name] == dict(
            by_name[control], name=name, workloads=[CELL])
        reader = bench_run.load_module("layer_metrics", name).read
        assert reader.__module__ == f"benchmark.layer_metrics.{module}"
    step = by_name["scan_s"]["layer"]
    for name in ("affinity_in_scan", "affinity_readers"):
        assert by_name[name] == {
            "name": name, "unit": by_name[name]["unit"], "better": "lower",
            "source": "program_counter", "layer": step,
            "moves": "lane_events_per_s", "workloads": [CELL]}
    # nothing that stood was touched: the cell is the only one of its
    # configuration, and no accepted metric lists it
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == "openb-clustering"] == [CELL]
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", []) and m["name"] not in NEW]


# ---------------------------------------------------------------- (b)
def _rehearse(bench_run, capsys, trace):
    assert bench_run.main([
        "--workload", CELL, "--seed", "3000000045", "--seconds", "0.5",
        "--trace", str(trace), "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_tiny_cells_traced_line_reads_the_add_in_the_scan(
        bench_run, capsys, compile_cache_put_back):
    for _ in range(3):
        got = _rehearse(bench_run, capsys, trace=1)
        assert got["correct"] is True and got["failed"] == 0
        # as in test_table_reuse_metric: a preempted tiny wave reads nothing
        if "affinity_in_scan" in got["metrics"]:
            break
    assert set(NEW) <= set(got["metrics"])
    # and the five that list no cell, less the device's own two
    assert {"host_s", "scan_s", "fetch_s"} <= set(got["metrics"])
    assert got["metrics"]["affinity_in_scan"] == {"value": 1, "unit": "share"}
    assert got["metrics"]["affinity_readers"] == {
        "value": 1, "unit": "policies"}
    assert 0.15 < got["metrics"]["cluster_rejected_create_share"]["value"] < 0.4
    assert got["metrics"]["cluster_report_postpass_s"]["value"] > 0
    tail = program_log()[-(got["attempted"] + 2):]
    # 2 shuffles x 2 seeds, each lane all of its own trace: 953 and 923
    assert {(rec.lanes, rec.events) for rec in tail} == {(4, 953)}
    assert {(rec.affinity_deferred, rec.affinity_readers) for rec in tail} == {
        (0, 1)}
    assert all(rec.to_dict()["affinity_readers"] == 1 for rec in tail)
    assert all("trace vmap" in rec.engine for rec in tail)


@pytest.mark.parametrize("control", ["dropped_add", "deferred_record"])
def test_a_bent_run_reads_not_correct(
        bench_run, capsys, compile_cache_put_back, monkeypatch, control):
    """What the cell is for, from the other side: a reference whose Bind
    drops the affinity add parts from the lane, and a record that says the
    program deferred the add is refused whatever the lanes hold."""
    if control == "dropped_add":
        whole = reference_clustering.replay
        monkeypatch.setattr(
            reference_clustering, "replay",
            lambda *a, **kw: whole(*a, **kw, count_affinity=False))
    else:
        sweep = driver.schedule_pods_sweep

        def deferred(sim, *args, **kw):
            out = sweep(sim, *args, **kw)
            sim.obs.sweeps[-1].affinity_deferred = 1
            return out

        monkeypatch.setattr(driver, "schedule_pods_sweep", deferred)
    got = _rehearse(bench_run, capsys, trace=0)
    assert got["correct"] is False
    assert set(got["metrics"]) == {"lane_events_per_s", "wave_s", "setup_s"}
    monkeypatch.undo()
    assert _rehearse(bench_run, capsys, trace=0)["correct"] is True


def test_record_gaps_passes_over_a_field_the_program_lacks():
    said = []
    old = types.SimpleNamespace(affinity_deferred=0, table_pass_events=16)
    got = cluster_wave.record_gaps(
        [old, old], {"affinity_deferred": 0, "affinity_readers": 1,
                     "table_pass_events": 16}, said.append)
    assert [(g, lim) for _, g, lim in got] == [(0, 0), (0, 0)]
    assert len(said) == 1 and "affinity_readers" in said[0]
    new = types.SimpleNamespace(affinity_deferred=1, affinity_readers=0,
                                table_pass_events=1)
    got = cluster_wave.record_gaps(
        [old, new], {"affinity_deferred": 0, "table_pass_events": 16}, print)
    assert [g for _, g, _ in got] == [1, 1]


# ---------------------------------------------------------------- (c)
def _record(spans=(), **fields):
    return types.SimpleNamespace(lanes=4, events=953, spans=list(spans),
                                 **fields)


def _span(name, dispatch_s=0.25, block_s=0.5):
    return types.SimpleNamespace(name=name, dispatch_s=dispatch_s,
                                 block_s=block_s,
                                 total_s=dispatch_s + block_s)


@pytest.mark.parametrize("metric, window, want", [
    ("affinity_in_scan", [_record(affinity_deferred=0)] * 3, 1),
    ("affinity_in_scan", [_record(affinity_deferred=1)] * 2, 0),
    ("affinity_in_scan", [_record(), _record(affinity_deferred=0)], None),
    ("affinity_in_scan", None, None),
    ("affinity_readers", [_record(affinity_readers=1)] * 2, 1),
    ("affinity_readers", [_record(affinity_readers=0)] * 2, 0),
    ("affinity_readers", [_record(affinity_deferred=0)] * 2, None),
    ("affinity_readers", None, None),
    ("cluster_rejected_create_share", [_record(rejected_creates=900)] * 3,
     0.24),
    ("cluster_report_postpass_s", [_record([_span("event_metrics")])] * 2,
     0.75),
    ("cluster_step_us_per_lane_event",
     [_record([_span("scan", 0.0, 0.0375)])] * 2, 10.0),
], ids=["the add in the loop", "deferred", "one without", "no log",
        "GpuClustering", "FGD", "the parent", "no log readers", "rejected",
        "the report's span", "step"])
def test_a_new_reader_reads_the_record_or_nothing(
        bench_run, monkeypatch, metric, window, want):
    reader = bench_run.load_module("layer_metrics", metric)
    monkeypatch.setattr(
        sweep_log, "records",
        lambda run: None if window is None else (_record(), window))
    got = reader.read({"real_events": 3750})
    assert got == (want if want is None else pytest.approx(want))


# ------------------------------------------------------ the artifact's anchor
def test_shuffle_42_under_seed_42_is_the_artifacts_row():
    """The whole cluster, the whole trace, one lane on the cell's path:
    the committed artifact's GpuClustering row of the default trace, tuning
    seed 42 (experiments/analysis_results/analysis_allo_discrete.csv:722):
    90.98 % of the 6,212 GPUs allocated at 130 % arrived load; and the lane
    IS the plain reference's replay, every event scored."""
    from tpusim.io.trace import load_node_csv, load_pod_csv

    with open(os.path.join(REPO, "experiments", "analysis_results",
                           "analysis_allo_discrete.csv"), newline="") as f:
        row = next(r for r in csv.DictReader(f) if (
            r["workload"], r["sc_policy"], r["seed"]) == (
                "openb_pod_list_default", "03-GpuClustering", "42"))
    assert (row["tune"], row["total_gpus"], row["130"]) == (
        "1.3", "6212", "90.98")
    config = _json("configs", "openb-clustering.json")
    nodes, pods = load_node_csv(inputs.NODE_CSV), load_pod_csv(inputs.POD_CSV)
    cfg = wave.simulator_config(config["simulator"], 42, profile=False,
                                report_per_event=True)
    sim = wave.build_simulator(nodes, pods, cfg)
    trace = sim.prepare_pods(tuning_seed=42)
    (lane,) = driver.schedule_pods_sweep(
        sim, None, np.full((1, 1), 1000, np.int32), [42], lane_pods=[trace])
    assert (lane.events, lane.placed, lane.failed) == (10811, 8024, 2787)
    assert round(lane.gpu_alloc_pct, 2) == float(row["130"])
    assert int(lane.metrics.used_gpu_milli[-1]) == 5_651_860
    rec = sim.obs.sweeps[-1]
    assert (rec.affinity_deferred, rec.affinity_readers) == (0, 1)
    ref = load_wave.reference_side(config, len(nodes))
    _want, differing, first, series, crossings = cluster_wave.hold_lane(
        ref, load_wave.trace_rows(trace, ref[2]), lane, 42, 1000)
    assert not any(differing.values()) and first == -1, (differing, first)
    assert len(crossings) == 130
    for what, got, limit in series:
        assert got <= limit, (what, got, limit)
