"""CPU checks of the cell `openb.fgd-seeds` at `--rehearse` sizes (96 of the
1,213 nodes, 3 lanes, 64 events): it runs through the harness as it is, its
three per-layer metrics are read from the sweep record, the control comes
out not correct, and the benchmark's copy of the plain reference is the
program's, letter for letter.

Run: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402  (benchmark/run.py)

CELL = "openb.fgd-seeds"
NEW_METRICS = {"flat_step_us_per_lane_event", "lane_host_ms_per_lane",
               "dense_access_sites"}


def rehearse(capsys, trace, seed=3000000019):
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                         "0.5", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_is_the_one_the_issue_names():
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = bench_run.by_name(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "openb", "fgd-seeds-2560", 1)
    config = bench_run.load_json(os.path.join(BENCH, "configs", "openb.json"))
    traffic = bench_run.load_json(
        os.path.join(BENCH, "traffic", "fgd-seeds-2560.json"))
    assert (traffic["driver"], traffic["depth_events"], traffic["check_lanes"]) == (
        "wave", 512, 1)
    assert 2560 <= traffic["lanes"] <= 3584 and traffic["lanes"] % 256 == 0
    sim = config["simulator"]
    assert sim["policies"] == [["FGDScore", 1000]]
    assert (sim["gpu_sel_method"], sim["dim_ext_method"], sim["norm_method"],
            sim["tuning_ratio"], sim["shuffle_pod"],
            sim["pod_popularity_threshold"], sim["engine"]) == (
        "FGDScore", "share", "max", 0.0, False, 95, "table")
    assert config["reduced"] == ["depth_events", "arrival_order"]
    # the guarantees of the cell that stands, word for word
    synth = bench_run.load_json(os.path.join(BENCH, "configs", "synth100k.json"))
    assert config["guarantees"] == synth["guarantees"]
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]


def test_end_to_end_line_of_the_cell(capsys):
    got = rehearse(capsys, trace=0)
    assert got["correct"] is True and got["failed"] == 0
    assert set(got["metrics"]) == {"lane_events_per_s", "wave_s", "setup_s"}


def test_traced_line_reads_the_three_new_metrics(capsys):
    got = rehearse(capsys, trace=1)
    assert got["correct"] is True
    assert NEW_METRICS | {"host_s", "scan_s", "fetch_s"} <= set(got["metrics"])
    for name in NEW_METRICS:
        assert got["metrics"][name]["value"] > 0, name
    # the flat step of a short cluster: every write site of the node state
    # and the tables, and every read, in the dense form
    # (21 since PR 42: the commit's add into aff_cnt left the flat body)
    assert got["metrics"]["dense_access_sites"] == {"value": 21,
                                                    "unit": "sites"}
    # metrics listed for the other cell only are not read here
    assert "table_build_s" not in got["metrics"]


def test_a_record_without_the_field_leaves_the_metric_out(monkeypatch):
    """The parent's SweepRecord has no `dense_accesses`: the reader finds
    nothing, returns None and does not raise."""
    from benchmark.lib import sweep_log

    metric = bench_run.load_module("layer_metrics", "dense_access_sites")
    old = types.SimpleNamespace(lanes=3, events=64, spans=[])
    monkeypatch.setattr(sweep_log, "records", lambda run: (old, [old, old]))
    assert metric.read({}) is None
    monkeypatch.setattr(sweep_log, "records", lambda run: None)
    assert metric.read({}) is None
    for name in NEW_METRICS - {"dense_access_sites"}:
        assert bench_run.load_module("layer_metrics", name).read({}) is None


def test_the_control_is_not_correct_in_this_cell(capsys):
    from tpusim.sim import driver

    import control_on_chip

    undo = control_on_chip.share_one_tie_break(driver)
    try:
        got = rehearse(capsys, trace=0)
    finally:
        undo()
    assert got["correct"] is False


def test_the_benchmarks_reference_is_the_programs_copy():
    """The yardstick keeps its own file; it may not drift from the one the
    program's tests hold the engines to."""
    with open(os.path.join(BENCH, "lib", "reference_fgd.py")) as f:
        mine = f.read()
    with open(os.path.join(REPO, "tpusim", "ref", "fgd_numpy.py")) as f:
        theirs = f.read()
    assert mine == theirs


def test_the_reference_script_holds_a_lane_of_the_rehearsal(capsys):
    import reference_on_chip

    argv = sys.argv
    sys.argv = ["reference_on_chip.py", "--workload", CELL, "--seeds", "11",
                "--rehearse"]
    try:
        assert reference_on_chip.main() == 0
    finally:
        sys.argv = argv
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert got["ok"] is True and got["runs"][0]["events_held"] == 64
    assert not any(got["runs"][0]["differing"].values())
