"""CPU checks of the cell `openb-families.fgd-seeds` at `--rehearse` sizes
(96 of the 1,213 nodes, 2 of the 5 families, 2 shuffles x 2 seeds, 64
events): it runs from its own files alone through the harness as it is,
prints the benchmark's three end-to-end metrics, the five per-layer metrics
that carry no `workloads` list (the two device ones only on a chip) and its
own three; lanes scored against another family's typical pods come out not
correct; a program without `lane_typical` fails at once.

Run: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402  (benchmark/run.py)

CELL = "openb-families.fgd-seeds"
NEW_METRICS = {"specs_ms_per_trace", "typical_sets",
               "trace_step_us_per_lane_event"}
# readers the benchmark had, which a `benchmark` PR listed the cell on later
LISTED_SINCE = {"table_pass_events"}


def rehearse(capsys, trace, seed=3000000019):
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                         "0.5", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_is_the_one_the_issue_names():
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = bench_run.by_name(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "openb-families", "family-seeds-600", 1)
    entry = bench_run.by_name(bench["configs"], "openb-families", "config")
    assert entry["reduced"] == ["families", "policies", "depth_events"]
    config = bench_run.load_json(os.path.join(REPO, entry["file"]))
    traffic = bench_run.load_json(
        os.path.join(BENCH, "traffic", "family-seeds-600.json"))
    work = config["workload"]
    assert work["families"] == ["default", "cpu250", "gpushare100",
                                "gpuspec33", "multigpu50"]
    assert work["tuning_seeds"] == list(range(42, 52))
    assert (traffic["driver"], traffic["depth_events"]) == ("family_wave", 512)
    assert traffic["lanes"] == 5 * 10 * traffic["seeds_per_shuffle"]
    assert traffic["seeds_per_shuffle"] in (8, 12, 16)
    sim = config["simulator"]
    assert sim["policies"] == [["FGDScore", 1000]]
    assert (sim["gpu_sel_method"], sim["dim_ext_method"], sim["norm_method"],
            sim["tuning_ratio"], sim["shuffle_pod"],
            sim["pod_popularity_threshold"], sim["engine"]) == (
        "FGDScore", "share", "max", 1.3, True, 95, "table")
    # the three guarantees of the cells that stand and the family's own
    openb = bench_run.load_json(os.path.join(BENCH, "configs", "openb.json"))
    assert len(config["guarantees"]) == len(openb["guarantees"]) + 1
    assert config["guarantees"][1:3] == openb["guarantees"][1:3]
    assert "its own family's typical pods" in config["guarantees"][3]
    for path in (work["pod_csv"].format(family=f) for f in work["families"]):
        assert os.path.isfile(os.path.join(REPO, path)), path
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
    # of what the benchmark had, only the readers a `benchmark` PR listed
    # the cell on since (PR 48: the grouped body's `table_pass_events`)
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])} == NEW_METRICS | LISTED_SINCE


def test_end_to_end_line_of_the_cell(capsys):
    got = rehearse(capsys, trace=0)
    assert got["correct"] is True and got["failed"] == 0
    assert set(got["metrics"]) == {"lane_events_per_s", "wave_s", "setup_s"}
    assert got["attempted"] >= 1


def test_traced_line_reads_the_list_less_metrics_and_the_three_new(capsys):
    got = rehearse(capsys, trace=1)
    assert got["correct"] is True
    # scan_roofline and device_idle_pct are a chip's: a rehearsal has no
    # device time to divide by
    assert set(got["metrics"]) >= NEW_METRICS | LISTED_SINCE | {
        "host_s", "scan_s", "fetch_s"}
    for name in NEW_METRICS | {"host_s", "scan_s", "fetch_s"}:
        assert got["metrics"][name]["value"] > 0, name
    # the rehearsal's lanes are under the grouped body's 64
    assert got["metrics"]["table_pass_events"] == {"value": 1,
                                                   "unit": "events"}
    assert got["metrics"]["typical_sets"] == {"value": 2.0, "unit": "sets"}
    assert {"busy_s", "window_s"} <= set(got["device"])


def test_the_device_metrics_read_the_cells_shape():
    """What `scan_roofline` and `device_idle_pct` read on a chip, from the
    run object the driver returns: K is the tables' K (the union type set
    on the program's buckets), not one trace's."""
    from benchmark.drivers import family_wave
    from benchmark.lib import inputs, roofline
    from tpusim.io.trace import load_pod_csv

    pods = load_pod_csv(inputs.POD_CSV)[:64]
    k = family_wave.table_pod_types([pods[:32], pods[16:]])
    assert k % 16 == 0 and 16 <= k <= 64 + 32
    run = {"traced": {"scan_device_s": 2.0, "busy_s": 3.0, "window_s": 4.0},
           "device_kind": "TPU v5 lite", "rehearsal": False,
           "shape": {"nodes": 1213, "pod_types": 400, "policies": 1,
                     "lanes": 600, "events": 512}}
    got = bench_run.load_module("layer_metrics", "scan_roofline").read(run)
    moved = roofline.scan_bytes_per_lane_event(1213, 400, 1) * 600 * 512
    assert moved == 9665 * 600 * 512
    assert got == pytest.approx(100 * moved / 819e9 / 2.0)
    assert bench_run.load_module(
        "layer_metrics", "device_idle_pct").read(run) == pytest.approx(25.0)


def test_lanes_scored_against_another_familys_typical_pods_are_not_correct(
        capsys):
    from tpusim.sim import driver

    import family_control

    undo = family_control.hand_on_the_typical_pods(driver)
    try:
        got = rehearse(capsys, trace=0)
    finally:
        undo()
    assert got["correct"] is False
    assert rehearse(capsys, trace=0)["correct"] is True


def test_a_program_without_lane_typical_fails_at_once(monkeypatch):
    """The parent of the PR that brought the cell: its sweep takes one
    typical-pod set. The driver says so before it builds anything."""
    from tpusim.sim import driver

    real = driver.schedule_pods_sweep

    def parents(sim, pods, weights, seeds=None, bucket=512, *, lane_pods=None,
                fault_specs=None, min_pods=0, min_events=0):
        return real(sim, pods, weights, seeds, bucket, lane_pods=lane_pods,
                    fault_specs=fault_specs, min_pods=min_pods,
                    min_events=min_events)

    monkeypatch.setattr(driver, "schedule_pods_sweep", parents)
    with pytest.raises(RuntimeError, match="takes no lane_typical"):
        bench_run.main(["--workload", CELL, "--seed", "7", "--seconds", "0.5",
                        "--trace", "0", "--rehearse"])
