"""CPU checks of the cell `openb-pwrfgd.mix-seeds` at `--rehearse` sizes (96
of the 1,213 nodes, 3 weight rows x 2 shuffles x 2 seeds, 64 events): it
runs from its own files alone through the harness as it is, prints the
benchmark's three end-to-end metrics, the per-layer metrics that carry no
`workloads` list (the two device ones only on a chip) and its own four; the
configuration is the fork's rows 08/11/12 with its cuts and five guarantees;
lanes come row-major; an oracle under the next weight row reads not correct;
a program without `power_cpu_w` fails before anything is built.

Run: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import dataclasses
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

CELL = "openb-pwrfgd.mix-seeds"
NEW_METRICS = {"mix_step_us_per_lane_event", "weight_rows",
               "normalized_policies", "mix_postpass_s"}
ROWS = [[500, 500], [100, 900], [50, 950]]


def rehearse(capsys, trace, seed=3000000019):
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                         "0.5", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_is_the_one_the_issue_names():
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = bench_run.by_name(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "openb-pwrfgd", "mix-seeds-1200", 1)
    entry = bench_run.by_name(bench["configs"], "openb-pwrfgd", "config")
    assert entry["reduced"] == ["depth_events", "families", "methods"]
    config = bench_run.load_json(os.path.join(REPO, entry["file"]))
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    for said in ("AllMethodList", "08/11/12", "500/500, 100/900, 50/950",
                 "gpusel FGDScore", "tune 1.3", "seeds 42-51"):
        assert said in entry["source"], said
    assert config["reduced"] == entry["reduced"]
    assert set(config["reduced_why"]) == set(entry["reduced"])
    assert len(config["assumed"]) == 2
    traffic = bench_run.load_json(
        os.path.join(BENCH, "traffic", "mix-seeds-1200.json"))
    work, sim = config["workload"], config["simulator"]
    assert work["tuning_seeds"] == list(range(42, 52))
    assert os.path.isfile(os.path.join(REPO, work["pod_csv"]))
    assert (traffic["driver"], traffic["depth_events"]) == ("mix_wave", 512)
    assert traffic["seeds_per_shuffle"] in (24, 40, 64)
    assert traffic["lanes"] == 3 * 10 * traffic["seeds_per_shuffle"]
    assert sim["policies"] == [["PWRScore", 500], ["FGDScore", 500]]
    assert sim["weight_rows"] == ROWS
    assert (sim["gpu_sel_method"], sim["dim_ext_method"], sim["norm_method"],
            sim["tuning_ratio"], sim["shuffle_pod"],
            sim["pod_popularity_threshold"], sim["engine"]) == (
        "FGDScore", "share", "max", 1.3, True, 95, "table")
    # the fork's default CPU row and its GPU rows, as data for the reference
    energy = config["energy_model"]
    assert energy["cpu_default"] == {"idle_w": 15, "full_w": 120, "cores": 16}
    assert energy["gpu_idle_full_w"]["G3"] == [50, 400]
    # openb's three guarantees and the deployment's two
    openb = bench_run.load_json(os.path.join(BENCH, "configs", "openb.json"))
    assert len(config["guarantees"]) == len(openb["guarantees"]) + 2 == 5
    assert config["guarantees"][1:3] == openb["guarantees"][1:3]
    assert "ITS weight row" in config["guarantees"][3]
    assert "power_cpu_w and power_gpu_w" in config["guarantees"][4]
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
    assert NEW_METRICS <= {m["name"] for m in bench["per_layer"]}
    # nothing the benchmark had lists the new cell: it cannot move them
    assert all(CELL not in m.get("workloads", [])
               for m in bench["per_layer"] if m["name"] not in NEW_METRICS)


def test_lanes_come_row_major_and_a_shuffle_s_lanes_share_its_trace():
    from benchmark.drivers import mix_wave

    traffic = bench_run.load_json(
        os.path.join(BENCH, "traffic", "mix-seeds-1200.json"))
    per = traffic["seeds_per_shuffle"]
    grid = mix_wave.lane_grid(3, 10, per)
    assert len(grid) == traffic["lanes"]
    for row, shuffle, k in ((0, 0, 0), (1, 3, 17), (2, 9, per - 1)):
        assert grid[(row * 10 + shuffle) * per + k] == (row, shuffle)
    assert [g[0] for g in grid] == sorted(g[0] for g in grid)
    # a shuffle's lanes of all three rows hand over one trace object: ten
    # distinct traces
    assert len({s for _, s in grid}) == 10
    assert sum(1 for _, s in grid if s == 4) == 3 * per


def test_end_to_end_line_of_the_cell(capsys):
    got = rehearse(capsys, trace=0)
    assert got["correct"] is True and got["failed"] == 0
    assert set(got["metrics"]) == {"lane_events_per_s", "wave_s", "setup_s"}
    assert got["attempted"] >= 1


def test_traced_line_reads_the_list_less_metrics_and_the_four_new(capsys):
    from tpusim.obs.spans import sweep_log

    got = rehearse(capsys, trace=1)
    assert got["correct"] is True
    # scan_roofline and device_idle_pct are a chip's: a rehearsal has no
    # device time to divide by
    assert set(got["metrics"]) >= NEW_METRICS | {"host_s", "scan_s", "fetch_s"}
    for name in NEW_METRICS | {"host_s", "scan_s", "fetch_s"}:
        assert got["metrics"][name]["value"] > 0, name
    assert got["metrics"]["weight_rows"] == {"value": 3, "unit": "rows"}
    assert got["metrics"]["normalized_policies"] == {
        "value": 1, "unit": "policies"}
    assert {"busy_s", "window_s"} <= set(got["device"])
    # the window's records: two traces, three rows, the grouped body needs
    # 64 lanes and the rehearsal has 12, so a column an event
    rec = sweep_log()[-1]
    assert (rec.lanes, rec.traces, rec.weight_rows, rec.tables_reused) == (
        12, 2, 3, 1)


def test_the_device_metrics_read_the_cells_shape():
    """What `scan_roofline` reads on a chip, from the run object the driver
    returns: two policies and the tables' K."""
    from benchmark.lib import roofline

    run = {"traced": {"scan_device_s": 2.0, "busy_s": 3.0, "window_s": 4.0},
           "device_kind": "TPU v5 lite", "rehearsal": False,
           "shape": {"nodes": 1213, "pod_types": 144, "policies": 2,
                     "lanes": 1920, "events": 512}}
    got = bench_run.load_module("layer_metrics", "scan_roofline").read(run)
    moved = roofline.scan_bytes_per_lane_event(1213, 144, 2) * 1920 * 512
    assert moved == 12789 * 1920 * 512
    assert got == pytest.approx(100 * moved / 819e9 / 2.0)
    assert roofline.carry_bytes_per_lane(1213, 144, 2, 512, 512) == 2399984


def test_an_oracle_under_the_next_weight_row_is_not_correct(capsys):
    import mix_control

    assert mix_control.configured_rows() == ROWS
    undo = mix_control.hand_the_oracle_the_next_row(ROWS)
    try:
        got = rehearse(capsys, trace=0)
    finally:
        undo()
    assert got["correct"] is False
    assert rehearse(capsys, trace=0)["correct"] is True


def test_a_program_without_power_cpu_w_fails_before_anything_is_built(
        monkeypatch):
    """The parent of the PR that brought the cell: its SweepLane carries no
    watts. The driver says so before it loads a node or touches a device."""
    from tpusim.io import trace
    from tpusim.sim import driver

    fields = [(f.name, f.type) if f.default is dataclasses.MISSING
              else (f.name, f.type, dataclasses.field(default=f.default))
              for f in dataclasses.fields(driver.SweepLane)
              if not f.name.startswith("power_")]
    monkeypatch.setattr(driver, "SweepLane",
                        dataclasses.make_dataclass("SweepLane", fields))

    def built(*_a, **_k):
        raise AssertionError("the driver built something first")

    monkeypatch.setattr(trace, "load_node_csv", built)
    with pytest.raises(RuntimeError, match="carries no power_cpu_w"):
        bench_run.main(["--workload", CELL, "--seed", "7", "--seconds", "0.5",
                        "--trace", "0", "--rehearse"])
