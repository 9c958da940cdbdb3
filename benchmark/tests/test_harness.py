"""CPU checks of the harness at `--rehearse` sizes: names resolve to
files, what is missing is an error, the last line is the contract's
object, and a broken timed path comes out as `correct: false`.

Run: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402  (benchmark/run.py)

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
# what the driver ignores: the window's walls, stalled waves and set-up by
# parts; and, LAST in the line, every number compared beside its limit
EXTRA_KEYS = {"window", "checks"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
CELL = "synth100k.fgd-seeds"


def last_line(capsys) -> dict:
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def rehearse(capsys, *extra, cell=CELL, seed=3000000019, trace=0):
    rc = bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                         "0.5", "--trace", str(trace), "--rehearse", *extra])
    assert rc == 0
    return last_line(capsys)


def test_every_name_in_benchmark_json_resolves_to_a_file():
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    for cfg in bench["configs"]:
        body = bench_run.load_json(os.path.join(REPO, cfg["file"]))
        for key in ("source", "reduced", "assumed", "guarantees"):
            assert key in body, (cfg["name"], key)
        assert body["source"] == cfg["source"] and len(body["source"]) <= 200
        assert body["reduced"] == cfg["reduced"]
    for cell in bench["workloads"]:
        traffic = bench_run.load_json(
            os.path.join(BENCH, "traffic", f"{cell['traffic']}.json"))
        assert hasattr(bench_run.load_module("drivers", traffic["driver"]), "run")
        assert cell["chips"] == 1 and 0 < len(cell["why"]) <= 200
    for metric in bench["per_layer"]:
        assert hasattr(bench_run.load_module("layer_metrics", metric["name"]), "read")


def test_unknown_names_are_errors(capsys):
    with pytest.raises(KeyError, match="no workload 'nope'"):
        bench_run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"])
    with pytest.raises(KeyError, match="no layer_metrics/nope.py"):
        bench_run.load_module("layer_metrics", "nope")
    with pytest.raises(KeyError, match="no drivers/nope.py"):
        bench_run.load_module("drivers", "nope")
    assert capsys.readouterr().out == ""


def test_a_backend_that_is_not_a_tpu_is_an_error_without_rehearse(capsys):
    with pytest.raises(RuntimeError, match="not on a TPU"):
        bench_run.main(["--workload", CELL, "--seed", "1", "--seconds", "1"])
    assert capsys.readouterr().out == ""


def test_end_to_end_line_is_the_contracts_object(capsys):
    got = rehearse(capsys)
    assert set(got) == CONTRACT_KEYS | EXTRA_KEYS | {"rehearsal"}
    assert list(got)[-1] == "checks" and got["checks"]
    assert all(c["value"] <= c["limit"] for c in got["checks"].values())
    assert "compiles inside the window" in got["checks"]
    window = got["window"]
    assert len(window["wall_s"]) == got["attempted"]
    # (a rehearsal keeps ONE warm wave; the cell's own two are rehearsed in
    # test_steady_window.py)
    assert window["warm_waves"] == 1 and window["stalled_waves"] >= 0
    parts = window["setup_parts"]
    assert len(parts["warm_waves_s"]) == 1
    assert (parts["before_s"] + parts["inputs_s"] + parts["simulator_s"]
            + sum(parts["warm_waves_s"])) == pytest.approx(
        got["metrics"]["setup_s"]["value"])
    assert set(got["device"]) == DEVICE_KEYS
    assert got["correct"] is True and got["failed"] == 0 and got["attempted"] >= 1
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    assert set(got["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert got["metrics"][m["name"]]["unit"] == m["unit"]
        assert got["metrics"][m["name"]]["value"] > 0


def test_traced_line_has_span_metrics_busy_window_and_breakdown(capsys):
    got = rehearse(capsys, trace=1)
    assert set(got) == CONTRACT_KEYS | EXTRA_KEYS | {"rehearsal", "breakdown"}
    assert list(got)[-1] == "checks"
    assert set(got["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < got["device"]["busy_s"] <= got["device"]["window_s"]
    # span metrics are read; the device metrics find no device plane to
    # read in a rehearsal, return nothing and are left out of the line
    assert {"host_s", "scan_s", "fetch_s"} <= set(got["metrics"])
    assert "device_idle_pct" not in got["metrics"]
    assert "scan_roofline" not in got["metrics"]
    assert set(got["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(got["breakdown"]["idle_gaps"]) <= 10


def test_same_seed_same_lanes_and_other_seed_other_lanes():
    from benchmark.drivers import wave

    a = wave.lane_seeds(3000000019, 1, 2560)
    assert a == wave.lane_seeds(3000000019, 1, 2560)
    assert len(set(a) | set(wave.lane_seeds(3000000019, 2, 2560))) == 5120
    assert not set(a) & set(wave.lane_seeds(3000000020, 1, 2560))
    assert all(0 <= s < 2**31 for s in a)


def test_openb_csv_sources_build_the_published_cluster_and_trace():
    from benchmark.lib import inputs

    nodes, pods = inputs.build({"cluster": {"source": "openb_csv"},
                                "workload": {"source": "openb_csv"}}, 1, 0)
    assert (len(nodes), len(pods)) == (1213, 8152)
    with pytest.raises(KeyError, match="unknown cluster source"):
        inputs.build({"cluster": {"source": "nope"}, "workload": {}}, 1, 0)


def corrupting(monkeypatch, damage):
    """The timed path, broken underneath: every wave's lanes go through
    `damage` before the harness sees them."""
    from tpusim.sim import driver

    real = driver.schedule_pods_sweep

    def broken(sim, pods, weights, seeds=None, **kw):
        lanes = real(sim, pods, weights, seeds, **kw)
        damage(lanes)
        return lanes

    monkeypatch.setattr(driver, "schedule_pods_sweep", broken)


def test_a_placement_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    def damage(lanes):
        for lane in lanes:  # one pod of each lane moves to another node
            moved = lane.placed_node.copy()
            moved[5] = (moved[5] + 1) % 64
            lane.placed_node = moved

    corrupting(monkeypatch, damage)
    got = rehearse(capsys)
    assert got["correct"] is False


def test_a_final_state_leaf_altered_is_not_correct(capsys, monkeypatch):
    """Every NodeState field is held: one count of one node's affinity
    classes off in every lane, nothing else touched."""
    def damage(lanes):
        for lane in lanes:
            aff = np.array(lane.state.aff_cnt)
            aff[7, 0] += 1
            lane.state = lane.state._replace(aff_cnt=aff)

    corrupting(monkeypatch, damage)
    assert rehearse(capsys)["correct"] is False


def test_lanes_handed_back_in_another_order_are_not_correct(
        capsys, monkeypatch):
    corrupting(monkeypatch, lambda lanes: lanes.reverse())
    assert rehearse(capsys)["correct"] is False


def test_the_control_one_tie_break_shared_by_all_lanes_is_not_correct(
        capsys, monkeypatch):
    """The control of control_on_chip.py at a tiny size: the guarantee
    'no lane is approximated' broken by the shortcut of one tie-break
    permutation for the whole wave."""
    from tpusim.sim import driver

    sys.path.insert(0, HERE)
    import control_on_chip

    undo = control_on_chip.share_one_tie_break(driver)
    try:
        got = rehearse(capsys)
    finally:
        undo()
    assert got["correct"] is False


def test_one_lane_whose_counters_miss_an_event_is_not_correct(
        capsys, monkeypatch):
    def damage(lanes):
        lanes[2].counters[0] -= 1  # a create the scan did not count

    corrupting(monkeypatch, damage)
    got = rehearse(capsys)
    assert got["correct"] is False and got["failed"] == got["attempted"]


def test_a_cell_a_metric_and_a_driver_kind_are_added_as_files(tmp_path):
    """A later PR's move: copy the benchmark, ADD a traffic file, a driver
    kind, a per-layer metric and BENCHMARK.json entries, edit no file that
    is there, and run the new cell."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("tpusim", "data"):
        os.symlink(os.path.join(REPO, name), tmp_path / name)
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    (tmp_path / "benchmark/traffic/echo-3.json").write_text(
        json.dumps({"driver": "echo", "answers": 3}))
    (tmp_path / "benchmark/drivers/echo.py").write_text(
        "def run(ctx):\n"
        "    n = ctx.traffic['answers']\n"
        "    return {'correct': True, 'attempted': n, 'failed': 0,\n"
        "            'memory_peak_bytes': 0, 'answers': n,\n"
        "            'end_to_end': {'lane_events_per_s': 1.0, 'wave_s': 1.0,\n"
        "                           'setup_s': 1.0}}\n")
    (tmp_path / "benchmark/layer_metrics/answers_n.py").write_text(
        "def read(run):\n    return run.get('answers')\n")
    bench["workloads"].append({"name": "synth100k.echo", "config": "synth100k",
                               "traffic": "echo-3", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "answers_n", "unit": "n", "better": "higher",
                               "source": "program_counter", "layer": "Echo",
                               "moves": "wave_s", "workloads": ["synth100k.echo"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for trace, want in ((0, {"lane_events_per_s", "wave_s", "setup_s"}),
                        (1, {"answers_n"})):
        done = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", "synth100k.echo",
             "--seed", "7", "--seconds", "1", "--trace", str(trace), "--rehearse"],
            cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        got = json.loads(done.stdout.strip().splitlines()[-1])
        assert set(got["metrics"]) == want, got
        assert got["attempted"] == 3


def test_only_benchmark_json_and_the_benchmark_directory_is_an_error(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "7",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert not any(line.startswith("{") for line in done.stdout.splitlines())
