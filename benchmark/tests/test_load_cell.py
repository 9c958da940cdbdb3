"""CPU checks of the cell `openb-load130.report-seeds` at `--rehearse` sizes
(96 of the 1,213 nodes, shuffles 42 and 43 of the pod list tuned to 130 % of
THAT cluster and replayed whole, 2 seeds each, the report on): it runs from
its own files alone through the harness as it is; the configuration is the
artifact's protocol with two cuts, neither of them depth; the window counts
each lane's own events; the reference's shuffle and tuning of the CSV's rows
is the program's; the two controls read not correct.

Run: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402  (benchmark/run.py)

CELL = "openb-load130.report-seeds"
NEW = ["load_step_us_per_lane_event", "rejected_create_share",
       "load_dense_access_sites", "report_postpass_s", "report_roofline",
       "report_series_bytes", "load_fetch_copy_s", "load_host_lead_s",
       "load_host_tail_s"]
EVENTS = [10811, 10763, 10837, 10893, 10878, 10862, 10770, 10807, 10815, 10877]


def rehearse(capsys, trace, seed=3000000041):
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                         "0.5", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_is_the_one_the_issue_names():
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = bench_run.by_name(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "openb-load130", "report-seeds-320", 1)
    assert len(cell["why"]) <= 200
    entry = bench_run.by_name(bench["configs"], "openb-load130", "config")
    assert entry["reduced"] == ["families", "policies"]
    config = bench_run.load_json(os.path.join(REPO, entry["file"]))
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert config["reduced"] == entry["reduced"]
    assert set(config["reduced_why"]) == set(entry["reduced"])
    assert "depth_events" in config["not_reduced"]
    # the family configuration's simulator, with the report on
    families = bench_run.load_json(
        os.path.join(BENCH, "configs", "openb-families.json"))
    assert config["simulator"] == dict(
        families["simulator"], report_per_event=True)
    assert config["workload"]["tuning_seeds"] == list(range(42, 52))
    assert config["guarantees"][:2] == [
        g.replace("family workload, tuning seed", "tuning seed")
        for g in families["guarantees"][:2]]
    assert any("none sampled, truncated or approximated" in g
               for g in config["guarantees"])
    assert any("rejected create leaves the node state untouched" in g
               for g in config["guarantees"])
    traffic = bench_run.load_json(
        os.path.join(BENCH, "traffic", "report-seeds-320.json"))
    assert (traffic["driver"], traffic["check_lanes"]) == ("load_wave", 1)
    assert "depth_events" not in traffic
    assert traffic["events_by_shuffle"] == EVENTS and sum(EVENTS) == 108313
    assert traffic["seeds_per_shuffle"] in (16, 24, 32, 48, 64)
    assert traffic["lanes"] == 10 * traffic["seeds_per_shuffle"]
    assert traffic["scored_creates"] >= 1024
    # by name, not by position: later PRs append after them
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + len(NEW)] == NEW
    for m in bench["per_layer"][at:at + len(NEW)]:
        assert m["workloads"] == [CELL]
    # nothing the benchmark had lists the new cell: it cannot move them
    assert all(CELL not in m.get("workloads", [])
               for m in bench["per_layer"][:at])


def test_the_references_shuffle_and_tuning_are_the_programs():
    """Tuning DOWN (the tiny cluster: the list asks for far more than 130 %
    of 96 nodes) and tuning UP (the whole cluster: clones appended) both."""
    from benchmark.drivers import load_wave, wave
    from benchmark.lib import inputs, reference_follow_load
    from tpusim.io.trace import load_node_csv, load_pod_csv

    config = bench_run.load_json(
        os.path.join(BENCH, "configs", "openb-load130.json"))
    pods = load_pod_csv(inputs.POD_CSV)
    for nodes, seeds, lengths in ((96, (42, 43), (953, 923)),
                                  (None, (42, 51), (10811, 10877))):
        ref = load_wave.reference_side(config, nodes)
        cfg = wave.simulator_config(config["simulator"], 42, profile=False)
        sim = wave.build_simulator(
            load_node_csv(inputs.NODE_CSV)[:nodes], pods, cfg)
        capacity = int(ref[0]["gpu_cnt"].sum()) * 1000
        for seed, length in zip(seeds, lengths):
            trace = sim.prepare_pods(tuning_seed=seed)
            want = reference_follow_load.tuned_order(
                ref[2], ref[1]["gpu_milli"], ref[1]["gpu_num"], capacity,
                1.3, seed)
            assert len(trace) == length
            assert load_wave.trace_rows(trace, ref[2]) == want


def test_the_crossings_are_the_discrete_schemas_points():
    from benchmark.lib import reference_follow_load

    milli = np.full(1000, 500)
    num = np.ones(1000, int)
    # 100,000 milli of capacity: a new per cent every second create
    got = reference_follow_load.load_crossings(milli, num, 100_000)
    assert got[:3] == [1, 3, 5] and got[-1] == 999 and len(got) == 500


def test_the_scored_creates_are_placed_ones_of_every_decile():
    from benchmark.drivers import load_wave

    milli, num = np.full(5000, 1000), np.ones(5000, int)
    placed = np.arange(5000) < 4000  # the last fifth is rejected
    got = load_wave.scored_events(milli, num, placed, 0, 1000,
                                  np.random.default_rng(3))
    assert len(got) == len(set(got)) == 1000 and max(got) < 4000
    per_decile = np.bincount(np.asarray(got) // 500, minlength=10)
    assert (per_decile[:8] >= 100).all() and per_decile[8:].sum() == 0
    assert load_wave.scored_events(
        milli, num, placed, 0, 10**9, np.random.default_rng(3)) == list(
        range(4000))


def test_a_rehearsal_prints_the_cells_lines(capsys):
    got = rehearse(capsys, trace=0)
    assert got["correct"] is True and got["rehearsal"] is True
    assert set(got["metrics"]) == {"lane_events_per_s", "wave_s", "setup_s"}
    got = rehearse(capsys, trace=1)
    assert got["correct"] is True
    assert {"host_s", "scan_s", "fetch_s"} <= set(got["metrics"])
    assert set(NEW) - {"report_roofline"} <= set(got["metrics"])
    assert 0.15 < got["metrics"]["rejected_create_share"]["value"] < 0.3


def test_the_window_counts_each_lanes_own_events(capsys, monkeypatch):
    """lane_events_per_s is the real events of the window's whole waves over
    the sum of their walls: 2 x (953 + 923) a wave at the tiny size, never
    4 x the longest lane or the bucket."""
    from benchmark.drivers import load_wave

    seen = {}
    real = load_wave.statistics.median

    def spy(values):
        values = list(values)
        seen.setdefault("medians", []).append(values)
        return real(values)

    monkeypatch.setattr(load_wave.statistics, "median", spy)
    got = rehearse(capsys, trace=0)
    walls = seen["medians"][-1]
    assert got["metrics"]["lane_events_per_s"]["value"] == (
        2 * (953 + 923) * len(walls) / sum(walls))


def test_the_two_controls_read_not_correct(capsys):
    import load_control

    config = bench_run.load_json(
        os.path.join(BENCH, "configs", "openb-load130.json"))
    bends = (load_control.dropped_delta,
             lambda: load_control.another_shuffle(
                 config["tiny"]["workload"]["tuning_seeds"], 1.3))
    for bend in bends:
        undo = bend()
        try:
            assert rehearse(capsys, trace=0)["correct"] is False
        finally:
            undo()
    assert rehearse(capsys, trace=0)["correct"] is True
