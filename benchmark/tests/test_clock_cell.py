"""CPU checks of the cell `openb-clock.fgd-seeds` at `--rehearse` sizes (96
of the 1,213 nodes, 3 lanes, the stream's first 64 events of which 18 are
deletions): it runs from its own files alone through the harness as it is,
prints the benchmark's three end-to-end metrics, the per-layer metrics that
carry no `workloads` list (the two device ones only on a chip), its own
two and seven that the control cell has, read here under names of the
cell's own; the configuration is openb by its own clock with its one cut
and five guarantees; the driver raises when the window is not the stream's;
the reference reads the CSV files itself; a reference that ignores
deletions reads not correct.

Run: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402  (benchmark/run.py)

CELL = "openb-clock.fgd-seeds"
NEW_METRICS = {"clock_step_us_per_lane_event", "delete_share"}
# the control cell's readers under this cell's names: what the cell exists
# for (resident tables, the grouped body) and where its wave's time goes
SHARED = ("table_reuse_share", "table_pass_events", "sub_requests",
          "host_lead_s", "host_tail_s", "fetch_copy_s", "fetch_bytes")


def rehearse(capsys, trace, seed=3000000019):
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                         "0.5", "--trace", str(trace), "--rehearse"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_cell_is_the_one_the_issue_names():
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = bench_run.by_name(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "openb-clock", "clock-seeds", 1)
    assert len(cell["why"]) <= 200
    entry = bench_run.by_name(bench["configs"], "openb-clock", "config")
    # by name, not by position: later PRs append after them
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == "openb-clock"] == [CELL]
    assert entry["reduced"] == ["depth_events"]
    config = bench_run.load_json(os.path.join(REPO, entry["file"]))
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    for said in ("cluster-trace-gpu-v2023", "openb_pod_list_default.csv",
                 "creation_time/deletion_time", "simulator.go:672-717",
                 "stable sort by time"):
        assert said in entry["source"], said
    assert config["reduced"] == entry["reduced"]
    assert set(config["reduced_why"]) == set(entry["reduced"])
    for said in ("16,304", "274 creations", "238 deletions", "1.1 %",
                 "no create is rejected", "36 of them"):
        assert said in config["reduced_why"]["depth_events"], said
    # openb's simulator and nothing else but the clock
    openb = bench_run.load_json(os.path.join(BENCH, "configs", "openb.json"))
    assert config["simulator"] == dict(openb["simulator"], use_timestamps=True)
    assert (config["cluster"], config["workload"], config["assumed"],
            config["tiny"]) == (openb["cluster"], openb["workload"],
                                openb["assumed"], openb["tiny"])
    # openb's three guarantees and the deployment's two
    assert config["guarantees"][:3] == openb["guarantees"]
    assert len(config["guarantees"]) == 5
    assert "a deletion gives back exactly" in config["guarantees"][3]
    assert "deletions of pods that were placed" in config["guarantees"][4]
    traffic = bench_run.load_json(
        os.path.join(BENCH, "traffic", "clock-seeds.json"))
    assert (traffic["driver"], traffic["depth_events"],
            traffic["delete_events"], traffic["check_lanes"]) == (
        "clock_wave", 512, 238, 1)
    assert traffic["lanes"] >= 2560 and traffic["lanes"] % 256 == 0
    listed = {m["name"]: m for m in bench["per_layer"]
              if m["name"] in NEW_METRICS}
    assert set(listed) == NEW_METRICS
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("clock_step_us_per_lane_event")
    assert names[at:at + 9] == [
        "clock_step_us_per_lane_event", "delete_share"] + [
        f"clock_{name}" for name in SHARED]
    step = next(m for m in bench["per_layer"] if m["name"] == "scan_s")
    for m in listed.values():
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["moves"]) == (step["layer"], "lane_events_per_s")
    assert (listed["delete_share"]["source"], listed["delete_share"]["unit"],
            listed["delete_share"]["better"]) == (
        "program_counter", "share", "higher")
    # the seven are the control's entries but for the name and the cell
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in SHARED:
        assert by_name[f"clock_{name}"] == dict(
            by_name[name], name=f"clock_{name}", workloads=[CELL])
        # the control's own list holds the control; a `benchmark` PR may
        # list further cells on it (PR 48: the family cell)
        assert "openb.fgd-seeds" in by_name[name]["workloads"]
        assert CELL not in by_name[name]["workloads"]
        reader = bench_run.load_module("layer_metrics", f"clock_{name}").read
        assert reader.__module__ == f"benchmark.layer_metrics.{name}"
    # nothing the benchmark had lists the new cell: it cannot move them
    assert all(CELL not in m.get("workloads", [])
               for m in bench["per_layer"][:at])


def test_the_window_is_the_streams_first_events_or_the_driver_raises():
    from benchmark.drivers import clock_wave
    from benchmark.lib import inputs, reference_clock
    from tpusim.io.trace import load_pod_csv

    pods = load_pod_csv(inputs.POD_CSV)
    window, (kind, pod) = clock_wave.stream_window(pods, 512, 238)
    assert (len(window), len(kind)) == (274, 512)
    assert int((kind == reference_clock.EV_DELETE).sum()) == 238
    assert sum(1 for p in window if not p.deletion_time) == 36
    assert len({inputs.pod_shape(p) for p in window}) == 52
    # the one pod deleted in the second it was created: created first
    (twin,) = [i for i, p in enumerate(pods)
               if p.creation_time == p.deletion_time]
    whole = reference_clock.event_stream(
        [p.creation_time for p in pods], [p.deletion_time for p in pods])
    at = np.flatnonzero(whole[1] == twin)
    assert whole[0][at].tolist() == [0, 1] and len(whole[0]) == 16304
    with pytest.raises(ValueError, match="512 events of which 238"):
        clock_wave.stream_window(pods, 512, 237)
    # a list out of creation order is not the stream's window
    swapped = [pods[1], pods[0]] + pods[2:]
    with pytest.raises(ValueError, match="not the list's first"):
        clock_wave.stream_window(swapped, 512, 238)
    # a window whose survivors kept their deletion times holds more events
    late = [dataclasses.replace(p, deletion_time=p.deletion_time or 1)
            for p in window]
    assert len(reference_clock.event_stream(
        [p.creation_time for p in late],
        [p.deletion_time for p in late])[0]) == 548


def test_the_reference_reads_the_files_itself():
    """`lib/reference_inputs.py` against the program's loader, expansion
    and rank table, on the cell's own files: the two sides of the walk are
    made apart and have to agree here, where neither is under test."""
    from benchmark.lib import inputs, reference_inputs
    from tpusim import constants
    from tpusim.io.trace import (
        load_node_csv, load_pod_csv, nodes_to_state, pods_to_specs,
        tiebreak_rank)

    with open(os.path.join(BENCH, "lib", "reference_inputs.py")) as f:
        assert "import tpusim" not in f.read().replace("`tpusim", "")
    ids = constants.GPU_MODEL_IDS
    mine = reference_inputs.cluster(inputs.NODE_CSV, ids)
    state = nodes_to_state(load_node_csv(inputs.NODE_CSV))
    assert len(mine["cpu_cap"]) == 1213 and mine["gpu_cnt"].sum() == 6212
    for f in ("cpu_cap", "mem_cap", "gpu_cnt", "gpu_type"):
        np.testing.assert_array_equal(mine[f], np.asarray(getattr(state, f)))
    assert len(reference_inputs.cluster(inputs.NODE_CSV, ids, 96)["cpu_cap"]) == 96
    rows = load_pod_csv(inputs.POD_CSV)
    pods = reference_inputs.pods(inputs.POD_CSV, ids)
    specs = pods_to_specs(rows, None, device=False)
    for f in ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask"):
        np.testing.assert_array_equal(pods[f], np.asarray(getattr(specs, f)))
    assert pods["creation_time"].tolist() == [p.creation_time for p in rows]
    assert pods["deletion_time"].tolist() == [p.deletion_time for p in rows]
    assert len(reference_inputs.pods(inputs.POD_CSV, ids, 274)["cpu"]) == 274
    for seed in (0, 42, 2**31 - 2):
        np.testing.assert_array_equal(
            reference_inputs.tiebreak_rank(1213, seed),
            tiebreak_rank(1213, seed))


def test_end_to_end_line_of_the_cell(capsys):
    got = rehearse(capsys, trace=0)
    assert got["correct"] is True and got["failed"] == 0
    assert set(got["metrics"]) == {"lane_events_per_s", "wave_s", "setup_s"}
    assert got["attempted"] >= 1


def test_traced_line_reads_the_list_less_metrics_and_the_two_new(capsys):
    from tpusim.obs.spans import sweep_log

    got = rehearse(capsys, trace=1)
    assert got["correct"] is True
    # scan_roofline and device_idle_pct are a chip's: a rehearsal has no
    # device time to divide by
    expected = (NEW_METRICS | {"host_s", "scan_s", "fetch_s"}
                | {f"clock_{name}" for name in SHARED})
    assert set(got["metrics"]) >= expected
    for name in expected:
        assert got["metrics"][name]["value"] > 0, name
    assert got["metrics"]["delete_share"] == {"value": 18 / 64, "unit": "share"}
    value = {k: v["value"] for k, v in got["metrics"].items()}
    # resident from wave to wave though every wave ends elsewhere than it
    # began; under 64 lanes the plain body: a column an event
    assert (value["clock_table_reuse_share"],
            value["clock_table_pass_events"]) == (1.0, 1)
    assert value["clock_fetch_copy_s"] < value["clock_host_tail_s"]
    assert {"busy_s", "window_s"} <= set(got["device"])
    rec = sweep_log()[-1]
    assert (rec.lanes, rec.events, rec.delete_events, rec.traces,
            rec.tables_reused) == (3, 64, 3 * 18, 1, 1)


def test_a_record_without_the_counter_reads_nothing(monkeypatch):
    """The parent of the PR that brought the cell: its sweep record has no
    `delete_events`. The reader leaves the metric out, no raise."""
    import types

    from benchmark.lib import sweep_log

    metric = bench_run.load_module("layer_metrics", "delete_share")
    rec = types.SimpleNamespace(lanes=2560, events=512, spans=[],
                                delete_events=2560 * 238)
    old = types.SimpleNamespace(lanes=2560, events=512, spans=[])
    for window, want in (([rec, rec], 238 / 512), ([old, old], None),
                         ([rec, old], None), (None, None)):
        monkeypatch.setattr(
            sweep_log, "records",
            lambda run, w=window: None if w is None else (rec, w))
        assert metric.read({}) == want
    assert 238 / 512 == 0.46484375


def test_the_device_metrics_read_the_cells_shape():
    """What `scan_roofline` reads on a chip, from the run object the driver
    returns: one policy and the tables' K on the program's buckets."""
    from benchmark.lib import roofline

    run = {"traced": {"scan_device_s": 1.2, "busy_s": 1.3, "window_s": 2.4},
           "device_kind": "TPU v5 lite", "rehearsal": False,
           "shape": {"nodes": 1213, "pod_types": 64, "policies": 1,
                     "lanes": 2560, "events": 512}}
    got = bench_run.load_module("layer_metrics", "scan_roofline").read(run)
    moved = roofline.scan_bytes_per_lane_event(1213, 64, 1) * 2560 * 512
    assert moved == 6641 * 2560 * 512
    assert got == pytest.approx(100 * moved / 819e9 / 1.2)


def test_a_reference_that_ignores_deletions_is_not_correct(capsys):
    import clock_control

    undo = clock_control.hand_the_reference_a_stream_without_deletions()
    try:
        got = rehearse(capsys, trace=0)
    finally:
        undo()
    assert got["correct"] is False
    assert rehearse(capsys, trace=0)["correct"] is True


def test_the_walk_tells_a_record_that_is_not_the_lanes(capsys):
    """The walk ties the record of the events to what the lane returned:
    a release the record names and the lane never made ends on another
    state; a deletion recorded on another node differs where it stands."""
    from types import SimpleNamespace

    from benchmark.lib import reference_clock, reference_follow_clock

    rng = np.random.default_rng(3)
    n, p = 6, 5
    cluster = {"cpu_cap": np.full(n, 32000), "mem_cap": np.full(n, 65536),
               "gpu_cnt": np.full(n, 2), "gpu_type": np.zeros(n, int)}
    pods = {"cpu": np.full(p, 4000), "mem": np.full(p, 1024),
            "gpu_milli": np.asarray([500, 1000, 500, 250, 1000]),
            "gpu_num": np.ones(p, int), "gpu_mask": np.zeros(p, int)}
    typical = {"cpu": np.asarray([4000, 8000]),
               "gpu_milli": np.asarray([500, 1000]),
               "gpu_num": np.asarray([1, 1]), "gpu_mask": np.asarray([0, 0]),
               "freq": np.asarray([0.6, 0.4])}
    events = reference_clock.event_stream(
        [0, 1, 2, 3, 9], [5, 7, 0, 4, 0])
    rank = rng.permutation(n)
    ref = reference_clock.replay(cluster, pods, events, typical, rank)

    def lane_of(r):
        return SimpleNamespace(
            placed_node=r["placed_node"], dev_mask=r["dev_mask"],
            ever_failed=r["ever_failed"], state=SimpleNamespace(
                **{f: r[f] for f in ("cpu_left", "mem_left", "gpu_left",
                                     "aff_cnt")}))

    def walk(record, lane=None):
        return reference_follow_clock.walk(
            cluster, pods, events, typical, rank, lane or lane_of(ref),
            record)

    sound = walk((ref["event_node"], ref["event_dev"]))
    assert sound["events_held"] == 8 and sound["deletes_held"] == 3
    assert not any(sound["differing"].values())
    first = int(np.flatnonzero(events[0] == reference_clock.EV_DELETE)[0])
    moved = ref["event_node"].copy()
    moved[first] = (moved[first] + 1) % n
    got = walk((moved, ref["event_dev"]))
    assert got["events_held"] == first and got["differing"]["event_node"] == 1
    # the lane's final arrays say a pod is gone that the record kept
    kept = dict(ref, cpu_left=ref["cpu_left"].copy())
    kept["cpu_left"][int(ref["event_node"][first])] -= 4000
    got = walk((ref["event_node"], ref["event_dev"]), lane_of(kept))
    assert got["events_held"] == 8 and got["differing"]["state.cpu_left"] == 1
