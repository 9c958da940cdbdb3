"""The load cell (`openb-load130.report-seeds`, ISSUE 41): whole tuned traces
on a cluster that fills up, with the per-event report on.

(a) On a loaded small cluster (96 nodes, the default pod list tuned to 130 %
of ITS capacity, two shuffles of different length) each lane of one sweep
equals its standalone replay bit for bit, its placements are the plain
reference's over EVERY event and its series `ref/report_numpy.py`'s at
EVERY event, with over 15 % of the creates rejected. (b) A pod axis over
`lane_write`'s line for a short leaf, with a trace a lane and deletions in
the stream, equals the standalone runs. (c) The report program's span and
the two counters of the sweep record, blocked and not. (d) The cell's nine
readers on a rehearsal, and its two controls. Here and not only under
benchmark/tests: the tier-1 lane runs it.
"""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import load_wave, wave
from benchmark.lib import (
    compare,
    inputs,
    reference_follow_load,
    reference_inputs,
    sweep_log,
)
from tests.test_table_reuse_metric import (  # noqa: F401  (fixtures)
    bench_run,
    compile_cache_put_back,
)
from tpusim.obs import sweep_log as program_log
from tpusim.ref import report_numpy
from tpusim.sim import driver, lane_write

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "openb-load130.report-seeds"
NEW = ["load_step_us_per_lane_event", "rejected_create_share",
       "load_dense_access_sites", "report_postpass_s", "report_roofline",
       "report_series_bytes", "load_fetch_copy_s", "load_host_lead_s",
       "load_host_tail_s"]
SHARED = {"load_dense_access_sites": "dense_access_sites",
          "load_fetch_copy_s": "fetch_copy_s",
          "load_host_lead_s": "host_lead_s",
          "load_host_tail_s": "host_tail_s"}


def _config(tiny: bool = True) -> dict:
    with open(os.path.join(
            REPO, "benchmark", "configs", "openb-load130.json")) as f:
        return wave.sized(json.load(f), tiny)


@pytest.fixture(scope="module")
def loaded():
    """The cell's tiny deployment: (config, nodes, pods, the lead Simulator,
    its two tuned traces, the reference's side)."""
    from tpusim.io.trace import load_node_csv, load_pod_csv

    config = _config()
    nodes = load_node_csv(inputs.NODE_CSV)[: config["cluster"]["nodes"]]
    pods = load_pod_csv(inputs.POD_CSV)
    cfg = wave.simulator_config(config["simulator"], 42, profile=False,
                                report_per_event=True)
    lead = wave.build_simulator(nodes, pods, cfg)
    traces = [lead.prepare_pods(tuning_seed=s)
              for s in config["workload"]["tuning_seeds"]]
    return config, nodes, pods, lead, traces, load_wave.reference_side(
        config, len(nodes))


@pytest.fixture(scope="module")
def swept(loaded):
    """One sweep of three lanes: both shuffles, one of them twice."""
    _config_, _nodes, _pods, lead, traces, _ref = loaded
    of = [0, 1, 1]
    seeds = [11, 12, 13]
    lanes = driver.schedule_pods_sweep(
        lead, None, np.full((3, 1), 1000, np.int32), seeds,
        lane_pods=[traces[s] for s in of])
    return of, seeds, lanes


def test_the_tiny_cluster_is_loaded_and_the_traces_differ_in_length(
        loaded, swept):
    config, _nodes, _pods, _lead, traces, ref = loaded
    of, _seeds, lanes = swept
    assert len(traces[0]) != len(traces[1])
    capacity = int(ref[0]["gpu_cnt"].sum()) * 1000
    for trace in traces:  # tuned to 130 % of THIS cluster's GPUs
        asked = sum(p.total_gpu_milli for p in trace)
        assert 1.29 < asked / capacity <= 1.3
    for s, lane in zip(of, lanes):
        assert lane.events == len(traces[s]) == len(lane.metrics.used_nodes)
        assert lane.failed / lane.events > 0.15
        assert lane.gpu_alloc_pct > 90.0


@pytest.mark.parametrize("i", [0, 1, 2])
def test_a_lane_of_the_sweep_equals_its_standalone_replay(loaded, swept, i):
    """Placements, masks, flags, every NodeState field and every integer
    series bit for bit; the float series within the cell's limits."""
    config, nodes, pods, _lead, traces, _ref = loaded
    of, seeds, lanes = swept
    want = load_wave.oracle_lane(
        nodes, pods, config["simulator"],
        config["workload"]["tuning_seeds"][of[i]], traces[of[i]],
        np.asarray([1000]), seeds[i])
    assert all(d == 0 for _, d in compare.lane_differences(lanes[i], want))
    e = lanes[i].events
    series = type(want.metrics)(*(np.asarray(a)[:e] for a in want.metrics))
    for what, got, limit in load_wave.series_differences(
            lanes[i].metrics, series):
        assert got <= limit, (what, got, limit)
    assert np.array_equal(lanes[i].event_node, np.asarray(want.event_node)[:e])


@pytest.mark.parametrize("i", [0, 1])
def test_a_lane_is_the_plain_references_at_every_event(loaded, swept, i):
    """The walk scores EVERY create and keeps the reference's state after
    EVERY event; `report_numpy` recomputes the report from each."""
    _config_, _nodes, _pods, _lead, traces, ref = loaded
    of, seeds, lanes = swept
    cluster, requests, names, typical, energy = ref
    rows = load_wave.trace_rows(traces[of[i]], names)
    trace = {k: v[rows] for k, v in requests.items()}
    lane, events = lanes[i], len(rows)
    walked = reference_follow_load.walk(
        cluster, trace, typical,
        reference_inputs.tiebreak_rank(len(cluster["cpu_cap"]), seeds[i]),
        lane, 1000, range(events), range(events))
    assert walked["events_held"] == events
    assert not any(walked["differing"].values()), walked["differing"]
    assert walked["scored"] == lane.placed
    assert walked["rejected"] == lane.failed == int(lane.counters[2])
    at = {e: report_numpy.report(cluster, *walked["states"][e], typical,
                                 energy, trace, np.arange(e + 1))
          for e in range(events)}
    for what, got, limit in load_wave.report_differences(lane.metrics, at):
        assert got <= limit, (what, got, limit)


def test_the_report_reference_shares_nothing_and_its_copy_is_it():
    ref_dir = os.path.join(REPO, "tpusim", "ref")
    with open(os.path.join(ref_dir, "report_numpy.py")) as f:
        own = f.read()
    with open(os.path.join(REPO, "benchmark", "lib",
                           "reference_report.py")) as f:
        copy = f.read()
    for banned in ("tpusim.ops", "tpusim.sim", "tpusim.policies", "jax"):
        assert f"import {banned}" not in own and f"from {banned}" not in own
    assert copy == own.replace(
        "from tpusim.ref import fgd_numpy as fgd",
        "from benchmark.lib import reference_fgd as fgd").replace(
        "from tpusim.ref import mix_numpy as mix",
        "from benchmark.lib import reference_mix as mix")


def test_a_dropped_delta_and_a_bfloat16_series_are_outside_the_limits(
        loaded, swept):
    """The float limit from the other side: one placed GPU create's delta
    left out of the frag series, or the series rounded to bfloat16, reads
    over `FLOAT_LIMITS`."""
    import ml_dtypes

    of, _seeds, lanes = swept
    lane = lanes[0]
    frag = np.asarray(lane.metrics.frag_amounts)
    bent = frag.astype(ml_dtypes.bfloat16).astype(np.float32)
    got = dict((w, g) for w, g, _ in load_wave.series_differences(
        lane.metrics._replace(frag_amounts=bent), lane.metrics))
    assert got["frag_amounts"] > 10 * load_wave.FLOAT_LIMITS["frag_amounts"]
    step = np.abs(np.diff(frag, axis=0)).max(1)
    gpu = np.asarray([p.total_gpu_milli for p in loaded[4][of[0]]])
    placed_on_gpus = (np.asarray(lane.placed_node) >= 0) & (gpu > 0)
    assert step[placed_on_gpus[1:]].min() > load_wave.FLOAT_LIMITS[
        "frag_amounts"]


# ---------------------------------------------------------------- (b)
@pytest.fixture(scope="module")
def long_axis():
    """Two lanes of the WHOLE recorded pod list by its own clock (every pod
    a creation and a deletion) on 64 nodes: the bookkeeping rows hold 8,193
    pods, over `lane_write`'s line for a short leaf, each lane brings its
    own pod index, and half of the events read `masks[idx]` back."""
    from tpusim.io.trace import load_node_csv, load_pod_csv

    with open(os.path.join(
            REPO, "benchmark", "configs", "openb-clock.json")) as f:
        sim_cfg = json.load(f)["simulator"]
    nodes = load_node_csv(inputs.NODE_CSV)[:64]
    pods = load_pod_csv(inputs.POD_CSV)
    cfg = wave.simulator_config(sim_cfg, 42, profile=False,
                                use_timestamps=True)
    sim = wave.build_simulator(nodes, pods, cfg)
    traces = [list(pods), [p for i, p in enumerate(pods) if i % 97]]
    with lane_write.counting() as sites:
        lanes = driver.schedule_pods_sweep(
            sim, None, np.full((2, 1), 1000, np.int32), [7, 8],
            lane_pods=traces)
    return sim_cfg, nodes, pods, traces, lanes, sites


def test_the_pod_axis_is_over_the_line_and_the_read_takes_the_dense_form(
        long_axis):
    from tpusim.sim.table_engine import BLOCKED_MIN_NODES

    *_, traces, lanes, sites = long_axis
    assert BLOCKED_MIN_NODES <= 8192 + 1  # the padded pod axis, one spare row
    assert all(7680 < len(t) <= 8192 for t in traces)
    # the three bookkeeping writes of the body and of the epilogue are
    # scatters (not dense), the delete branch's read of masks is dense;
    # 16 write sites where ISSUE 41 counted 17: the body's commit no longer
    # adds into aff_cnt (ISSUE 42: chunk_affinity, once a chunk)
    assert len(sites) == 16 and 0 < len(sites.dense) < 30
    unbatched = jnp.arange(3 * 8200 * 8).reshape(3, 8200, 8) % 7 == 0
    idx = jnp.asarray([0, 4100, 8199])
    with lane_write.counting() as read_sites:
        got = jax.vmap(lane_write.read_pod)(unbatched, idx)
    assert len(read_sites.dense) == 1
    assert np.array_equal(got, unbatched[jnp.arange(3), idx])
    # a short axis and a 1-D leaf stay the plain index: no site of the rule
    with lane_write.counting() as none:
        jax.vmap(lane_write.read_pod)(unbatched[:, :513], idx % 513)
        jax.vmap(lane_write.read_pod)(unbatched[:, :, 0], idx)
    assert not none.dense and not none


@pytest.mark.parametrize("i", [0, 1])
def test_a_lane_over_the_line_equals_its_standalone_run(long_axis, i):
    from tpusim.io.trace import build_events, pods_to_specs

    sim_cfg, nodes, pods, traces, lanes, _sites = long_axis
    lane = lanes[i]
    cfg = wave.simulator_config(sim_cfg, 42, profile=False,
                                use_timestamps=True, seed=lane.seed)
    alone = wave.build_simulator(nodes, pods, cfg)
    kinds, idx = build_events(traces[i], True)
    want = alone.run_events(
        alone.init_state, pods_to_specs(traces[i], alone.node_index),
        jnp.asarray(kinds), jnp.asarray(idx), jax.random.PRNGKey(lane.seed),
        bucket=512)
    assert "table" in str(alone._last_engine)
    assert lane.events == len(kinds) == 2 * len(traces[i])
    assert int(lane.counters[3]) == len(traces[i])  # every pod deleted
    assert all(d == 0 for _, d in compare.lane_differences(lane, want))
    assert np.array_equal(lane.event_node,
                          np.asarray(want.event_node)[:lane.events])
    assert np.array_equal(lane.event_dev,
                          np.asarray(want.event_dev)[:lane.events])


# ---------------------------------------------------------------- (c)
@pytest.mark.parametrize("blocked", [True, False], ids=["blocked", "not"])
def test_the_report_program_has_its_span_and_the_record_its_counters(
        loaded, blocked):
    config, nodes, pods, _lead, traces, _ref = loaded
    cfg = wave.simulator_config(config["simulator"], 42, profile=blocked,
                                report_per_event=True)
    sim = wave.build_simulator(nodes, pods, cfg)
    lanes = driver.schedule_pods_sweep(
        sim, None, np.full((2, 1), 1000, np.int32), [5, 6], lane_pods=traces)
    rec = program_log()[-1]
    names = [sp.name for sp in rec.spans]
    assert names == ["specs", "lane_keys", "lane_ranks", "init_tables",
                     "scan", "frag_postpass", "event_metrics", "fetch",
                     "slice_lanes"]
    report = rec.spans[names.index("event_metrics")]
    post = rec.spans[names.index("frag_postpass")]
    # a wave that does not block leaves the span at its dispatch
    assert report.dispatch_s > 0 and (blocked or report.block_s < 1e-3)
    assert "gathered" in post.marks and not report.marks
    assert rec.blocked is blocked
    assert rec.rejected_creates == sum(lane.failed for lane in lanes) > 0
    # nine series, 60 bytes an event, over the padded event axis
    assert rec.series_bytes == 2 * 60 * 1024 < rec.fetch_bytes
    doc = rec.to_dict()
    assert doc["rejected_creates"] == rec.rejected_creates
    assert doc["series_bytes"] == rec.series_bytes
    by_name = {sp["name"]: sp for sp in doc["spans"]}
    assert by_name["fetch"]["meta"]["series_bytes"] == rec.series_bytes
    assert by_name["slice_lanes"]["meta"]["rejected_creates"] == (
        rec.rejected_creates)
    # the derived seconds still add up to the call's last span's end
    parts = [getattr(rec, f) for f in (
        "host_lead_s", "covered_s", "device_block_s", "device_wait_s",
        "host_tail_s")]
    last = rec.spans[-1]
    assert sum(parts) == pytest.approx(
        last.start_s + last.total_s - (rec.start_s - rec.epoch), abs=1e-9)
    assert rec.device_block_s >= report.block_s
    # the Chrome trace carries both through the spans' args
    from tpusim.obs.emitters import chrome_trace_events

    args = {ev["name"]: ev.get("args", {})
            for ev in chrome_trace_events(rec.spans)}
    assert args["fetch:dispatch"]["series_bytes"] == rec.series_bytes
    assert args["slice_lanes:dispatch"]["rejected_creates"] == (
        rec.rejected_creates)
    assert "event_metrics:dispatch" in args


def test_a_sweep_without_the_report_has_no_such_span_and_no_series(loaded):
    config, nodes, pods, _lead, traces, _ref = loaded
    cfg = wave.simulator_config(config["simulator"], 42, profile=False)
    sim = wave.build_simulator(nodes, pods, cfg)
    driver.schedule_pods_sweep(
        sim, None, np.full((1, 1), 1000, np.int32), [5],
        lane_pods=[traces[0][:64]])
    rec = program_log()[-1]
    assert "event_metrics" not in [sp.name for sp in rec.spans]
    assert rec.series_bytes == 0 and rec.rejected_creates == 0


def test_the_report_program_runs_under_its_named_scope(loaded):
    from tpusim.sim.metrics import compute_event_metrics

    _config_, _nodes, _pods, lead, traces, _ref = loaded
    from tpusim.io.trace import build_events, pods_to_specs

    trace = traces[0][:32]
    kinds, idx = build_events(trace)
    lowered = compute_event_metrics.lower(
        lead.init_state, pods_to_specs(trace, lead.node_index),
        jnp.asarray(kinds), jnp.asarray(idx),
        jnp.zeros(len(kinds), jnp.int32) - 1,
        jnp.zeros((len(kinds), 8), bool), lead.typical)
    assert "tpusim.event_metrics" in lowered.as_text(debug_info=True)


# ---------------------------------------------------------------- (d)
def test_the_cell_and_its_nine_metrics_stand_as_entered(bench_run):
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    # by name, after the clock cell's: later PRs append after them (PR 45:
    # the clustering cell and its eight metrics)
    cells = [w["name"] for w in bench["workloads"]]
    at = cells.index(CELL)
    assert cells[at - 1] == "openb-clock.fgd-seeds"
    assert bench["workloads"][at] == {
        "name": CELL, "config": "openb-load130",
        "traffic": "report-seeds-320", "chips": 1,
        "why": bench["workloads"][at]["why"]}
    entry = next(c for c in bench["configs"] if c["name"] == "openb-load130")
    assert (entry["file"], entry["reduced"]) == (
        "benchmark/configs/openb-load130.json", ["families", "policies"])
    assert "depth_events" not in json.dumps(_config(False)["reduced"])
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])
    assert names[first - 1] == "clock_fetch_bytes"
    assert names[first:first + len(NEW)] == NEW
    by_name = dict(zip(names, bench["per_layer"]))
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert hasattr(bench_run.load_module("layer_metrics", name), "read")
    for name, control in SHARED.items():
        assert by_name[name] == dict(
            by_name[control], name=name, workloads=[CELL])
        reader = bench_run.load_module("layer_metrics", name).read
        assert reader.__module__ == f"benchmark.layer_metrics.{control}"
    assert (by_name["report_roofline"]["unit"],
            by_name["report_roofline"]["source"]) == ("%", "device_trace")


def _record(spans=(), **fields):
    return types.SimpleNamespace(lanes=4, events=953, spans=list(spans),
                                 **fields)


def _span(name, dispatch_s=0.25, block_s=0.5):
    return types.SimpleNamespace(name=name, dispatch_s=dispatch_s,
                                 block_s=block_s,
                                 total_s=dispatch_s + block_s)


@pytest.mark.parametrize("metric, window, want", [
    ("rejected_create_share", [_record(rejected_creates=900)] * 3, 0.24),
    ("rejected_create_share", [_record(rejected_creates=0)] * 2, 0.0),
    ("rejected_create_share", [_record(), _record()], None),
    ("rejected_create_share", None, None),
    ("report_series_bytes", [_record(series_bytes=245760)] * 2, 245760),
    ("report_series_bytes", [_record(series_bytes=1), _record()], None),
    ("report_postpass_s", [_record([_span("event_metrics")])] * 2, 0.75),
    ("report_postpass_s", [_record([_span("frag_postpass")])] * 2, None),
    ("load_step_us_per_lane_event",
     [_record([_span("scan", 0.0, 0.0375)])] * 2, 10.0),
], ids=["the load cell", "an empty cluster", "the parent", "no log",
        "series", "one without", "the span", "the parent's spans", "step"])
def test_a_new_reader_reads_the_record_or_nothing(
        bench_run, monkeypatch, metric, window, want):
    reader = bench_run.load_module("layer_metrics", metric)
    monkeypatch.setattr(
        sweep_log, "records",
        lambda run: None if window is None else (_record(), window))
    got = reader.read({"real_events": 3750})
    assert got == (want if want is None else pytest.approx(want))


def test_the_report_roofline_divides_algorithm_bytes_by_device_time(
        bench_run):
    from benchmark.lib import roofline_report

    reader = bench_run.load_module("layer_metrics", "report_roofline")
    run = {"device_kind": "TPU v5 lite", "real_events": 3_466_016,
           "shape": {"nodes": 1213, "lanes": 320},
           "traced": {"report_device_s": 0.5}}
    moved = 3_466_016 * 72 + 320 * 1213 * 96
    assert roofline_report.report_bytes(1213, 320, 3_466_016) == moved
    assert reader.read(run) == pytest.approx(100 * moved / 819e9 / 0.5)
    assert reader.read({**run, "traced": {"report_device_s": None}}) is None
    assert reader.read({**run, "traced": None}) is None
    trace = {"wave": (1.0, 3.0), "devices": {"/device:TPU:0": {
        "ops": [], "modules": [("jit__replay_impl(1)", 1.0, 2.0),
                               ("jit_compute_event_metrics(7)", 2.0, 2.5),
                               ("jit_compute_event_metrics(7)", 2.9, 3.4)]}}}
    assert roofline_report.report_device_seconds(trace) == pytest.approx(0.6)
    assert roofline_report.report_device_seconds(
        {"wave": (1.0, 3.0), "devices": {"d": {"modules": [], "ops": []}}}
    ) is None


def _rehearse(bench_run, capsys, trace):
    assert bench_run.main([
        "--workload", CELL, "--seed", "3000000041", "--seconds", "0.5",
        "--trace", str(trace), "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_tiny_load_cells_traced_line_reads_the_full_cluster(
        bench_run, capsys, compile_cache_put_back):
    for _ in range(3):
        got = _rehearse(bench_run, capsys, trace=1)
        assert got["correct"] is True and got["failed"] == 0
        # as in test_table_reuse_metric: a preempted tiny wave reads nothing
        if "rejected_create_share" in got["metrics"]:
            break
    # every new reader but the device's own (a rehearsal has no device time)
    assert set(NEW) - {"report_roofline"} <= set(got["metrics"])
    assert 0.15 < got["metrics"]["rejected_create_share"]["value"] < 0.3
    assert got["metrics"]["report_series_bytes"]["value"] == 4 * 60 * 1024
    assert got["metrics"]["report_postpass_s"]["value"] > 0
    tail = program_log()[-(got["attempted"] + 2):]
    # 2 shuffles x 2 seeds, each lane all of its own trace: 953 and 923
    assert {(rec.lanes, rec.events) for rec in tail} == {(4, 953)}
    assert all(rec.rejected_creates > 0 for rec in tail)


@pytest.mark.parametrize("control", ["another_shuffle", "dropped_delta"])
def test_a_bent_reference_reads_not_correct(
        bench_run, capsys, compile_cache_put_back, control):
    sys.path.insert(0, os.path.join(REPO, "benchmark", "tests"))
    try:
        import load_control
    finally:
        sys.path.pop(0)
    config = _config()
    undo = (load_control.dropped_delta() if control == "dropped_delta" else
            load_control.another_shuffle(
                config["workload"]["tuning_seeds"],
                float(config["simulator"]["tuning_ratio"])))
    try:
        got = _rehearse(bench_run, capsys, trace=0)
    finally:
        undo()
    assert got["correct"] is False
    assert set(got["metrics"]) == {"lane_events_per_s", "wave_s", "setup_s"}
    assert _rehearse(bench_run, capsys, trace=0)["correct"] is True


# ------------------------------------------------------- the README's anchor
def test_shuffle_42_under_seed_42_is_the_readmes_anchor():
    """The whole cluster, the whole trace, one lane on the cell's path
    (README.md "Results", PR 22's chip check: 10,811 events, 8,350 placed,
    95.52 % of the GPUs allocated), its report series of its own length
    and its last row the final state's."""
    from tpusim.io.trace import load_node_csv, load_pod_csv

    config = _config(tiny=False)
    cfg = wave.simulator_config(config["simulator"], 42, profile=False,
                                report_per_event=True)
    sim = wave.build_simulator(load_node_csv(inputs.NODE_CSV),
                               load_pod_csv(inputs.POD_CSV), cfg)
    trace = sim.prepare_pods(tuning_seed=42)
    (lane,) = driver.schedule_pods_sweep(
        sim, None, np.full((1, 1), 1000, np.int32), [42], lane_pods=[trace])
    assert (lane.events, lane.placed, lane.failed) == (10811, 8350, 2461)
    assert round(lane.gpu_alloc_pct, 2) == 95.52
    m = lane.metrics
    assert len(m.used_gpu_milli) == 10811
    assert int(m.used_gpu_milli[-1]) == round(
        lane.gpu_alloc_pct / 100 * 6_212_000)
    assert int(m.arrived_gpu_milli[-1]) == sum(
        p.total_gpu_milli for p in trace)
    assert 1.2999 < int(m.arrived_gpu_milli[-1]) / 6_212_000 <= 1.3
