"""tpusim.obs — telemetry, profiling, and the bench gate (ISSUE 3).

The contracts under test:
  (1) the in-scan counters are EXACT and engine-invariant — the same
      trace yields bit-identical counter vectors (modulo the documented
      engine-specific `rebuilds` slot) on the flat, blocked, sequential,
      and shard_map engines;
  (2) telemetry is continuous across checkpoint kill/resume and across
      fault-path segment splits — the resumed/segmented run's counters
      equal the uninterrupted run's;
  (3) the JSONL record's `deterministic` block is bit-identical across
      two same-seed runs;
  (4) the emitters round-trip their schema;
  (5) the content-keyed init_tables cache is bit-transparent;
  (6) the bench gate's parse/compare logic.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.fixtures import random_cluster, random_pods
from tpusim.io.trace import NodeRow, PodRow, pods_to_specs
from tpusim.policies import make_policy
from tpusim.sim.driver import Simulator, SimulatorConfig
from tpusim.sim.engine import EV_CREATE, EV_DELETE, make_replay
from tpusim.sim.table_engine import build_pod_types, make_table_replay


def _mixed_events(num_pods, rng):
    kinds, idxs, seen = [], [], set()
    for i in range(num_pods):
        kinds.append(EV_CREATE)
        idxs.append(i)
        if rng.random() < 0.3 and i > 0:
            victim = int(rng.integers(0, i + 1))
            if victim not in seen:
                seen.add(victim)
                kinds.append(EV_DELETE)
                idxs.append(victim)
    return jnp.asarray(kinds, jnp.int32), jnp.asarray(idxs, jnp.int32)


@pytest.mark.slow
def test_counters_engine_invariant():
    """The same create/delete mix yields bit-identical invariant counters
    (creates/binds/fail_creates/deletes/skips) on the flat, blocked,
    sequential, and shard_map engines — and the counts agree with the
    per-event telemetry they summarize.

    slow-marked (tier-1 budget, ROADMAP): it compiles four engines; the
    tier-1 lane still pins table-engine counters through the driver tests
    below, and this runs under `make resume-smoke` / plain pytest."""
    from tpusim.obs.counters import counters_from_telemetry
    from tpusim.parallel import make_mesh, pad_nodes, shard_state
    from tpusim.parallel.shard_engine import make_shardmap_table_replay

    rng = np.random.default_rng(7)
    state, tp = random_cluster(rng, num_nodes=24)
    pods = random_pods(rng, num_pods=40)
    ev_kind, ev_pod = _mixed_events(40, rng)
    policies = [(make_policy("FGDScore"), 1000)]
    key = jax.random.PRNGKey(3)
    rank = jnp.asarray(rng.permutation(24).astype(np.int32))
    types = build_pod_types(pods)

    flat = make_table_replay(policies, gpu_sel="FGDScore", block_size=-1)(
        state, pods, types, ev_kind, ev_pod, tp, key, rank
    )
    blocked = make_table_replay(policies, gpu_sel="FGDScore", block_size=8)(
        state, pods, types, ev_kind, ev_pod, tp, key, rank
    )
    seq = make_replay(policies, gpu_sel="FGDScore", report=False)(
        state, pods, ev_kind, ev_pod, tp, key, rank
    )
    mesh = make_mesh(4)
    st_p, rank_p = pad_nodes(state, rank, 4)
    shard = make_shardmap_table_replay(policies, mesh, gpu_sel="FGDScore")(
        shard_state(st_p, mesh), pods, types, ev_kind, ev_pod, tp, key,
        rank_p,
    )

    ref = np.asarray(flat.counters)
    for out in (blocked, seq, shard):
        assert np.array_equal(np.asarray(out.counters)[:5], ref[:5])
        assert np.array_equal(
            np.asarray(out.placed_node), np.asarray(flat.placed_node)
        )
    # counters agree with the telemetry they summarize
    derived = counters_from_telemetry(ev_kind, flat.event_node)
    assert np.array_equal(derived[:5], ref[:5].astype(np.int64))
    # sanity: the mix actually exercised creates AND deletes
    assert ref[0] > 0 and ref[3] > 0 and ref[0] == ref[1] + ref[2]


def _driver_inputs():
    rng = np.random.default_rng(31)
    nodes = [
        NodeRow(f"n{i}", 32000, 131072, int(g), "V100M16" if g else "")
        for i, g in enumerate(rng.choice([0, 2, 4, 8], 12))
    ]
    pods = [
        PodRow(f"p{i}", int(rng.choice([1000, 4000])), 1024,
               int(rng.choice([0, 1])), 500)
        for i in range(30)
    ]
    return nodes, pods


def _run_driver(nodes, pods, every=0, ckdir="", seed=42, profile=False,
                table_cache=""):
    sim = Simulator(nodes, SimulatorConfig(
        policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
        report_per_event=True, checkpoint_every=every,
        checkpoint_dir=ckdir, seed=seed, profile=profile,
        table_cache_dir=table_cache,
    ))
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    specs = pods_to_specs(pods)
    out = sim.run_events(
        sim.init_state, specs, jnp.zeros(len(pods), jnp.int32),
        jnp.arange(len(pods), dtype=jnp.int32), jax.random.PRNGKey(2),
    )
    return sim, out


@pytest.mark.slow  # tier-1 trim, ISSUE 16: rides resume-smoke
def test_counters_survive_kill_resume(tmp_path):
    """Telemetry continuity across checkpoint kill/resume: the counters
    ride the carry, so a resumed run's final vector is bit-identical to
    the uninterrupted run's (nothing is double- or under-counted)."""
    import tpusim.io.storage as storage

    nodes, pods = _driver_inputs()
    _, r0 = _run_driver(nodes, pods)
    assert r0.counters is not None

    real_save = storage.save_checkpoint

    def killing_save(*a, **k):
        real_save(*a, **k)
        raise KeyboardInterrupt("simulated preemption")

    storage.save_checkpoint = killing_save
    try:
        with pytest.raises(KeyboardInterrupt):
            _run_driver(nodes, pods, every=10, ckdir=str(tmp_path))
    finally:
        storage.save_checkpoint = real_save
    assert os.listdir(tmp_path)

    sim, r2 = _run_driver(nodes, pods, every=10, ckdir=str(tmp_path))
    assert any("[Checkpoint] resumed replay" in l for l in sim.log.lines)
    assert np.array_equal(np.asarray(r0.counters), np.asarray(r2.counters))
    # and through the telemetry record (padding-corrected dict form)
    rec = sim.run_telemetry().to_record()
    assert rec["deterministic"]["counters"]["creates"] == len(pods)
    assert rec["deterministic"]["counters"]["skips"] == 0  # padding removed


def test_telemetry_record_deterministic_and_profiled():
    """Two same-seed profiled runs emit bit-identical `deterministic`
    blocks; profiling attributes walls to the compile(dispatch)/execute
    (block) halves of the scan span."""
    nodes, pods = _driver_inputs()
    sim1, _ = _run_driver(nodes, pods, profile=True)
    sim2, _ = _run_driver(nodes, pods, profile=True)
    rec1 = sim1.run_telemetry().to_record()
    rec2 = sim2.run_telemetry().to_record()
    blob1 = json.dumps(rec1["deterministic"], sort_keys=True)
    blob2 = json.dumps(rec2["deterministic"], sort_keys=True)
    assert blob1 == blob2
    names = [s["name"] for s in rec1["timing"]["spans"]]
    assert "scan" in names and "typical_pods" in names
    scan = next(s for s in rec1["timing"]["spans"] if s["name"] == "scan")
    assert scan["dispatch_s"] >= 0 and scan["block_s"] >= 0
    # the three fields are rounded to 6 dp independently
    assert scan["total_s"] == pytest.approx(
        scan["dispatch_s"] + scan["block_s"], abs=2e-6
    )
    assert rec1["deterministic"]["engines"] == ["table"]


def test_fault_run_counters_and_disruption():
    """The fault path's segmented replays accumulate into ONE counter set
    (continuity across segments), and the [Disruption] block's totals are
    machine-readable from the record — same numbers, same seed, twice."""
    from tpusim.sim.engine import EV_NODE_FAIL
    from tpusim.sim.faults import FaultEvent

    nodes, pods = _driver_inputs()
    faults = [FaultEvent(pos=10, kind=EV_NODE_FAIL, node=0)]

    def run():
        sim = Simulator(nodes, SimulatorConfig(
            policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
            report_per_event=False, seed=42,
        ))
        sim.set_workload_pods(pods)
        res = sim.schedule_pods_with_faults(pods, faults=faults)
        return sim, res

    sim1, res1 = run()
    sim2, res2 = run()
    rec1 = res1.telemetry.to_record()["deterministic"]
    rec2 = res2.telemetry.to_record()["deterministic"]
    assert rec1 == rec2
    dm = sim1.last_disruption
    assert rec1["disruption"]["node_failures"] == dm.node_failures == 1
    assert rec1["disruption"]["evicted_pods"] == dm.evicted_pods
    # creates across ALL segments = base creations + retry re-creations
    assert rec1["counters"]["creates"] == len(pods) + dm.retries_enqueued
    assert rec1["counters"]["skips"] == 0


def test_emitter_schema_roundtrip(tmp_path):
    """JSONL append/read round-trip, Prometheus textfile well-formedness,
    Chrome-trace structure — on a real recorder snapshot."""
    from tpusim.obs import Recorder, emitters

    rec = Recorder(enabled=True)
    with rec.span("scan", engine="table") as h:
        h.dispatched()
    rec.count("degrade_vmem")
    rec.note_scan("table", counters=np.array([5, 4, 1, 0, 2, 0]),
                  pad_skips=2, events=5)
    tel = rec.snapshot(meta={"seed": 1})
    record = tel.to_record()
    assert record["schema"] == "tpusim-obs-v1"
    assert record["deterministic"]["counters"] == {
        "creates": 5, "binds": 4, "fail_creates": 1, "deletes": 0,
        "skips": 0, "rebuilds": 0,
    }
    assert record["deterministic"]["degrades"] == {"degrade_vmem": 1}

    # JSONL: append twice, read back both, bit-identical lines
    path = str(tmp_path / "runs.jsonl")
    emitters.append_jsonl(path, record)
    emitters.append_jsonl(path, record)
    lines = open(path).read().splitlines()
    assert len(lines) == 2 and lines[0] == lines[1]
    assert emitters.read_jsonl(path)[0] == record

    # Prometheus: every line is a comment or `name{labels} value`
    prom = str(tmp_path / "m.prom")
    emitters.write_prometheus(prom, record)
    sample = re.compile(
        r"^[a-z0-9_]+(\{[^}]*\})? -?[0-9.e+-]+$"
    )
    for line in open(prom).read().splitlines():
        assert line.startswith("# TYPE ") or sample.match(line), line
    assert "tpusim_counter_binds 4" in open(prom).read()

    # Chrome trace: a JSON object with X-phase events in microseconds
    tr = str(tmp_path / "t.json")
    emitters.write_chrome_trace(tr, tel.spans)
    data = json.loads(open(tr).read())
    assert data["traceEvents"], "no trace events"
    for ev in data["traceEvents"]:
        assert ev["ph"] == "X" and "ts" in ev and "dur" in ev


def test_table_cache_bit_transparent(tmp_path):
    """Content-keyed init_tables reuse: first run misses and persists,
    second (fresh Simulator, same inputs) hits — placements, counters,
    and metrics bit-identical; a config change changes the key."""
    nodes, pods = _driver_inputs()
    cache = str(tmp_path / "tables")
    _, r0 = _run_driver(nodes, pods)  # uncached reference
    sim1, r1 = _run_driver(nodes, pods, table_cache=cache)
    sim2, r2 = _run_driver(nodes, pods, table_cache=cache)
    assert sim1.obs.table_cache == "miss"
    assert sim2.obs.table_cache == "hit"
    assert any("[TableCache] reused" in l for l in sim2.log.lines)
    for r in (r1, r2):
        assert np.array_equal(
            np.asarray(r0.placed_node), np.asarray(r.placed_node)
        )
        assert np.array_equal(np.asarray(r0.counters), np.asarray(r.counters))
        for a, b in zip(jax.tree.leaves(r0.state), jax.tree.leaves(r.state)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert len(os.listdir(cache)) == 1
    # different seed -> different tie-break rank but SAME tables digest
    # (the build never reads rank/key): still a hit, still exact
    sim3, _ = _run_driver(nodes, pods, seed=43, table_cache=cache)
    assert sim3.obs.table_cache == "hit"


@pytest.mark.slow
def test_heartbeat_ticks_from_scan():
    """A heartbeat-built table engine fires host ticks every N processed
    events without touching the trajectory. slow-marked: heartbeat_every
    is part of the engine cache key, so this test pays a full extra
    engine compile; runs under `make resume-smoke` / plain pytest."""
    from tpusim.obs import heartbeat

    rng = np.random.default_rng(7)
    state, tp = random_cluster(rng, num_nodes=24)
    pods = random_pods(rng, num_pods=40)
    ev_kind = jnp.zeros(40, jnp.int32)
    ev_pod = jnp.arange(40, dtype=jnp.int32)
    policies = [(make_policy("FGDScore"), 1000)]
    rank = jnp.arange(24, dtype=jnp.int32)
    types = build_pod_types(pods)
    key = jax.random.PRNGKey(3)

    ref = make_table_replay(policies, gpu_sel="FGDScore", block_size=-1)(
        state, pods, types, ev_kind, ev_pod, tp, key, rank
    )
    lines = []
    old_min = heartbeat.MIN_INTERVAL_S
    heartbeat.MIN_INTERVAL_S = 0.0
    try:
        heartbeat.configure(40, "test", sink=lines.append)
        hb = make_table_replay(
            policies, gpu_sel="FGDScore", block_size=-1, heartbeat_every=10
        )(state, pods, types, ev_kind, ev_pod, tp, key, rank)
        jax.block_until_ready(hb.state)
    finally:
        heartbeat.MIN_INTERVAL_S = old_min
    assert heartbeat.tick_count() == 4  # 10, 20, 30, 40
    assert all("events" in l for l in lines)
    assert np.array_equal(
        np.asarray(ref.placed_node), np.asarray(hb.placed_node)
    )


def test_heartbeat_tail_relative_resume():
    """The honest-progress satellite (ISSUE 16): a scan resumed from a
    checkpoint (or a fork restored from a base carry) reports rate and
    ETA over the events THIS process actually executed — note_resume's
    done0 never counts toward ev/s, and the fault path's `base` offset
    shifts the run-level done counter without inflating the rate."""
    from tpusim.obs import heartbeat

    infos = []
    heartbeat.add_listener(infos.append)
    try:
        heartbeat.configure(100, "test", sink=lambda _line: None)
        heartbeat.note_resume(90)
        t0 = heartbeat._STATE["t0"]
        heartbeat._STATE["t0"] = t0 - 2.0  # a deterministic 2s clock
        heartbeat.tick(95)
        info = infos[-1]
        assert info["done"] == 95 and info["total"] == 100
        # 5 fresh events over ~2s — never 95/2
        assert 2.0 <= info["rate"] <= 3.0
        assert info["eta"] == pytest.approx(5 / info["rate"], rel=0.05)

        # the fault-segment offset: device counts restart at 0, the
        # run-level done is base + raw, the rate is still fresh-only
        heartbeat.configure(100, "test", sink=lambda _line: None,
                            base=40)
        heartbeat._STATE["t0"] -= 2.0
        heartbeat.tick(10)
        info = infos[-1]
        assert info["done"] == 50 and 4.0 <= info["rate"] <= 6.0

        # complete() disarms with the same fresh-only mean
        heartbeat.complete()
        assert infos[-1]["final"] is True
        heartbeat.complete()  # second call is a no-op
    finally:
        heartbeat.remove_listener(infos.append)
        heartbeat._STATE["total"] = 0


def test_gate_parse_and_compare(tmp_path):
    """latest_baseline parses the committed BENCH_r*.json shape; compare
    fails on quality drift, tolerates same-backend throughput noise, and
    treats cross-backend throughput as advisory."""
    from tpusim.obs import gate

    payload = {
        "n": 7, "cmd": "python bench.py", "rc": 0,
        "tail": "[bench] events=10811 placed=8350 wall=0.19s "
        "(first incl. compile 5.0s) gpu_alloc=95.52% \n",
        "parsed": {"metric": "m", "value": 43841.3,
                   "unit": "placements/sec", "platform": "tpu"},
    }
    with open(tmp_path / "BENCH_r07.json", "w") as f:
        json.dump(payload, f)
    # an older, and a torn, baseline must lose to / not shadow r07
    with open(tmp_path / "BENCH_r06.json", "w") as f:
        json.dump({**payload, "n": 6, "parsed": {"value": 1.0}}, f)
    (tmp_path / "BENCH_r08.json").write_text("{not json")
    base = gate.latest_baseline(str(tmp_path))
    assert base["n"] == 7 and base["events"] == 10811
    assert base["placed"] == 8350 and base["backend"] == "tpu"
    assert base["gpu_alloc"] == pytest.approx(95.52)

    cur = {"throughput": 100.0, "events": 10811, "placed": 8350,
           "gpu_alloc": 95.52, "backend": "cpu"}
    ok, msgs = gate.compare(base, cur, tol=0.5, alloc_tol=0.05)
    assert ok, msgs  # cross-backend throughput is advisory
    assert any("advisory" in m for m in msgs)

    bad = dict(cur, placed=8349)
    ok, _ = gate.compare(base, bad, tol=0.5, alloc_tol=0.05)
    assert not ok  # one lost placement fails the gate

    same_backend = dict(cur, backend="tpu", throughput=43841.3 * 0.4)
    ok, _ = gate.compare(base, same_backend, tol=0.5, alloc_tol=0.05)
    assert not ok  # same-backend 60% regression fails

    # a capture from before bench.py stamped its device has an UNKNOWN
    # backend — never an assumed "cpu" — so its throughput is advisory
    del payload["parsed"]["platform"]
    with open(tmp_path / "BENCH_r09.json", "w") as f:
        json.dump({**payload, "n": 9}, f)
    old = gate.latest_baseline(str(tmp_path))
    assert old["n"] == 9 and old["backend"] is None
    ok, msgs = gate.compare(old, same_backend, tol=0.5, alloc_tol=0.05)
    assert ok and any("advisory" in m for m in msgs)


def test_prometheus_type_declared_once_per_metric(tmp_path):
    """Strict promtext parsers reject duplicate `# TYPE` declarations:
    two samples of one metric name — labeled span series, or two record
    keys sanitizing to the same name — must share ONE declaration."""
    from tpusim.obs import Recorder, emitters

    rec = Recorder(enabled=True)
    # two spans of the same name -> labeled samples under one metric
    for _ in range(2):
        with rec.span("scan", engine="table") as h:
            h.dispatched()
    # two count keys that sanitize to the SAME metric name
    rec.count("cache hit")
    rec.count("cache_hit", 2)
    record = rec.snapshot(meta={}).to_record()
    lines = emitters.prometheus_lines(record)
    types = [l.split()[2] for l in lines if l.startswith("# TYPE ")]
    assert len(types) == len(set(types)), types
    # ... and one SAMPLE per (name, labelset): the colliding count keys
    # collapse to a single line instead of an invalid duplicate pair
    samples = [l for l in lines if not l.startswith("#")]
    keys = [l.rsplit(" ", 1)[0] for l in samples]
    assert len(keys) == len(set(keys)), keys
    assert sum(k == "tpusim_count_cache_hit" for k in keys) == 1
    # the span series still carries both labeled samples
    span_samples = [
        l for l in lines if l.startswith("tpusim_span_seconds_total{")
    ]
    assert len(span_samples) >= 2


def test_heartbeat_final_tick():
    """complete() always emits one 100% line (total wall + mean ev/s)
    even when the run finished inside the rate limit, then disarms —
    repeated calls and unarmed calls are no-ops."""
    from tpusim.obs import heartbeat

    lines = []
    heartbeat.configure(40, "scan", sink=lines.append)
    # run finished before any periodic tick fired
    heartbeat.complete()
    assert len(lines) == 1
    assert "40/40" in lines[0] and "ev/s mean" in lines[0]
    assert heartbeat.tick_count() == 1
    heartbeat.complete()  # disarmed: no second line
    assert len(lines) == 1
    # armed with a bucket-PADDED size, completed with the true count:
    # the final line reports the pre-padding total
    heartbeat.configure(512, "scan", sink=lines.append)
    heartbeat.complete(40)
    assert len(lines) == 2 and "40/40" in lines[1]


@pytest.mark.slow
def test_heartbeat_final_tick_from_driver(monkeypatch):
    """A heartbeat-configured driver replay always fires complete() with
    the heartbeat still armed — i.e. a run too short for any periodic
    tick (rate limit / large `every`) still reports its final line.
    slow-marked (tier-1 budget): heartbeat_every is part of the engine
    cache key, so this pays a fresh engine compile; the complete() host
    logic itself is tier-1-covered by test_heartbeat_final_tick."""
    from tpusim.obs import heartbeat

    calls = []
    real_complete = heartbeat.complete

    def spy(true_total=0):
        calls.append(heartbeat._STATE["total"])  # armed total at fire time
        calls.append(true_total)  # the driver's PRE-padding event count
        lines = []
        heartbeat._STATE["sink"] = lines.append
        real_complete(true_total)
        calls.append(lines[0] if lines else None)

    monkeypatch.setattr(heartbeat, "complete", spy)
    nodes, pods = _driver_inputs()
    sim = Simulator(nodes, SimulatorConfig(
        policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
        report_per_event=False, heartbeat_every=10_000,
    ))
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    specs = pods_to_specs(pods)
    out = sim.run_events(
        sim.init_state, specs, jnp.zeros(len(pods), jnp.int32),
        jnp.arange(len(pods), dtype=jnp.int32), jax.random.PRNGKey(2),
    )
    assert out.placed_node.shape[0] == len(pods)
    armed_total, true_total, line = calls
    assert armed_total > 0  # still armed: no periodic tick had disarmed it
    # the final line reports the PRE-padding count, not the padded
    # stream size the heartbeat was armed with
    assert true_total == len(pods) and armed_total >= true_total
    assert f"{true_total}/{true_total}" in line and "ev/s mean" in line


def test_chrome_counter_tracks(tmp_path):
    """write_chrome_trace emits `"ph": "C"` counter events for per-event
    series, laid across the scan spans' wall window, dense series
    strided down but always charting the final value."""
    import json as _json

    from tpusim.obs import Recorder, emitters

    rec = Recorder(enabled=True)
    with rec.span("typical_pods") as h:
        h.dispatched()
    with rec.span("scan", engine="table") as h:
        h.dispatched()
    tel = rec.snapshot(meta={})
    series = {
        "frag_gpu_milli": [float(i) for i in range(5000)],
        "used_gpu_milli": [1, 2, 3],
    }
    path = str(tmp_path / "trace.json")
    emitters.write_chrome_trace(path, tel.spans, series)
    data = _json.loads(open(path).read())
    counters = [e for e in data["traceEvents"] if e["ph"] == "C"]
    assert counters, "no counter events"
    frag = [e for e in counters if e["name"] == "frag_gpu_milli"]
    assert 0 < len(frag) <= emitters.MAX_COUNTER_POINTS + 1
    assert frag[-1]["args"]["frag_gpu_milli"] == 4999.0  # final value kept
    used = [e for e in counters if e["name"] == "used_gpu_milli"]
    assert [e["args"]["used_gpu_milli"] for e in used] == [1, 2, 3]
    # counter tracks sit inside the span window
    xs = [e for e in data["traceEvents"] if e["ph"] == "X"]
    t_lo = min(e["ts"] for e in xs)
    t_hi = max(e["ts"] + e["dur"] for e in xs)
    assert all(t_lo <= e["ts"] <= t_hi + 1 for e in counters)
    # emit_all threads the series through
    paths = emitters.emit_all(
        tel, trace=str(tmp_path / "t2.json"), counter_series=series
    )
    data2 = _json.loads(open(paths[0]).read())
    assert any(e["ph"] == "C" for e in data2["traceEvents"])


def test_bench_measure_protocol():
    """obs.bench.measure: one cold + N warm calls, min over warm."""
    from tpusim.obs import bench

    calls = []
    m = bench.measure(lambda: calls.append(1), warm_runs=3)
    assert len(calls) == 4
    assert m["min_s"] == min(m["samples_s"]) and len(m["samples_s"]) == 3
    cw = bench.measure_cold_warm(lambda: calls.append(1))
    assert "cold_s" in cw and "warm_s" in cw
    assert bench.round_row({"a": 1.23456, "b": [1.23456], "c": "x"}) == {
        "a": 1.235, "b": [1.235], "c": "x"
    }
