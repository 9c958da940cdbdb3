"""The sweep's own tracing: one SweepRecord per schedule_pods_sweep call
made of eight flat spans, the exact compile counter at the same boundary,
the profiler annotations of the spans, and the named scopes of the step
body (tpusim/obs/spans.py, tpusim/sim/driver.py, tpusim/sim/table_engine.py).
"""

import re

import jax
import numpy as np
import pytest

from tests.sweep_program import capture_sweep
from tests.test_sweep import _cfg, _mk_cluster, _mk_pods
from tpusim.obs import Recorder, compile_counts, note_compile_cache, sweep_log
from tpusim.obs.spans import CACHE_HIT_EVENT, COMPILE_EVENT
from tpusim.sim import driver
from tpusim.sim.driver import Simulator, schedule_pods_sweep

SPAN_NAMES = ["specs", "lane_keys", "lane_ranks", "init_tables", "scan",
              "frag_postpass", "fetch", "slice_lanes"]
BODY_SCOPES = {
    # block_size: the scopes its step body carries
    -1: {"tpusim.commit", "tpusim.refresh", "tpusim.select",
         "tpusim.affinity"},
    8: {"tpusim.commit", "tpusim.refresh", "tpusim.summary", "tpusim.select"},
}
WEIGHTS = [[1000], [1000], [700]]
SEEDS = [11, 12, 13]


def _sim(block_size, profile=False, engine="table"):
    rng = np.random.default_rng(5)
    sim = Simulator(_mk_cluster(rng), _cfg(
        42, engine=engine, block_size=block_size, profile=profile))
    sim.set_workload_pods(_mk_pods(rng))
    sim.set_typical_pods()
    return sim, sim.prepare_pods()


class _Listener:
    """An independent count of what jax.monitoring reports."""

    def __init__(self):
        self.requests = self.cache_loads = 0

    def on_duration(self, event, _secs, **_kw):
        self.requests += event == COMPILE_EVENT

    def on_event(self, event, **_kw):
        self.cache_loads += event == CACHE_HIT_EVENT

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)


@pytest.fixture(scope="module")
def two_sweeps():
    """Two sweeps of one tiny Simulator (flat step, profile off), each
    beside an independent listener's counts over the same call."""
    sim, trace = _sim(block_size=-1)
    calls = []
    for _ in range(2):
        with _Listener() as seen:
            lanes = schedule_pods_sweep(sim, trace, WEIGHTS, SEEDS)
        calls.append((sweep_log()[-1], seen, lanes))
    return sim, trace, calls


def test_a_sweep_records_eight_flat_spans_under_one_id(two_sweeps):
    sim, trace, calls = two_sweeps
    rec = calls[0][0]
    assert [s.name for s in rec.spans] == SPAN_NAMES
    assert {s.sweep for s in rec.spans} == {rec.id}
    assert (rec.engine, rec.lanes, rec.events, rec.blocked) == (
        "table (3-config vmap sweep)", 3, len(trace), False)
    # flat: back to back on the recorder's clock, none inside another,
    # and the recorder's own list holds the same objects and no root span
    at = rec.start_s - sim.obs.epoch
    for s in rec.spans:
        assert s.start_s >= at - 1e-9, (s.name, s.start_s, at)
        at = s.start_s + s.total_s
    assert at <= rec.start_s - sim.obs.epoch + rec.wall_s + 1e-9
    assert sum(s.total_s for s in rec.spans) >= 0.95 * rec.wall_s
    assert [s for s in sim.obs.spans if s.sweep == rec.id] == rec.spans


def test_a_second_sweep_appends_the_next_record(two_sweeps):
    sim, _, calls = two_sweeps
    first, second = calls[0][0], calls[1][0]
    assert second.id == first.id + 1
    assert second.start_s >= first.start_s + first.wall_s
    assert [s.name for s in second.spans] == SPAN_NAMES
    assert sim.obs.sweeps == [first, second]
    timing = sim.run_telemetry().to_record()["timing"]
    assert [r["id"] for r in timing["sweeps"]] == [first.id, second.id]
    assert timing["sweeps"][0]["spans"][0]["sweep"] == first.id
    assert timing["spans"][-1]["sweep"] == second.id


def test_compile_counts_equal_an_independent_listeners(two_sweeps):
    _, _, calls = two_sweeps
    for rec, seen, _ in calls:
        assert rec.programs_requested == seen.requests
        assert rec.cache_loads == seen.cache_loads
        assert rec.compiled == seen.requests - seen.cache_loads
    # the first call builds every program; the second still requests the
    # frag post-pass, whose jit wraps a new function object each call
    assert calls[0][0].programs_requested > calls[1][0].programs_requested
    assert calls[1][0].programs_requested >= 1


def test_spans_outside_a_sweep_carry_no_sweep_id():
    rec = Recorder()
    with rec.span("report"):
        pass
    with rec.sweep(lanes=1) as sw:
        with rec.span("scan"):
            pass
    with rec.span("report"):
        pass
    assert [s.sweep for s in rec.spans] == [None, sw.id, None]
    assert "sweep" not in rec.spans[0].to_dict()
    assert rec.spans[1].to_dict()["sweep"] == sw.id
    # a call that raises leaves no record
    with pytest.raises(RuntimeError):
        with rec.sweep(lanes=1):
            raise RuntimeError("boom")
    assert rec.sweeps == [sw] and sweep_log()[-1] is sw
    with rec.span("report"):
        pass
    assert rec.spans[-1].sweep is None


@pytest.fixture(scope="module", params=[False, True],
                ids=["unprofiled", "profiled"])
def one_sweep(request):
    """One sweep of a tiny Simulator in either mode: its record, the calls
    of jax.block_until_ready it made, and the (shape, dtype) of every
    device leaf it handed to device_fetch."""
    profile = request.param
    sim, trace = _sim(block_size=-1, profile=profile)
    blocked, fetched = [], []
    real_block, real_fetch = jax.block_until_ready, driver.device_fetch

    def fetch(tree, **kw):
        fetched.extend((l.shape, l.dtype) for l in jax.tree.leaves(tree)
                       if isinstance(l, jax.Array))
        return real_fetch(tree, **kw)

    jax.block_until_ready = lambda x: blocked.append(1) or real_block(x)
    driver.device_fetch = fetch
    try:
        schedule_pods_sweep(sim, trace, WEIGHTS, SEEDS)
    finally:
        jax.block_until_ready, driver.device_fetch = real_block, real_fetch
    return profile, sim, sweep_log()[-1], blocked, fetched


def _span(rec, name):
    return next(s for s in rec.spans if s.name == name)


def test_only_profile_blocks_on_the_phases(one_sweep):
    """Unprofiled no PHASE blocks: settle calls jax.block_until_ready not
    once. The fetch waits for its packed buffer either way (the array's own
    method, where np.asarray would have waited), and stamps `ready`."""
    profile, _, rec, blocked, _ = one_sweep
    assert rec.blocked is profile
    # specs, lane_keys, lane_ranks, init_tables, scan, frag_postpass
    assert len(blocked) == (6 if profile else 0)
    assert "ready" in _span(rec, "fetch").marks


def test_the_fetchs_marks_are_ordered_and_partition_the_span(one_sweep):
    _, _, rec, _, _ = one_sweep
    fetch = _span(rec, "fetch")
    assert list(fetch.marks) == ["ready", "copied"]
    ready, copied = fetch.marks.values()
    assert 0.0 < ready <= copied <= fetch.total_s
    # wait, copy and unpack: three pieces, nothing between or beyond them
    pieces = [ready, copied - ready, fetch.total_s - copied]
    assert all(p >= 0.0 for p in pieces)
    assert sum(pieces) == pytest.approx(fetch.total_s, abs=1e-12)
    # the two other marked spans: one mark each, inside the dispatch half
    for name, mark in (("lane_ranks", "stacked"),
                       ("frag_postpass", "gathered")):
        span = _span(rec, name)
        assert list(span.marks) == [mark]
        assert 0.0 < span.marks[mark] <= span.dispatch_s
    assert [s.name for s in rec.spans if s.marks] == [
        "lane_ranks", "frag_postpass", "fetch"]


def test_the_derived_fields_account_for_the_call(one_sweep):
    """Lead, covered, device block, device wait and tail: from the call's
    start to the end of its last span, blocked or not; in to_dict() (so
    in the run record's timing.sweeps) beside the marks and the bytes."""
    from tpusim.obs.spans import DERIVED_FIELDS
    from tpusim.sim.fetch import PIECE_BYTES

    profile, sim, rec, _, fetched = one_sweep
    values = [getattr(rec, name) for name in DERIVED_FIELDS]
    assert all(v is not None and v >= 0.0 for v in values), values
    last = rec.spans[-1]
    end = last.start_s + last.total_s - (rec.start_s - sim.obs.epoch)
    assert sum(values) == pytest.approx(end, abs=1e-9)
    assert 0.95 * rec.wall_s <= sum(values) <= rec.wall_s
    scan, post, fetch = (_span(rec, n) for n in (
        "scan", "frag_postpass", "fetch"))
    assert rec.device_block_s == scan.block_s + post.block_s
    assert rec.device_wait_s == fetch.marks["ready"]
    assert rec.host_lead_s == pytest.approx(
        scan.start_s + scan.dispatch_s - (rec.start_s - sim.obs.epoch))
    # an unblocked wave waits for the device in the fetch alone
    if not profile:
        assert rec.device_block_s < 0.05 * rec.wall_s
    # the packed buffer: every device leaf's bytes, a bool one byte
    want = sum(int(np.prod(shape)) * np.dtype(dtype).itemsize
               for shape, dtype in fetched)
    assert rec.fetch_bytes == want > 0
    # of them, what the lanes share: the five capacity leaves came to the
    # fetch ONCE, [N] (ISSUE 43), 20 bytes a node; with the lane axis on
    # them, as every other leaf has it, the buffer were (B - 1) x 20 x N
    # bytes larger (tests/test_sweep_shared.py builds that program)
    n, b = len(sim.nodes), rec.lanes
    assert n != b and all(shape[0] == b for shape, _ in fetched
                          if shape != (n,))
    shared = [dtype for shape, dtype in fetched if shape == (n,)]
    assert shared == [np.dtype(np.int32)] * 5
    a_lane = {shape[1:] for shape, _ in fetched if shape != (n,)}
    assert {(n,), (n, 8), (n, 9)} <= a_lane  # cpu_left, gpu_left, aff_cnt
    # a buffer of at most one piece: one transfer, no landing block
    assert want <= PIECE_BYTES
    assert fetch.meta == {"events": 3 * rec.events, "bytes": want,
                          "fetch_pieces": 1, "landing_reused": 0,
                          "shared_bytes": 20 * n}
    d = rec.to_dict()
    assert d["fetch_bytes"] == want
    assert (d["fetch_pieces"], d["landing_reused"]) == (
        rec.fetch_pieces, rec.landing_reused) == (1, 0)
    for name, value in zip(DERIVED_FIELDS, values):
        assert d[name] == round(value, 6)
    by_name = {s["name"]: s for s in d["spans"]}
    assert list(by_name["fetch"]["marks"]) == ["ready", "copied"]
    assert [n for n, s in by_name.items() if "marks" in s] == [
        "lane_ranks", "frag_postpass", "fetch"]
    timing = sim.run_telemetry().to_record()["timing"]
    assert timing["sweeps"][-1] == d


def test_device_fetch_with_and_without_marks_returns_the_same_bits():
    from tpusim.sim.fetch import device_fetch

    rng = np.random.default_rng(3)
    f32 = rng.standard_normal((5, 7)).astype(np.float32)
    f32[0, :3] = [np.nan, -0.0, np.inf]
    tree = {
        "i": jax.numpy.asarray(rng.integers(-2**31, 2**31, (4, 3), np.int64)
                               .astype(np.int32)),
        "f": jax.numpy.asarray(f32),
        "b": jax.numpy.asarray(rng.random((6, 5, 8)) < 0.5),
        "none": None, "host": np.arange(3), "scalar": 7,
        "nested": (jax.numpy.zeros((0,), np.int32), [jax.numpy.int32(-5)]),
    }
    rec = Recorder()
    with rec.span("fetch") as h:
        marked = device_fetch(tree, marks=h)
    plain = device_fetch(tree)
    a, ta = jax.tree_util.tree_flatten(marked)
    b, tb = jax.tree_util.tree_flatten(plain)
    want, tw = jax.tree_util.tree_flatten(tree)
    assert ta == tb == tw
    for x, y, w in zip(a, b, want):
        if isinstance(w, jax.Array):
            assert type(x) is type(y) is np.ndarray
            assert x.dtype == y.dtype == w.dtype and x.shape == w.shape
            assert x.tobytes() == y.tobytes() == np.asarray(w).tobytes()
        else:
            assert x is y is w
    span = rec.spans[0]
    assert list(span.marks) == ["ready", "copied"]
    assert span.meta == {"bytes": 4 * 12 + 4 * 35 + 240 + 0 + 4,
                         "fetch_pieces": 1, "landing_reused": 0}
    # nothing to move: no mark, no bytes, the tree itself
    with rec.span("fetch") as h:
        assert device_fetch({"none": None, "n": 3}, marks=h) == {
            "none": None, "n": 3}
    assert rec.spans[1].marks == {} and rec.spans[1].meta == {}


def test_a_span_without_marks_serializes_as_before():
    """`marks` is emitted only where there are any, in the record and in
    the Chrome trace, whose mark slices nest inside the span's two."""
    from tpusim.obs import emitters

    rec = Recorder()
    with rec.span("scan", engine="table") as h:
        h.dispatched()
    with rec.span("report"):
        pass
    with rec.span("fetch") as h:
        h.mark("ready", then="copy")
        h.mark("copied")
        h.dispatched()
    plain, bare, marked = rec.spans
    assert set(plain.to_dict()) == {
        "name", "start_s", "dispatch_s", "block_s", "total_s", "meta"}
    assert set(bare.to_dict()) == {
        "name", "start_s", "dispatch_s", "block_s", "total_s"}
    assert plain.marks == {} and bare.marks == {}
    d = marked.to_dict()
    assert list(d["marks"]) == ["ready", "copied"]
    assert 0.0 <= d["marks"]["ready"] <= d["marks"]["copied"] <= d["total_s"]
    marked.dispatch_s, marked.block_s = 0.25, 0.75  # a split to straddle
    marked.marks = {"ready": 0.1, "copied": 0.4}
    events = emitters.chrome_trace_events(rec.spans)
    assert {e["name"] for e in events if ".." not in e["name"]} <= {
        "scan:dispatch", "scan:block", "report:dispatch", "fetch:dispatch",
        "fetch:block"}
    assert all(e["name"].startswith("fetch:") for e in events
               if ".." in e["name"])
    t0 = marked.start_s * 1e6
    slices = [(e["name"], round(e["ts"] - t0), round(e["dur"]))
              for e in events if ".." in e["name"]]
    assert slices == [
        ("fetch:..ready", 0, 100000),
        ("fetch:ready..copied", 100000, 150000),  # to the dispatch's end
        ("fetch:ready..copied", 250000, 150000),
        ("fetch:copied..", 400000, 600000),
    ]
    halves = {e["name"]: (e["ts"], e["ts"] + e["dur"]) for e in events
              if e["name"] in ("fetch:dispatch", "fetch:block")}
    for e in events:
        if ".." in e["name"]:
            assert any(lo - 1e-6 <= e["ts"] and e["ts"] + e["dur"] <= hi + 1e-6
                       for lo, hi in halves.values()), e


def test_note_compile_cache_exact_fields():
    """The run record's compile-cache note is counted, not guessed:
    programs requested since the recorder's epoch, those the persistent
    cache served, and the rest."""
    rec = Recorder()
    with rec.span("scan") as h:
        h.dispatched()
    rec.spans[0].dispatch_s = 6.5
    before = compile_counts()
    with _Listener() as seen:
        jax.jit(lambda x: x * 3 + 1)(np.arange(7)).block_until_ready()
    assert seen.requests >= 1
    assert compile_counts()[0] - before[0] == seen.requests
    info = note_compile_cache(rec, enabled=True, cache_dir="/tmp/cc")
    assert info == {
        "enabled": True, "dir": "/tmp/cc", "first_scan_dispatch_s": 6.5,
        "requests": seen.requests, "cache_loads": seen.cache_loads,
        "compiled": seen.requests - seen.cache_loads,
    }
    record = rec.snapshot().to_record()
    assert record["timing"]["compile_cache"] == info
    # nothing requested since a fresh epoch, cache off, no scan yet
    assert note_compile_cache(Recorder(), enabled=False) == {
        "enabled": False, "dir": "", "first_scan_dispatch_s": None,
        "requests": 0, "cache_loads": 0, "compiled": 0,
    }
    # never assessed -> no block in the record
    assert "compile_cache" not in Recorder().snapshot().to_record()["timing"]


def test_spans_are_annotations_on_the_profilers_host_plane(
        two_sweeps, tmp_path):
    sim, trace, _ = two_sweeps
    jax.profiler.start_trace(str(tmp_path))
    try:
        schedule_pods_sweep(sim, trace, WEIGHTS, SEEDS)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    host = next(p for p in data.planes if p.name == "/host:CPU")
    names = {ev.name for line in host.lines for ev in line.events}
    assert {f"tpusim/{n}" for n in SPAN_NAMES} <= names
    # the sub-phases the marks open, nested in their span's annotation
    assert {"tpusim/lane_ranks/transfer", "tpusim/frag_postpass/program",
            "tpusim/fetch/copy", "tpusim/fetch/unpack"} <= names


@pytest.fixture(scope="module", params=sorted(BODY_SCOPES))
def scoped(request):
    """One sweep on the flat (-1) or blocked (8) step body, with the
    shapes its vmapped engine was called on."""
    block_size = request.param
    sim, trace = _sim(block_size=block_size)
    fn, shapes, lanes = capture_sweep(sim, trace, WEIGHTS, SEEDS, run=True)
    return block_size, sim, lanes, {"fn": fn, "shapes": shapes}


def test_every_stage_of_the_step_body_has_its_scope(scoped):
    block_size, sim, lanes, called = scoped
    text = called["fn"].lower(*called["shapes"]).as_text(debug_info=True)
    for scope in BODY_SCOPES[block_size]:
        assert scope in text, scope
    assert ("tpusim.summary" in text) == (block_size > 0)
    # the chunk's affinity counts, after the scan: the flat body of a
    # program whose kernels do not read aff_cnt (FGD); the blocked body
    # keeps the add in its commit
    assert ("tpusim.affinity" in text) == (block_size < 0)
    # and where the add stays in the loop it has a scope of its own inside
    # the commit's (ISSUE 45); FGD's flat program has no such scope at all:
    # its epilogue's whole commit is outside both
    assert ("tpusim.commit/tpusim.commit.affinity" in text) == (block_size > 0)
    assert ("tpusim.commit.affinity" in text) == (block_size > 0)
    assert sim.obs.sweeps[-1].affinity_deferred == (block_size < 0)
    assert sim.obs.sweeps[-1].affinity_readers == 0
    assert sim.obs.sweeps[-1].to_dict()["affinity_deferred"] == int(
        block_size < 0)
    # the dense forms of sim/lane_write.py sit inside the stage that calls
    # them: the commit's masked adds, the refresh's column writes and row
    # reads, the select's row and entry reads
    for scope, op in (("commit", "select_n"), ("refresh", "select_n"),
                      ("refresh", "reduce_sum"), ("select", "reduce_sum")):
        assert re.search(rf'tpusim\.{scope}/vmap[^"/]*/{op}', text), (
            scope, op)
    state, _, types, _, _, tp, keys = called["shapes"][:7]
    build = sim._table_fn.build_tables.lower(
        state, types, tp, jax.ShapeDtypeStruct(keys.shape[1:], keys.dtype))
    assert "tpusim.table_build" in build.as_text(debug_info=True)
    states = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), lanes[0].state)
    post = jax.jit(jax.vmap(driver._lane_postpass, in_axes=(0, None)))
    stacked = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((3,) + a.shape, a.dtype), states)
    assert "tpusim.frag_postpass" in post.lower(stacked, tp).as_text(
        debug_info=True)


def test_a_program_whose_kernel_reads_aff_cnt_keeps_the_add_in_its_loop():
    """GpuClustering scores by the affinity counts: its flat sweep's record
    reads affinity_deferred 0, its program has no tpusim.affinity scope and
    all seventeen write sites (tests/test_affinity_deferred.py holds its
    lanes to the oracle)."""
    rng = np.random.default_rng(5)
    sim = Simulator(_mk_cluster(rng), _cfg(
        42, (("GpuClusteringScore", 1000),), "best", engine="table",
        block_size=-1))
    sim.set_workload_pods(_mk_pods(rng))
    sim.set_typical_pods()
    fn, shapes, _ = capture_sweep(
        sim, sim.prepare_pods(), WEIGHTS, SEEDS, run=True)
    rec = sim.obs.sweeps[-1]
    assert rec.affinity_deferred == rec.to_dict()["affinity_deferred"] == 0
    assert rec.lane_writes == WRITE_SITES[8]  # the whole commit, twice
    text = fn.lower(*shapes).as_text(debug_info=True)
    assert "tpusim.commit" in text and "tpusim.affinity" not in text
    # the add it cannot defer, under its own scope inside the commit's
    assert rec.affinity_readers == rec.to_dict()["affinity_readers"] == 1
    assert re.search(
        r'tpusim\.commit/tpusim\.commit\.affinity/vmap[^"/]*/add', text)


def test_a_scoped_sweep_equals_the_sequential_oracle(scoped):
    """Names are metadata: placements, masks, counters and final state of
    every lane equal a standalone replay on the sequential engine."""
    from tpusim.obs.counters import COUNTER_FIELDS, INVARIANT_FIELDS

    _, sim, lanes, _ = scoped
    for lane, wrow, seed in zip(lanes, WEIGHTS, SEEDS):
        rng = np.random.default_rng(5)
        oracle = Simulator(_mk_cluster(rng), _cfg(
            seed, (("FGDScore", wrow[0]),), engine="sequential"))
        oracle.set_workload_pods(_mk_pods(rng))
        res = oracle.run()
        assert "sequential" in oracle._last_engine
        np.testing.assert_array_equal(lane.placed_node, res.placed_node)
        np.testing.assert_array_equal(lane.dev_mask, res.dev_mask)
        for a, b in zip(jax.tree.leaves(lane.state),
                        jax.tree.leaves(res.state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        got = dict(zip(COUNTER_FIELDS, (int(c) for c in lane.counters)))
        want = res.telemetry.counters
        assert all(got[f] == want[f] for f in INVARIANT_FIELDS), (got, want)


# the step body: three column writes (score, device, feasibility: the
# blocked body's write_column, the flat body's write_columns in its flush)
# and the commit's add_row x4 (cpu_left, mem_left, gpu_left, aff_cnt) and set_row x3
# (placed, masks, failed); the commit once more in the replay's epilogue.
# Since ISSUE 42 the FLAT body's commit (block size -1) has one add_row
# fewer, 17 -> 16: where no kernel reads aff_cnt (FGD) its add left the
# event loop for table_engine.chunk_affinity, which is no write site; the
# epilogue's commit and the blocked body's are whole
WRITE_SITES = {-1: 3 + 6 + 7, 8: 3 + 7 + 7}


def test_lane_writes_counts_the_sites_the_batching_rule_lowered(
        scoped, two_sweeps):
    block_size, sim, _, _ = scoped
    assert sim.obs.sweeps[-1].lane_writes == WRITE_SITES[block_size]
    assert sim.run_telemetry().to_record()["timing"]["sweeps"][-1][
        "lane_writes"] == WRITE_SITES[block_size]
    # a warm sweep traces nothing and reports what its program's trace saw
    _, _, calls = two_sweeps
    assert [rec.lane_writes for rec, _, _ in calls] == [WRITE_SITES[-1]] * 2


def test_the_standalone_replay_lowers_as_it_always_did(scoped):
    """Not vmapped, nothing goes through the rule: no site is counted, the
    column writes are dynamic_update_slices, the commit's `.at[]` updates
    are scatters and there is no custom call (no kernel). The vmapped
    program of these short clusters takes the rule's dense forms: the
    scatters of the node state's write sites are gone (the bookkeeping
    rows' stay: the lanes share their index; the blocked body's summary
    rows, not this module's, become three), and so are the column writes'
    dynamic_update_slices."""
    from tpusim.sim import lane_write

    block_size, sim, _, called = scoped
    shapes = list(called["shapes"])
    for i in (6, 7, 8):  # key, weights, tie-break rank: one lane's
        shapes[i] = jax.ShapeDtypeStruct(shapes[i].shape[1:], shapes[i].dtype)
    with lane_write.counting() as sites:
        text = sim._table_fn.engine.replay.lower(*shapes).as_text()
    assert not sites and not sites.dense
    assert "custom_call" not in text
    assert text.count("stablehlo.dynamic_update_slice") >= 3
    # the commit, twice; the flat body's without its aff_cnt add (ISSUE 42)
    assert text.count('"stablehlo.scatter"') >= 14 - (block_size < 0)
    swept = called["fn"].lower(*called["shapes"]).as_text()
    assert swept.count('"stablehlo.scatter"') <= text.count(
        '"stablehlo.scatter"') - 5
    assert swept.count("stablehlo.dynamic_update_slice") <= text.count(
        "stablehlo.dynamic_update_slice") - 3
    assert "custom_call" not in swept


# every write site but the set_rows (the lanes of one event stream share
# the bookkeeping row's index: vmap's one update serves them all), and the
# reads: the dirty row of the nine NodeState leaves, the selected gpu_left
# row and the device table's entry
DENSE_SITES = {-1: WRITE_SITES[-1] - 2 * 3 + 9 + 2}  # 21; 22 before ISSUE 42


def test_dense_accesses_counts_the_sites_lowered_in_the_dense_form(scoped):
    """A short cluster's sweep takes the dense form at every site; a
    program that is not vmapped (the test above), and the blocked body
    from 8,192 nodes (tests/test_sweep_compile.py), at none."""
    block_size, sim, _, _ = scoped
    rec = sim.obs.sweeps[-1]
    assert rec.lane_writes == WRITE_SITES[block_size]
    assert rec.dense_accesses > 0
    if block_size in DENSE_SITES:
        assert rec.dense_accesses == DENSE_SITES[block_size]
    assert sim.run_telemetry().to_record()["timing"]["sweeps"][-1][
        "dense_accesses"] == rec.dense_accesses


def test_table_pass_events_is_the_flat_bodys_group(scoped):
    """Events whose columns one dense pass over a table writes. A sweep
    this narrow (3 lanes) writes a column every event on either body: 1.
    From FLAT_GROUP_MIN_LANES lanes the flat body runs in groups and the
    record reads the group (the same Simulator, the wider program); the
    blocked body forced onto the short cluster stays at 1; 0 where no
    table is written densely (100,000 nodes: tests/test_sweep_compile.py)
    and in a record no sweep filled."""
    from tpusim.obs.spans import SweepRecord
    from tpusim.sim.table_engine import (
        FLAT_GROUP_EVENTS, FLAT_GROUP_MIN_LANES)

    block_size, sim, lanes, _ = scoped
    rec = sim.obs.sweeps[-1]
    assert rec.table_pass_events == 1
    assert sim.run_telemetry().to_record()["timing"]["sweeps"][-1][
        "table_pass_events"] == 1
    assert SweepRecord(id=0, start_s=0.0, blocked=False).to_dict()[
        "table_pass_events"] == 0
    wide = FLAT_GROUP_MIN_LANES
    got = schedule_pods_sweep(
        sim, sim.prepare_pods(), [WEIGHTS[i % 3] for i in range(wide)],
        [SEEDS[i % 3] for i in range(wide)])
    rec = sim.obs.sweeps[-1]
    assert rec.lanes == wide and rec.lane_writes == WRITE_SITES[block_size]
    assert rec.table_pass_events == (
        FLAT_GROUP_EVENTS if block_size < 0 else 1)
    assert rec.to_dict()["table_pass_events"] == rec.table_pass_events
    for i in range(3):  # the wide lanes are the narrow sweep's, repeated
        for g in (got[i], got[i + 3]):
            np.testing.assert_array_equal(
                g.placed_node, lanes[i].placed_node)
            np.testing.assert_array_equal(g.dev_mask, lanes[i].dev_mask)
            for a, b in zip(jax.tree.leaves(g.state),
                            jax.tree.leaves(lanes[i].state)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the narrow program's record is still its own
    schedule_pods_sweep(sim, sim.prepare_pods(), WEIGHTS, SEEDS)
    assert sim.obs.sweeps[-1].table_pass_events == 1


@pytest.mark.parametrize("seeds", [
    [0, 1, 42, 2**31 - 1],  # int32: one transfer, one vmapped program
    [-1, -2**31, 7],
    [5, 2**31, 3000000019],  # beyond int32: PRNGKey's own wrap, a lane each
])
def test_lane_keys_equal_one_prngkey_a_lane(seeds):
    got = np.asarray(driver._lane_keys(seeds))
    want = np.stack([np.asarray(jax.random.PRNGKey(s)) for s in seeds])
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


def test_lane_ranks_equal_one_permutation_a_lane():
    from tpusim.io.trace import tiebreak_rank

    seeds = [0, 11, 2**31 - 2]
    got = driver._lane_ranks(97, seeds)
    assert isinstance(got, jax.Array) and got.dtype == np.int32
    np.testing.assert_array_equal(
        np.asarray(got), np.stack([tiebreak_rank(97, s) for s in seeds]))
