"""The sweep's lane slicing (ISSUE 39): driver._slice_sweep_lanes sums the
fetched arrays once an array over the lane axis and then builds views. Held
here, on every form a sweep takes:

  1. every summary field of every lane `==` (no tolerance) what
     driver.lane_from_arrays, the per-lane form with the slot mask, gives
     from that lane's own arrays, and every array of today's length;
  2. the invariant the mask-free allocation ratio rests on: `gpu_left[n, d]
     == 0` for `d >= gpu_cnt[n]`, in the loaders' initial state and in
     every lane's final state, on every body of the step;
  3. the structure: no `[B, N, 8]` temporary and no copy a lane (plain
     numpy, a synthetic fetched result);
  4. what the lanes share (ISSUE 43): the five capacity leaves come
     fetched without a lane axis, and every lane's state holds the SAME
     read-only view of each, equal to the start state's.
"""

import dataclasses
import functools
import os
import tracemalloc

import numpy as np
import pytest

from tests.test_sweep import REPO, _cfg, _mk_cluster, _mk_pods
from tests.test_sweep_paths import _faults
from tpusim.constants import MILLI
from tpusim.io.trace import PodRow, load_node_csv
from tpusim.obs import NUM_COUNTERS, sweep_log
from tpusim.ops.frag import frag_sum_except_q3
from tpusim.sim import driver
from tpusim.sim.driver import Simulator, lane_from_arrays, schedule_pods_sweep
from tpusim.sim.engine import EventMetrics, ReplayResult
from tpusim.sim.table_engine import FLAT_GROUP_EVENTS, FLAT_GROUP_MIN_LANES
from tpusim.types import CAPACITY_LEAVES, NodeState

WEIGHTS = [[1000], [700], [1000], [850]]
SEEDS = [11, 12, 13, 2**31 + 5]


def _pods():
    return _mk_pods(np.random.default_rng(6), 60)


def _sim(pods=None, nodes=10, **cfg):
    """A cluster too small for the 60 pods: some creates are rejected."""
    cfg.setdefault("engine", "table")
    sim = Simulator(_mk_cluster(np.random.default_rng(5), nodes),
                    _cfg(42, **cfg))
    sim.set_workload_pods(_pods() if pods is None else pods)
    sim.set_typical_pods()
    return sim


def _with_lifetimes(pods):
    """The pods by a clock of their own: two of three are deleted, some in
    the second they were created."""
    rng = np.random.default_rng(9)
    return [dataclasses.replace(
        p, creation_time=10 * i,
        deletion_time=0 if i % 3 == 0 else 10 * i + int(rng.choice([0, 25, 90])))
        for i, p in enumerate(pods)]


def _shared(**cfg):
    sim = _sim(**cfg)
    return sim, dict(pods=sim.prepare_pods())


def _a_trace_a_lane():
    # 60, 41, 17 and 60 pods: the pod and event axes are padded to the
    # longest lane's buckets, so three lanes end short of them
    sim = _sim()
    trace = sim.prepare_pods()
    return sim, dict(pods=None, lane_pods=[
        trace, trace[:41], trace[3:20], trace])


def _deletions():
    pods = _with_lifetimes(_pods())
    return _sim(pods=pods, use_timestamps=True), dict(pods=pods)


def _families():
    """Two workload families of one cluster: lanes 0, 2 scored against the
    first list's typical pods, lanes 1, 3 against the second's (all whole
    GPUs, fewer of them), each replaying its own family's trace."""
    first = _sim()
    whole = [PodRow(f"w{i:03d}", 2000, 4096, 1 + i % 2, 1000)
             for i in range(30)]
    second = _sim(pods=whole)
    kinds = [int((np.asarray(s.typical.freq) > 0).sum()) for s in (first, second)]
    assert kinds[0] > kinds[1] > 0
    sims = [first, second, first, second]
    return first, dict(
        pods=None, lane_pods=[s.prepare_pods() for s in sims],
        lane_typical=[s.typical for s in sims])


def _fault_plans():
    # node loss and return (mtbf / mttr), evictions and retries
    sim = _sim()
    return sim, dict(pods=sim.prepare_pods(),
                     fault_specs=_faults(len(WEIGHTS)))


FORMS = {
    "a shared trace, rows per event": functools.partial(
        _shared, report_per_event=True),
    "a trace a lane of differing lengths": _a_trace_a_lane,
    "a stream with deletions": _deletions,
    "typical pods a family": _families,
    "fault plans": _fault_plans,
    "the sequential engine": functools.partial(_shared, engine="sequential"),
}
# the bodies of the table engine's step that no form above reaches:
# (lanes, what the Simulator is built with)
BODIES = {
    "the plain flat body, 8 lanes": (8, {}),
    "the grouped flat body": (FLAT_GROUP_MIN_LANES, {}),
    "a blocked body": (4, {"block_size": 8, "nodes": 16}),
}


class Swept:
    """One sweep of a form, with what its slicing was handed (the fetched
    result and the per-lane sizes) kept beside the lanes it gave."""

    def __init__(self, sim, kw, weights=WEIGHTS, seeds=SEEDS):
        self.sim, self.kw = sim, kw
        real, seen = driver._slice_sweep_lanes, []

        def spy(*args):
            seen.append(args)
            return real(*args)

        driver._slice_sweep_lanes = spy
        try:
            self.lanes = schedule_pods_sweep(
                sim, kw["pods"], weights, seeds,
                **{k: v for k, v in kw.items() if k != "pods"})
        finally:
            driver._slice_sweep_lanes = real
        self.record = sweep_log()[-1]
        (self.args,) = seen  # fault sweeps go through the same function

    def typical(self, i):
        given = self.kw.get("lane_typical")
        return self.sim.typical if given is None else given[i]


@functools.lru_cache(maxsize=None)
def swept(what):
    if what in FORMS:
        return Swept(*FORMS[what]())
    lanes, cfg = BODIES[what]
    return Swept(*_shared(**cfg), [[1000 - i] for i in range(lanes)],
                 list(range(lanes)))


def _lane_state(state, i, copy=False):
    """Lane i's NodeState out of a fetched sweep's: its own row of the
    four leaves a step writes, the shared [N] capacity leaves whole."""
    leaves = (leaf if f in CAPACITY_LEAVES else leaf[i]
              for f, leaf in zip(NodeState._fields, state))
    return NodeState(*(np.array(a) if copy else a for a in leaves))


def _assert_arrays_equal(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype, what
    np.testing.assert_array_equal(a, b, what)


@pytest.mark.parametrize("form", FORMS)
def test_every_lane_equals_the_per_lane_form_of_its_own_arrays(form):
    s = swept(form)
    out, amounts, watts, w, seeds, pods_n, events_n, pad_skips = s.args
    faulted = "fault_specs" in s.kw
    assert len(s.lanes) == len(WEIGHTS) and list(seeds) == SEEDS
    if form == "a trace a lane of differing lengths":
        assert len(set(pods_n)) > 2 and len(set(events_n)) > 2
        assert min(pods_n) < out.placed_node.shape[1]
    if form == "a stream with deletions":
        assert s.record.delete_events > 0
    if faulted:
        assert any(ln.disruption.node_failures for ln in s.lanes)
        assert any(ln.disruption.node_recoveries for ln in s.lanes)
    for i, got in enumerate(s.lanes):
        p, e = pods_n[i], events_n[i]
        state = _lane_state(out.state, i, copy=True)
        want = lane_from_arrays(
            state, np.array(out.placed_node[i][:p]),
            np.array(out.dev_mask[i][:p]), np.array(out.ever_failed[i][:p]),
            None if out.counters is None else np.array(out.counters[i]),
            s.typical(i), WEIGHTS[i], SEEDS[i], e, pad_skips[i])
        who = f"{form}, lane {i}"
        # `==`, no tolerance: the same integers and the same two IEEE
        # operations of the ratio
        assert got.gpu_alloc_pct == want.gpu_alloc_pct, who
        # frag and watts are the HOST's part of the per-lane form, on the
        # amounts the sweep fetched for this lane. lane_from_arrays sums a
        # lane's nodes in a program of its own (one lane, not vmapped),
        # whose f32 order is not the sweep's post-pass's: that far only
        assert got.frag_gpu_milli == float(frag_sum_except_q3(amounts[i]))
        assert got.frag_gpu_milli == pytest.approx(
            want.frag_gpu_milli, rel=1e-6), who
        assert (got.power_cpu_w, got.power_gpu_w) == (
            float(watts[i][0]), float(watts[i][1])), who
        assert (got.power_cpu_w, got.power_gpu_w) == pytest.approx(
            (want.power_cpu_w, want.power_gpu_w), rel=1e-6), who
        assert (got.placed, got.failed, got.seed) == (
            want.placed, want.failed, want.seed), who
        for v in (got.gpu_alloc_pct, got.frag_gpu_milli, got.power_cpu_w,
                  got.power_gpu_w):
            assert type(v) is float, who
        for v in (got.placed, got.failed, got.unscheduled, got.events,
                  got.seed):
            assert type(v) is int, who
        _assert_arrays_equal(got.counters, want.counters, who)
        _assert_arrays_equal(got.weights, want.weights, who)
        for f in ("placed_node", "dev_mask", "ever_failed"):
            _assert_arrays_equal(getattr(got, f), getattr(want, f), who)
        assert got.placed_node.shape == (p,) and got.dev_mask.shape == (p, 8)
        assert type(got.state) is NodeState
        for name, a, b in zip(NodeState._fields, got.state, want.state):
            _assert_arrays_equal(a, b, f"{who}: state.{name}")
        if faulted:
            # the merged stream's steps: base events and the retries run
            retries = int((out.fault_ys.rpod[i] >= 0).sum())
            assert got.events == e + retries
            assert pad_skips[i] == out.fault_ys.rpod.shape[1] - e - retries
            dead = out.fault_carry.dead[i][:p]
            assert got.unscheduled == int(
                ((want.placed_node < 0) & (want.ever_failed | dead)).sum())
            assert got.unscheduled >= want.unscheduled
            assert got.event_node is None and got.event_dev is None
        else:
            assert (got.events, got.unscheduled) == (
                want.events, want.unscheduled), who
            _assert_arrays_equal(got.event_node, out.event_node[i][:e], who)
            _assert_arrays_equal(got.event_dev, out.event_dev[i][:e], who)
            assert got.event_dev.shape == (e, 8)
            assert got.disruption is None
        if out.metrics is None:
            assert got.metrics is None
        else:
            assert type(got.metrics) is EventMetrics
            for name, a, b in zip(EventMetrics._fields, got.metrics,
                                  out.metrics):
                _assert_arrays_equal(a, b[i][:e], f"{who}: metrics.{name}")
    # the lanes differ among themselves, so a lane read at another's index
    # would show
    assert len({ln.placed_node.tobytes() for ln in s.lanes}) > 1
    if form.startswith("a shared trace"):
        assert s.lanes[0].metrics is not None
        assert any(ln.failed for ln in s.lanes)


def _pads(state):
    """gpu_left beyond each node's devices: [..., N, 8] entries that the
    allocation ratio counts as nothing."""
    gpu_left, gpu_cnt = np.asarray(state.gpu_left), np.asarray(state.gpu_cnt)
    # (a fetched sweep's gpu_cnt is the cluster's, [N], for every lane)
    beyond = np.broadcast_to(
        np.arange(gpu_left.shape[-1]) >= gpu_cnt[..., None], gpu_left.shape)
    assert beyond.any() and not beyond.all()
    return gpu_left[beyond]


@pytest.mark.parametrize("what", list(FORMS) + list(BODIES))
def test_gpu_left_is_zero_beyond_a_nodes_devices(what):
    """types.NodeState: "rows are padded with 0 beyond gpu_cnt devices".
    _slice_sweep_lanes leans on it (used = MILLI x devices - what is left)
    in place of a slot mask a lane; a fault plan zeroes a DOWN node's rows
    and no step writes past gpu_cnt."""
    s = swept(what)
    assert not _pads(s.sim.init_state).any()
    assert not _pads(s.args[0].state).any()  # every lane, as fetched
    for lane in s.lanes:
        assert not _pads(lane.state).any()
        # and so the identity: the masked sum is the unmasked one
        slot = np.arange(8) < lane.state.gpu_cnt[:, None]
        assert int(np.where(slot, MILLI - lane.state.gpu_left, 0).sum()) == (
            MILLI * int(lane.state.gpu_cnt.sum())
            - int(lane.state.gpu_left.sum()))
    assert any((ln.state.gpu_left != s.sim.init_state.gpu_left).any()
               for ln in s.lanes)
    if what in BODIES:
        lanes, cfg = BODIES[what]
        assert s.record.lanes == lanes and "table" in s.record.engine
        assert s.sim.cfg.block_size == cfg.get("block_size", 0)
        assert s.record.table_pass_events == (
            FLAT_GROUP_EVENTS if lanes >= FLAT_GROUP_MIN_LANES else 1)
    if what == "fault plans":
        # a node still DOWN at the end holds nothing: its real slots read 0
        assert any((ln.state.mem_left < 0).any() for ln in s.lanes)


@pytest.mark.parametrize("what", list(FORMS) + list(BODIES))
def test_the_lanes_share_one_read_only_view_of_the_capacity_leaves(what):
    """The fetched state holds the five never-written leaves once, [N],
    equal to the start state's in value and dtype; every lane's state is a
    whole NodeState of today's shapes whose five are that one array (the
    same object, so `np.shares_memory`), not writeable, while the four
    leaves a step writes are the lane's own rows."""
    s = swept(what)
    fetched, start = s.args[0].state, s.sim.init_state
    lanes, n = len(s.lanes), start.num_nodes
    first = s.lanes[0].state
    for f in NodeState._fields:
        whole, own = getattr(fetched, f), getattr(first, f)
        want = np.asarray(getattr(start, f))
        assert own.shape == want.shape and own.dtype == want.dtype, f
        if f in CAPACITY_LEAVES:
            assert whole.shape == (n,), f
            _assert_arrays_equal(whole, want, f)
            assert not whole.flags.writeable, f
            for lane in s.lanes:
                leaf = getattr(lane.state, f)
                assert leaf is whole and not leaf.flags.writeable, f
            assert np.shares_memory(own, getattr(s.lanes[-1].state, f)), f
        else:
            assert whole.shape == (lanes,) + want.shape, f
            for i, lane in enumerate(s.lanes):
                leaf = getattr(lane.state, f)
                assert np.shares_memory(leaf, whole), f
                assert not leaf.flags.writeable, f
                _assert_arrays_equal(leaf, whole[i], f)
            assert not np.shares_memory(own, getattr(s.lanes[-1].state, f))
    with pytest.raises(ValueError, match="read-only"):
        first.gpu_cnt[0] = 0
    # one sum of the cluster's devices serves every lane's allocation ratio
    cnt = int(np.asarray(start.gpu_cnt).sum())
    for lane in s.lanes:
        assert lane.gpu_alloc_pct == 100.0 * float(
            MILLI * cnt - int(lane.state.gpu_left.sum())) / (cnt * MILLI)


def test_the_csv_loaders_cluster_starts_with_zero_pads():
    nodes = load_node_csv(
        os.path.join(REPO, "data/csv/openb_node_list_gpu_node.csv"))
    sim = Simulator(nodes, _cfg(42))
    assert sim.init_state.gpu_left.shape == (1213, 8)
    assert not _pads(sim.init_state).any()


def _synthetic_fetch(lanes, nodes, pods, events, rng):
    """A fetched sweep result in plain numpy: random cluster states with
    zero pads on one cluster's capacities, random placements, counters,
    frag amounts and watts."""
    def i32(*shape, low=-5, high=50000):
        return rng.integers(low, high, shape, dtype=np.int32)

    cnt = i32(nodes, low=0, high=9)
    left = i32(lanes, nodes, 8, low=0, high=MILLI + 1)
    left[:, np.arange(8) >= cnt[:, None]] = 0
    state = NodeState(
        cpu_left=i32(lanes, nodes), cpu_cap=i32(nodes),
        mem_left=i32(lanes, nodes), mem_cap=i32(nodes),
        gpu_left=left, gpu_cnt=cnt, gpu_type=i32(nodes),
        cpu_type=i32(nodes), aff_cnt=i32(lanes, nodes, 9))
    out = ReplayResult(
        state=state,
        placed_node=i32(lanes, pods, low=-1, high=nodes),
        dev_mask=rng.random((lanes, pods, 8)) < 0.3,
        ever_failed=rng.random((lanes, pods)) < 0.4,
        metrics=None,
        event_node=i32(lanes, events, low=-1, high=nodes),
        event_dev=rng.random((lanes, events, 8)) < 0.3,
        counters=i32(lanes, NUM_COUNTERS, low=0, high=900))
    amounts = (rng.random((lanes, 7)) * 3e6).astype(np.float32)
    watts = (rng.random((lanes, 2)) * 1e6).astype(np.float32)
    return out, amounts, watts


def test_the_slice_makes_no_lane_by_node_temporary_and_no_copy_a_lane():
    """Batching the MASKED allocation ratio reads slower than the loop it
    replaced (three [B, N, 8] temporaries that leave the cache): the pass
    has to stay one sum an array. And a lane holds views."""
    # wide enough that a mask over [B, N, 8] outweighs the lanes' own
    # objects (2.5 KB of views and scalars a lane, held when the call
    # returns): a bool one is a quarter of gpu_left, an i32 one all of it
    lanes, nodes, pods, events = 512, 1024, 24, 32
    rng = np.random.default_rng(39)
    out, amounts, watts = _synthetic_fetch(lanes, nodes, pods, events, rng)
    w = rng.integers(0, 1000, (lanes, 2), dtype=np.int32)
    seeds = list(range(lanes))
    pods_n = rng.integers(1, pods + 1, lanes).tolist()
    events_n = rng.integers(1, events + 1, lanes).tolist()
    pad_skips = rng.integers(0, 40, lanes).tolist()

    tracemalloc.start()
    try:
        got = driver._slice_sweep_lanes(
            out, amounts, watts, w, seeds, pods_n, events_n, pad_skips)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < out.state.gpu_left.nbytes // 4, peak

    fetched = [*out.state, out.placed_node, out.dev_mask, out.ever_failed,
               out.event_node, out.event_dev]
    for made_once in ("counters", "weights"):
        base = getattr(got[0], made_once).base
        assert base.shape[0] == lanes
        assert all(getattr(ln, made_once).base is base for ln in got)
    assert not np.shares_memory(got[0].weights, w)
    assert not np.shares_memory(got[0].counters, out.counters)
    for i in (0, 1, 77, lanes - 1):
        ln = got[i]
        views = [*ln.state, ln.placed_node, ln.dev_mask, ln.ever_failed,
                 ln.event_node, ln.event_dev]
        for view, whole in zip(views, fetched):
            assert np.shares_memory(view, whole)
            if view.shape == whole.shape:  # a capacity leaf: no lane axis
                assert view is whole
            else:
                np.testing.assert_array_equal(view, whole[i][:len(view)])
        # against the loop it replaced, a lane at a time with the mask
        slot = np.arange(8) < ln.state.gpu_cnt[:, None]
        used = int(np.where(slot, MILLI - ln.state.gpu_left, 0).sum())
        assert ln.gpu_alloc_pct == 100.0 * float(used) / max(
            int(ln.state.gpu_cnt.sum()) * MILLI, 1)
        pn, failed = ln.placed_node, ln.ever_failed
        assert (len(pn), ln.events) == (pods_n[i], events_n[i])
        assert (ln.placed, ln.failed, ln.unscheduled) == (
            int((pn >= 0).sum()), int(failed.sum()),
            int(((pn < 0) & failed).sum()))
        want = np.asarray(out.counters[i]).astype(np.int64)
        want[4] = max(int(want[4]) - pad_skips[i], 0)
        np.testing.assert_array_equal(ln.counters, want)
        assert ln.counters.dtype == np.int64 and ln.weights.dtype == np.int32
        np.testing.assert_array_equal(ln.weights, w[i])
        assert ln.frag_gpu_milli == float(frag_sum_except_q3(amounts[i]))
        assert (ln.power_cpu_w, ln.power_gpu_w) == (
            float(watts[i][0]), float(watts[i][1]))
    assert any(c[4] == 0 for c in (ln.counters for ln in got))  # the clamp


def test_a_cluster_without_gpus_reads_zero_allocation():
    out, amounts, watts = _synthetic_fetch(
        2, 5, 3, 3, np.random.default_rng(1))
    empty = out.state._replace(
        gpu_cnt=np.zeros_like(out.state.gpu_cnt),
        gpu_left=np.zeros_like(out.state.gpu_left))
    got = driver._slice_sweep_lanes(
        out._replace(state=empty, counters=None), amounts, watts,
        [[1000], [900]], [1, 2], [3, 2], [3, 1], [0, 0])
    assert [ln.gpu_alloc_pct for ln in got] == [0.0, 0.0]
    assert [ln.counters for ln in got] == [None, None]
    assert [len(ln.placed_node) for ln in got] == [3, 2]
