"""The affinity counts outside the event loop (ISSUE 42): where no scoring
kernel of the program reads NodeState.aff_cnt and no fault step rewrites it,
the flat table replay's commit leaves the leaf alone and run_chunk adds the
chunk's counts once, after its scan, from the events' own record
(table_engine.chunk_affinity). The leaf must be, bit for bit, what the
per-event commit leaves: at finish, and in the carry at every cut of a
chunked replay, whichever form wrote the checkpoint and whichever resumes
it. The per-event form is the same engine built on a kernel that does not
say what it reads (so it counts as a reader: the program decides, nothing
selects the form), the third party the sequential oracle's final state and
a plain numpy count over the oracle's own record of its events."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.fixtures import random_cluster, random_pods
from tpusim.policies import make_policy
from tpusim.policies.clustering import pod_affinity_class
from tpusim.sim.engine import EV_CREATE, EV_DELETE, EV_SKIP, make_replay
from tpusim.sim.step import resolve_weights
from tpusim.sim.table_engine import (
    AFFINITY_EVENTS,
    FLAT_GROUP_EVENTS,
    FLAT_GROUP_MIN_LANES,
    PodTypes,
    build_pod_types,
    make_table_replay,
)

DEPTH = 43
CUTS = [0, 1, 15, 16, 17, 29, DEPTH]  # 29: a prime; 15 / 16 / 17: a group's edge
FGD = make_policy("FGDScore")


def _silent_fgd(state, pod, ctx):
    """FGD's scores from a kernel that declares nothing about aff_cnt."""
    return FGD(state, pod, ctx)


_silent_fgd.normalize = FGD.normalize
_silent_fgd.policy_name = FGD.policy_name
_silent_fgd.branches = FGD.branches

DEFERRED = [(FGD, 1000)]
PER_EVENT = [(_silent_fgd, 1000)]


def _creates(rng):
    return np.zeros(DEPTH, np.int32), np.arange(DEPTH, dtype=np.int32)


def _clock(rng):
    """Creates, deletes of pods created earlier (placed or REJECTED: the
    cluster is tight), a delete of a pod that never arrived and skips."""
    kinds, idxs, nxt = [], [], 0
    while len(kinds) < DEPTH:
        u = rng.random()
        if len(kinds) == 5:
            kinds.append(EV_DELETE)  # pod 40 has not been created
            idxs.append(40)
        elif u < 0.3 and nxt > 2:
            kinds.append(EV_DELETE)
            idxs.append(int(rng.integers(nxt)))
        elif u < 0.36:
            kinds.append(EV_SKIP)
            idxs.append(int(rng.integers(DEPTH)))
        else:
            kinds.append(EV_CREATE)
            idxs.append(nxt)
            nxt += 1
    return np.asarray(kinds, np.int32), np.asarray(idxs, np.int32)


def _case(stream, nodes, lanes=None, seed=71):
    """(state, tp, pods, types, ev_kind, ev_pod, keys, ranks): one replay,
    or `lanes` of them, each its own shuffle of one pod list (a trace a
    lane: pods, type ids and streams carry the lane axis; the type set is
    shared) under its own key and tie-break rank."""
    rng = np.random.default_rng(seed)
    state, tp = random_cluster(rng, num_nodes=nodes)
    pods = random_pods(rng, num_pods=DEPTH)
    types = build_pod_types(pods)
    b = lanes or 1
    orders = [rng.permutation(DEPTH) for _ in range(b)]
    streams = [stream(rng) for _ in range(b)]
    keys = jnp.stack([jax.random.PRNGKey(100 + i) for i in range(b)])
    ranks = jnp.stack([jnp.asarray(rng.permutation(nodes), jnp.int32)
                       for _ in range(b)])
    lane_pods = jax.tree.map(
        lambda a: jnp.stack([a[o] for o in orders]), pods)
    lane_types = types._replace(
        type_id=jnp.stack([types.type_id[o] for o in orders]))
    ev_kind = jnp.asarray(np.stack([k for k, _ in streams]))
    ev_pod = jnp.asarray(np.stack([i for _, i in streams]))
    out = (state, tp, lane_pods, lane_types, ev_kind, ev_pod, keys, ranks)
    if lanes is None:
        return jax.tree.map(
            lambda a: a[0] if a.shape[:1] == (1,) else a, out,
        )
    return out


def _what_if(stream, nodes):
    """A what-if from another state: the cluster as a first replay left it
    (a non-zero aff_cnt_0), then a second stream over fresh pods."""
    state, tp, pods, types, ev_kind, ev_pod, key, rank = _case(_creates, nodes)
    first = make_replay(DEFERRED, gpu_sel="FGDScore", report=False)(
        state, pods, ev_kind[:12], ev_pod[:12], tp, key, rank)
    assert int(np.asarray(first.state.aff_cnt).sum()) > 0
    _, _, pods, types, ev_kind, ev_pod, key, rank = _case(
        stream, nodes, seed=73)
    return first.state, tp, pods, types, ev_kind, ev_pod, key, rank


CASES = {
    "creates only": lambda: _case(_creates, 10),
    "the clock stream": lambda: _case(_clock, 3),
    "a what-if from another state": lambda: _what_if(_clock, 10),
    "a trace a lane, 8 lanes (plain)": lambda: _case(_clock, 4, lanes=8),
    "a trace a lane, 64 lanes (grouped)": lambda: _case(
        _clock, 4, lanes=FLAT_GROUP_MIN_LANES),
}


def _engine(policies, lanes, gpu_sel="FGDScore"):
    """(init_carry, run_chunk, finish, replay, deferred) of the flat table
    engine for `policies`, vmapped as a sweep with a trace a lane where
    `lanes` is given (from FLAT_GROUP_MIN_LANES on its grouped body)."""
    tab = make_table_replay(policies, gpu_sel=gpu_sel, block_size=-1)
    eng, wts = tab.engine, resolve_weights(policies, None)
    if lanes is None:
        return (
            lambda st, p, t, tp, key, rank: eng.init_carry(
                st, p, t, tp, key, wts, rank),
            lambda c, p, t, k, i, tp, rank: eng.run_chunk(
                c, p, t, k, i, tp, wts, rank),
            eng.finish,
            lambda st, p, t, k, i, tp, key, rank: eng.replay(
                st, p, t, k, i, tp, key, wts, rank),
            eng.affinity_deferred,
        )
    group = FLAT_GROUP_EVENTS if lanes >= FLAT_GROUP_MIN_LANES else 1
    tid = PodTypes(None, None, 0)
    return (
        jax.jit(jax.vmap(
            lambda st, p, t, tp, key, rank: eng.init_carry(
                st, p, t, tp, key, wts, rank),
            in_axes=(None, 0, tid, None, 0, 0))),
        jax.jit(jax.vmap(
            lambda c, p, t, k, i, tp, rank: eng.run_chunk(
                c, p, t, k, i, tp, wts, rank, group=group),
            in_axes=(0, 0, tid, 0, 0, None, 0))),
        jax.jit(jax.vmap(eng.finish)),
        jax.jit(jax.vmap(
            lambda st, p, t, k, i, tp, key, rank: eng.replay(
                st, p, t, k, i, tp, key, wts, rank, group=group),
            in_axes=(None, 0, tid, 0, 0, None, 0, 0))),
        eng.affinity_deferred,
    )


def _counted(aff0, pods, ev_kind, ev_pod, event_node, upto):
    """aff_cnt after the first `upto` events' commits, counted in plain
    numpy from a replay's own record of its events."""
    out = np.array(aff0, np.int64)
    cls = np.asarray(pod_affinity_class(pods))[np.asarray(ev_pod)]
    kind = np.clip(np.asarray(ev_kind), 0, 2)
    for e in range(upto):
        node = int(event_node[e])
        if node >= 0 and cls[e] >= 0:
            out[node, cls[e]] += -1 if kind[e] == EV_DELETE else 1
    return out


def _host(tree):
    """A checkpoint's round trip: every leaf through host memory."""
    return jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), tree)


def _assert_trees_equal(a, b, what):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), what)


@pytest.mark.parametrize("case", CASES)
def test_the_deferred_affinity_counts_equal_the_per_event_commits(case):
    state, tp, pods, types, ev_kind, ev_pod, keys, ranks = CASES[case]()
    lanes = None if ev_kind.ndim == 1 else ev_kind.shape[0]
    nodes = state.num_nodes
    forms = {"deferred": _engine(DEFERRED, lanes),
             "per event": _engine(PER_EVENT, lanes)}
    assert forms["deferred"][4](nodes, types)
    assert not forms["per event"][4](nodes, types)

    def lane(tree, i):
        return tree if lanes is None else jax.tree.map(lambda a: a[i], tree)

    # the sequential oracle, a lane at a time, and the numpy count over its
    # own record: the third party both forms are held to
    seq = make_replay(DEFERRED, gpu_sel="FGDScore", report=False)
    oracle = [
        seq(state, lane(pods, i), lane(ev_kind, i), lane(ev_pod, i), tp,
            lane(keys, i), lane(ranks, i))
        for i in range(lanes or 1)
    ]
    for i, res in enumerate(oracle):
        np.testing.assert_array_equal(
            np.asarray(res.state.aff_cnt),
            _counted(state.aff_cnt, lane(pods, i), lane(ev_kind, i),
                     lane(ev_pod, i), np.asarray(res.event_node), DEPTH))
    if "clock" in case or "lane" in case or "what-if" in case:
        # the stream holds what the issue names: a delete that gives a node
        # back, a rejected create and a delete that finds nothing (node -1)
        kinds = np.concatenate(
            [np.asarray(lane(ev_kind, i)) for i in range(lanes or 1)])
        hit = np.concatenate([np.asarray(r.event_node) for r in oracle])
        assert ((kinds == EV_DELETE) & (hit >= 0)).any()
        assert ((kinds == EV_DELETE) & (hit < 0)).any()
        if nodes < 10:
            assert ((kinds == EV_CREATE) & (hit < 0)).any()
    assert sum(int(np.abs(np.asarray(r.state.aff_cnt)).sum())
               for r in oracle) > 0

    # one replay of the whole stream (two whole groups and a tail where it
    # runs grouped): every leaf the oracle's
    res = forms["deferred"][3](
        state, pods, types, ev_kind, ev_pod, tp, keys, ranks)
    for i, want in enumerate(oracle):
        got = lane(res, i)
        _assert_trees_equal(got.state, want.state, "one replay: state")
        for f in ("placed_node", "dev_mask", "ever_failed", "event_node",
                  "event_dev"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
                f"one replay: {f}")

    def chunk(at):
        lo, hi = CUTS[at], CUTS[at + 1]
        sl = (slice(lo, hi),) if lanes is None else (
            slice(None), slice(lo, hi))
        return ev_kind[sl], ev_pod[sl]

    # the chunked replay: at every cut the two forms' carries are one
    # carry, and its aff_cnt holds the commits that landed (the scan is one
    # event deep: all but the cut's last event)
    carries = {}
    for name, (init, run, _, _, _) in forms.items():
        carries[name] = [init(state, pods, types, tp, keys, ranks)]
        for at in range(len(CUTS) - 1):
            carry, _ = run(carries[name][-1], pods, types, *chunk(at), tp,
                           ranks)
            carries[name].append(carry)
    for at, (a, b) in zip(CUTS, zip(*carries.values())):
        assert type(a).__name__ == "FlatTableCarry"
        _assert_trees_equal(a, b, f"the carry at event {at}")
        for i, want in enumerate(oracle):
            np.testing.assert_array_equal(
                np.asarray(lane(a.state.aff_cnt, i)),
                _counted(state.aff_cnt, lane(pods, i), lane(ev_kind, i),
                         lane(ev_pod, i), np.asarray(want.event_node),
                         max(at - 1, 0)),
                f"aff_cnt in the carry at event {at}, lane {i}")

    # a checkpoint written by one form, resumed by the other (through host
    # memory), at EVERY cut: the next chunk under the other form gives the
    # next cut's carry; and the crossed chain's finish is the oracle's end
    for wrote, resumes in (("deferred", "per event"),
                           ("per event", "deferred")):
        _, run, finish, _, _ = forms[resumes]
        for at in range(len(CUTS) - 1):
            carry, _ = run(_host(carries[wrote][at]), pods, types,
                           *chunk(at), tp, ranks)
            _assert_trees_equal(
                carry, carries[wrote][at + 1],
                f"{wrote} -> {resumes} at event {CUTS[at]}")
        st, placed, masks, failed = finish(carry)
        for i, want in enumerate(oracle):
            what = f"{wrote} -> {resumes}, finished, lane {i}"
            _assert_trees_equal(lane(st, i), want.state, what)
            np.testing.assert_array_equal(
                np.asarray(lane(placed, i)), np.asarray(want.placed_node),
                what)
            np.testing.assert_array_equal(
                np.asarray(lane(masks, i)), np.asarray(want.dev_mask), what)
            np.testing.assert_array_equal(
                np.asarray(lane(failed, i)), np.asarray(want.ever_failed),
                what)


def test_an_empty_chunk_leaves_the_pending_commit_pending():
    """No event ran: the incoming commit has not landed, and the counts of
    a chunk of no events are none (chunk_affinity's own edge)."""
    state, tp, pods, types, ev_kind, ev_pod, key, rank = CASES[
        "creates only"]()
    init, run, finish, _, _ = _engine(DEFERRED, None)
    carry, _ = run(init(state, pods, types, tp, key, rank), pods, types,
                   ev_kind[:7], ev_pod[:7], tp, rank)
    assert int(carry.pend.node) >= 0
    same, _ = run(carry, pods, types, ev_kind[:0], ev_pod[:0], tp, rank)
    _assert_trees_equal(same, carry, "an empty chunk")


def test_the_counts_are_summed_in_blocks_of_events():
    """More events than one block holds, not a multiple of it: the padded
    tail adds nothing, and a vmapped call equals its lanes."""
    from tpusim.sim.step import no_pending_commit
    from tpusim.sim.table_engine import chunk_affinity

    events, nodes, lanes = 2 * AFFINITY_EVENTS + 37, 21, 3
    rng = np.random.default_rng(9)
    pods = random_pods(rng, num_pods=50)
    ev_kind = jnp.asarray(rng.integers(0, 4, (lanes, events)), jnp.int32)
    ev_pod = jnp.asarray(rng.integers(0, 50, (lanes, events)), jnp.int32)
    node = jnp.asarray(rng.integers(-1, nodes, (lanes, events)), jnp.int32)
    pend = no_pending_commit(50)._replace(
        node=jnp.int32(4), cls=jnp.int32(2), rs=jnp.int32(-1))
    one = functools.partial(chunk_affinity, pend, pods)
    got = jax.jit(jax.vmap(lambda k, i, n: one(k, i, n, nodes, 9)))(
        ev_kind, ev_pod, node)
    for b in range(lanes):
        want = _counted(np.zeros((nodes, 9)), pods, ev_kind[b], ev_pod[b],
                        np.asarray(node[b]), events - 1)
        want[4, 2] += 1  # the incoming commit: a bind of class 2 on node 4
        np.testing.assert_array_equal(np.asarray(got[b]), want)
        np.testing.assert_array_equal(
            np.asarray(one(ev_kind[b], ev_pod[b], node[b], nodes, 9)), want)


# ------------------------------------------- who keeps the per-event add


def _driver_sim(policies, gpu_sel, seed=42, **cfg):
    from tests.test_sweep import _cfg, _mk_cluster, _mk_pods
    from tpusim.sim.driver import Simulator

    rng = np.random.default_rng(5)
    sim = Simulator(_mk_cluster(rng), _cfg(
        seed, policies, gpu_sel, engine=cfg.pop("engine", "table"), **cfg))
    sim.set_workload_pods(_mk_pods(rng))
    sim.set_typical_pods()
    return sim


def _clustering_sweep():
    """GpuClustering reads aff_cnt every event: its program keeps the add
    in the commit and its lanes equal the sequential oracle's runs."""
    from tpusim.sim.driver import schedule_pods_sweep

    policies = (("GpuClusteringScore", 1000),)
    sim = _driver_sim(policies, "best")
    seeds = [11, 12]
    lanes = schedule_pods_sweep(
        sim, sim.prepare_pods(), [[1000]] * 2, seeds)
    oracle = []
    for seed in seeds:
        solo = _driver_sim(policies, "best", seed=seed, engine="sequential")
        oracle.append(solo.run())
        assert "sequential" in solo._last_engine
    return sim, lanes, oracle


def _fault_sweep(policies=(("FGDScore", 1000),), gpu_sel="FGDScore"):
    """A fault plan a lane: fault steps zero and rewrite aff_cnt rows
    mid-scan, so the add stays in the commit; each lane equals the
    standalone run under its schedule."""
    from tests.test_sweep_paths import _faults

    sim = _driver_sim(policies, gpu_sel)
    specs = _faults(2)
    lanes = sim.run_sweep(
        np.asarray([[1000]] * 2, np.int32), seeds=[42] * 2, faults=specs)
    assert sim._last_engine.endswith("chaos sweep)")
    oracle = []
    for spec in specs:
        solo = _driver_sim(policies, gpu_sel)
        oracle.append(solo.run_with_faults(fault_cfg=spec))
    assert any(lane.disruption.evicted_pods for lane in lanes)
    return sim, lanes, oracle


def _fgd_sweep():
    from tpusim.sim.driver import schedule_pods_sweep

    policies = (("FGDScore", 1000),)
    sim = _driver_sim(policies, "FGDScore")
    seeds = [11, 12]
    lanes = schedule_pods_sweep(
        sim, sim.prepare_pods(), [[1000]] * 2, seeds)
    oracle = [_driver_sim(policies, "FGDScore", seed=seed,
                          engine="sequential").run() for seed in seeds]
    return sim, lanes, oracle


@pytest.mark.parametrize("sweep, deferred, readers", [
    (_fgd_sweep, 1, 0), (_clustering_sweep, 0, 1), (_fault_sweep, 0, 0)],
    ids=["FGD", "GpuClustering", "fault plans"])
def test_the_sweep_record_says_which_form_its_program_took(
        sweep, deferred, readers):
    sim, lanes, oracle = sweep()
    rec = sim.obs.sweeps[-1]
    assert rec.affinity_deferred == deferred
    assert rec.to_dict()["affinity_deferred"] == deferred
    # and why: how many kernels of the program read the counts (a fault
    # plan keeps the add for its fault steps, with no reader)
    assert rec.affinity_readers == readers
    assert rec.to_dict()["affinity_readers"] == readers
    # the flat body's commit: four adds and three sets, less the aff_cnt add
    # where it left the loop; the epilogue's commit is whole
    assert rec.lane_writes == 3 + (6 if deferred else 7) + 7
    for lane, want in zip(lanes, oracle):
        np.testing.assert_array_equal(lane.placed_node, want.placed_node)
        for a, b in zip(jax.tree.leaves(lane.state),
                        jax.tree.leaves(want.state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sum(int(np.asarray(l.state.aff_cnt).sum()) for l in lanes) > 0


def test_the_blocked_body_and_the_sequential_engine_keep_the_add():
    from tpusim.obs.spans import SweepRecord
    from tpusim.sim.driver import schedule_pods_sweep

    policies = (("FGDScore", 1000),)
    blocked = _driver_sim(policies, "FGDScore", block_size=8)
    schedule_pods_sweep(blocked, blocked.prepare_pods(), [[1000]] * 2, [1, 2])
    assert blocked.obs.sweeps[-1].affinity_deferred == 0
    seq = _driver_sim(policies, "FGDScore", engine="sequential")
    schedule_pods_sweep(seq, seq.prepare_pods(), [[1000]] * 2, [1, 2])
    assert "sequential" in seq._last_engine
    assert seq.obs.sweeps[-1].affinity_deferred == 0
    assert blocked.obs.sweeps[-1].affinity_readers == 0
    assert seq.obs.sweeps[-1].affinity_readers == 0
    empty = SweepRecord(id=0, start_s=0.0, blocked=False).to_dict()
    assert (empty["affinity_deferred"], empty["affinity_readers"]) == (0, 0)
