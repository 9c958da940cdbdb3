"""Incremental score-table engine (tpusim.sim.table_engine) must be
bit-identical to the sequential oracle engine (tpusim.sim.engine) — same
kernels, different evaluation schedule. Randomized create/delete mixes over
heterogeneous clusters pin the equivalence for every table-izable policy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.fixtures import random_cluster, random_pods
from tpusim.policies import make_policy
from tpusim.sim.engine import EV_CREATE, EV_DELETE, make_replay
from tpusim.sim.table_engine import build_pod_types, make_table_replay


def _events_with_deletes(num_pods, rng):
    """Creation for every pod; ~1/3 get a later deletion (stable order)."""
    kinds, idxs = [], []
    for i in range(num_pods):
        kinds.append(EV_CREATE)
        idxs.append(i)
        if rng.random() < 0.34 and i > 0:
            victim = int(rng.integers(0, i + 1))
            kinds.append(EV_DELETE)
            idxs.append(victim)
    # dedup double-deletes (unschedule of an already-deleted pod is a no-op
    # in both engines, but keep the trace clean)
    seen = set()
    ek, ei = [], []
    for k, i in zip(kinds, idxs):
        if k == EV_DELETE:
            if i in seen:
                continue
            seen.add(i)
        ek.append(k)
        ei.append(i)
    return jnp.asarray(ek, jnp.int32), jnp.asarray(ei, jnp.int32)


def _assert_equal(r0, r1):
    assert np.array_equal(np.asarray(r0.placed_node), np.asarray(r1.placed_node))
    assert np.array_equal(np.asarray(r0.dev_mask), np.asarray(r1.dev_mask))
    assert np.array_equal(np.asarray(r0.ever_failed), np.asarray(r1.ever_failed))
    for a, b in zip(jax.tree.leaves(r0.state), jax.tree.leaves(r1.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "policy,gpu_sel",
    [
        ("FGDScore", "FGDScore"),
        ("BestFitScore", "best"),
        ("GpuPackingScore", "worst"),
        ("GpuClusteringScore", "best"),
        ("DotProductScore", "DotProductScore"),
        ("PWRScore", "PWRScore"),
        ("Simon", "best"),
        # per-event-random configs: bit-identical since round 5 (the table
        # body follows the oracle's key-split discipline and recomputes
        # the draw per event)
        ("RandomScore", "best"),
        ("RandomScore", "random"),
        ("FGDScore", "random"),
    ],
    ids=lambda p: str(p),
)
def test_table_engine_matches_sequential(policy, gpu_sel):
    rng = np.random.default_rng(7)
    state, tp = random_cluster(rng, num_nodes=24)
    pods = random_pods(rng, num_pods=60)
    ev_kind, ev_pod = _events_with_deletes(60, rng)
    policies = [(make_policy(policy), 1000)]
    key = jax.random.PRNGKey(3)
    rank = jnp.asarray(rng.permutation(24).astype(np.int32))

    seq = make_replay(policies, gpu_sel=gpu_sel, report=False)
    r0 = seq(state, pods, ev_kind, ev_pod, tp, key, rank)
    tab = make_table_replay(policies, gpu_sel=gpu_sel)
    r1 = tab(state, pods, build_pod_types(pods), ev_kind, ev_pod, tp, key, rank)
    _assert_equal(r0, r1)


def test_table_engine_weighted_multi_policy():
    """Two weighted score plugins (the reference's PWR+FGD mixes,
    generate_run_scripts.py AllMethodList rows 08/11/12)."""
    rng = np.random.default_rng(11)
    state, tp = random_cluster(rng, num_nodes=16)
    pods = random_pods(rng, num_pods=40)
    ev_kind, ev_pod = _events_with_deletes(40, rng)
    policies = [(make_policy("PWRScore"), 500), (make_policy("FGDScore"), 500)]
    key = jax.random.PRNGKey(5)
    rank = jnp.asarray(rng.permutation(16).astype(np.int32))

    seq = make_replay(policies, gpu_sel="FGDScore", report=False)
    r0 = seq(state, pods, ev_kind, ev_pod, tp, key, rank)
    tab = make_table_replay(policies, gpu_sel="FGDScore")
    r1 = tab(state, pods, build_pod_types(pods), ev_kind, ev_pod, tp, key, rank)
    _assert_equal(r0, r1)


def test_table_engine_pinned_pods():
    """nodeSelector-pinned pods (snapshot re-bind path) stay a per-event
    feasibility mask, not part of the type key."""
    rng = np.random.default_rng(13)
    state, tp = random_cluster(rng, num_nodes=8)
    pods = random_pods(rng, num_pods=12)
    pinned = np.full(12, -1, np.int32)
    pinned[3] = 5
    pinned[7] = 2
    pods = pods._replace(pinned=jnp.asarray(pinned))
    ev_kind = jnp.zeros(12, jnp.int32)
    ev_pod = jnp.arange(12, dtype=jnp.int32)
    policies = [(make_policy("FGDScore"), 1000)]
    key = jax.random.PRNGKey(1)

    seq = make_replay(policies, gpu_sel="FGDScore", report=False)
    r0 = seq(state, pods, ev_kind, ev_pod, tp, key)
    tab = make_table_replay(policies, gpu_sel="FGDScore")
    r1 = tab(state, pods, build_pod_types(pods), ev_kind, ev_pod, tp, key)
    _assert_equal(r0, r1)
    placed = np.asarray(r1.placed_node)
    assert placed[3] in (5, -1) and placed[7] in (2, -1)


def test_random_policy_rejected_by_pallas_only():
    """Per-event randomness runs on the table engine since round 5; only
    the fused Pallas kernel (no jax.random inside) still rejects it."""
    from tpusim.sim.pallas_engine import make_pallas_replay

    make_table_replay([(make_policy("RandomScore"), 1000)])  # no raise
    with pytest.raises(ValueError):
        make_pallas_replay([(make_policy("RandomScore"), 1000)])
    with pytest.raises(ValueError):
        make_pallas_replay([(make_policy("FGDScore"), 1000)], gpu_sel="random")


def test_pod_type_partition():
    rng = np.random.default_rng(17)
    pods = random_pods(rng, num_pods=50)
    t = build_pod_types(pods)
    ks = int(t.share.cpu.shape[0])
    kw = int(t.whole.cpu.shape[0])
    # share group: exactly-one-GPU fractional requests
    assert bool(
        ((t.share.gpu_num == 1) & (t.share.gpu_milli > 0) & (t.share.gpu_milli < 1000)).all()
    )
    # ids must map each pod onto a type with identical resources
    tid = np.asarray(t.type_id)
    assert tid.min() >= 0 and tid.max() < ks + kw
    cat = lambda f: np.concatenate([np.asarray(getattr(t.share, f)), np.asarray(getattr(t.whole, f))])
    for f in ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask"):
        assert np.array_equal(cat(f)[tid], np.asarray(getattr(pods, f)))


@pytest.mark.parametrize(
    "policy,gpu_sel",
    [
        ("FGDScore", "FGDScore"),
        ("BestFitScore", "best"),
        # tier-1 trim, ISSUE 16: per-event report rows are policy-agnostic
        # plumbing — two policies pin the contract; the rest ride
        # resume-smoke
        pytest.param("PWRScore", "PWRScore", marks=pytest.mark.slow),
        pytest.param("GpuPackingScore", "worst", marks=pytest.mark.slow),
    ],
    ids=lambda p: str(p),
)
def test_table_engine_report_rows_match_sequential(policy, gpu_sel):
    """Per-event report series: the table engine's telemetry through the
    shared post-pass must match the sequential oracle's in-scan rows
    (integer series exactly; float series to f32 tolerance — the post-pass
    accumulates row deltas where the oracle re-reduces per event)."""
    from tpusim.sim.metrics import compute_event_metrics

    rng = np.random.default_rng(23)
    state, tp = random_cluster(rng, num_nodes=12)
    pods = random_pods(rng, num_pods=30)
    ev_kind, ev_pod = _events_with_deletes(30, rng)
    policies = [(make_policy(policy), 1000)]
    key = jax.random.PRNGKey(9)
    rank = jnp.asarray(rng.permutation(12).astype(np.int32))

    seq = make_replay(policies, gpu_sel=gpu_sel, report=True)
    r0 = seq(state, pods, ev_kind, ev_pod, tp, key, rank)
    tab = make_table_replay(policies, gpu_sel=gpu_sel)
    r1 = tab(state, pods, build_pod_types(pods), ev_kind, ev_pod, tp, key, rank)
    _assert_equal(r0, r1)
    m1 = compute_event_metrics(
        state, pods, ev_kind, ev_pod, r1.event_node, r1.event_dev, tp
    )
    for f in ("used_nodes", "used_gpus", "used_gpu_milli", "used_cpu_milli",
              "arrived_gpu_milli", "arrived_cpu_milli"):
        np.testing.assert_array_equal(
            np.asarray(getattr(m1, f)), np.asarray(getattr(r0.metrics, f)),
            err_msg=f,
        )
    for f in ("frag_amounts", "power_cpu", "power_gpu"):
        np.testing.assert_allclose(
            np.asarray(getattr(m1, f)), np.asarray(getattr(r0.metrics, f)),
            rtol=2e-5, atol=1e-2, err_msg=f,
        )


def test_bucketed_padding_equivalence():
    """run_events' shape bucketing (inert pods + EV_SKIP events + dummy
    types) must not change results."""
    from tpusim.io.trace import NodeRow, PodRow, pods_to_specs
    from tpusim.sim.driver import Simulator, SimulatorConfig

    rng = np.random.default_rng(31)
    nodes = [
        NodeRow(f"n{i}", 32000, 131072, int(g), "V100M16" if g else "")
        for i, g in enumerate(rng.choice([0, 2, 4, 8], 10))
    ]
    pods = [
        PodRow(f"p{i}", int(rng.choice([1000, 4000])), 1024,
               int(rng.choice([0, 1])), 500)
        for i in range(23)
    ]
    sim = Simulator(nodes, SimulatorConfig(
        policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
        report_per_event=True,
    ))
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    specs = pods_to_specs(pods)
    ev_kind = jnp.zeros(23, jnp.int32)
    ev_pod = jnp.arange(23, dtype=jnp.int32)
    key = jax.random.PRNGKey(2)
    r0 = sim.run_events(sim.init_state, specs, ev_kind, ev_pod, key, bucket=1)
    r1 = sim.run_events(sim.init_state, specs, ev_kind, ev_pod, key, bucket=512)
    _assert_equal(r0, r1)
    for a, b in zip(r0.metrics, r1.metrics):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


@pytest.mark.slow  # tier-1 trim, ISSUE 16: the unswitched-select A/B knob's big compile; rides resume-smoke
def test_unswitched_flat_bit_identity():
    """Round 18 A/B pin: the flat body's unconditional-select layout
    (`unswitched=True` — the shard engine's Round-15 form ported back)
    is bit-identical to the default event-switch layout across
    create/delete mixes, a policy mix with normalization, and
    per-event randomness (RandomScore recomputes its draw from the same
    pre-split k_rand either way)."""
    rng = np.random.default_rng(23)
    state, tp = random_cluster(rng, num_nodes=20)
    pods = random_pods(rng, num_pods=50)
    ev_kind, ev_pod = _events_with_deletes(50, rng)
    key = jax.random.PRNGKey(5)
    rank = jnp.asarray(rng.permutation(20).astype(np.int32))
    types = build_pod_types(pods)
    for policies, gpu_sel in (
        ([("FGDScore", 1000)], "FGDScore"),
        ([("PWRScore", 500), ("BestFitScore", 500)], "best"),
        ([("RandomScore", 1000)], "random"),
    ):
        pol = [(make_policy(n), w) for n, w in policies]
        switched = make_table_replay(pol, gpu_sel=gpu_sel, block_size=-1)
        unswitched = make_table_replay(
            pol, gpu_sel=gpu_sel, block_size=-1, unswitched=True
        )
        r0 = switched(state, pods, types, ev_kind, ev_pod, tp, key, rank)
        r1 = unswitched(state, pods, types, ev_kind, ev_pod, tp, key, rank)
        _assert_equal(r0, r1)

    # the user-reachable compositions exercise the unswitched merge code
    # the plain path does not: the decision-pytree where-merge, and the
    # fault build's kc clipping (fault kinds must fall through to skip
    # in both layouts)
    pol = [(make_policy("FGDScore"), 1000)]
    for kw in (dict(decisions=True),):
        r0 = make_table_replay(pol, gpu_sel="FGDScore", block_size=-1, **kw)(
            state, pods, types, ev_kind, ev_pod, tp, key, rank
        )
        r1 = make_table_replay(
            pol, gpu_sel="FGDScore", block_size=-1, unswitched=True, **kw
        )(state, pods, types, ev_kind, ev_pod, tp, key, rank)
        _assert_equal(r0, r1)
        for a, b in zip(jax.tree.leaves(r0.decisions),
                        jax.tree.leaves(r1.decisions)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow  # tier-1 trim, ISSUE 16: same knob through the fault lane; rides resume-smoke
def test_unswitched_fault_lane_bit_identity():
    """The unswitched layout under the in-scan fault plane: the driver's
    run_with_faults scan lane threads SimulatorConfig.unswitched_select,
    so the full fault trajectory (placements, DisruptionMetrics) must be
    bit-identical to the default switch layout."""
    from tpusim.io.trace import NodeRow, PodRow
    from tpusim.sim.driver import Simulator, SimulatorConfig
    from tpusim.sim.faults import FaultConfig

    rng = np.random.default_rng(13)
    nodes = [
        NodeRow(f"n{i}", 32000, 131072, int(g), "V100M16" if g else "")
        for i, g in enumerate(rng.choice([2, 4, 8], 10))
    ]
    pods = [
        PodRow(f"p{i}", int(rng.choice([1000, 4000])), 1024, 1,
               int(rng.choice([300, 500, 1000])))
        for i in range(40)
    ]
    fcfg = FaultConfig(mtbf_events=12, mttr_events=10,
                       evict_every_events=9, seed=3)
    results = []
    for unswitched in (False, True):
        sim = Simulator(nodes, SimulatorConfig(
            policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
            engine="table", block_size=-1, seed=7,
            report_per_event=False, fault_mode="scan",
            unswitched_select=unswitched,
        ))
        sim.set_workload_pods(list(pods))
        results.append(sim.run_with_faults(fcfg))
    r0, r1 = results
    assert sim._last_engine == "table (fault lane)"
    np.testing.assert_array_equal(
        np.asarray(r0.placed_node), np.asarray(r1.placed_node)
    )
    np.testing.assert_array_equal(
        np.asarray(r0.dev_mask), np.asarray(r1.dev_mask)
    )


# ------------------------------------------------ deferred column writes
# (the grouped flat body: what a wide sweep runs, table_engine
# .flat_group_events; the engine's own entries take it as static `group=`)
def _grouped(tab, policies, group):
    """(replay, run_chunk) of `tab`'s engine on the grouped flat body, with
    the weight operand filled in as make_table_replay's wrappers do."""
    from tpusim.sim.step import resolve_weights

    wts = resolve_weights(policies, None)

    def replay(state, pods, types, ev_kind, ev_pod, tp, key, rank):
        return tab.engine.replay(state, pods, types, ev_kind, ev_pod, tp,
                                 key, wts, rank, group=group)

    def run_chunk(carry, pods, types, ev_kind, ev_pod, tp, rank):
        return tab.engine.run_chunk(carry, pods, types, ev_kind, ev_pod, tp,
                                    wts, rank, group=group)

    return replay, run_chunk


def _mixed_events(depth, num_pods, rng):
    """`depth` events: creates, deletes of pods created earlier and skips
    (EV_SKIP: a padded or unscheduled-annotated pod), in one stream."""
    from tpusim.sim.engine import EV_SKIP

    kinds, idxs, live, nxt = [], [], [], 0
    while len(kinds) < depth:
        u = rng.random()
        if u < 0.2 and live:
            kinds.append(EV_DELETE)
            idxs.append(live.pop(int(rng.integers(len(live)))))
        elif u < 0.3 or nxt == num_pods:
            kinds.append(EV_SKIP)
            idxs.append(int(rng.integers(num_pods)))
        else:
            kinds.append(EV_CREATE)
            idxs.append(nxt)
            live.append(nxt)
            nxt += 1
    return jnp.asarray(kinds, jnp.int32), jnp.asarray(idxs, jnp.int32)


def _flat_case(depth, nodes=10, seed=41):
    rng = np.random.default_rng(seed)
    state, tp = random_cluster(rng, num_nodes=nodes)
    pods = random_pods(rng, num_pods=depth)
    ev_kind, ev_pod = _mixed_events(depth, depth, rng)
    rank = jnp.asarray(rng.permutation(nodes).astype(np.int32))
    return state, tp, pods, ev_kind, ev_pod, rank


def _assert_tables_current(replay, carry, types, tp, key):
    """Nothing pending: the carried tables are a rebuild on the carried
    state (the last event's commit is still in the pipeline register, and
    its column is the next event's refresh)."""
    want = replay.build_tables(carry.state, types, tp, key)
    for got, w in zip((carry.score_tbl, carry.sdev_tbl, carry.feas_tbl),
                      want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))


@pytest.mark.parametrize("over", [0, 1, -1], ids=["0", "1", "B-1"])
def test_flat_replay_with_late_columns_matches_sequential(over):
    """The flat step writes its dirty columns a group of FLAT_GROUP_EVENTS
    at a time and patches the rows it reads in between: every leaf equals
    the sequential oracle's for depths that are 0, 1 and B - 1 modulo the
    group, with deletes and skips in the stream and nodes dirtied more
    than once inside one group (the later column must win)."""
    from tpusim.sim.table_engine import FLAT_GROUP_EVENTS as group

    depth = 3 * group + over
    state, tp, pods, ev_kind, ev_pod, rank = _flat_case(depth)
    assert {0, 1, 2} <= set(np.asarray(ev_kind).tolist())
    policies = [(make_policy("FGDScore"), 1000)]
    key = jax.random.PRNGKey(3)
    types = build_pod_types(pods)
    r0 = make_replay(policies, gpu_sel="FGDScore", report=False)(
        state, pods, ev_kind, ev_pod, tp, key, rank)
    tab = make_table_replay(policies, gpu_sel="FGDScore", block_size=-1)
    replay, run_chunk = _grouped(tab, policies, group)
    r1 = replay(state, pods, types, ev_kind, ev_pod, tp, key, rank)
    _assert_equal(r0, r1)
    np.testing.assert_array_equal(
        np.asarray(r0.event_node), np.asarray(r1.event_node))
    np.testing.assert_array_equal(
        np.asarray(r0.event_dev), np.asarray(r1.event_dev))
    touched = np.asarray(r1.event_node)[:group]
    touched = touched[touched >= 0]
    assert len(set(touched.tolist())) < len(touched)  # a node dirtied twice
    carry = tab.init_carry(state, pods, types, tp, key, rank)
    carry, _ = run_chunk(carry, pods, types, ev_kind, ev_pod, tp, rank)
    _assert_tables_current(tab, carry, types, tp, key)
    # and the plain body (a group of 1: what a replay runs unless told)
    _assert_equal(r0, tab(state, pods, types, ev_kind, ev_pod, tp, key, rank))


def test_flat_chunks_cut_inside_a_group_equal_one_replay():
    """run_chunk over cut points that are no multiples of the group: each
    chunk ends in its own flush, so the carry between chunks has nothing
    pending (its tables are a rebuild on its state) and the chain is
    bit-identical to one replay() (grouped or not: the carry between
    chunks is the same carry)."""
    from tpusim.sim.table_engine import FLAT_GROUP_EVENTS as group

    depth = 3 * group + 5
    state, tp, pods, ev_kind, ev_pod, rank = _flat_case(depth, seed=43)
    policies = [(make_policy("FGDScore"), 1000)]
    key = jax.random.PRNGKey(5)
    types = build_pod_types(pods)
    tab = make_table_replay(policies, gpu_sel="FGDScore", block_size=-1)
    whole = tab(state, pods, types, ev_kind, ev_pod, tp, key, rank)
    replay, run_chunk = _grouped(tab, policies, group)
    np.testing.assert_array_equal(
        np.asarray(whole.event_node), np.asarray(replay(
            state, pods, types, ev_kind, ev_pod, tp, key, rank).event_node))
    cuts = [0, 1, group - 1, group + 2, 2 * group + 2, depth]
    carry = tab.init_carry(state, pods, types, tp, key, rank)
    nodes, devs = [], []
    for lo, hi in zip(cuts, cuts[1:]):
        carry, (nd, dv) = run_chunk(
            carry, pods, types, ev_kind[lo:hi], ev_pod[lo:hi], tp, rank)
        assert type(carry).__name__ == "FlatTableCarry"
        _assert_tables_current(tab, carry, types, tp, key)
        nodes.append(nd)
        devs.append(dv)
    st, placed, masks, failed = tab.finish(carry)
    np.testing.assert_array_equal(
        np.asarray(placed), np.asarray(whole.placed_node))
    np.testing.assert_array_equal(np.asarray(masks), np.asarray(whole.dev_mask))
    np.testing.assert_array_equal(
        np.asarray(failed), np.asarray(whole.ever_failed))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(x) for x in nodes]),
        np.asarray(whole.event_node))
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(x) for x in devs]),
        np.asarray(whole.event_dev))
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(whole.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_vmapped_flat_sweep_equals_its_standalone_lanes():
    """Lanes with their own key and tie-break rank dirty their own nodes:
    each slot of the pending block holds one index a lane. Every lane of
    the vmapped engine equals its standalone replay in every leaf."""
    import functools

    from tpusim.sim.step import resolve_weights
    from tpusim.sim.table_engine import FLAT_GROUP_EVENTS as group

    depth, nodes, lanes = 2 * group + 3, 10, 3
    state, tp, pods, ev_kind, ev_pod, _ = _flat_case(depth, nodes, seed=47)
    policies = [(make_policy("FGDScore"), 1000)]
    tab = make_table_replay(policies, gpu_sel="random", block_size=-1)
    types = build_pod_types(pods)
    keys = jnp.stack([jax.random.PRNGKey(s) for s in range(lanes)])
    ranks = jnp.stack([
        jnp.asarray(np.random.default_rng(s).permutation(nodes), jnp.int32)
        for s in range(lanes)])
    wts = resolve_weights(policies, None)
    swept = jax.jit(jax.vmap(
        functools.partial(tab.engine.replay, group=group),
        in_axes=(None, None, None, None, None, None, 0, None, 0)))(
        state, pods, types, ev_kind, ev_pod, tp, keys, wts, ranks)
    differ = False
    for i in range(lanes):
        one = tab(state, pods, types, ev_kind, ev_pod, tp, keys[i], ranks[i])
        lane = jax.tree.map(lambda a: a[i], swept)
        _assert_equal(one, lane)
        np.testing.assert_array_equal(
            np.asarray(one.event_node), np.asarray(lane.event_node))
        np.testing.assert_array_equal(
            np.asarray(one.counters), np.asarray(lane.counters))
        differ |= not np.array_equal(
            np.asarray(swept.event_node[0]), np.asarray(lane.event_node))
    assert differ  # the lanes did not all take the same nodes


def test_the_flat_group_follows_from_the_sweeps_shapes():
    """Wide on a short node axis: groups; narrow, or on the blocked
    body's clusters, every event. No option selects it."""
    from tpusim.sim.table_engine import (
        BLOCKED_MIN_NODES, FLAT_GROUP_EVENTS, FLAT_GROUP_MIN_LANES,
        flat_group_events)

    assert flat_group_events(2560, 1213) == FLAT_GROUP_EVENTS > 1
    assert flat_group_events(FLAT_GROUP_MIN_LANES, 16) == FLAT_GROUP_EVENTS
    assert flat_group_events(FLAT_GROUP_MIN_LANES - 1, 1213) == 1
    assert flat_group_events(1, 1213) == 1
    assert flat_group_events(2560, BLOCKED_MIN_NODES) == 1
    assert flat_group_events(40, 100_000) == 1


# ---- the whole group's distinct GPU requests (ISSUE 37) ----

def _shuffled(pods, rng):
    order = rng.permutation(int(pods.cpu.shape[0]))
    return jax.tree.map(lambda a: a[order], pods)


@pytest.mark.parametrize("padded", [False, True], ids=["built", "padded"])
def test_every_whole_type_knows_its_request(padded):
    """build_pod_types / pad_pod_types give each whole type the row of its
    own (gpu_milli, gpu_num) among the group's distinct requests; G sits on
    its bucket whichever shuffle of the pod list was loaded, and the rows
    past the distinct requests are no type's."""
    from tpusim.sim.table_engine import REQUEST_BUCKET, pad_pod_types

    rng = np.random.default_rng(37)
    pods = random_pods(rng, num_pods=50)
    shapes = set()
    for trace in (pods, _shuffled(pods, rng), jax.tree.map(
            lambda a: a[:20], _shuffled(pods, rng))):
        t = build_pod_types(trace)
        if padded:
            t = pad_pod_types(t)
        kw = int(t.whole.cpu.shape[0])
        requests, request_of = np.asarray(t.requests), np.asarray(t.request_of)
        assert request_of.shape == (kw,) and request_of.dtype == np.int32
        np.testing.assert_array_equal(
            requests[request_of],
            np.stack([np.asarray(t.whole.gpu_milli),
                      np.asarray(t.whole.gpu_num)], 1))
        distinct = len({tuple(r) for r in requests[request_of]})
        # random_pods: CPU-only and 1, 2 or 4 whole GPUs
        assert 1 < distinct <= 4 < kw
        assert requests.shape == (REQUEST_BUCKET, 2)
        assert int(request_of.max()) == distinct - 1  # the rest: no type's
        assert (requests[distinct:] == 0).all()
        if padded:
            assert kw % 16 == 0
            dummy = np.asarray(t.whole.cpu) == 2**30
            assert dummy.any() and (requests[request_of[dummy]] == 0).all()
        shapes.add(requests.shape)
    assert len(shapes) == 1


def _stub_policy(state, pod, ctx):
    """A scoring kernel with no branches and no split: free CPU after the
    pod, in cores."""
    from tpusim.policies import PolicyResult

    return PolicyResult(
        jnp.maximum(state.cpu_left - pod.cpu, 0) // 1000,
        jnp.full(state.num_nodes, -1, jnp.int32))


_stub_policy.normalize = "none"
_stub_policy.policy_name = "StubScore"


class _Counted:
    """A policy's whole_split with its request step counted."""

    def __init__(self, split):
        self.calls, (self._request, self.finish) = 0, split

    def request(self, *args):
        self.calls += 1
        return self._request(*args)


@pytest.mark.parametrize("policies,gpu_sel", [
    ([("FGDScore", 1000)], "FGDScore"),
    ([("PWRScore", 500), ("FGDScore", 500)], "FGDScore"),
    ([("BestFitScore", 1000)], "best"),
    ([("FGDScore", 700), (_stub_policy, 300)], "FGDScore"),
], ids=["fgd", "pwr+fgd", "bestfit", "fgd+stub"])
@pytest.mark.parametrize("builder", ["init_tables", "columns"])
def test_the_split_builds_what_the_per_type_path_builds(
        builder, policies, gpu_sel, monkeypatch):
    """Tables and columns through the split (Sub's hypothetical once a
    distinct request) equal the per-type fallback bit for bit: scores,
    sharedev, feas; FGD alone and beside PWR, which has no split and goes
    type by type in the same group; a policy family without one builds as
    it did."""
    from tpusim.policies.fgd import fgd_score
    from tpusim.sim.table_engine import (
        make_table_builders, pad_pod_types, selector_index, sub_requests)

    rng = np.random.default_rng(3737)
    state, tp = random_cluster(rng, num_nodes=24)
    # a used cluster: some devices taken or partly taken, some CPU gone
    left = np.asarray(state.gpu_left) * rng.choice(
        [0, 0.25, 0.5, 1, 1], state.gpu_left.shape)
    state = state._replace(
        gpu_left=jnp.asarray(left.astype(np.int32)),
        cpu_left=jnp.asarray(rng.integers(0, 32000, 24).astype(np.int32)))
    types = pad_pod_types(build_pod_types(random_pods(rng, num_pods=60)))
    bare = types._replace(requests=None, request_of=None)
    pol = [(n if callable(n) else make_policy(n), w) for n, w in policies]
    has_fgd = any(fn is fgd_score for fn, _ in pol)
    counted = _Counted(fgd_score.branches["whole_split"])
    monkeypatch.setitem(fgd_score.branches, "whole_split",
                        (counted.request, counted.finish))
    columns, init_tables = make_table_builders(
        pol, selector_index(pol, gpu_sel))
    key = jax.random.PRNGKey(0)
    if builder == "init_tables":
        build = jax.jit(init_tables)
        got, want = build(state, types, tp, key), build(state, bare, tp, key)
    else:
        from tpusim.sim.table_engine import _row_state

        def build(types):
            return jax.jit(jax.vmap(lambda i: columns(
                _row_state(state, i), types, tp, key)))(jnp.arange(24))
        got, want = build(types), build(bare)
    assert counted.calls == (1 if has_fgd else 0)  # and none for `bare`
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    scores = np.asarray(got[0])
    assert len(np.unique(scores)) > 2 and np.asarray(got[2]).any()
    kw = int(types.whole.cpu.shape[0])
    assert kw == 32 and types.requests.shape == (8, 2)
    assert sub_requests(pol, types) == (8 if policies == [
        ("FGDScore", 1000)] else kw)
    assert sub_requests(pol, bare) == kw
