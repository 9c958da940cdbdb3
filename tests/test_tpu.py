"""On-TPU test lane: `TPUSIM_TPU_TESTS=1 pytest -m tpu` (`make test-tpu`).

Asserts that the accelerator backend reproduces the CPU/Go-oracle
numerics: the golden frag values from the reference's frag_test.go, and
sequential-engine vs incremental-table-engine placement equality — the
same invariants the CPU suite pins, re-checked on real TPU hardware —
plus the two fused-kernel pins only Mosaic can give: the VMEM tier on the
full openb trace and the HBM tier at N = 8,192.

With no chip the lane FAILS: every test takes the `accel` fixture, which
errors when JAX did not come up on a TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def accel():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        pytest.fail(
            f"the tpu lane needs a TPU; JAX came up on {dev.platform!r} "
            f"({dev.device_kind})", pytrace=False,
        )
    return dev


def test_backend_is_accelerator(accel):
    assert accel.platform == "tpu"


def test_golden_frag_values_on_tpu(accel):
    """frag_test.go golden values, computed with TPU numerics (same shared
    cases the CPU suite pins — tests/fixtures.py FRAG_SCORE_GOLDENS)."""
    from tests.fixtures import FRAG_SCORE_GOLDENS, frag_golden_score

    for case in FRAG_SCORE_GOLDENS:
        actual, expected = frag_golden_score(case)
        assert actual == pytest.approx(expected, abs=0.05), case


def test_cluster_frag_report_tpu_matches_cpu(accel):
    """The vmapped cluster report must agree between TPU and host-CPU
    backends on a heterogeneous random cluster (f32 sums: exactness up to
    reduction order; assert tight tolerance)."""
    from tests.fixtures import random_cluster
    from tpusim.ops.frag import cluster_frag_report

    rng = np.random.default_rng(11)
    state, tp = random_cluster(rng, num_nodes=64)
    amounts_tpu = np.asarray(cluster_frag_report(state, tp)[0])

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        state_c = jax.device_put(state, cpu)
        tp_c = jax.device_put(tp, cpu)
        amounts_cpu = np.asarray(cluster_frag_report(state_c, tp_c)[0])
    np.testing.assert_allclose(amounts_tpu, amounts_cpu, rtol=1e-6, atol=0.5)


def test_engine_vs_table_engine_on_tpu(accel):
    """Placement-for-placement equality of the two engines, on device
    (the CPU suite pins this per policy; one FGD mix suffices on-chip)."""
    from tests.fixtures import random_cluster, random_pods
    from tpusim.policies import make_policy
    from tpusim.sim.engine import EV_CREATE, make_replay
    from tpusim.sim.table_engine import build_pod_types, make_table_replay

    rng = np.random.default_rng(5)
    state, tp = random_cluster(rng, num_nodes=32)
    pods = random_pods(rng, num_pods=48)
    ev_kind = jnp.full(48, EV_CREATE, jnp.int32)
    ev_pod = jnp.arange(48, dtype=jnp.int32)
    policies = [(make_policy("FGDScore"), 1000)]
    key = jax.random.PRNGKey(2)
    rank = jnp.asarray(rng.permutation(32).astype(np.int32))

    seq = make_replay(policies, "FGDScore", report=False)(
        state, pods, ev_kind, ev_pod, tp, key, rank
    )
    types = build_pod_types(pods)
    tab = make_table_replay(policies, "FGDScore", report=False)(
        state, pods, types, ev_kind, ev_pod, tp, key, rank
    )
    assert np.array_equal(np.asarray(seq.placed_node), np.asarray(tab.placed_node))
    assert np.array_equal(np.asarray(seq.dev_mask), np.asarray(tab.dev_mask))
    for a, b in zip(jax.tree.leaves(seq.state), jax.tree.leaves(tab.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "policy,gpu_sel",
    [("FGDScore", "FGDScore"), ("PWRScore", "PWRScore")],
    ids=["fgd", "pwr"],
)
def test_pallas_engine_full_openb_on_tpu(accel, policy, gpu_sel):
    """The fused whole-replay Pallas kernel must reproduce the table
    engine's placements/devices/state bit-for-bit on the FULL openb default
    trace at tune 1.3 — the headline-bench configuration. This is the
    pallas engine's exactness gate on real Mosaic numerics (the CPU suite
    only covers interpreter mode). FGD covers the frag f32 sums; PWR covers
    the energy-table lookups and its own normalize mode."""
    import os

    from tpusim.io.trace import build_events, load_node_csv, load_pod_csv, pods_to_specs
    from tpusim.sim.driver import Simulator, SimulatorConfig
    from tpusim.sim.pallas_engine import make_pallas_replay
    from tpusim.sim.table_engine import build_pod_types
    from tpusim.sim.typical import TypicalPodsConfig

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    nodes = load_node_csv(os.path.join(repo, "data/csv/openb_node_list_gpu_node.csv"))
    pods = load_pod_csv(os.path.join(repo, "data/csv/openb_pod_list_default.csv"))
    cfg = SimulatorConfig(
        policies=((policy, 1000),), gpu_sel_method=gpu_sel,
        tuning_ratio=1.3, tuning_seed=42, seed=42, shuffle_pod=True,
        report_per_event=False,
        typical_pods=TypicalPodsConfig(pod_popularity_threshold=95),
    )
    sim = Simulator(nodes, cfg)
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    trace = sim.prepare_pods()
    specs = pods_to_specs(trace)
    ev_kind, ev_pod = build_events(trace)
    ev_kind, ev_pod = jnp.asarray(ev_kind), jnp.asarray(ev_pod)
    key = jax.random.PRNGKey(42)
    types = build_pod_types(specs)

    tab = sim._table_fn(
        sim.init_state, specs, types, ev_kind, ev_pod, sim.typical, key, sim.rank
    )
    pal = make_pallas_replay(list(sim._policy_fns), gpu_sel=gpu_sel)(
        sim.init_state, specs, types, ev_kind, ev_pod, sim.typical, key, sim.rank
    )
    assert np.array_equal(np.asarray(tab.placed_node), np.asarray(pal.placed_node))
    assert np.array_equal(np.asarray(tab.dev_mask), np.asarray(pal.dev_mask))
    assert np.array_equal(np.asarray(tab.ever_failed), np.asarray(pal.ever_failed))
    assert np.array_equal(np.asarray(tab.event_node), np.asarray(pal.event_node))
    for a, b in zip(jax.tree.leaves(tab.state), jax.tree.leaves(pal.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mix", [False, True], ids=["fgd", "pwr+fgd"])
def test_pallas_hbm_tier_n8192_on_tpu(accel, mix):
    """The HBM-resident kernel compiled by Mosaic (real DMAs, real
    semaphores) against the blocked table engine, bit for bit, at the
    acceptance shape of tests/test_pallas_hbm.py: N = 8,192, K = 151
    distinct pod types, creates and deletes. The PWR+FGD mix adds the
    normalizer path (block extrema, drift rebuilds) FGD alone never
    enters. select_residency must route this shape to `hbm`."""
    from tests.fixtures import random_cluster
    from tests.test_pallas_hbm import _FGD, _MIX, _check, _pods_k_types, _run_both
    from tests.test_table_engine import _events_with_deletes
    from tpusim.sim import pallas_engine

    policies, gpu_sel = (_MIX, "FGDScore") if mix else (_FGD, "FGDScore")
    rng = np.random.default_rng(29)
    state, tp = random_cluster(rng, num_nodes=8192)
    pods = _pods_k_types(151, rng)
    ev_kind, ev_pod = _events_with_deletes(151, rng)
    rank = jnp.asarray(rng.permutation(8192).astype(np.int32))
    assert pallas_engine.select_residency(
        8192, 151, len(policies), 151, int(ev_kind.shape[0]),
        pallas_engine.num_normalized(policies),
    ) == "hbm"
    r0, r1, dma = _run_both(policies, gpu_sel, state, tp, pods, ev_kind,
                            ev_pod, rank, interpret=False)
    _check(r0, r1, dma)


def test_driver_small_run_on_tpu(accel):
    """A tiny end-to-end driver run on the accelerator: placements land,
    reports emit, no unscheduled pods."""
    from tpusim.io.trace import NodeRow, PodRow
    from tpusim.sim.driver import Simulator, SimulatorConfig

    nodes = [
        NodeRow("t-cpu", 32000, 262144, 0, ""),
        NodeRow("t-gpu", 96000, 786432, 8, "V100M16"),
    ]
    pods = [
        PodRow(f"p{i}", 4000, 8192, 1, 500, "", creation_time=i) for i in range(4)
    ] + [PodRow("pc", 2000, 4096, 0, 0, "", creation_time=9)]
    sim = Simulator(
        nodes,
        SimulatorConfig(policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore"),
    )
    sim.set_workload_pods(pods)
    res = sim.run()
    assert not res.unscheduled_pods
    assert (np.asarray(res.placed_node[:4]) == 1).all()
    assert "Cluster Analysis Results" in sim.log.dump()


def test_shardmap_engine_compiles_on_tpu(accel):
    """The explicit-collective shard_map engine must compile and run its
    collective path (psum/pmax lanes) on the real chip — the CPU suite only
    exercises it on the virtual host mesh (VERDICT r3 §6: 'the TPU test
    lane never compiles the collective path on real hardware'). One device
    suffices: the collectives still lower and execute, just degenerate."""
    from tests.fixtures import random_cluster, random_pods
    from tpusim.parallel.shard_engine import make_shardmap_table_replay
    from tpusim.parallel.sharding import make_mesh, pad_nodes, shard_state
    from tpusim.policies import make_policy
    from tpusim.sim.engine import EV_CREATE
    from tpusim.sim.table_engine import build_pod_types, make_table_replay

    rng = np.random.default_rng(17)
    state, tp = random_cluster(rng, num_nodes=24)
    pods = random_pods(rng, num_pods=40)
    ev_kind = jnp.full(40, EV_CREATE, jnp.int32)
    ev_pod = jnp.arange(40, dtype=jnp.int32)
    policies = [(make_policy("FGDScore"), 1000)]
    key = jax.random.PRNGKey(3)
    rank = jnp.asarray(rng.permutation(24).astype(np.int32))

    plain = make_table_replay(policies, "FGDScore", report=False)(
        state, pods, build_pod_types(pods), ev_kind, ev_pod, tp, key, rank
    )
    mesh = make_mesh(1)
    pstate, prank = pad_nodes(state, rank, 1)
    pstate = shard_state(pstate, mesh)
    sharded = make_shardmap_table_replay(policies, mesh, gpu_sel="FGDScore")(
        pstate, pods, build_pod_types(pods), ev_kind, ev_pod, tp, key, prank
    )
    assert np.array_equal(
        np.asarray(plain.placed_node), np.asarray(sharded.placed_node)
    )
    assert np.array_equal(
        np.asarray(plain.dev_mask), np.asarray(sharded.dev_mask)
    )
    for a, b in zip(jax.tree.leaves(plain.state), jax.tree.leaves(sharded.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_seed_batched_replay_on_tpu(accel):
    """Per-seed bit-identity of the vmapped batch on the real chip (the
    device where the sweep actually runs it)."""
    from tests.fixtures import random_cluster, random_pods
    from tpusim.io.trace import tiebreak_rank
    from tpusim.policies import make_policy
    from tpusim.sim.engine import EV_CREATE
    from tpusim.sim.table_engine import build_pod_types, make_table_replay

    rng = np.random.default_rng(23)
    state, tp = random_cluster(rng, num_nodes=24)
    pods = random_pods(rng, num_pods=40)
    ev_kind = jnp.full(40, EV_CREATE, jnp.int32)
    ev_pod = jnp.arange(40, dtype=jnp.int32)
    policies = [(make_policy("FGDScore"), 1000)]
    tab = make_table_replay(policies, "FGDScore", report=False)

    ranks = jnp.stack(
        [jnp.asarray(tiebreak_rank(24, s)) for s in range(4)]
    )
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(4))
    types = build_pod_types(pods)
    batched = jax.jit(
        jax.vmap(lambda k, r: tab(state, pods, types, ev_kind, ev_pod, tp, k, r))
    )(keys, ranks)
    for s in range(4):
        single = tab(state, pods, types, ev_kind, ev_pod, tp, keys[s], ranks[s])
        assert np.array_equal(
            np.asarray(single.placed_node), np.asarray(batched.placed_node[s])
        ), f"seed {s}"


def test_cell_sized_sweep_holds_no_whole_carry_copy_on_tpu(accel):
    """ISSUE 27: compile (do not run) the benchmark cell's sweep, 100,000
    nodes x 40 lanes x K=71, on the chip itself, and list every `copy` in
    its scan whose result is as large as lanes x nodes. The parent's
    program held twenty (five whole carried arrays, each in all four
    steps of the unrolled body)."""
    from tests import sweep_program

    nodes, lanes = 100_000, 40
    sim, trace, cfg = sweep_program.cell_simulator(nodes, 512)
    fn, shapes, _ = sweep_program.capture_sweep(
        sim, trace, sweep_program.cell_weights(cfg, lanes),
        list(range(lanes)))
    assert shapes[9][0].shape[1] == 71
    text = fn.lower(*shapes).compile().as_text()
    found = sweep_program.big_copies_in_scan(text, lanes * nodes)
    assert not found, "\n".join(f"{c}: {n} = copy -> {s}"
                                for c, n, s, _ in found)


def _openb_flat_sweep(lanes, events):
    """(sim, trace, seeds, lanes' results) of an openb FGD sweep on the
    flat step body, and a function that replays one lane on the
    sequential oracle."""
    from benchmark.lib import inputs
    from tests.test_sweep import _cfg
    from tpusim.io.trace import load_node_csv, load_pod_csv
    from tpusim.sim.driver import Simulator, schedule_pods_sweep

    nodes = load_node_csv(inputs.NODE_CSV)
    assert len(nodes) == 1213
    pods = load_pod_csv(inputs.POD_CSV)[:events]
    policies = (("FGDScore", 1000),)
    sim = Simulator(nodes, _cfg(42, policies, engine="table", block_size=-1))
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    trace = sim.prepare_pods()
    seeds = [1000 + i for i in range(lanes)]
    out = schedule_pods_sweep(
        sim, trace, np.full((lanes, 1), 1000, np.int32), seeds)
    assert "table" in sim._last_engine and len(out) == lanes

    def oracle(i):
        one = Simulator(nodes, _cfg(seeds[i], policies, engine="sequential"))
        one.set_workload_pods(pods)
        want = one.run()
        assert "sequential" in one._last_engine
        return want

    return sim, trace, seeds, out, oracle


# the first from PR 27; S0_LANES x 64 is the smallest width at which the
# parent's sweep came back with NodeState.aff_cnt off the oracle (my chip
# run, PR 28: PERF.md section 6); the last is the benchmark cell's shape
S0_LANES = 1144


@pytest.mark.parametrize("lanes,events", [
    (256, 256), (S0_LANES, 64), (2560, 512)])
def test_wide_flat_sweep_equals_the_oracle_in_every_leaf_on_tpu(
        accel, lanes, events):
    """1,213 openb nodes on the flat step body: placements, masks and
    EVERY NodeState leaf of lanes 0, 1, the middle and the last, aff_cnt
    among them (ROADMAP S0: the leaf that came back wrong from wide sweeps
    while its write was a scatter with one index row a lane), against a
    standalone replay on the sequential oracle."""
    _, _, _, out, oracle = _openb_flat_sweep(lanes, events)
    differing = []
    for i in (0, 1, lanes // 2 - 1, lanes - 1):
        want, lane = oracle(i), out[i]
        pairs = [("placed_node", lane.placed_node, want.placed_node),
                 ("dev_mask", lane.dev_mask, want.dev_mask)]
        pairs += [(f"state.{f}", getattr(lane.state, f),
                   getattr(want.state, f)) for f in lane.state._fields]
        for name, got, exp in pairs:
            bad = int((np.asarray(got) != np.asarray(exp)).sum())
            if bad:
                differing.append((i, name, bad))
    assert not differing, differing


def test_lanes_of_the_widest_sweep_equal_the_numpy_reference_on_tpu(accel):
    """Two lanes of a 2,560-lane openb sweep, all 512 events, against
    tpusim/ref/fgd_numpy.py (numpy only, none of the program's kernels).
    The tolerance and its reason are tests/test_reference_fgd.py's: every
    integer exact; a score may differ by 1 only within NEAR of an integer,
    and a lane is held up to the first event such an entry could decide.
    Under this trace's typical pods lane 2,000 meets 11 such entries and
    none can decide (held in full, final state too); lane 1,777 meets one
    that can, at event 228 (held for 228 placements)."""
    from tests.test_reference_fgd import (
        held_to_the_reference,
        reference_inputs,
    )
    from tpusim.ref import fgd_numpy

    sim, trace, seeds, out, _ = _openb_flat_sweep(2560, 512)
    for lane, undecided in ((2000, -1), (1777, 228)):
        ref = fgd_numpy.replay(*reference_inputs(sim, trace, seeds[lane]))
        print(f"lane {lane}: {ref['near_entries']} score entries within "
              f"{fgd_numpy.NEAR} of an integer; first event one could "
              f"decide: {ref['first_undecided']}")
        assert ref["first_undecided"] == undecided
        assert held_to_the_reference(out[lane], ref, f"lane {lane}") == (
            len(trace) if undecided < 0 else undecided)
