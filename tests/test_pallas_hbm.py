"""HBM-residency fused Pallas engine (ENGINES.md Round 19): the
[K, N] score/sdev/feas tables live in HBM (`pl.ANY`) with
per-event double-buffered DMA, selectHost runs over VMEM-resident block
summaries — and placements/devices/failure flags/final state must stay
bit-identical to the (blocked) table engine.

The CPU lane runs the kernel in Pallas interpreter mode (the Mosaic +
real-DMA path needs TPU hardware; real-chip numbers are advisory).
Interpreter steps are slow, so the tier-1 slice uses small multi-chunk
traces plus the double-buffer boundary cases and the two-tier footprint
math; the above-the-old-ceiling N ∈ {5000, 8192} acceptance runs are
slow-marked into `make resume-smoke` (the ROADMAP tier-1 budget rule).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.fixtures import random_cluster, random_pods
from tests.test_table_engine import _assert_equal, _events_with_deletes
from tpusim.policies import make_policy
from tpusim.sim.engine import EV_CREATE
from tpusim.sim import pallas_engine
from tpusim.sim.pallas_engine import make_pallas_replay
from tpusim.sim.table_engine import build_pod_types, make_table_replay
from tpusim.types import PodSpec

# module-level policy lists: the replay cache keys on the policy fn
# OBJECTS, so sharing them across tests shares one traced replayer per
# shape instead of re-tracing per test
_FGD = [(make_policy("FGDScore"), 1000)]
_BESTFIT = [(make_policy("BestFitScore"), 1000)]
_MIX = [(make_policy("PWRScore"), 500), (make_policy("FGDScore"), 500)]


def _run_both(policies, gpu_sel, state, tp, pods, ev_kind, ev_pod, rank,
              block_size=128, interpret=True):
    """(blocked table engine, hbm pallas) results + the DMA stats row.
    interpret=False is the on-chip lane's (tests/test_tpu.py)."""
    key = jax.random.PRNGKey(3)
    types = build_pod_types(pods)
    tab = make_table_replay(policies, gpu_sel=gpu_sel,
                            block_size=block_size)
    r0 = tab(state, pods, types, ev_kind, ev_pod, tp, key, rank)
    hbm = make_pallas_replay(policies, gpu_sel=gpu_sel, interpret=interpret,
                             residency="hbm")
    r1, dma = hbm(state, pods, types, ev_kind, ev_pod, tp, key, rank)
    return r0, r1, np.asarray(dma)


def _check(r0, r1, dma):
    _assert_equal(r0, r1)
    assert np.array_equal(np.asarray(r0.event_node),
                          np.asarray(r1.event_node))
    assert np.array_equal(np.asarray(r0.event_dev),
                          np.asarray(r1.event_dev))
    # every started DMA was waited — the kernel leaks no transfers
    assert dma[0] == dma[1] and dma[1] > 0


def _pods_k_types(k, rng):
    """Exactly k DISTINCT pod types (cpu strictly increasing per type)
    spanning cpu-only / share / whole kinds — the K = 151 acceptance
    shape without relying on random dedup."""
    kind = rng.integers(0, 3, k)
    cpu = (1000 + 100 * np.arange(k)).astype(np.int32)
    mem = rng.choice([1024, 4096, 16384], k).astype(np.int32)
    gpu_milli = np.where(
        kind == 1, rng.choice([100, 250, 500, 750], k), 1000
    ).astype(np.int32)
    gpu_milli = np.where(kind == 0, 0, gpu_milli)
    gpu_num = np.where(
        kind == 2, rng.choice([1, 2, 4], k), np.where(kind == 1, 1, 0)
    ).astype(np.int32)
    return PodSpec(
        cpu=jnp.asarray(cpu),
        mem=jnp.asarray(mem),
        gpu_milli=jnp.asarray(gpu_milli),
        gpu_num=jnp.asarray(gpu_num),
        gpu_mask=jnp.zeros(k, jnp.int32),
        pinned=jnp.full(k, -1, jnp.int32),
    )


def test_hbm_matches_blocked_engine_multichunk():
    """N = 512 (4 lane-chunks): the full DMA choreography — dirty-column
    writeback, row-slice prefetch + patch, summary maintenance, drift
    rebuild — against the blocked table engine, bit-exact, for a
    normalize=none policy and a minmax one."""
    rng = np.random.default_rng(11)
    state, tp = random_cluster(rng, num_nodes=512)
    pods = random_pods(rng, num_pods=64)
    ev_kind, ev_pod = _events_with_deletes(64, rng)
    rank = jnp.asarray(rng.permutation(512).astype(np.int32))
    for policies, gpu_sel in ((_FGD, "FGDScore"), (_BESTFIT, "best")):
        r0, r1, dma = _run_both(
            policies, gpu_sel, state, tp, pods, ev_kind, ev_pod, rank
        )
        _check(r0, r1, dma)


@pytest.mark.slow  # tier-1 trim, ISSUE 16: rides resume-smoke
def test_hbm_same_block_twice_and_edges():
    """Double-buffer boundary cases: consecutive events touching the SAME
    128-node block (pinned pods force it — the row-slice prefetch left
    HBM before that column's refresh, so only the in-VMEM patch can keep
    it current), a delete immediately re-touching the block it freed,
    and the first/last-event edges (init builds + final writeback
    waits)."""
    rng = np.random.default_rng(17)
    state, tp = random_cluster(rng, num_nodes=200)  # 2 chunks
    pods = random_pods(rng, num_pods=12)
    # pin pods 0..3 to nodes in BOTH chunks: same-chunk twice (3, 7),
    # then a chunk hop (140), then back (9); the rest select freely.
    # The pinned pods are tiny cpu-only requests so every node hosts
    # them — the pins decide, not feasibility
    small = jnp.asarray([1000] * 4 + [0] * 8, jnp.int32)
    sel4 = jnp.arange(12) < 4
    pods = pods._replace(
        cpu=jnp.where(sel4, small, pods.cpu),
        mem=jnp.where(sel4, 512, pods.mem),
        gpu_milli=jnp.where(sel4, 0, pods.gpu_milli),
        gpu_num=jnp.where(sel4, 0, pods.gpu_num),
        gpu_mask=jnp.where(sel4, 0, pods.gpu_mask),
        pinned=pods.pinned.at[0].set(3).at[1].set(7).at[2].set(140)
        .at[3].set(9),
    )
    kinds = [0, 0, 0, 0, 1, 0, 0, 1, 0, 0, 0, 0]
    idxs = [0, 1, 2, 3, 1, 4, 5, 0, 6, 7, 8, 9]
    ev_kind = jnp.asarray(kinds, jnp.int32)
    ev_pod = jnp.asarray(idxs, jnp.int32)
    rank = jnp.asarray(rng.permutation(200).astype(np.int32))
    r0, r1, dma = _run_both(_FGD, "FGDScore", state, tp, pods, ev_kind,
                            ev_pod, rank)
    _check(r0, r1, dma)
    # binds actually landed on the pinned nodes (same-block-twice hit;
    # pods 0/1 are later deleted, so check the event telemetry)
    ev_nodes = np.asarray(r1.event_node)
    assert ev_nodes[0] == 3 and ev_nodes[1] == 7
    assert ev_nodes[2] == 140 and ev_nodes[3] == 9


def test_hbm_single_event():
    """E = 1: init + one event + final writeback wait, no prefetch ever
    started — the kernel must not deadlock on unsignaled semaphores."""
    rng = np.random.default_rng(3)
    state, tp = random_cluster(rng, num_nodes=130)
    pods = random_pods(rng, num_pods=1)
    rank = jnp.asarray(rng.permutation(130).astype(np.int32))
    ev_kind = jnp.zeros(1, jnp.int32)
    ev_pod = jnp.zeros(1, jnp.int32)
    r0, r1, dma = _run_both(_FGD, "FGDScore", state, tp, pods, ev_kind,
                            ev_pod, rank)
    _check(r0, r1, dma)


def test_two_tier_fits_vmem_boundary():
    """The residency select's boundary math: exact byte thresholds flip
    each tier, and the documented HBM ceiling at K = 151 clears 256k."""
    shape = (4096, 151, 1, 2048, 4096)
    v = pallas_engine.vmem_resident_bytes(*shape)
    h = pallas_engine.vmem_resident_bytes_hbm(*shape, num_norm=1)
    assert h < v  # the whole point: the HBM tier's working set shrinks

    import os
    budget = os.environ.get("TPUSIM_PALLAS_VMEM_BYTES")
    try:
        os.environ["TPUSIM_PALLAS_VMEM_BYTES"] = str(v)
        assert pallas_engine.fits_vmem(*shape)
        assert pallas_engine.select_residency(*shape) == "vmem"
        os.environ["TPUSIM_PALLAS_VMEM_BYTES"] = str(v - 1)
        assert not pallas_engine.fits_vmem(*shape)
        assert pallas_engine.select_residency(*shape, num_norm=1) == "hbm"
        os.environ["TPUSIM_PALLAS_VMEM_BYTES"] = str(h)
        assert pallas_engine.fits_hbm(*shape, num_norm=1)
        os.environ["TPUSIM_PALLAS_VMEM_BYTES"] = str(h - 1)
        assert not pallas_engine.fits_hbm(*shape, num_norm=1)
        assert pallas_engine.select_residency(*shape, num_norm=1) is None
        # ceiling under the threshold budget is a pure function of it
        assert pallas_engine.hbm_ceiling_nodes(
            151, 1, 1, 2048, 4096, budget=h
        ) >= 4096
    finally:
        if budget is None:
            os.environ.pop("TPUSIM_PALLAS_VMEM_BYTES", None)
        else:
            os.environ["TPUSIM_PALLAS_VMEM_BYTES"] = budget

    # the default-budget auto-select at the acceptance shapes: old
    # ceiling -> vmem; above it -> hbm; genuinely impossible -> None
    assert pallas_engine.select_residency(512, 151, 1, 2048, 4096) == "vmem"
    assert pallas_engine.select_residency(8192, 151, 1, 2048, 4096) == "hbm"
    assert pallas_engine.select_residency(10**6, 151, 1, 2048, 4096) is None
    # the ROADMAP/ISSUE headline: HBM ceiling >= 256k at K = 151
    assert pallas_engine.hbm_ceiling_nodes(151, 1, 1) >= 256 * 1024
    assert pallas_engine.hbm_ceiling_nodes(151, 2, 2) >= 128 * 1024


def test_vmem_budget_env_fails_loudly(monkeypatch):
    """TPUSIM_PALLAS_VMEM_BYTES with a non-integer value raises NAMING
    the variable (the shared tpusim.envutil helper) instead of silently
    reverting to the default — at every consumer of the budget."""
    monkeypatch.setenv("TPUSIM_PALLAS_VMEM_BYTES", "14MB")
    with pytest.raises(ValueError, match="TPUSIM_PALLAS_VMEM_BYTES"):
        pallas_engine.vmem_budget()
    with pytest.raises(ValueError, match="TPUSIM_PALLAS_VMEM_BYTES"):
        pallas_engine.fits_vmem(512, 10, 1, 64, 64)
    with pytest.raises(ValueError, match="TPUSIM_PALLAS_VMEM_BYTES"):
        pallas_engine.fits_hbm(512, 10, 1, 64, 64)
    monkeypatch.setenv("TPUSIM_PALLAS_VMEM_BYTES", "-5")
    with pytest.raises(ValueError, match="TPUSIM_PALLAS_VMEM_BYTES"):
        pallas_engine.vmem_budget()
    monkeypatch.setenv("TPUSIM_PALLAS_VMEM_BYTES", str(2**24))
    assert pallas_engine.vmem_budget() == 2**24
    # the lease knobs ride the same shared helper (one validation path)
    from tpusim.svc import leases

    monkeypatch.setenv("TPUSIM_LEASE_SKEW_S", "soon")
    with pytest.raises(ValueError, match="TPUSIM_LEASE_SKEW_S"):
        leases.lease_skew_s()


def test_driver_residency_knob():
    """SimulatorConfig.table_residency routes the fused-engine dispatch:
    a forced 'hbm' run (CPU -> interpreter) reproduces forced 'table'
    exactly through the full driver path, the obs record carries the
    residency + exact DMA counters, and bad knobs raise at
    construction."""
    from tests.test_batch import _mk_cluster, _mk_pods
    from tpusim.sim.driver import Simulator, SimulatorConfig
    from tpusim.sim.typical import TypicalPodsConfig

    rng = np.random.default_rng(23)
    nodes = _mk_cluster(rng)
    pods = _mk_pods(rng, n=24)

    def run(engine, residency):
        cfg = SimulatorConfig(
            policies=(("FGDScore", 1000),),
            gpu_sel_method="FGDScore",
            shuffle_pod=True,
            seed=42,
            report_per_event=False,
            engine=engine,
            table_residency=residency,
            typical_pods=TypicalPodsConfig(pod_popularity_threshold=95),
        )
        sim = Simulator(nodes, cfg)
        sim.set_workload_pods(pods)
        return sim, sim.run()

    s_t, r_t = run("table", "auto")
    s_h, r_h = run("pallas", "hbm")
    assert s_h._last_engine == "pallas (hbm)"
    assert not any("[Degrade]" in l for l in s_h.log.lines)
    assert np.array_equal(r_t.placed_node, r_h.placed_node)
    assert np.array_equal(r_t.dev_mask, r_h.dev_mask)
    det = s_h.run_telemetry().to_record()["deterministic"]
    assert det["pallas_residency"] == "hbm"
    assert det["counts"]["pallas_dma_waits"] > 0
    assert det["counts"]["pallas_dma_waits"] == \
        det["counts"]["pallas_dma_starts"]

    from tpusim.sim.driver import Simulator as S, SimulatorConfig as C

    with pytest.raises(ValueError, match="table_residency"):
        S(nodes, C(table_residency="sram"))


@pytest.mark.slow  # interpreter compile + N-sized DMAs: resume-smoke lane
@pytest.mark.parametrize(
    "n_nodes,policies,gpu_sel",
    [
        (5000, _BESTFIT, "best"),
        (8192, _FGD, "FGDScore"),
        (8192, _MIX, "FGDScore"),
    ],
    ids=("5000-bestfit", "8192-fgd", "8192-pwr+fgd"),
)
def test_hbm_above_old_ceiling(n_nodes, policies, gpu_sel):
    """The acceptance pin: N ∈ {5000, 8192} at K = 151 — ABOVE the
    N ≤ 4096 VMEM ceiling — replayed by the HBM-residency kernel in
    interpreter mode, bit-identical to the blocked table engine across
    policy/mix/gpu_sel, with the residency select routing 'hbm'."""
    rng = np.random.default_rng(31)
    state, tp = random_cluster(rng, num_nodes=n_nodes)
    pods = _pods_k_types(151, rng)
    types = build_pod_types(pods)
    k = int(types.share.cpu.shape[0]) + int(types.whole.cpu.shape[0])
    assert k == 151
    ev_kind, ev_pod = _events_with_deletes(151, rng)
    rank = jnp.asarray(rng.permutation(n_nodes).astype(np.int32))
    res = pallas_engine.select_residency(
        n_nodes, k, len(policies), 151, int(ev_kind.shape[0]),
        pallas_engine.num_normalized(policies),
    )
    # N=8192 at K=151 is past the VMEM tier — auto-select must route
    # hbm; N=5000 still fits VMEM at this tiny workload (the old 4096
    # "ceiling" was measured at openb's event/pod sizes), so the select
    # just must not degrade. The replay below forces the HBM kernel
    # either way — the bit-identity claim is residency-independent.
    assert res == "hbm" if n_nodes >= 8192 else res is not None
    r0, r1, dma = _run_both(policies, gpu_sel, state, tp, pods, ev_kind,
                            ev_pod, rank)
    _check(r0, r1, dma)


@pytest.mark.slow  # full driver path at N=8192: resume-smoke lane
def test_driver_8192_runs_hbm_without_degrading():
    """Driver-level acceptance: a forced pallas engine at N = 8192 /
    K = 151 no longer prints [Degrade] — the auto residency select
    lands on the HBM tier and the run reconciles the table engine
    bit-exactly."""
    from tpusim.io.trace import NodeRow
    from tpusim.sim.driver import Simulator, SimulatorConfig
    from tpusim.sim.typical import TypicalPodsConfig
    from tpusim.io.trace import PodRow

    rng = np.random.default_rng(7)
    gpus = rng.choice([0, 2, 4, 8], 8192)
    nodes = [
        NodeRow(
            f"n{i:05d}",
            int(rng.choice([32000, 64000, 96000])),
            int(rng.choice([131072, 262144])),
            int(g),
            ["2080", "T4", "V100M16"][i % 3] if g else "",
        )
        for i, g in enumerate(gpus)
    ]
    kinds = rng.integers(0, 3, 151)
    pods = [
        PodRow(
            f"p{i:04d}",
            1000 + 100 * i,
            int(rng.choice([1024, 4096])),
            (0 if kinds[i] == 0 else 1 if kinds[i] == 1
             else int(rng.choice([1, 2]))),
            (0 if kinds[i] == 0
             else int(rng.choice([250, 500])) if kinds[i] == 1
             else 1000),
        )
        for i in range(151)
    ]

    def run(engine):
        cfg = SimulatorConfig(
            policies=(("FGDScore", 1000),),
            gpu_sel_method="FGDScore",
            seed=42,
            report_per_event=False,
            engine=engine,
            typical_pods=TypicalPodsConfig(pod_popularity_threshold=95),
        )
        sim = Simulator(nodes, cfg)
        sim.set_workload_pods(pods)
        return sim, sim.run()

    s_h, r_h = run("pallas")
    assert s_h._last_engine == "pallas (hbm)"
    assert not any("[Degrade]" in l for l in s_h.log.lines)
    s_t, r_t = run("table")
    assert np.array_equal(r_t.placed_node, r_h.placed_node)
    assert np.array_equal(r_t.dev_mask, r_h.dev_mask)


@pytest.mark.slow  # tier-1 trim, ISSUE 16: rides resume-smoke
def test_hbm_two_normalized_policies():
    """nn = 2 (BestFit minmax + PWR pwr in one mix): two brmin/brmax
    summary slots, two stored-extrema lanes, independent drift
    channels — the widest normalizer shape the column registry can
    express, bit-identical to the blocked table engine."""
    rng = np.random.default_rng(53)
    state, tp = random_cluster(rng, num_nodes=160)
    pods = random_pods(rng, num_pods=48)
    ev_kind, ev_pod = _events_with_deletes(48, rng)
    rank = jnp.asarray(rng.permutation(160).astype(np.int32))
    policies = [(make_policy("BestFitScore"), 400),
                (make_policy("PWRScore"), 600)]
    assert pallas_engine.num_normalized(policies) == 2
    r0, r1, dma = _run_both(policies, "PWRScore", state, tp, pods,
                            ev_kind, ev_pod, rank)
    _check(r0, r1, dma)
    assert dma[2] > 0  # at least one extrema-drift rebuild fired
