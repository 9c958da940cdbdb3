"""Fault injection (ISSUE 2): NodeFail/NodeRecover/Evict replay, the
retry/backoff requeue, terminal UnscheduledPod state, and the determinism
acceptance criteria — identical disruption metrics for identical seeds,
and NodeFail → retry → reschedule landing a pod on a DIFFERENT node."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from tpusim.io.trace import NodeRow, PodRow
from tpusim.sim.driver import Simulator, SimulatorConfig, validate_events
from tpusim.sim.engine import EV_EVICT, EV_NODE_FAIL, EV_NODE_RECOVER
from tpusim.sim.faults import (
    FaultConfig,
    FaultEvent,
    fail_node,
    generate_fault_schedule,
    is_down,
    recover_node,
    validate_fault_schedule,
)
from tpusim.sim.queues import RetryQueue

# metric-free by default: the per-event report path compiles its own
# post-pass per segment shape, and one test (the evict one) covering it
# under faults is enough for the tier-1 budget
CFG = dict(
    policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
    report_per_event=False,
)


def _sim(nodes, pods, **over):
    sim = Simulator(nodes, SimulatorConfig(**{**CFG, **over}))
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    return sim


def _two_nodes():
    return [
        NodeRow("host-a", 16000, 65536, 2, "V100M16"),
        NodeRow("host-b", 16000, 65536, 2, "V100M16"),
    ]


def _share_pods(n):
    return [PodRow(f"p{i}", 2000, 1024, 1, 500) for i in range(n)]


# ---- retry queue ----


def test_retry_queue_backoff_caps():
    rq = RetryQueue(base=8, cap=100, max_retries=5)
    assert [rq.backoff(k) for k in (1, 2, 3, 4, 5)] == [8, 16, 32, 64, 100]


def test_retry_queue_terminal_after_max_retries():
    rq = RetryQueue(base=2, cap=16, max_retries=2)
    assert rq.push(7, 0, 1) == 2
    assert rq.push(7, 2, 2) == 6
    assert rq.push(7, 6, 3) is None  # out of retries -> dead list
    assert rq.dead == [(7, 2)]


def test_retry_queue_fifo_among_same_position():
    rq = RetryQueue(base=4, cap=4, max_retries=3)
    for pod in (3, 1, 2):
        rq.push(pod, 0, 1)
    assert rq.next_ready() == 4
    assert [p for p, _ in rq.pop_due(4)] == [3, 1, 2]  # insertion order
    assert len(rq) == 0 and rq.pop_due(100) == []


# ---- fault state transitions ----


def test_fail_and_recover_node_state():
    from tpusim.types import make_node_state

    state = make_node_state(
        cpu_cap=[8000, 8000], mem_cap=[4096, 4096], gpu_cnt=[2, 2],
        gpu_type=[0, 0],
    )
    down = fail_node(state, 0)
    assert bool(is_down(down)[0]) and not bool(is_down(down)[1])
    # down encoding must be filter-infeasible for ANY pod, even 0-request
    from tpusim.sim.step import filter_nodes
    from tpusim.types import make_pod

    feas = filter_nodes(down, make_pod(cpu=0, mem=0))
    assert not bool(feas[0]) and bool(feas[1])
    back = recover_node(down, 0)
    assert not bool(is_down(back)[0])
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))  # empty again


def test_generated_schedule_deterministic_and_valid():
    cfg = FaultConfig(mtbf_events=5, mttr_events=7, evict_every_events=11,
                      seed=9)
    a = generate_fault_schedule(6, 200, cfg)
    b = generate_fault_schedule(6, 200, cfg)
    assert a == b and len(a) > 0
    validate_fault_schedule(a, 6, 100)
    assert all(e.pos == sorted(x.pos for x in a)[i] for i, e in enumerate(a))


def test_validate_fault_schedule_rejects_bad_targets():
    with pytest.raises(ValueError, match="node 5 out of range"):
        validate_fault_schedule(
            [FaultEvent(0, EV_NODE_FAIL, node=5)], 2, 10
        )
    with pytest.raises(ValueError, match="kind"):
        validate_fault_schedule([FaultEvent(0, 99)], 2, 10)


# ---- run_events validation satellite ----


def test_run_events_rejects_fault_kinds_and_bad_indices():
    """Fault kinds and out-of-range pod indices must raise at run_events
    entry instead of becoming silent no-op scatters under jit."""
    nodes = _two_nodes()
    pods = _share_pods(3)
    sim = _sim(nodes, pods)
    from tpusim.io.trace import pods_to_specs

    specs = pods_to_specs(pods)
    key = jax.random.PRNGKey(0)
    with pytest.raises(ValueError, match="unknown kind"):
        sim.run_events(
            sim.init_state, specs, jnp.asarray([0, EV_NODE_FAIL], jnp.int32),
            jnp.asarray([0, 1], jnp.int32), key,
        )
    with pytest.raises(ValueError, match="out of range"):
        sim.run_events(
            sim.init_state, specs, jnp.zeros(2, jnp.int32),
            jnp.asarray([0, 3], jnp.int32), key,
        )
    with pytest.raises(ValueError, match="shape mismatch"):
        validate_events(np.zeros(2, np.int32), np.zeros(3, np.int32), 5)


# ---- end-to-end fault replay ----


@pytest.mark.slow  # tier-1 trim, ISSUE 16: rides resume-smoke
def test_nodefail_retry_reschedules_on_different_node():
    """The acceptance scenario: a pod placed on host-a loses its node,
    waits out its backoff in the retry queue while the trace continues,
    and re-lands MID-TRACE on host-b with a positive reschedule latency."""
    nodes = _two_nodes()
    # p0 is the GPU pod under test; p1..p3 are cpu-only filler that keeps
    # the trace running past the retry's ready position
    pods = [PodRow("p0", 2000, 1024, 1, 500)] + [
        PodRow(f"f{i}", 1000, 512, 0, 0) for i in range(3)
    ]
    sim = _sim(nodes, pods)
    first = int(sim.schedule_pods(pods).placed_node[0])

    sim2 = _sim(nodes, pods)
    res = sim2.schedule_pods_with_faults(
        pods,
        faults=[FaultEvent(pos=1, kind=EV_NODE_FAIL, node=first)],
        fault_cfg=FaultConfig(backoff_base=2, backoff_cap=8),
    )
    dm = sim2.last_disruption
    assert dm.node_failures == 1 and dm.evicted_pods == 1
    assert dm.rescheduled_pods == 1
    # the pod re-landed, on the OTHER host, 1 + backoff events later
    assert int(res.placed_node[0]) >= 0
    assert int(res.placed_node[0]) != first
    assert dm.reschedule_latency_events == [2]


def test_fault_replay_deterministic_under_seed():
    """Two runs of the same MTBF seed must agree on every placement and
    every disruption number (the pinned determinism criterion)."""
    nodes = _two_nodes()
    pods = _share_pods(6)
    fcfg = FaultConfig(mtbf_events=3, mttr_events=4, evict_every_events=5,
                       seed=5, backoff_base=2, backoff_cap=8, max_retries=2)
    sims = [_sim(nodes, pods) for _ in range(2)]
    results = [s.schedule_pods_with_faults(pods, fault_cfg=fcfg)
               for s in sims]
    assert np.array_equal(results[0].placed_node, results[1].placed_node)
    assert np.array_equal(results[0].dev_mask, results[1].dev_mask)
    a, b = (s.last_disruption for s in sims)
    assert a.as_dict() == b.as_dict()
    assert a.reschedule_latency_events == b.reschedule_latency_events
    # the [Disruption] block made it into the log + the direct-CSV stash
    assert any("[Disruption]" in l for l in sims[0].log.lines)
    assert any(k.startswith("disruption_")
               for k in sims[0].analysis_summary)


def test_max_retries_terminal_unscheduled():
    """A pod whose only feasible host never comes back burns its retries
    and lands in the terminal UnscheduledPod state with the dedicated
    reason."""
    nodes = [NodeRow("only", 16000, 65536, 2, "V100M16")]
    pods = _share_pods(1)
    sim = _sim(nodes, pods)
    res = sim.schedule_pods_with_faults(
        pods,
        faults=[FaultEvent(pos=1, kind=EV_NODE_FAIL, node=0)],
        fault_cfg=FaultConfig(max_retries=2, backoff_base=2, backoff_cap=4),
    )
    dm = sim.last_disruption
    assert dm.unscheduled_after_retries == 1
    assert dm.retries_enqueued == 2  # both retries ran, both failed
    assert res.placed_node[0] == -1
    reasons = [u.reason for u in res.unscheduled_pods]
    assert reasons == ["max-retries-exceeded"]
    # permanent loss clocks dark capacity to end of trace: the failure
    # fired AT the last base event (pos 1 of a 1-event trace), so 0 here
    assert dm.failed_node_gpu_events == 0


def test_evict_event_requeues_and_reports():
    """A single-pod Evict preemption returns resources, requeues the pod,
    and the pod re-lands after its backoff — with per-event reporting on,
    so the fault segments exercise the report/metrics path too."""
    nodes = _two_nodes()
    pods = _share_pods(2)
    sim = _sim(nodes, pods, report_per_event=True)
    res = sim.schedule_pods_with_faults(
        pods,
        faults=[FaultEvent(pos=2, kind=EV_EVICT, pod=0)],
        fault_cfg=FaultConfig(backoff_base=2, backoff_cap=4),
    )
    dm = sim.last_disruption
    assert dm.evicted_pods == 1 and dm.rescheduled_pods == 1
    assert (res.placed_node >= 0).all()
    assert any("[Fault] pod p0 evicted" in l for l in sim.log.lines)


def test_recovery_frag_delta_and_gpu_events():
    """Fail + recover accounts the dark capacity window and records a
    post-recovery frag delta sample."""
    nodes = _two_nodes()
    pods = _share_pods(4)
    sim = _sim(nodes, pods)
    sim.schedule_pods_with_faults(
        pods,
        faults=[
            FaultEvent(pos=1, kind=EV_NODE_FAIL, node=0),
            FaultEvent(pos=3, kind=EV_NODE_RECOVER, node=0),
        ],
    )
    dm = sim.last_disruption
    assert dm.node_failures == 1 and dm.node_recoveries == 1
    assert dm.failed_node_gpu_events == 2 * (3 - 1)  # 2 GPUs x 2 events
    assert len(dm.post_recovery_frag_delta) == 1


def test_faults_rejects_timestamp_traces():
    nodes = _two_nodes()
    pods = _share_pods(2)
    sim = Simulator(nodes, SimulatorConfig(use_timestamps=True, **CFG))
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    with pytest.raises(ValueError, match="creation-ordered"):
        sim.schedule_pods_with_faults(pods)


def test_pallas_vmem_degrades_to_table(monkeypatch):
    """Graceful degradation: a forced pallas engine whose resident set
    cannot fit the VMEM budget falls back to the table engine with a
    [Degrade] warning — same placements, no death."""
    monkeypatch.setenv("TPUSIM_PALLAS_VMEM_BYTES", "1024")  # nothing fits
    nodes = _two_nodes()
    pods = _share_pods(4)

    def run(engine):
        sim = Simulator(nodes, SimulatorConfig(
            policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
            report_per_event=False, engine=engine,
        ))
        sim.set_workload_pods(pods)
        sim.set_typical_pods()
        from tpusim.io.trace import pods_to_specs

        specs = pods_to_specs(pods)
        out = sim.run_events(
            sim.init_state, specs, jnp.zeros(4, jnp.int32),
            jnp.arange(4, dtype=jnp.int32), jax.random.PRNGKey(0),
        )
        return sim, out

    sim_p, out_p = run("pallas")
    assert any("[Degrade]" in l and "VMEM" in l for l in sim_p.log.lines)
    assert sim_p._last_engine == "table"
    monkeypatch.delenv("TPUSIM_PALLAS_VMEM_BYTES")
    sim_t, out_t = run("table")
    assert np.array_equal(
        np.asarray(out_p.placed_node), np.asarray(out_t.placed_node)
    )


def test_pallas_failure_raises_instead_of_rerunning():
    """Only the size-based routing above reroutes a fused-kernel replay. A
    kernel that fails (a Mosaic compile error, a death mid-scan) or
    returns out-of-range telemetry raises: no `except` turns it into a
    quiet run on the table engine with exit 0."""
    from tpusim.io.trace import pods_to_specs

    nodes = _two_nodes()
    pods = _share_pods(4)
    sim = Simulator(nodes, SimulatorConfig(
        policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
        report_per_event=False, engine="pallas",
    ))
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    args = (sim.init_state, pods_to_specs(pods), jnp.zeros(4, jnp.int32),
            jnp.arange(4, dtype=jnp.int32), jax.random.PRNGKey(0))
    good = sim._pallas_fn

    def dies(*a, **k):
        raise jax.errors.JaxRuntimeError("INTERNAL: Mosaic failed to compile")

    sim._pallas_fn = dies
    with pytest.raises(jax.errors.JaxRuntimeError, match="Mosaic"):
        sim.run_events(*args)

    def corrupt(*a, **k):
        out = good(*a, **k)
        return out._replace(event_node=out.event_node + 10_000)

    sim._pallas_fn = corrupt
    with pytest.raises(RuntimeError, match="corrupt telemetry"):
        sim.run_events(*args)
    assert not any(k.startswith("degrade_") for k in sim.obs.counts)
    assert not any("[Degrade]" in l for l in sim.log.lines)


@pytest.mark.slow  # compiles its own chunked segment lengths
def test_fault_replay_composes_with_checkpointing(tmp_path):
    """The create/delete/fault-mix half of the resume acceptance: fault
    segments run through the normal run_events dispatch, so a fault replay
    with checkpointing enabled must equal the unsegmented fault replay —
    placements AND disruption metrics."""
    nodes = _two_nodes()
    pods = _share_pods(6)
    fcfg = FaultConfig(mtbf_events=3, mttr_events=4, seed=5,
                       backoff_base=2, backoff_cap=8)
    sim_a = _sim(nodes, pods)
    ra = sim_a.schedule_pods_with_faults(pods, fault_cfg=fcfg)
    sim_b = _sim(nodes, pods, checkpoint_every=2,
                 checkpoint_dir=str(tmp_path))
    rb = sim_b.schedule_pods_with_faults(pods, fault_cfg=fcfg)
    assert np.array_equal(ra.placed_node, rb.placed_node)
    assert np.array_equal(ra.dev_mask, rb.dev_mask)
    assert sim_a.last_disruption.as_dict() == sim_b.last_disruption.as_dict()


@pytest.mark.slow  # tier-1 trim, ISSUE 16: rides resume-smoke
def test_retry_budget_resets_on_successful_reschedule():
    """max_retries bounds CONSECUTIVE failures: a pod evicted more than
    max_retries separate times, rescheduling successfully in between, must
    never be terminally killed by accumulation."""
    nodes = _two_nodes()
    pods = [PodRow("p0", 2000, 1024, 1, 500)] + [
        PodRow(f"f{i}", 1000, 512, 0, 0) for i in range(6)
    ]
    sim = _sim(nodes, pods)
    res = sim.schedule_pods_with_faults(
        pods,
        faults=[FaultEvent(pos=p, kind=EV_EVICT, pod=0) for p in (1, 3, 5)],
        fault_cfg=FaultConfig(max_retries=2, backoff_base=1, backoff_cap=1),
    )
    dm = sim.last_disruption
    assert dm.evicted_pods == 3 and dm.rescheduled_pods == 3
    assert dm.unscheduled_after_retries == 0
    assert int(res.placed_node[0]) >= 0
    assert res.unscheduled_pods == []
