"""The sweep's score tables stay on the device from one sweep of a
Simulator to the next (ISSUE 31, Simulator._sweep_tables): a second sweep of
an unchanged cluster, type set, typical pods and scoring kernels reads the
tables the first one built, says so in its record (`tables_reused`, the
`init_tables` span's cache="resident"), and returns the lanes a fresh
Simulator's first sweep returns. Anything the build reads that changed is a
miss, which builds as before and replaces the one entry."""

import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_sweep import _cfg, _mk_cluster, _mk_pods
from tests.test_sweep_trace import SPAN_NAMES
from tpusim.obs import sweep_log
from tpusim.sim.driver import Simulator, schedule_pods_sweep
from tpusim.sim.faults import FaultConfig

WEIGHTS = [[1000], [1000], [700]]
SEEDS = [11, 12, 13]
OTHER_SEEDS = [21, 22, 23]
BODIES = {"flat": -1, "blocked": 8}  # SimulatorConfig.block_size


def _sim(block_size=-1):
    """A tiny Simulator and its trace, the same in every call."""
    rng = np.random.default_rng(5)
    sim = Simulator(_mk_cluster(rng), _cfg(
        42, engine="table", block_size=block_size))
    sim.set_workload_pods(_mk_pods(rng))
    sim.set_typical_pods()
    return sim, sim.prepare_pods()


def _other_pods(n=40):
    """A trace whose type set is not the default workload's."""
    pods = _mk_pods(np.random.default_rng(77), n)
    return [dataclasses.replace(p, cpu_milli=p.cpu_milli + 500) for p in pods]


def _sweep(sim, pods, seeds=SEEDS, **kw):
    """(lanes, the call's record, its init_tables span's `cache`)."""
    lanes = schedule_pods_sweep(sim, pods, WEIGHTS, seeds, **kw)
    rec = sweep_log()[-1]
    assert [s.name for s in rec.spans] == SPAN_NAMES
    (span,) = [s for s in rec.spans if s.name == "init_tables"]
    return lanes, rec, span.meta.get("cache")


def _assert_lanes_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.weights, b.weights)
        np.testing.assert_array_equal(a.placed_node, b.placed_node)
        np.testing.assert_array_equal(a.dev_mask, b.dev_mask)
        np.testing.assert_array_equal(a.ever_failed, b.ever_failed)
        np.testing.assert_array_equal(a.counters, b.counters)
        for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
            np.testing.assert_array_equal(x, y)
        assert (a.seed, a.events, a.placed, a.failed, a.unscheduled,
                a.gpu_alloc_pct, a.frag_gpu_milli) == (
            b.seed, b.events, b.placed, b.failed, b.unscheduled,
            b.gpu_alloc_pct, b.frag_gpu_milli)
        assert (a.disruption is None) == (b.disruption is None)
        if a.disruption is not None:
            assert a.disruption.as_dict() == b.disruption.as_dict()


@pytest.mark.parametrize("body", sorted(BODIES))
def test_a_second_sweep_reads_the_tables_the_first_built(body):
    sim, trace = _sim(BODIES[body])
    _, first, cache = _sweep(sim, trace)
    assert (first.tables_reused, cache) == (0, "sweep-shared")
    built = sim._resident_tables.tables

    lanes, second, cache = _sweep(sim, trace, OTHER_SEEDS)
    assert (second.tables_reused, cache) == (1, "resident")
    assert second.to_dict()["tables_reused"] == 1
    assert first.to_dict()["tables_reused"] == 0
    assert sim.obs.counts["table_resident_hit"] == 1
    # the very arrays, still alive: the wave neither donated nor copied them
    assert all(a is b for a, b in zip(sim._resident_tables.tables, built))
    assert not any(t.is_deleted() for t in built)

    fresh, fresh_trace = _sim(BODIES[body])
    want, rec, _ = _sweep(fresh, fresh_trace, OTHER_SEEDS)
    assert rec.tables_reused == 0
    _assert_lanes_equal(lanes, want)
    # the seeds matter, so equal lanes are not a constant
    assert any(not np.array_equal(a.placed_node, b.placed_node)
               for a, b in zip(lanes, _sweep(fresh, fresh_trace)[0]))


def _another_type_set(sim, trace):
    return _other_pods(), {}


def _another_initial_state(sim, trace):
    # half the nodes nearly out of CPU, as if pods were running there
    cpu = np.asarray(sim.init_state.cpu_left).copy()
    cpu[::2] = 2500
    sim.init_state = sim.init_state._replace(cpu_left=jnp.asarray(cpu))
    return trace, {}


def _equal_state_in_new_arrays(sim, trace):
    # equal content, other arrays: identity cannot prove it, so a miss
    sim.init_state = jax.tree.map(
        lambda a: jnp.asarray(np.asarray(a)), sim.init_state)
    return trace, {}


def _a_mutable_state_leaf(sim, trace):
    # a numpy leaf could change under the entry: never a proof
    sim.init_state = sim.init_state._replace(
        gpu_type=np.asarray(sim.init_state.gpu_type))
    return trace, {}


def _other_typical_pods(sim, trace):
    # another workload's distribution (same trace replayed): the content
    # differs. The same distribution in new arrays is a hit: run_sweep
    sim.set_workload_pods(_mk_pods(np.random.default_rng(9)))
    sim.set_typical_pods()
    return trace, {}


def _another_gpu_sel_method(sim, trace):
    # the fault family's replayer is built from the configuration at the
    # call, so this is a live change of what the build closes over
    sim.cfg.gpu_sel_method = "best"
    return trace, {"fault_specs": [None] * len(WEIGHTS)}


INVALIDATIONS = {
    "another type set": _another_type_set,
    "another initial state": _another_initial_state,
    "an equal state in new arrays": _equal_state_in_new_arrays,
    "a mutable state leaf": _a_mutable_state_leaf,
    "other typical pods": _other_typical_pods,
    "another gpu_sel_method": _another_gpu_sel_method,
}


@pytest.mark.parametrize("what", sorted(INVALIDATIONS))
def test_what_the_build_reads_changed_so_the_sweep_builds(what):
    change = INVALIDATIONS[what]
    sim, trace = _sim()
    first_kw = ({"fault_specs": [None] * len(WEIGHTS)}
                if what == "another gpu_sel_method" else {})
    _sweep(sim, trace, **first_kw)
    pods, kw = change(sim, trace)
    lanes, rec, cache = _sweep(sim, pods, OTHER_SEEDS, **kw)
    assert (rec.tables_reused, cache) == (0, "sweep-shared")
    assert "table_resident_hit" not in sim.obs.counts

    fresh, fresh_trace = _sim()
    pods, kw = change(fresh, fresh_trace)
    want, rec, _ = _sweep(fresh, pods, OTHER_SEEDS, **kw)
    assert rec.tables_reused == 0
    _assert_lanes_equal(lanes, want)

    # the miss replaced the entry: the same sweep again reads it, unless
    # nothing can prove it
    again, rec, cache = _sweep(sim, pods, OTHER_SEEDS, **kw)
    provable = what != "a mutable state leaf"
    assert (rec.tables_reused, cache) == (
        (1, "resident") if provable else (0, "sweep-shared"))
    assert (sim._resident_tables is not None) == provable
    _assert_lanes_equal(again, want)


@pytest.mark.parametrize("how", ["seeds", "tunes", "faults"])
def test_run_sweep_reuses_though_it_sets_the_typical_pods_anew(how):
    """The public entry (the tuner's rollouts, `tpusim apply`, the gate)
    recomputes the typical pods in every call: equal content in new
    arrays, which the proof reads as content."""
    faults = [FaultConfig(mtbf_events=9 + i, mttr_events=8, seed=5 + i)
              for i in range(len(WEIGHTS))]
    kw = {"seeds": {}, "tunes": {"tunes": [0.0, 1.2, 1.2]},
          "faults": {"faults": faults}}[how]

    def run(sim, seeds):
        lanes = sim.run_sweep(WEIGHTS, seeds=seeds, **kw)
        # run_sweep resets the Simulator's record: the process's log
        rec = sweep_log()[-1]
        (span,) = [s for s in rec.spans if s.name == "init_tables"]
        return lanes, rec.tables_reused, span.meta["cache"]

    sim, _ = _sim()
    typical = sim.typical
    assert run(sim, SEEDS)[1:] == (0, "sweep-shared")
    assert sim.typical is not typical
    for seeds in (OTHER_SEEDS, SEEDS):
        lanes, reused, cache = run(sim, seeds)
        assert (reused, cache) == (1, "resident")
    fresh, _ = _sim()
    want, reused, _ = run(fresh, SEEDS)
    assert reused == 0
    _assert_lanes_equal(lanes, want)


def test_a_trace_a_lane_reuses_while_its_type_set_repeats():
    sim, trace = _sim()
    own = [trace, trace[:30], _other_pods(36)]
    _, rec, cache = _sweep(sim, None, lane_pods=own)
    assert (rec.tables_reused, cache) == (0, "sweep-shared")
    # other lanes, the same union of types: the set is what is compared
    lanes, rec, cache = _sweep(
        sim, None, OTHER_SEEDS, lane_pods=[own[2], own[0], own[0]])
    assert (rec.tables_reused, cache) == (1, "resident")
    fresh, _ = _sim()
    want, _, _ = _sweep(
        fresh, None, OTHER_SEEDS, lane_pods=[own[2], own[0], own[0]])
    _assert_lanes_equal(lanes, want)
    # a batch whose union differs builds
    _, rec, cache = _sweep(sim, None, lane_pods=[trace] * 3)
    assert (rec.tables_reused, cache) == (0, "sweep-shared")


def test_fault_plans_reuse_and_share_the_entry_with_the_plain_family():
    faults = [FaultConfig(
        mtbf_events=9 + i, mttr_events=8, evict_every_events=7, seed=5 + i,
        backoff_base=2, backoff_cap=8, max_retries=2, queue_capacity=8)
        for i in range(len(WEIGHTS))]
    sim, trace = _sim()
    _, rec, cache = _sweep(sim, trace, fault_specs=faults)
    assert (rec.tables_reused, cache) == (0, "sweep-shared")
    lanes, rec, cache = _sweep(sim, trace, OTHER_SEEDS, fault_specs=faults)
    assert (rec.tables_reused, cache) == (1, "resident")
    fresh, _ = _sim()
    want, _, _ = _sweep(fresh, trace, OTHER_SEEDS, fault_specs=faults)
    _assert_lanes_equal(lanes, want)
    assert any(lane.disruption.node_failures for lane in lanes)
    # another replayer, the same builder's closure and operands (copies of
    # the trace a lane pad K like a fault sweep does): one entry serves both
    lanes, rec, cache = _sweep(sim, None, lane_pods=[trace] * 3)
    assert (rec.tables_reused, cache) == (1, "resident")
    want, _, _ = _sweep(fresh, None, lane_pods=[trace] * 3)
    _assert_lanes_equal(lanes, want)


def test_resident_before_disk_and_a_disk_hit_fills_the_entry(
        tmp_path, monkeypatch):
    plain, trace = _sim()
    want, _, _ = _sweep(plain, trace)
    want_other, _, _ = _sweep(plain, trace, OTHER_SEEDS)

    monkeypatch.setenv("TPUSIM_TABLE_CACHE_DIR", str(tmp_path / "tables"))
    sim, trace = _sim()
    lanes, rec, cache = _sweep(sim, trace)
    assert (rec.tables_reused, cache, sim.obs.table_cache) == (0, "miss", "miss")
    _assert_lanes_equal(lanes, want)
    lanes, rec, cache = _sweep(sim, trace, OTHER_SEEDS)
    assert (rec.tables_reused, cache) == (1, "resident")
    _assert_lanes_equal(lanes, want_other)
    # the disk was asked once: the second sweep never reached it
    assert sim.obs.counts == {"table_cache_miss": 1, "table_resident_hit": 1}
    assert len(list((tmp_path / "tables").iterdir())) == 1

    again, trace = _sim()
    lanes, rec, cache = _sweep(again, trace)
    assert (rec.tables_reused, cache, again.obs.table_cache) == (0, "hit", "hit")
    _assert_lanes_equal(lanes, want)
    lanes, rec, cache = _sweep(again, trace, OTHER_SEEDS)
    assert (rec.tables_reused, cache) == (1, "resident")
    assert again.obs.counts == {"table_cache_hit": 1, "table_resident_hit": 1}
    _assert_lanes_equal(lanes, want_other)


def test_the_entry_holds_one_build_after_three_type_sets():
    sim, trace = _sim()
    seen = []
    for pods in (trace, _other_pods(), trace[:12]):
        _, rec, _ = _sweep(sim, pods)
        assert rec.tables_reused == 0
        held = sim._resident_tables
        seen.append([weakref.ref(t) for t in held.tables])
        # six fields a group of types: the first of each has its rows
        k = sum(int(rows.shape[0]) for rows in held.rows[:12:6])
        assert held.tables[0].shape == (1, k, len(sim.nodes))
        del held
    gc.collect()
    # the two replaced sets are gone, the last is the Simulator's
    assert [[r() is None for r in refs] for refs in seen] == [
        [True] * 3, [True] * 3, [False] * 3]
    assert len({s[0]().shape for s in seen[2:]}) == 1
    del sim
    gc.collect()
    assert all(r() is None for r in seen[2])


def test_a_worker_pins_the_tables_of_the_family_it_serves_and_no_other(
        tmp_path):
    """svc.worker keeps a Simulator a family for its whole life; only the
    one whose batch is being served keeps its tables on the device."""
    from tests.test_svc import FAM, _drain, _post, _service
    from tpusim.svc import jobs as svc_jobs
    from tpusim.svc.worker import TraceRef

    rng = np.random.default_rng(3)
    nodes, pods = _mk_cluster(rng), _mk_pods(rng)
    queue, worker, service = _service(
        TraceRef("default", nodes, pods, svc_jobs.trace_digest(nodes, pods)),
        tmp_path)

    def serve(gpu_sel, seed):
        doc = {"policies": FAM, "weights": [1000, 500], "seed": seed,
               "engine": "table", "gpu_sel": gpu_sel}
        assert _post(service, doc)[0] == 202
        assert _drain(queue, worker) == 1
        return sweep_log()[-1].tables_reused

    def pinned():
        return [sim._resident_tables is not None
                for sim in worker._sims.values()]

    assert serve("best", 1) == 0 and pinned() == [True]
    assert serve("best", 2) == 1 and pinned() == [True]
    assert serve("FGDScore", 3) == 0 and pinned() == [False, True]
    assert serve("FGDScore", 4) == 1 and pinned() == [False, True]
    assert serve("best", 5) == 0 and pinned() == [True, False]
    assert len(worker._sims) == 2
