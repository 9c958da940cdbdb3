"""The one sweep path over its two independent inputs (ISSUE 30): a lane
replays the shared trace or its own, under a fault plan or none. All four
combinations run through one wrapper factory (driver._sweep_engine, which
reads the vmap axes off the operands), one host prep, one dispatch and one
tail, and leave one SweepRecord; the fault-free sweeps, of one shared trace
or of one a lane, run the flat step in groups (ISSUE 33), fault plans keep
the plain body."""

import jax
import numpy as np
import pytest

from tests.sweep_program import capture_sweep
from tests.test_sweep import _cfg, _mk_cluster, _mk_pods
from tests.test_sweep_trace import SPAN_NAMES
from tpusim.obs import sweep_log
from tpusim.sim import driver
from tpusim.sim.driver import Simulator, _sweep_engine, schedule_pods_sweep
from tpusim.sim.faults import FaultConfig
from tpusim.sim.table_engine import FLAT_GROUP_EVENTS, FLAT_GROUP_MIN_LANES

LANES = FLAT_GROUP_MIN_LANES  # the narrowest sweep that may run grouped
COMBOS = ["shared", "per-lane", "shared+faults", "per-lane+faults"]


def _faults(b):
    return [FaultConfig(
        mtbf_events=9 + i % 5, mttr_events=8, evict_every_events=7,
        seed=5 + i % 7, backoff_base=2, backoff_cap=8, max_retries=2,
        queue_capacity=8) for i in range(b)]


def _sim(**cfg):
    rng = np.random.default_rng(5)
    sim = Simulator(_mk_cluster(rng), _cfg(42, engine="table", **cfg))
    sim.set_workload_pods(_mk_pods(rng))
    sim.set_typical_pods()
    return sim, sim.prepare_pods()


def _operands(combo, trace, lanes):
    """A combination as the data schedule_pods_sweep is given: the
    per-lane traces are `lanes` copies of the shared one."""
    kw = {}
    if combo.startswith("per-lane"):
        kw["lane_pods"] = [trace] * lanes
    if combo.endswith("faults"):
        kw["fault_specs"] = _faults(lanes)
    return (None if "lane_pods" in kw else trace), kw


@pytest.fixture(scope="module")
def swept():
    """{combo: (sim, lanes, the log's records of the call)}: each of the
    four combinations once, LANES wide, on one short cluster (flat step),
    every lane under its own weights and seed."""
    weights = [[1000 - i] for i in range(LANES)]
    seeds = list(range(LANES))
    out = {}
    for combo in COMBOS:
        sim, trace = _sim()
        pods, kw = _operands(combo, trace, LANES)
        before = len(sweep_log())
        lanes = schedule_pods_sweep(sim, pods, weights, seeds, **kw)
        out[combo] = sim, lanes, sweep_log()[before:]
    return out


@pytest.mark.parametrize("combo", COMBOS)
def test_every_sweep_leaves_one_record_of_eight_spans(swept, combo):
    sim, lanes, records = swept[combo]
    (rec,) = records
    assert sim.obs.sweeps == [rec]
    assert [s.name for s in rec.spans] == SPAN_NAMES
    assert {s.sweep for s in rec.spans} == {rec.id}
    assert (rec.lanes, rec.events) == (LANES, 40) and len(lanes) == LANES
    assert rec.engine == sim._last_engine and rec.engine.startswith("table")
    assert rec.lane_writes > 0 and rec.dense_accesses > 0
    # the wrapper the call dispatched, for the executables census
    assert sim._last_sweep_fn._cache_size() >= 1
    # true events, less padding, merged fault steps and retries
    assert sim.obs.scan_events == LANES * 40
    assert all((lane.disruption is not None) == combo.endswith("faults")
               for lane in lanes)


@pytest.mark.parametrize("combo", COMBOS)
def test_the_fault_free_sweeps_run_grouped_and_fault_plans_plain(
        swept, combo):
    """The flat group is where the chip judged it: no fault operands, one
    shared trace (PR 29) or a trace a lane (PR 33: type ids one a lane).
    Fault plans keep the plain body, one dense column write an event."""
    rec = swept[combo][0].obs.sweeps[-1]
    if combo.endswith("faults"):
        assert rec.table_pass_events == 1
    else:
        assert rec.table_pass_events == FLAT_GROUP_EVENTS


@pytest.mark.parametrize("faults", ["", "+faults"])
def test_copies_of_one_trace_a_lane_equal_the_shared_trace(swept, faults):
    _, shared, _ = swept["shared" + faults]
    _, own, _ = swept["per-lane" + faults]
    for a, b in zip(shared, own):
        np.testing.assert_array_equal(a.placed_node, b.placed_node)
        np.testing.assert_array_equal(a.dev_mask, b.dev_mask)
        np.testing.assert_array_equal(a.ever_failed, b.ever_failed)
        np.testing.assert_array_equal(a.counters, b.counters)
        for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
            np.testing.assert_array_equal(x, y)
        assert (a.events, a.placed, a.failed, a.unscheduled) == (
            b.events, b.placed, b.failed, b.unscheduled)
        if faults:
            assert a.disruption.as_dict() == b.disruption.as_dict()
    # the lanes differ among themselves: weights, seeds, fault schedules
    assert any(not np.array_equal(shared[0].placed_node, lane.placed_node)
               for lane in shared[1:])


@pytest.mark.parametrize("combo", COMBOS)
@pytest.mark.parametrize("report", [False, True])
def test_the_wrapper_is_read_off_the_operands(combo, report):
    """One factory, one cache: the vmap axes follow what was stacked a
    lane, the tie-break rank is always donated and a per-lane event stream
    unless the report post-pass re-reads it (a fault sweep has none)."""
    from tpusim.sim.fault_lane import FaultOps
    from tpusim.sim.table_engine import PodTypes

    sim, trace = _sim(report_per_event=report)
    pods, kw = _operands(combo, trace, 3)
    fn, shapes, _ = capture_sweep(
        sim, pods, [[1000]] * 3, [1, 2, 3], **kw)
    own, faulted = combo.startswith("per-lane"), combo.endswith("faults")
    keep = report and not faulted
    assert sim._last_sweep_fn is fn
    ((engine, in_axes, donate),) = [
        key for key, wrapper in driver._SWEEP_WRAP_CACHE.items()
        if wrapper is fn]
    assert fn is _sweep_engine(engine, shapes, keep_streams=keep)
    trace_ax = 0 if own else None
    ev_ax = 0 if own or faulted else None
    assert in_axes == (
        None, trace_ax, PodTypes(None, None, trace_ax), ev_ax, ev_ax, None,
        0, 0, 0, None,
    ) + ((FaultOps(0, 0, 0, 0, 0, None), None) if faulted else ())
    assert donate == (8,) + ((4,) if ev_ax == 0 and not keep else ())
    # the operands as the engine takes them: the pods, type ids and
    # streams of a lane's own trace lead with the lane axis
    assert shapes[1].cpu.shape == ((3, 64) if own else (64,))
    assert shapes[2].type_id.shape == shapes[1].cpu.shape
    assert len(shapes[3].shape) == (2 if ev_ax == 0 else 1)
    assert len(shapes) == (12 if faulted else 10)


def test_a_sweep_takes_one_shared_trace_or_one_a_lane():
    sim, trace = _sim()
    grid = [[1000], [900]]
    with pytest.raises(ValueError, match="ONE shared trace"):
        schedule_pods_sweep(sim, trace, grid, lane_pods=[trace, trace])
    with pytest.raises(ValueError, match="ONE shared trace"):
        schedule_pods_sweep(sim, None, grid)
    with pytest.raises(ValueError, match="lane_pods has 1 traces for 2"):
        schedule_pods_sweep(sim, None, grid, lane_pods=[trace])
    with pytest.raises(ValueError, match="fault_specs has 1 entries for 2"):
        schedule_pods_sweep(sim, trace, grid, fault_specs=[None])
    assert sim.obs.sweeps == [] and sim._last_sweep_fn is None
