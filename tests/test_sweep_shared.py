"""What a sweep's lanes share stays out of what its program gives back
(ISSUE 43): the five capacity leaves of a NodeState (types.CAPACITY_LEAVES:
cpu_cap, mem_cap, gpu_cnt, gpu_type, cpu_type) are written by no step of
any engine, so driver._sweep_engine hands them out of the vmapped replay as
they entered it, [N], and the final states carry the lane axis on the four
leaves a step can write. Held here on every body a sweep can run, a case a
body:

  1. BY VALUE, the ground the rule stands on: the same program with the
     lane axis left on every leaf (driver._share_capacity taken out) gives
     every lane the start state's five, bit for bit: nothing wrote them;
  2. the program as the sweep dispatches it gives those five with no lane
     axis, equal to the start state's, and every other output equal to the
     all-lanes form's;
  3. its jaxpr's and its compiled module's results hold no [B, N] copy of
     them: five arrays of the lanes' width fewer than the all-lanes form.

tests/test_sweep_compile.py holds the shapes in the cells' own programs,
compiled for the chip.
"""

import functools

import jax
import numpy as np
import pytest

from tests import sweep_program
from tests.test_sweep_paths import _faults
from tests.test_sweep_slice import _deletions, _families, _shared
from tpusim.sim import driver
from tpusim.sim.table_engine import FLAT_GROUP_MIN_LANES
from tpusim.types import CAPACITY_LEAVES, NodeState


def _many(lanes, own=False, faults=False, **cfg):
    """`lanes` lanes of one small cluster: one shared trace or the trace a
    lane (of two lengths), with a fault plan a lane or without."""
    sim, kw = _shared(**cfg)
    if own:
        trace = kw["pods"]
        kw = dict(pods=None, lane_pods=[
            trace if i % 2 else trace[:41] for i in range(lanes)])
    if faults:
        kw["fault_specs"] = _faults(lanes)
    return sim, kw, lanes


def _four(form):
    sim, kw = form()
    return sim, kw, 4


BLOCKED = {"block_size": 8, "nodes": 16}
# body of the step x what the lanes carry: (Simulator, sweep keywords, lanes)
SWEEPS = {
    "flat plain, one shared trace": functools.partial(_many, 8),
    "flat plain, a trace a lane": functools.partial(_many, 8, own=True),
    "flat grouped, one shared trace": functools.partial(
        _many, FLAT_GROUP_MIN_LANES),
    "flat grouped, a trace a lane": functools.partial(
        _many, FLAT_GROUP_MIN_LANES, own=True),
    "blocked, one shared trace": functools.partial(_many, 4, **BLOCKED),
    "blocked, a trace a lane": functools.partial(
        _many, 4, own=True, **BLOCKED),
    "sequential, one shared trace": functools.partial(
        _many, 4, engine="sequential"),
    "sequential, a trace a lane": functools.partial(
        _many, 4, own=True, engine="sequential"),
    "a fault plan a lane, flat": functools.partial(_many, 4, faults=True),
    "a fault plan and a trace a lane": functools.partial(
        _many, 4, own=True, faults=True),
    "a fault plan a lane, blocked": functools.partial(
        _many, 4, faults=True, **BLOCKED),
    "a fault plan a lane, sequential": functools.partial(
        _many, 4, faults=True, engine="sequential"),
    "the report on": functools.partial(_many, 4, report_per_event=True),
    "a stream with deletions": functools.partial(_four, _deletions),
    "typical pods a family": functools.partial(_four, _families),
}


class _Stop(Exception):
    pass


def _program(sim, kw, lanes):
    """The sweep's program and its operands, taken at the dispatch and the
    sweep stopped there: (engine, keep_streams, fn, operands on the host)."""
    seen = {}
    real_engine, real_dispatch = (
        driver._sweep_engine, driver._dispatch_counting_lane_sites)

    def engine_spy(engine, args, keep_streams=False):
        seen["engine"], seen["keep"] = engine, keep_streams
        return real_engine(engine, args, keep_streams)

    def dispatch_spy(fn, _lanes, *args):
        seen["fn"] = fn
        seen["args"] = jax.tree.map(np.asarray, args)
        raise _Stop()

    driver._sweep_engine = engine_spy
    driver._dispatch_counting_lane_sites = dispatch_spy
    try:
        with pytest.raises(_Stop):
            driver.schedule_pods_sweep(
                sim, kw["pods"], [[1000 - i] for i in range(lanes)],
                list(range(lanes)),
                **{k: v for k, v in kw.items() if k != "pods"})
    finally:
        driver._sweep_engine = real_engine
        driver._dispatch_counting_lane_sites = real_dispatch
    return seen["engine"], seen["keep"], seen["fn"], seen["args"]


def _wide(avals, lanes, nodes):
    """How many of `avals` are i32[lanes, nodes]: a leaf a lane."""
    return sum(tuple(a.shape) == (lanes, nodes) and a.dtype == np.int32
               for a in avals)


@pytest.mark.parametrize("what", SWEEPS)
def test_the_capacity_leaves_leave_the_program_without_a_lane_axis(
        what, monkeypatch):
    sim, kw, lanes = SWEEPS[what]()
    engine, keep, fn, args = _program(sim, kw, lanes)
    start = jax.tree.map(np.asarray, sim.init_state)
    n = start.num_nodes
    # no other axis of the sweep is the nodes': a [lanes, n] i32 result is a
    # node leaf a lane
    assert n not in (args[1].cpu.shape[-1], lanes)

    lowered = fn.lower(*args)
    compiled = lowered.compile()
    out = jax.tree.map(np.asarray, compiled(*args))

    # the same replay with the lane axis left on every leaf
    monkeypatch.setattr(driver, "_share_capacity", lambda out, state: out)
    monkeypatch.setattr(driver, "_SWEEP_WRAP_CACHE", {})
    fn_all = driver._sweep_engine(engine, args, keep_streams=keep)
    assert fn_all is not fn
    whole = jax.tree.map(np.asarray, fn_all(*args))
    monkeypatch.undo()

    # 1. nothing wrote them: every lane ends with the start state's five
    moved = False
    for f in NodeState._fields:
        a, b, first = (getattr(t, f) for t in (out.state, whole.state, start))
        assert b.shape == (lanes,) + first.shape and b.dtype == first.dtype
        if f in CAPACITY_LEAVES:
            for i in range(lanes):
                np.testing.assert_array_equal(b[i], first, f"{f}, lane {i}")
            # 2. and the sweep's program hands them out once, as they came
            assert a.shape == (n,) and a.dtype == first.dtype, f
            np.testing.assert_array_equal(a, first, f)
        else:
            assert a.shape == b.shape and a.dtype == b.dtype, f
            np.testing.assert_array_equal(a, b, f)
            moved |= bool((b != first).any())
    assert moved  # the lanes did run: what a step writes has changed
    rest_a, rest_b = (jax.tree.leaves(t._replace(state=None))
                      for t in (out, whole))
    assert len(rest_a) == len(rest_b) > 0
    for a, b in zip(rest_a, rest_b):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    assert out.placed_node.shape[0] == lanes and (out.placed_node >= 0).any()
    if "fault" in what:
        assert out.fault_ys is not None and out.fault_carry is not None

    # 3. neither the jaxpr's nor the compiled module's results hold a
    # [lanes, n] copy of them: cpu_left and mem_left are what is left of
    # the state (a fault plan's carry has one such leaf of its own, the
    # step at which each node went down)
    kept = 2 + _wide(rest_a, lanes, n)
    assert kept == (3 if "fault" in what else 2)
    all_lanes = _wide(jax.tree.leaves(jax.eval_shape(fn_all, *args)), lanes, n)
    got = jax.make_jaxpr(fn)(*args).out_avals
    assert _wide(got, lanes, n) == all_lanes - len(CAPACITY_LEAVES) == kept
    results = sweep_program.entry_results(compiled.as_text())
    assert len(results) == len(got)
    assert results.count(("s32", (lanes, n))) == kept
    assert results.count(("s32", (n,))) == len(CAPACITY_LEAVES)
    # what the fetch no longer moves: the five, for every lane but one
    assert (sum(a.nbytes for a in jax.tree.leaves(whole))
            - sum(a.nbytes for a in jax.tree.leaves(out))
            == (lanes - 1) * 20 * n)
