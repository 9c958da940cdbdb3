"""Streams with deletions through the one sweep (ISSUE 38): a trace replayed
by its own clock (`SimulatorConfig.use_timestamps`: every pod a creation
event and, where it has a deletion time, a deletion event, stable-sorted by
timestamp) against tpusim/ref/clock_numpy.py, the plain numpy reference of
a create/delete stream (float64 scores, integer state, nothing of the
program), on every flat body a sweep can run: plain (under 64 lanes),
grouped with one shared trace (deletes inside a group of 16 events), grouped
with a trace a lane. A deletion gives back exactly what its creation bound;
a deletion of a pod that was rejected changes nothing. The tolerance is
tests/test_reference_fgd.py's: integers exact, a lane held event for event
up to the first event a near-integer score could decide.
"""

import ast
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_reference_fgd import _cluster, reference_inputs
from tests.test_sweep import _cfg
from tpusim.io.trace import (
    NodeRow,
    PodRow,
    build_events,
    load_node_csv,
    load_pod_csv,
    pods_to_specs,
)
from tpusim.obs import sweep_log
from tpusim.obs.spans import SweepRecord
from tpusim.ref import clock_numpy
from tpusim.sim.driver import Simulator, schedule_pods_sweep
from tpusim.sim.engine import EV_CREATE, EV_DELETE
from tpusim.sim.table_engine import FLAT_GROUP_EVENTS, FLAT_GROUP_MIN_LANES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSV = os.path.join(REPO, "data", "csv")
WIDE = FLAT_GROUP_MIN_LANES  # the narrowest sweep that runs grouped


def _simulator(nodes, pods, seed=7):
    sim = Simulator(nodes, _cfg(seed, engine="table", use_timestamps=True))
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    return sim


def _openb_window(depth=128):
    """96 of openb's nodes and the first `depth` events of its time-sorted
    stream, as the clock cell's driver cuts it (which raises unless the
    window's stream is the whole stream's first events)."""
    from benchmark.drivers.clock_wave import stream_window

    nodes = load_node_csv(os.path.join(CSV, "openb_node_list_gpu_node.csv"))
    pods = load_pod_csv(os.path.join(CSV, "openb_pod_list_default.csv"))
    window, _ = stream_window(pods, depth, {128: 49, 64: 18}[depth])
    assert len(window) == {128: 79, 64: 46}[depth]
    return _simulator(nodes[:96], pods), window


def _lifetimes(seed, events=120):
    """A seeded cluster tight on CPU (late creates are rejected) and pods
    with random lifetimes: a third outlive the stream, some are deleted in
    the second they were created, and a rejected pod's deletion comes
    too."""
    rng = np.random.default_rng(seed)
    nodes = _cluster(rng)[:24]
    pods = []
    for i in range(events):
        gpu = int(rng.choice([0, 1, 1, 1, 2, 4]))
        milli = 1000 if gpu > 1 else int(rng.choice([250, 500, 700, 1000]))
        born = 10 * i
        life = int(rng.choice([0, 0, 5, 40, 90, 200, 400]))
        pods.append(PodRow(
            f"p{i:04d}", int(rng.choice([4000, 8000, 16000, 32000])),
            int(rng.choice([2048, 8192, 32768])), gpu, milli if gpu else 0,
            creation_time=born,
            deletion_time=0 if rng.random() < 0.33 else born + life))
    return _simulator(nodes, pods), pods


def _rejected_then_deleted():
    """Two one-GPU nodes and three whole-GPU pods alive at once: the third
    creation is rejected, its deletion finds nothing to give back, and the
    fourth pod arrives after a real release."""
    nodes = [NodeRow(f"n{i}", 16000, 65536, 1, "T4") for i in range(2)]
    times = [(0, 50), (1, 60), (2, 30), (55, 0)]
    pods = [PodRow(f"p{i}", 4000, 2048, 1, 1000, creation_time=c,
                   deletion_time=d) for i, (c, d) in enumerate(times)]
    return _simulator(nodes, pods), pods


STREAMS = {"openb window": _openb_window, "random lifetimes": lambda: _lifetimes(3),
           "a rejected pod's deletion": _rejected_then_deleted}
BODIES = ["plain", "grouped, one shared trace", "grouped, a trace a lane"]


def _events_as_the_reference_makes_them(trace):
    kind, pod = clock_numpy.event_stream(
        [p.creation_time for p in trace], [p.deletion_time for p in trace])
    mine = build_events(trace, True)
    np.testing.assert_array_equal(kind, mine[0])
    np.testing.assert_array_equal(pod, mine[1])
    return kind, pod


def _held(lane, ref, who) -> bool:
    """`lane` against the reference's replay of its stream: its record of
    every event up to the first a near-integer score could decide, and, if
    there is none, everything it returned; True where it was held whole."""
    stop = ref["first_undecided"]
    upto = len(ref["event_node"]) if stop < 0 else stop
    np.testing.assert_array_equal(
        lane.event_node[:upto], ref["event_node"][:upto], who)
    np.testing.assert_array_equal(
        lane.event_dev[:upto], ref["event_dev"][:upto], who)
    if stop >= 0:
        return False
    for f in ("placed_node", "dev_mask", "ever_failed"):
        np.testing.assert_array_equal(
            np.asarray(getattr(lane, f)), ref[f], f"{who}: {f}")
    for f in ("cpu_left", "mem_left", "gpu_left", "aff_cnt"):
        np.testing.assert_array_equal(
            np.asarray(getattr(lane.state, f)), ref[f], f"{who}: {f}")
    return True


@pytest.mark.parametrize("body", BODIES)
@pytest.mark.parametrize("stream", STREAMS)
def test_a_stream_with_deletions_equals_the_numpy_reference(stream, body):
    sim, trace = STREAMS[stream]()
    lanes = 3 if body == "plain" else WIDE
    seeds = [100 + 7 * i for i in range(lanes)]
    traces, kw = [trace], {}
    if body == "grouped, a trace a lane":
        # odd lanes replay the stream with every deletion a tick later
        later = [dataclasses.replace(
            p, deletion_time=p.deletion_time and p.deletion_time + 1)
            for p in trace]
        traces = [trace, later]
        kw = {"lane_pods": [traces[i % 2] for i in range(lanes)]}
    before = len(sweep_log())
    out = schedule_pods_sweep(
        sim, None if kw else trace, [[1000]] * lanes, seeds, **kw)
    (rec,) = sweep_log()[before:]
    streams = [_events_as_the_reference_makes_them(t) for t in traces]
    deletes = [int((k == EV_DELETE).sum()) for k, _ in streams]
    assert min(deletes) > 0
    assert rec.table_pass_events == (1 if body == "plain" else FLAT_GROUP_EVENTS)
    assert rec.traces == len(traces)
    # the record counts the stream's deletions over its lanes, on the host
    assert rec.delete_events == sum(
        deletes[i % len(traces)] for i in range(lanes))
    assert rec.to_dict()["delete_events"] == rec.delete_events
    if body != "plain":
        # deletes INSIDE a group of 16: no group of the stream is all
        # creations, so the pending block holds released columns too
        kinds = streams[0][0]
        groups = [kinds[i:i + FLAT_GROUP_EVENTS]
                  for i in range(0, len(kinds), FLAT_GROUP_EVENTS)]
        assert sum(1 for g in groups if (g == EV_DELETE).any()
                   and (g == EV_CREATE).any()) >= 1

    whole = 0
    for i in sorted({0, 1, lanes // 2, lanes - 2, lanes - 1}):
        events = streams[i % len(traces)]
        lane = out[i]
        cluster, pods, typical, rank = reference_inputs(
            sim, traces[i % len(traces)], seeds[i])
        ref = clock_numpy.replay(cluster, pods, events, typical, rank)
        whole += _held(lane, ref, f"{stream}, {body}, lane {i}")
        # the in-scan counters see every event of the stream, and the
        # placed pods are the binds less the deletions of pods that WERE
        # placed
        creates, binds, fails, dels, skips = (int(v) for v in lane.counters[:5])
        assert creates + dels + skips == len(events[0]) == lane.events
        assert dels == deletes[i % len(traces)] and binds + fails == creates
        gone = events[1][events[0] == EV_DELETE]
        never = int(lane.ever_failed[gone].sum())
        assert binds - (dels - never) == lane.placed
        if stream == "a rejected pod's deletion":
            assert never == 1 and fails == 1
            e = int(np.flatnonzero((events[0] == EV_DELETE)
                                   & (events[1] == 2))[0])
            assert lane.event_node[e] == -1 and not lane.event_dev[e].any()
            # and the real releases name the node they freed
            freed = lane.event_node[events[0] == EV_DELETE]
            assert sorted(freed.tolist()).count(-1) == 1
    assert whole >= 1  # some lane was held over the whole stream
    if stream == "random lifetimes":
        # the stream exercises what the reference claims: rejections,
        # deletions of rejected pods, pods deleted as they are created
        assert any(lane.failed for lane in out)
        assert any(p.deletion_time == p.creation_time for p in trace)


def test_a_lanes_event_record_is_the_standalone_runs():
    """`SweepLane.event_node` / `event_dev`: what ReplayResult keeps of
    every event, a lane; equal to the sequential engine's standalone run of
    that seed."""
    sim, trace = _lifetimes(5, events=60)
    lanes = schedule_pods_sweep(sim, trace, [[1000]] * 2, [11, 12])
    kind, pod = build_events(trace, True)
    for lane in lanes:
        alone = Simulator(sim.nodes, _cfg(
            lane.seed, engine="sequential", use_timestamps=True))
        alone.set_workload_pods(trace)
        alone.set_typical_pods()
        want = alone.run_events(
            alone.init_state, pods_to_specs(trace, alone.node_index),
            jnp.asarray(kind), jnp.asarray(pod),
            jax.random.PRNGKey(lane.seed))
        assert "sequential" in alone._last_engine
        np.testing.assert_array_equal(lane.event_node, want.event_node)
        np.testing.assert_array_equal(lane.event_dev, want.event_dev)
        np.testing.assert_array_equal(lane.placed_node, want.placed_node)
    assert (kind == EV_DELETE).sum() > 10
    assert lanes[0].event_node.shape == (lanes[0].events,)
    assert lanes[0].event_dev.shape == (lanes[0].events, 8)


def test_the_tables_stay_resident_across_timestamped_sweeps():
    """The build reads the INITIAL state, which no sweep writes: a stream
    that ends on another state than it began leaves the proof standing."""
    sim, trace = _openb_window(64)
    before = len(sweep_log())
    first = schedule_pods_sweep(sim, trace, [[1000]] * 2, [1, 2])
    second = schedule_pods_sweep(sim, trace, [[1000]] * 2, [3, 4])
    one, two = sweep_log()[before:]
    assert (one.tables_reused, two.tables_reused) == (0, 1)
    # and through Simulator.run_sweep, which prepares the workload itself
    own, _ = _lifetimes(5, events=40)
    before = len(sweep_log())
    own.run_sweep([[1000]] * 2, [1, 2])
    own.run_sweep([[1000]] * 2, [3, 4])
    assert [(r.tables_reused, r.delete_events > 0)
            for r in sweep_log()[before:]] == [(0, True), (1, True)]
    assert one.delete_events == two.delete_events > 0
    # the streams ended elsewhere than they began
    assert any((np.asarray(lane.state.cpu_left)
                != np.asarray(sim.init_state.cpu_left)).any()
               for lane in first + second)


def test_the_record_carries_the_delete_count():
    rec = SweepRecord(id=0, start_s=0.0, blocked=False)
    assert rec.delete_events == 0 == rec.to_dict()["delete_events"]
    rec.delete_events = 238 * 2560
    assert rec.to_dict()["delete_events"] == 609280
    # a creation stream in list order counts none
    sim, trace = _lifetimes(5, events=30)
    plain = Simulator(sim.nodes, _cfg(7, engine="table"))
    plain.set_workload_pods(trace)
    plain.set_typical_pods()
    before = len(sweep_log())
    schedule_pods_sweep(plain, trace, [[1000]], [1])
    assert sweep_log()[before:][0].delete_events == 0


@pytest.mark.parametrize("lanes", [2, 64], ids=["plain", "grouped"])
def test_a_stream_that_deletes_every_pod_ends_on_the_empty_cluster(lanes):
    """The release has no code of its own to hold to anything: it is the
    commit's one add a leaf with the sign as data (`PendingCommit.rs`). So
    the guarantee is held from the outside: where every pod that came also
    went, every NodeState field is the initial one's, bit for bit, and
    nobody is placed, though pods were."""
    sim, trace = _lifetimes(11, events=40)
    trace = [dataclasses.replace(p, deletion_time=p.deletion_time
                                 or p.creation_time + 1000) for p in trace]
    out = schedule_pods_sweep(sim, trace, [[1000]] * lanes, list(range(lanes)))
    for lane in out:
        creates, binds, _, deletes, _ = (int(v) for v in lane.counters[:5])
        assert (creates, deletes) == (40, 40) and binds > 20
        assert (np.asarray(lane.placed_node) == -1).all()
        assert not np.asarray(lane.dev_mask).any()
        for f in ("cpu_left", "mem_left", "gpu_left", "aff_cnt"):
            np.testing.assert_array_equal(
                np.asarray(getattr(lane.state, f)),
                np.asarray(getattr(sim.init_state, f)), f)


def test_the_clock_reference_imports_nothing_of_the_program():
    """numpy and the FGD reference only; and the benchmark's copy is this
    file but for the line that names ITS copy of the FGD reference."""
    path = os.path.join(REPO, "tpusim", "ref", "clock_numpy.py")
    with open(path) as f:
        text = f.read()
    imported = set()
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.update(f"{node.module}.{a.name}" for a in node.names)
    assert imported == {"__future__.annotations", "numpy",
                        "tpusim.ref.fgd_numpy"}, imported
    with open(os.path.join(REPO, "benchmark", "lib",
                           "reference_clock.py")) as f:
        copy = f.read()
    assert copy == text.replace("from tpusim.ref import fgd_numpy as fgd",
                                "from benchmark.lib import reference_fgd as fgd")


def test_a_reference_that_ignores_deletions_is_told_apart():
    """The control from the other side: the same lane against a replay
    that never releases ends on another state, and its record differs at
    the first deletion."""
    sim, trace = _openb_window(64)
    lane, = schedule_pods_sweep(sim, trace, [[1000]], [9])
    kind, pod = _events_as_the_reference_makes_them(trace)
    cluster, pods, typical, rank = reference_inputs(sim, trace, 9)
    ref = clock_numpy.replay(cluster, pods, (kind, pod), typical, rank)
    deaf = clock_numpy.replay(
        cluster, pods, (np.where(kind == EV_DELETE, 2, kind), pod), typical,
        rank)
    first = int(np.flatnonzero(kind == EV_DELETE)[0])
    assert lane.event_node[first] == ref["event_node"][first] >= 0
    assert deaf["event_node"][first] == -1
    assert (deaf["cpu_left"] != ref["cpu_left"]).any()
    assert (deaf["placed_node"] >= 0).sum() > (ref["placed_node"] >= 0).sum()
