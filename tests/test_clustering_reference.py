"""tpusim/ref/clustering_numpy.py, the plain numpy reference of one
GpuClustering lane with `best` devices (ISSUE 45: no kernel, table, engine
or commit of the program; its own affinity counts), and the sweep held to
it.

(a) The reference alone on hand-made nodes: one for each of the score's
five bands, the packing term, selectHost and the `best` device choice.
(b) One sweep of 64 lanes, a trace a lane (so the GROUPED flat body, whose
commit keeps the add into `aff_cnt` in its event loop), on seeded random
clusters that fill up: placements, device masks, failure flags and every
state leaf `==` the reference's in every lane, and both engines standalone.
(c) The comparison sees what the cell is for: a replay that drops the
affinity add differs from every lane. Every quantity is an integer: no
tolerance anywhere.
"""

import os

import numpy as np
import pytest

from tests.test_reference_fgd import _cluster, reference_inputs
from tests.test_sweep import _cfg
from tpusim.io.trace import PodRow
from tpusim.ref import clustering_numpy as ref
from tpusim.sim.driver import Simulator, schedule_pods_sweep
from tpusim.sim.table_engine import FLAT_GROUP_EVENTS, FLAT_GROUP_MIN_LANES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POLICIES = (("GpuClusteringScore", 1000),)
LANES, TRACES, EVENTS = FLAT_GROUP_MIN_LANES, 4, 220


# ---------------------------------------------------------------- (a)
def _nodes(gpu_left, aff):
    """Hand-made node rows: gpu_left [N, 8] milli, aff {node: {class: n}}."""
    counts = np.zeros((len(gpu_left), ref.AFFINITY_CLASSES), np.int64)
    for node, classes in aff.items():
        for cls, n in classes.items():
            counts[node, cls] = n
    return np.asarray(gpu_left, np.int64), counts


FULL = [1000] * 8
TWO_GPU = (4000, 1024, 1000, 2, 0)  # cpu, mem, milli, num, mask: class 2
SHARE = (4000, 1024, 500, 1, 0)  # class 0


@pytest.mark.parametrize("pod, aff, band", [
    (TWO_GPU, {0: {2: 3}}, 75),
    (TWO_GPU, {0: {2: 1, 0: 4}}, 50),
    (TWO_GPU, {}, 25),
    (TWO_GPU, {0: {0: 2, 8: 1}}, 0),
    ((4000, 1024, 0, 0, 0), {0: {2: 1}}, None),
    (SHARE, {0: {0: 1}}, 75),
    (SHARE, {0: {1: 1}}, 0),
], ids=["only the pod's class", "several with the pod's", "idle",
        "only other classes", "no GPU asked", "share-gpu is a class",
        "one whole GPU is not share-gpu"])
def test_the_score_has_five_bands_and_an_integer_packing_term(pod, aff, band):
    gpu_left, counts = _nodes([FULL, [1000, 1000, 300, 0, 0, 0, 0, 0]], aff)
    got = ref.score_nodes(gpu_left, counts, pod)
    if band is None:  # a pod without GPU scores 0 whatever the node holds
        assert got.tolist() == [0, 0]
        return
    # node 0 holds eight idle GPUs: pack 0; node 1 is idle in classes and
    # has 2,300 milli left: 25 * 5,700 // 8,000 = 17, a floor
    assert got.tolist() == [band, 25 + 17]
    assert 25 * (8000 - 2300) / 8000 == 17.8125


def test_select_host_takes_the_best_total_then_the_smallest_rank():
    total = np.asarray([40, 90, 90, 95, 90])
    feasible = np.asarray([True, True, True, False, True])
    assert ref.select_host(total, feasible, np.asarray([0, 3, 1, 2, 4])) == 2
    assert ref.select_host(total, feasible, np.asarray([0, 1, 3, 2, 4])) == 1
    assert ref.select_host(total, ~feasible | feasible,
                           np.asarray([0, 1, 3, 2, 4])) == 3
    assert ref.select_host(total, np.zeros(5, bool), np.arange(5)) == -1


@pytest.mark.parametrize("left, pod, devices", [
    ([1000, 400, 600, 300, 0, 0, 0, 0], SHARE, [2]),  # the tightest fit
    ([700, 500, 500, 1000, 0, 0, 0, 0], SHARE, [1]),  # the first on ties
    ([300, 1000, 1000, 0, 0, 0, 0, 0], (1, 1, 1000, 1, 0), [1]),
    ([300, 1000, 400, 1000, 1000, 0, 0, 0], TWO_GPU, [1, 3]),
    # several GPUs under a whole one each: floor(left / milli) units a device
    ([1000, 200, 500, 0, 0, 0, 0, 0], (1, 1, 400, 3, 0), [0, 2]),
    ([1000] * 8, (1, 1, 0, 0, 0), []),
], ids=["tightest", "ties", "one whole GPU", "two-pointer pack",
        "units of a device", "no GPU"])
def test_reserve_is_allocate_gpu_id_under_best(left, pod, devices):
    mask = ref.reserve_devices(np.asarray(left, np.int64), pod)
    assert np.flatnonzero(mask).tolist() == devices


def test_the_filter_counts_units_and_models():
    gpu_left, _ = _nodes([FULL, [400] * 8, [1000, 1000] + [0] * 6], {})
    cap = np.full(3, 64000, np.int64)
    gpu_cnt, gpu_type = np.asarray([8, 8, 2]), np.asarray([0, 1, -1])
    args = (cap, cap, gpu_left, gpu_cnt, gpu_type)
    assert ref.feasible_nodes(*args, (1, 1, 1000, 4, 0)).tolist() == [
        True, False, False]
    assert ref.feasible_nodes(*args, (1, 1, 400, 2, 0b10)).tolist() == [
        False, True, False]
    assert ref.feasible_nodes(*args, (64001, 1, 0, 0, 0)).tolist() == [
        False] * 3


def test_the_reference_imports_nothing_of_the_program_and_its_copy_is_it():
    with open(os.path.join(REPO, "tpusim", "ref", "clustering_numpy.py")) as f:
        own = f.read()
    with open(os.path.join(REPO, "benchmark", "lib",
                           "reference_clustering.py")) as f:
        assert f.read() == own
    imports = [ln for ln in own.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations",
                       "import numpy as np"]


# ---------------------------------------------------------------- (b)
def _pods(rng, events):
    """GPU-heavy requests of every affinity class, so that the cluster's
    GPUs run out and late creates are rejected."""
    out = []
    for i in range(events):
        gpu = int(rng.choice([0, 1, 1, 1, 1, 2, 2, 4, 8]))
        milli = 1000 if gpu > 1 else int(
            rng.choice([100, 250, 300, 500, 700, 1000]))
        spec = str(rng.choice(["", "", "", "T4", "V100M16|A10"])) if gpu else ""
        out.append(PodRow(
            f"p{i:04d}", int(rng.choice([2000, 4000, 8000])),
            int(rng.choice([2048, 8192])), gpu, milli if gpu else 0,
            gpu_spec=spec))
    return out


def _inputs(sim, trace, seed):
    """The reference's inputs as plain arrays, read off a Simulator (the FGD
    reference's, less the typical pods this score never reads)."""
    cluster, pods, _typical, rank = reference_inputs(sim, trace, seed)
    return cluster, pods, rank


def _sim(nodes, pods, seed=42, engine="table"):
    sim = Simulator(nodes, _cfg(seed, POLICIES, "best", engine=engine))
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    return sim


@pytest.fixture(scope="module")
def swept():
    """One sweep of 64 lanes over four traces of different lengths (a trace
    a lane, shuffle-major as the cell hands them over) on a 64-node cluster
    that fills up; with every lane the reference's replay of it."""
    rng = np.random.default_rng(45)
    nodes = _cluster(rng)
    sim = _sim(nodes, _pods(rng, EVENTS))
    whole = sim.prepare_pods()
    traces = [whole[: EVENTS - 7 * t] if t % 2 else
              [whole[i] for i in rng.permutation(EVENTS - 7 * t)]
              for t in range(TRACES)]
    of = [t for t in range(TRACES) for _ in range(LANES // TRACES)]
    seeds = [1000 + 7 * i for i in range(LANES)]
    lanes = schedule_pods_sweep(
        sim, None, np.full((LANES, 1), 1000, np.int32), seeds,
        lane_pods=[traces[t] for t in of])
    want = [ref.replay(*_inputs(sim, traces[t], s)) for t, s in zip(of, seeds)]
    return sim, nodes, traces, of, seeds, lanes, want


def test_the_sweep_is_the_grouped_body_with_the_add_in_its_loop(swept):
    sim, _nodes_, traces, _of, _seeds, lanes, want = swept
    rec = sim.obs.sweeps[-1]
    assert (rec.lanes, rec.traces) == (LANES, TRACES)
    assert rec.table_pass_events == FLAT_GROUP_EVENTS
    assert (rec.affinity_deferred, rec.affinity_readers) == (0, 1)
    assert "trace vmap" in rec.engine and "table" in rec.engine
    # the cluster fills: every lane rejects creates, and places pods of
    # shared, whole and several GPUs, so the score's bands are all in play
    for lane, w in zip(lanes, want):
        assert 0.1 < w["ever_failed"].mean() < 0.6
        assert lane.failed == int(w["ever_failed"].sum())
        assert (w["aff_cnt"][:, 0] > 0).any() and (w["aff_cnt"][:, 2:] > 0).any()
    # and the tie-break seed matters: lanes of one trace place differently
    assert not np.array_equal(lanes[0].placed_node, lanes[1].placed_node)


@pytest.mark.parametrize("lane", range(LANES))
def test_a_lane_of_the_sweep_equals_the_reference(swept, lane):
    """Placements, device masks, failure flags and every state leaf,
    `aff_cnt` among them: `==`, at every event."""
    *_, lanes, want = swept
    differing, first = ref.lane_differences(lanes[lane], want[lane])
    assert set(differing) == {"placed_node", "dev_mask", "ever_failed"} | {
        f"state.{f}" for f in ref.STATE_FIELDS}
    assert not any(differing.values()) and first == -1, (differing, first)
    assert lanes[lane].state.aff_cnt.dtype == want[lane]["aff_cnt"].dtype


@pytest.mark.parametrize("engine", ["table", "sequential"])
def test_a_standalone_run_equals_the_reference(swept, engine):
    _sim_, nodes, traces, *_ = swept
    sim = _sim(nodes, traces[1], seed=77, engine=engine)
    sim.cfg.shuffle_pod = False
    trace = sim.prepare_pods()
    assert [p.name for p in trace] == [p.name for p in traces[1]]
    got = sim.run()
    assert engine in sim._last_engine
    want = ref.replay(*_inputs(sim, trace, 77))
    np.testing.assert_array_equal(np.asarray(got.placed_node),
                                  want["placed_node"])
    np.testing.assert_array_equal(np.asarray(got.dev_mask), want["dev_mask"])
    for f in ref.STATE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got.state, f)), want[f], f)


# ---------------------------------------------------------------- (c)
@pytest.mark.parametrize("lane", [0, 17, 34, 63])
def test_a_dropped_affinity_add_fails_the_comparison(swept, lane):
    """A doctored reference whose Bind never adds into the counts (what a
    program that loses the add, or defers it past the events that read it,
    computes: every node looks idle to the score): its placements part from
    the lane's in the first half of the trace, and the final counts are
    empty."""
    sim, _nodes_, traces, of, seeds, lanes, _want = swept
    bent = ref.replay(*_inputs(sim, traces[of[lane]], seeds[lane]),
                      count_affinity=False)
    differing, first = ref.lane_differences(lanes[lane], bent)
    assert differing["placed_node"] > 0 and differing["state.aff_cnt"] > 0
    assert 0 <= first < lanes[lane].events // 2
    assert not bent["aff_cnt"].any()


def test_counts_added_a_chunk_late_fail_it_too(swept):
    """The deferred form itself (table_engine.chunk_affinity's timing: the
    counts arrive once a chunk of events, not once an event) is NOT what a
    reader may get: a replay that scores against counts a chunk old parts
    from the lane."""
    sim, _nodes_, traces, of, seeds, lanes, _want = swept
    cluster, pods, rank = _inputs(sim, traces[of[5]], seeds[5])
    late, chunk = {"aff": None}, 32
    score = ref.score_nodes

    def stale(gpu_left, aff_cnt, pod):
        e = stale.event
        if e % chunk == 0:
            late["aff"] = aff_cnt.copy()
        stale.event = e + 1
        return score(gpu_left, late["aff"], pod)

    stale.event = 0
    ref.score_nodes = stale
    try:
        bent = ref.replay(cluster, pods, rank)
    finally:
        ref.score_nodes = score
    differing, first = ref.lane_differences(lanes[5], bent)
    assert differing["placed_node"] > 0 and 0 <= first < EVENTS // 2
    # the counts themselves come out whole: only the reads were stale
    assert int(bent["aff_cnt"].sum()) == int(
        (np.asarray(pods["gpu_num"])[bent["placed_node"] >= 0] > 0).sum())
