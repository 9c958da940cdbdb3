"""Lanes of different workload families in one sweep (ISSUE 32):
`schedule_pods_sweep(lane_pods=..., lane_typical=...)` scores each lane
against its own family's typical pods, from its own family's score tables,
prepares each distinct trace once, and keeps all the table sets on the
device from wave to wave. Every lane equals the standalone run of a
Simulator built from its family's pod list."""

import os

import jax
import numpy as np
import pytest

from benchmark.drivers import wave
from benchmark.lib import compare, inputs, reference_typical
from tpusim import constants
from tpusim.io.trace import load_node_csv, load_pod_csv
from tpusim.obs.spans import sweep_log
from tpusim.sim.driver import schedule_pods_sweep

SIM = {
    "policies": [["FGDScore", 1000]], "gpu_sel_method": "FGDScore",
    "dim_ext_method": "share", "norm_method": "max", "tuning_ratio": 1.3,
    "shuffle_pod": True, "pod_popularity_threshold": 95, "engine": "table",
}
FAMILIES = ("default", "gpuspec33")  # 40 and 130 typical pods: T 48 and 144
ALL_FAMILIES = ("default", "cpu250", "gpushare100", "gpuspec33", "multigpu50")
SHUFFLES, PER_SHUFFLE, NODES, DEPTH = (42, 43), 2, 96, 48


def pod_csv(family):
    return os.path.join(inputs.REPO, "data", "csv",
                        f"openb_pod_list_{family}.csv")


class Wave:
    """2 families x 2 shuffles x 2 seeds on a 96-node cut of openb."""

    def __init__(self, shuffles=SHUFFLES, per_shuffle=PER_SHUFFLE,
                 depth=DEPTH):
        self.nodes = load_node_csv(inputs.NODE_CSV)[:NODES]
        self.pod_lists = [load_pod_csv(pod_csv(f)) for f in FAMILIES]
        cfg = wave.simulator_config(SIM, shuffles[0], profile=False)
        self.sims = [wave.build_simulator(self.nodes, pods, cfg)
                     for pods in self.pod_lists]
        self.traces = [[sim.prepare_pods(tuning_seed=s)[:depth]
                        for s in shuffles] for sim in self.sims]
        self.lane_of = [(f, s) for f in range(len(FAMILIES))
                        for s in range(len(shuffles))
                        for _ in range(per_shuffle)]
        self.lane_pods = [self.traces[f][s] for f, s in self.lane_of]
        self.lane_typical = [self.sims[f].typical for f, _ in self.lane_of]
        lanes = len(self.lane_of)
        self.weights = np.tile(np.asarray([[1000]], np.int32), (lanes, 1))
        self.seeds = [100 + i for i in range(lanes)]

    def sweep(self, seeds=None, lead=0, **kw):
        kw.setdefault("lane_pods", self.lane_pods)
        kw.setdefault("lane_typical", self.lane_typical)
        lanes = schedule_pods_sweep(
            self.sims[lead], None, self.weights, seeds or self.seeds, **kw)
        rec = sweep_log()[-1]
        (cache,) = [sp.meta["cache"] for sp in rec.spans
                    if sp.name == "init_tables"]
        return lanes, rec, cache


@pytest.fixture(scope="module")
def fam():
    w = Wave()
    w.lanes, w.rec, w.cache = w.sweep()
    return w


def assert_lanes_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert not [d for d in compare.lane_differences(a, b) if d[1]]
        np.testing.assert_array_equal(a.counters, b.counters)
        assert (a.seed, a.events, a.placed, a.failed, a.gpu_alloc_pct,
                a.frag_gpu_milli) == (b.seed, b.events, b.placed, b.failed,
                                      b.gpu_alloc_pct, b.frag_gpu_milli)


def test_the_families_typical_sets_differ_in_size(fam):
    assert [int(s.typical.cpu.shape[0]) for s in fam.sims] == [48, 144]
    assert (fam.rec.typical_sets, fam.rec.traces, fam.rec.lanes) == (2, 4, 8)
    assert fam.rec.to_dict()["typical_sets"] == 2
    # FGD takes its whole-branch types by request: the union type set's
    # distinct (gpu_milli, gpu_num), on their bucket of eight (ISSUE 37)
    assert fam.rec.sub_requests == fam.rec.to_dict()["sub_requests"] == 8
    assert fam.cache == "built 2 of 2" and fam.rec.tables_reused == 0
    # 8 lanes are under FLAT_GROUP_MIN_LANES: the plain flat body, one
    # dense column write an event; a trace a lane has 27 dense sites (28
    # before ISSUE 42: the flat commit's aff_cnt add left the event loop,
    # FGD does not read the leaf)
    assert "table" in fam.rec.engine
    assert (fam.rec.table_pass_events, fam.rec.dense_accesses) == (1, 27)
    assert fam.rec.affinity_deferred == 1


def test_a_wide_wave_of_families_runs_grouped_and_equals_the_plain_body(
        monkeypatch):
    """From FLAT_GROUP_MIN_LANES lanes a trace a lane runs the grouped
    flat body (ISSUE 33): 64 lanes of eight different traces and two
    typical-pod sets, 42 events (two whole groups and a tail of 10), equal
    the same sweep on the plain body (`replay(..., group=1)`) in every
    leaf, where two events of one group land on one node (the later
    pending column must win in the patch and in the flush)."""
    from tpusim.sim import driver, table_engine
    from tpusim.sim.table_engine import (
        FLAT_GROUP_EVENTS, FLAT_GROUP_MIN_LANES)

    depth = 2 * FLAT_GROUP_EVENTS + 10
    w = Wave(shuffles=(42, 43, 44, 45), per_shuffle=8, depth=depth)
    assert len(w.lane_of) == FLAT_GROUP_MIN_LANES
    grouped, rec, _ = w.sweep()
    assert (rec.lanes, rec.events, rec.traces, rec.typical_sets) == (
        FLAT_GROUP_MIN_LANES, depth, 8, 2)
    # 27 dense sites as on the plain body (28 before ISSUE 42 took the
    # aff_cnt add out of the event loop), and the three picks out of the
    # pending block (lane_write.read_pending: feas, score, sdev)
    assert (rec.table_pass_events, rec.dense_accesses) == (
        FLAT_GROUP_EVENTS, 30)

    # the plain body: the rule says 1, the wrapper is traced anew
    monkeypatch.setattr(table_engine, "flat_group_events", lambda *_: 1)
    monkeypatch.setattr(driver, "_SWEEP_WRAP_CACHE", {})
    plain, rec, _ = w.sweep()
    assert rec.table_pass_events == 1
    # placed_node, dev_mask, ever_failed, every NodeState field, counters
    assert_lanes_equal(grouped, plain)

    def lands_twice(lane):  # in one group, on one node
        first = lane.placed_node[:FLAT_GROUP_EVENTS]
        first = first[first >= 0]
        return len(set(first.tolist())) < len(first)

    assert any(map(lands_twice, grouped))
    assert len({lane.placed_node.tobytes() for lane in grouped}) > 8


@pytest.mark.parametrize("lane", range(8))
def test_a_lane_equals_its_standalone_sequential_replay(fam, lane):
    """Bit for bit, against the sequential oracle of a Simulator built
    from the lane's OWN family's pod list (its own set_typical_pods, of its
    own size: 48 rows against the wave's 144)."""
    f, s = fam.lane_of[lane]
    want = wave.oracle_lane(
        fam.nodes, fam.pod_lists[f], SIM, SHUFFLES[s], fam.lane_pods[lane],
        fam.weights[lane], fam.seeds[lane])
    differing = [d for d in compare.lane_differences(fam.lanes[lane], want)
                 if d[1]]
    assert not differing
    assert not [d for d in compare.counter_differences(fam.lanes[lane], DEPTH)
                if d[1]]


def test_every_lane_equals_a_one_lane_sweep_of_its_own_family(fam):
    """Counters and frag amounts too: the family's own Simulator, its own
    typical pods as the one broadcast set, the lane alone."""
    for i, (f, _) in enumerate(fam.lane_of):
        (alone,) = schedule_pods_sweep(
            fam.sims[f], fam.lane_pods[i], fam.weights[i:i + 1],
            [fam.seeds[i]])
        assert_lanes_equal([fam.lanes[i]], [alone])


def test_the_other_familys_typical_pods_give_other_lanes(fam):
    """The control: family 0's lanes scored against family 1's typical
    pods (and the reverse) differ in a placement or a frag amount."""
    swapped, rec, _ = fam.sweep(lane_typical=fam.lane_typical[::-1])
    assert rec.typical_sets == 2
    moved = [i for i, (a, b) in enumerate(zip(fam.lanes, swapped))
             if not np.array_equal(a.placed_node, b.placed_node)
             or a.frag_gpu_milli != b.frag_gpu_milli]
    assert moved
    assert any(not np.array_equal(a.placed_node, b.placed_node)
               for a, b in zip(fam.lanes, swapped))


@pytest.mark.parametrize("family", ALL_FAMILIES)
def test_the_plain_typical_pods_are_set_typical_pods(family):
    """benchmark/lib/reference_typical.py (csv and numpy alone) against the
    program's extraction on the configuration's five pod lists."""
    from tpusim.sim.typical import TypicalPodsConfig, get_typical_pods

    tp, _ = get_typical_pods(
        load_pod_csv(pod_csv(family)),
        TypicalPodsConfig(pod_popularity_threshold=95))
    ref = reference_typical.typical_pods(
        reference_typical.read_pod_keys(pod_csv(family)),
        constants.GPU_MODEL_IDS, popularity=95)
    assert len(ref["cpu"]) == {"default": 40, "cpu250": 40, "gpushare100": 30,
                               "gpuspec33": 130, "multigpu50": 50}[family]
    for field in ("cpu", "gpu_milli", "gpu_num", "gpu_mask"):
        np.testing.assert_array_equal(np.asarray(getattr(tp, field)),
                                      ref[field])
    np.testing.assert_array_equal(np.asarray(tp.freq),
                                  ref["freq"].astype(np.float32))
    assert abs(ref["freq"].sum() - 1.0) < 1e-12


def test_lanes_that_share_a_trace_object_are_prepared_once(fam):
    """Host prep goes with the distinct traces: one shared object is one
    trace whatever the lanes, a copy a lane is a trace a lane, and the
    lanes come out the same, as from the shared-trace sweep."""
    sim, trace = fam.sims[0], fam.traces[0][0]
    w, seeds = fam.weights[:4], fam.seeds[:4]
    shared = schedule_pods_sweep(sim, trace, w, seeds)
    assert sweep_log()[-1].traces == 1
    one = schedule_pods_sweep(sim, None, w, seeds, lane_pods=[trace] * 4)
    assert sweep_log()[-1].traces == 1
    each = schedule_pods_sweep(
        sim, None, w, seeds, lane_pods=[list(trace) for _ in range(4)])
    assert sweep_log()[-1].traces == 4
    two = schedule_pods_sweep(
        sim, None, w, seeds,
        lane_pods=[trace, fam.traces[0][1], trace, fam.traces[0][1]])
    assert sweep_log()[-1].traces == 2
    assert_lanes_equal(one, shared)
    assert_lanes_equal(each, shared)
    assert_lanes_equal(two[::2], shared[::2])
    assert not np.array_equal(two[1].placed_node, shared[1].placed_node)


def test_a_second_wave_reuses_every_table_set_and_a_changed_list_rebuilds():
    w = Wave()
    _, first, cache = w.sweep()
    assert (first.tables_reused, cache) == (0, "built 2 of 2")
    held = w.sims[0]._resident_tables.tables
    assert held[0].shape[0] == 2  # stacked a set

    other = [s + 50 for s in w.seeds]
    lanes, second, cache = w.sweep(other)
    assert (second.tables_reused, cache) == (1, "resident 2 of 2")
    assert all(a is b for a, b in zip(w.sims[0]._resident_tables.tables, held))
    fresh = Wave()
    assert_lanes_equal(lanes, fresh.sweep(other)[0])

    # the families the other way round: other lanes against other sets, so
    # another proof and both sets built again
    _, third, cache = w.sweep(other, lane_typical=w.lane_typical[::-1])
    assert (third.tables_reused, cache) == (0, "built 2 of 2")
    # one family's list alone is one stacked set of its own
    half = len(w.lane_of) // 2
    w.weights, was = w.weights[:half], w.weights
    _, rec, cache = w.sweep(
        other[:half], lane_pods=w.lane_pods[:half],
        lane_typical=w.lane_typical[:half])
    assert (rec.typical_sets, rec.tables_reused, cache) == (
        1, 0, "built 1 of 1")
    w.weights = was
    _, rec, cache = w.sweep(other)
    assert (rec.tables_reused, cache) == (0, "built 2 of 2")
    _, rec, cache = w.sweep(w.seeds)
    assert (rec.tables_reused, cache) == (1, "resident 2 of 2")


def test_lane_typical_has_to_name_a_set_for_every_lane(fam):
    with pytest.raises(ValueError, match="lane_typical has 3 typical-pod"):
        schedule_pods_sweep(
            fam.sims[0], None, fam.weights, fam.seeds,
            lane_pods=fam.lane_pods, lane_typical=fam.lane_typical[:3])


def test_one_shared_trace_takes_typical_pods_a_lane_too(fam):
    """`pods` with lane_typical: the shared trace, a set a lane. Each lane
    equals the sweep of a Simulator holding that set."""
    sim, trace = fam.sims[0], fam.traces[0][0]
    w, seeds = fam.weights[:2], fam.seeds[:2]
    got = schedule_pods_sweep(
        sim, trace, w, seeds,
        lane_typical=[fam.sims[0].typical, fam.sims[1].typical])
    rec = sweep_log()[-1]
    assert (rec.traces, rec.typical_sets) == (1, 2)
    (own,) = schedule_pods_sweep(sim, trace, w[:1], seeds[:1])
    assert_lanes_equal(got[:1], [own])
    assert jax.tree.structure(got[1].state) == jax.tree.structure(own.state)
    other = wave.build_simulator(fam.nodes, fam.pod_lists[1], sim.cfg)
    (theirs,) = schedule_pods_sweep(other, trace, w[1:], seeds[1:])
    assert_lanes_equal(got[1:], [theirs])


# ---- the plain reference walked beside a lane (benchmark/lib/reference_follow.py)

def _walk(fam, i, lane=None):
    from benchmark.drivers import family_wave

    f, _ = fam.lane_of[i]
    return family_wave.reference_walk(
        fam.sims[0], fam.lane_pods[i], lane or fam.lanes[i],
        int(fam.weights[i][0]), pod_csv(FAMILIES[f]), 95)


@pytest.mark.parametrize("lane", [0, 5])
def test_a_lane_is_held_to_the_plain_reference_over_all_its_events(fam, lane):
    got = _walk(fam, lane)
    assert got["events_held"] == DEPTH
    assert not any(got["differing"].values()), got
    assert set(got["differing"]) == {
        "placed_node", "dev_mask", "ever_failed", "state.cpu_left",
        "state.mem_left", "state.gpu_left", "state.aff_cnt"}


def test_a_choice_no_near_score_admits_ends_the_walk_where_it_differs(fam):
    import copy

    lane = copy.copy(fam.lanes[0])
    moved = lane.placed_node.copy()
    # a GPU pod sent to a node that scores far below the one chosen
    e = next(i for i in range(DEPTH) if lane.dev_mask[i].any())
    moved[e] = (moved[e] + 17) % NODES
    lane.placed_node = moved
    got = _walk(fam, 0, lane)
    assert got["events_held"] == e
    assert got["differing"]["placed_node"] + got["differing"]["dev_mask"] == 1
    assert "state.gpu_left" not in got["differing"]


def test_the_walk_goes_on_from_a_choice_the_tolerance_admits(
        fam, monkeypatch):
    """Where near-integer scores could decide, the lane's own choice is
    taken and the later events are held from there: with every choice
    admitted, the last event moved to another feasible node is followed,
    counted, and shows in the final state it was not made in."""
    import copy

    from benchmark.lib import reference_follow

    lane = copy.copy(fam.lanes[0])
    moved = lane.placed_node.copy()
    moved[-1] = fam.lanes[1].placed_node[-1]  # feasible: lane 1 chose it
    assert moved[-1] != lane.placed_node[-1]
    lane.placed_node, lane.dev_mask = moved, lane.dev_mask.copy()
    lane.dev_mask[-1] = fam.lanes[1].dev_mask[-1]
    monkeypatch.setattr(reference_follow, "_admissible", lambda *a: True)
    got = _walk(fam, 0, lane)
    assert (got["events_held"], got["admitted"]) == (DEPTH, 1)
    assert got["differing"]["placed_node"] == got["differing"]["dev_mask"] == 0
    # two nodes' rows: the one the lane's state says, the one the walk took
    assert got["differing"]["state.cpu_left"] == 2


def test_only_a_near_score_admits_another_choice():
    from benchmark.lib.reference_follow import _admissible

    total, rank = np.array([50000, 49000, 48000]), np.array([2, 0, 1])
    none, second, best = (np.array(x) for x in (
        [False] * 3, [False, True, False], [True, False, False]))
    assert not _admissible(total, none, rank, 1000, 1)
    assert _admissible(total, second, rank, 1000, 1)  # its score a step up
    assert _admissible(total, best, rank, 1000, 1)    # the best a step down
    assert not _admissible(total, second, rank, 1000, 2)
    assert _admissible(total, none, rank, 1000, 0)
    # a tie the step makes goes by rank, and an exact tie is no tolerance
    assert not _admissible(total, second, np.array([0, 2, 1]), 1000, 1)
    tie = np.array([50000, 50000, 48000])
    assert not _admissible(tie, none, np.array([0, 1, 2]), 1000, 1)
    assert _admissible(tie, none, np.array([1, 0, 2]), 1000, 1)
