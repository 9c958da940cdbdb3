"""The affinity counts nodes minor in the event loop (ISSUE 46): where a
scoring kernel reads NodeState.aff_cnt (GpuClustering) and no fault step
rewrites it, the flat table replay keeps the commit's add and the kernel's
read in its loop and holds the leaf [classes, N] there, beside its table
carry; `run_chunk` transposes on the way in and on the way out. Nothing a
caller sees may show it: every carry between two chunks holds aff_cnt
[N, classes] with the commits that landed, two segmentations of one stream
meet in equal carries, and the finished state is the sequential oracle's.
A fault-plan sweep under the same kernel keeps the [N, 9] add and equals
its standalone runs; the sweep's record says which form its program took."""

import jax
import numpy as np
import pytest

from tests.test_affinity_deferred import (
    DEPTH,
    _assert_trees_equal,
    _case,
    _clock,
    _counted,
    _engine,
    _host,
)
from tpusim.policies import make_policy
from tpusim.sim.engine import make_replay
from tpusim.sim.table_engine import FLAT_GROUP_MIN_LANES, FlatTableCarry

CLUSTERING = [(make_policy("GpuClusteringScore"), 1000)]
# two segmentations of the 43 events that meet at 16, 29 and the end; 16 is
# a group's edge, 5 and 20 cut groups, 30 leaves a chunk of one event
SEGMENTS = {"a": [0, 5, 16, 29, DEPTH], "b": [0, 16, 20, 29, 30, DEPTH]}


@pytest.mark.parametrize("lanes", [None, 8, FLAT_GROUP_MIN_LANES], ids=[
    "standalone", "a trace a lane, 8 lanes (plain)",
    "a trace a lane, 64 lanes (grouped)"])
def test_two_segmentations_of_one_stream_hand_back_equal_carries(lanes):
    state, tp, pods, types, ev_kind, ev_pod, keys, ranks = _case(
        _clock, 4, lanes=lanes)
    init, run, finish, _, deferred = _engine(CLUSTERING, lanes, "best")
    assert not deferred(state.num_nodes, types)  # the add is in the loop

    def lane(tree, i):
        return tree if lanes is None else jax.tree.map(lambda a: a[i], tree)

    seq = make_replay(CLUSTERING, gpu_sel="best", report=False)
    oracle = [seq(state, lane(pods, i), lane(ev_kind, i), lane(ev_pod, i),
                  tp, lane(keys, i), lane(ranks, i))
              for i in range(lanes or 1)]
    assert sum(int(np.abs(np.asarray(r.state.aff_cnt)).sum())
               for r in oracle) > 0

    carries = {}
    for name, cuts in SEGMENTS.items():
        carry = init(state, pods, types, tp, keys, ranks)
        carries[name] = {0: carry}
        for lo, hi in zip(cuts, cuts[1:]):
            sl = (slice(lo, hi),) if lanes is None else (
                slice(None), slice(lo, hi))
            # every chunk resumes a checkpoint: through host memory
            carry, _ = run(_host(carry), pods, types, ev_kind[sl],
                           ev_pod[sl], tp, ranks)
            carries[name][hi] = carry
    for name, at_cut in carries.items():
        for at, carry in at_cut.items():
            assert isinstance(carry, FlatTableCarry)
            # the leaf as every caller knows it, with the commits that
            # landed (the scan is one event deep: all but the last event's)
            assert carry.state.aff_cnt.shape[-2:] == (state.num_nodes, 9)
            for i, want in enumerate(oracle):
                np.testing.assert_array_equal(
                    np.asarray(lane(carry.state.aff_cnt, i)),
                    _counted(state.aff_cnt, lane(pods, i), lane(ev_kind, i),
                             lane(ev_pod, i), np.asarray(want.event_node),
                             max(at - 1, 0)),
                    f"aff_cnt in {name}'s carry at event {at}, lane {i}")
    for at in sorted(set(SEGMENTS["a"]) & set(SEGMENTS["b"])):
        _assert_trees_equal(carries["a"][at], carries["b"][at],
                            f"the two carries at event {at}")
    st, placed, masks, failed = finish(carries["b"][DEPTH])
    for i, want in enumerate(oracle):
        what = f"finished, lane {i}"
        _assert_trees_equal(lane(st, i), want.state, what)
        np.testing.assert_array_equal(
            np.asarray(lane(placed, i)), np.asarray(want.placed_node), what)
        np.testing.assert_array_equal(
            np.asarray(lane(masks, i)), np.asarray(want.dev_mask), what)
        np.testing.assert_array_equal(
            np.asarray(lane(failed, i)), np.asarray(want.ever_failed), what)


def _sweep(name):
    from tests import test_affinity_deferred as deferred

    return {"GpuClustering": deferred._clustering_sweep,
            "FGD": deferred._fgd_sweep,
            # a reader AND fault steps that rewrite aff_cnt rows mid-scan:
            # the loop keeps the [N, 9] add
            "GpuClustering, fault plans": lambda: deferred._fault_sweep(
                (("GpuClusteringScore", 1000),), "best")}[name]()


@pytest.mark.parametrize("sweep, reads", [
    ("GpuClustering", (0, 1, 1)), ("FGD", (1, 0, 0)),
    ("GpuClustering, fault plans", (0, 1, 0))])
def test_the_sweep_record_says_where_the_counts_ride(sweep, reads):
    sim, lanes, oracle = _sweep(sweep)
    rec = sim.obs.sweeps[-1]
    assert (rec.affinity_deferred, rec.affinity_readers,
            rec.affinity_nodes_minor) == reads
    said = rec.to_dict()
    assert (said["affinity_deferred"], said["affinity_readers"],
            said["affinity_nodes_minor"]) == reads
    # the add is a write site of lane_write's rule in either layout
    assert rec.lane_writes == 3 + (6 if reads[0] else 7) + 7
    for lane, want in zip(lanes, oracle):
        np.testing.assert_array_equal(
            np.asarray(lane.placed_node), np.asarray(want.placed_node))
        np.testing.assert_array_equal(
            np.asarray(lane.state.aff_cnt), np.asarray(want.state.aff_cnt))
        np.testing.assert_array_equal(
            np.asarray(lane.state.gpu_left), np.asarray(want.state.gpu_left))
