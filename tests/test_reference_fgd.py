"""tpusim/ref/fgd_numpy.py, the plain numpy reference of one FGD lane (no
kernel, table or engine of the program), against BOTH engines on seeded
random clusters.

The tolerance, and its reason. Everything integer is exact: feasibility,
device masks, every NodeState field, `placed_node`. A score is the floor of
a float the program computes in float32 and the reference in float64, so a
score may differ by 1, and only where the reference's value before the
floor lies within 1e-3 of an integer (`fgd_numpy.NEAR`; the float32 path's
error there stays under 1e-4). The reference counts such entries
(`near_entries`, printed) and names the first event one of them could
decide (`first_undecided`): a lane is held placement for placement up to
that event, and in full when there is none.
"""

import ast
import os

import numpy as np
import pytest

from tests.test_sweep import _cfg
from tpusim.io.trace import NodeRow, PodRow, pods_to_specs, tiebreak_rank
from tpusim.ref import fgd_numpy
from tpusim.sim.driver import Simulator, schedule_pods_sweep

NODES, EVENTS = 64, 200
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cluster(rng):
    """Mixed SKUs, tight enough on CPU that late creates are rejected."""
    models = ["V100M16", "T4", "A10"]
    return [
        NodeRow(f"n{i:03d}", int(rng.choice([16000, 32000, 64000])),
                int(rng.choice([65536, 131072])), int(g),
                str(rng.choice(models)) if g else "")
        for i, g in enumerate(rng.choice([0, 1, 2, 4, 8], NODES))
    ]


def _pods(rng):
    out = []
    for i in range(EVENTS):
        gpu = int(rng.choice([0, 1, 1, 1, 2, 4, 8]))
        milli = 1000 if gpu > 1 else int(
            rng.choice([100, 250, 300, 500, 700, 1000]))
        spec = str(rng.choice(["", "", "T4", "V100M16|A10"])) if gpu else ""
        out.append(PodRow(
            f"p{i:04d}", int(rng.choice([2000, 4000, 8000, 16000])),
            int(rng.choice([2048, 8192, 32768])), gpu, milli if gpu else 0,
            gpu_spec=spec))
    return out


def reference_inputs(sim, trace, seed):
    """The reference's inputs as plain arrays, read off a Simulator: the
    cluster's capacities, the trace's requests, the typical pods and the
    lane's tie-break rank are DATA to both sides."""
    state, specs, tp = sim.init_state, pods_to_specs(trace, sim.node_index), sim.typical
    assert (np.asarray(specs.pinned) < 0).all()
    cluster = {k: np.asarray(getattr(state, k))
               for k in ("cpu_cap", "mem_cap", "gpu_cnt", "gpu_type")}
    pods = {k: np.asarray(getattr(specs, k))
            for k in ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask")}
    typical = {k: np.asarray(getattr(tp, k))
               for k in ("cpu", "gpu_milli", "gpu_num", "gpu_mask", "freq")}
    return cluster, pods, typical, tiebreak_rank(len(sim.nodes), seed)


def held_to_the_reference(got, ref, who):
    """Exact up to the first event a near-integer score could decide."""
    stop = ref["first_undecided"]
    upto = len(ref["placed_node"]) if stop < 0 else stop
    np.testing.assert_array_equal(
        np.asarray(got.placed_node)[:upto], ref["placed_node"][:upto], who)
    np.testing.assert_array_equal(
        np.asarray(got.dev_mask)[:upto], ref["dev_mask"][:upto], who)
    if stop < 0:
        if hasattr(got, "ever_failed"):  # a SweepLane; run() reports pods
            np.testing.assert_array_equal(
                np.asarray(got.ever_failed), ref["ever_failed"], who)
        for f in ("cpu_left", "mem_left", "gpu_left", "aff_cnt"):
            np.testing.assert_array_equal(
                np.asarray(getattr(got.state, f)), ref[f], f"{who}: {f}")
    return upto


def _both_engines_and_the_reference(seed):
    rng = np.random.default_rng(seed)
    nodes, pods = _cluster(rng), _pods(rng)
    table = Simulator(nodes, _cfg(seed, engine="table"))
    table.set_workload_pods(pods)
    table.set_typical_pods()
    trace = table.prepare_pods()
    assert len(trace) == EVENTS
    ref = fgd_numpy.replay(*reference_inputs(table, trace, seed))
    print(f"seed {seed}: {ref['near_entries']} score entries within "
          f"{fgd_numpy.NEAR} of an integer, first event one could decide: "
          f"{ref['first_undecided']}")
    lane, = schedule_pods_sweep(table, trace, [[1000]], [seed])
    assert "table" in table._last_engine
    oracle = Simulator(nodes, _cfg(seed, engine="sequential"))
    oracle.set_workload_pods(pods)
    want = oracle.run()
    assert "sequential" in oracle._last_engine
    return ref, {"table engine": lane, "sequential engine": want}


# seeds whose 200 events no near-integer score could decide (16 has 41
# such entries, none within a point of a winner)
@pytest.mark.parametrize("seed", [11, 13, 16])
def test_both_engines_equal_the_numpy_reference(seed):
    ref, engines = _both_engines_and_the_reference(seed)
    # the trace exercises what the reference claims: rejections, shared
    # and whole GPUs, model constraints
    assert 0 < ref["ever_failed"].sum() < EVENTS // 2
    assert (ref["aff_cnt"][:, 0] > 0).any() and (ref["aff_cnt"][:, 2:] > 0).any()
    assert ref["first_undecided"] == -1
    for who, got in engines.items():
        assert held_to_the_reference(got, ref, who) == EVENTS


def test_a_near_integer_score_ends_the_comparison_where_it_could_decide():
    """Seed 12 draws a typical-pod mix under which a two-GPU pod on an
    idle two-GPU node scores sigmoid(1.2083) x 100 = 77.0004: the tolerance
    rule in action. The reference reports the event, the engines are held
    up to it and not beyond."""
    ref, engines = _both_engines_and_the_reference(12)
    assert ref["near_entries"] > 0 and 0 <= ref["first_undecided"] < EVENTS
    for who, got in engines.items():
        assert held_to_the_reference(got, ref, who) == ref["first_undecided"]


def test_a_lower_precision_reference_is_told_apart():
    """The band against precision. The program computes its scores in
    float32, the reference in float64. The reference with its typical-pod
    frequencies rounded to float32 moves scores only INSIDE the band (what
    the band is for); rounded to float16, the nearest precision below the
    program's, it moves scores outside it, which the tolerance reads as
    not correct."""
    rng = np.random.default_rng(5)
    m, t = 20000, 24
    typical = (rng.integers(1000, 16000, t), rng.integers(0, 1001, t),
               rng.integers(1, 3, t), np.zeros(t, np.int64),
               rng.dirichlet(np.ones(t)))
    gpu_left = rng.integers(0, 1001, (m, 8))
    cpu_left = rng.integers(0, 64000, m)
    gtype = np.zeros(m, np.int64)
    pod = (2000, 1024, 250, 1, 0)
    s64, d64, near = fgd_numpy.score_nodes(
        cpu_left, gpu_left, gtype, pod, typical)
    outside = {}
    for dtype in (np.float32, np.float16):
        rounded = typical[:4] + (typical[4].astype(dtype).astype(np.float64),)
        s, d, _ = fgd_numpy.score_nodes(
            cpu_left, gpu_left, gtype, pod, rounded)
        outside[dtype] = int((((s64 != s) | (d64 != d)) & ~near).sum())
    print(f"of {m} node scores, moved outside the band: {outside}")
    assert outside[np.float32] == 0 and outside[np.float16] > 0


def test_the_reference_imports_nothing_of_the_program():
    """numpy only: no score kernel, op or engine of tpusim reaches it."""
    path = os.path.join(REPO, "tpusim", "ref", "fgd_numpy.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert imported == {"__future__", "numpy"}, imported
