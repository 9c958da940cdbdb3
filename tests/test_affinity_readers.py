"""The kernels' declaration held to the kernels (ISSUE 42): the flat table
replay leaves the per-event add into NodeState.aff_cnt out of its event loop
where no scoring kernel of the program reads that leaf, and it reads that off
each kernel's `reads_affinity` (policies/base.py). Every registered kernel,
the learned feature kernels and every `branches` entry a table builder may
call in a kernel's place is traced here on abstract operands, and a kernel
whose jaxpr uses the aff_cnt input must be counted as a reader."""

import jax
import jax.numpy as jnp
import pytest
from jax._src.interpreters import partial_eval as pe

from tpusim.learn.policy import FEATURE_NAMES, learned_policy_name
from tpusim.policies import (
    POLICY_NAMES,
    ScoreContext,
    affinity_readers,
    make_policy,
    policies_read_affinity,
)
from tpusim.types import NodeState, PodSpec, TypicalPods

NODES, TYPICAL = 6, 4
AFF = NodeState._fields.index("aff_cnt")
NAMES = list(POLICY_NAMES) + [learned_policy_name(f) for f in FEATURE_NAMES]


def _abstract_operands():
    from tpusim.types import make_node_state, make_pod, make_typical_pods

    state = make_node_state(
        cpu_cap=[32000] * NODES, mem_cap=[65536] * NODES,
        gpu_cnt=[4] * NODES, gpu_type=[0] * NODES)
    tp = make_typical_pods(
        [(1000 * (i + 1), 512, 1, 0, 1 / TYPICAL) for i in range(TYPICAL)])
    pod = make_pod(2000, 1024, 500, 1)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype),
        (state, pod, tp))
    assert isinstance(shapes[0], NodeState) and isinstance(shapes[1], PodSpec)
    assert isinstance(shapes[2], TypicalPods)
    return shapes


def _uses_aff_cnt(call) -> bool:
    """Whether `call(state, pod, ctx)`'s jaxpr uses the aff_cnt input: the
    inputs that survive dead-code elimination with every output kept."""
    state, pod, tp = _abstract_operands()
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    feas = jax.ShapeDtypeStruct((NODES,), jnp.bool_)

    def fn(state, pod, tp, feas, key):
        return call(state, pod, ScoreContext(tp=tp, feasible=feas, rng=key))

    closed = jax.make_jaxpr(fn)(state, pod, tp, feas, key)
    _, used = pe.dce_jaxpr(
        closed.jaxpr, [True] * len(closed.jaxpr.outvars))
    assert len(used) == len(jax.tree.leaves((state, pod, tp, feas, key)))
    return used[AFF]  # the state's leaves come first, in field order


def _entries(fn):
    """(label, (state, pod, ctx) -> outputs) for the kernel and for each of
    its `branches`: what make_table_builders may call in its place."""
    yield "kernel", fn
    for which, branch in getattr(fn, "branches", {}).items():
        if which == "whole_split":
            request, finish = branch
            yield "whole_split", lambda s, p, c: finish(
                s, p, request(s, p.gpu_milli, p.gpu_num, c), c)
        else:
            yield which, branch


@pytest.mark.parametrize("name", NAMES)
def test_a_kernel_that_reads_aff_cnt_is_counted_as_a_reader(name):
    fn = make_policy(name)
    reads = any(_uses_aff_cnt(call) for _, call in _entries(fn))
    counted = policies_read_affinity([(fn, 1000)])
    assert counted or not reads, (
        f"{name} reads NodeState.aff_cnt and declares it does not")
    # and the declarations are exact, so no program pays the per-event add
    # for a kernel that never looks
    assert counted == reads == (name == "GpuClusteringScore")
    assert affinity_readers([(fn, 1000)]) == int(reads)


def test_a_kernel_that_says_nothing_counts_as_a_reader():
    def silent(state, pod, ctx):
        return make_policy("FGDScore")(state, pod, ctx)

    assert not hasattr(silent, "reads_affinity")
    assert policies_read_affinity([(silent, 1000)])
    assert policies_read_affinity(
        [(make_policy("FGDScore"), 500), (silent, 500)])
    assert not policies_read_affinity(
        [(make_policy("FGDScore"), 500), (make_policy("PWRScore"), 500)])
    # the jitted view keeps the declaration
    from tpusim.policies import jit_policy

    assert jit_policy(make_policy("FGDScore")).reads_affinity is False
    assert jit_policy(make_policy("GpuClusteringScore")).reads_affinity


@pytest.mark.parametrize("names, want", [
    ((), 0),
    (("FGDScore",), 0),
    (("GpuClusteringScore",), 1),
    (("FGDScore", "GpuClusteringScore"), 1),
    (("GpuClusteringScore", "PWRScore", "GpuClusteringScore"), 2),
    (("PWRScore", "FGDScore"), 0),
    (("FGDScore", None), 1),
    ((None, "GpuClusteringScore", None), 3),
], ids=["none", "FGD", "GpuClustering", "FGD+GpuClustering", "twice",
        "PWR+FGD", "a silent kernel", "silent kernels count"])
def test_affinity_readers_counts_the_kernels_that_read(names, want):
    """SweepRecord.affinity_readers (ISSUE 45): the count behind
    policies_read_affinity; None stands for a kernel that declares
    nothing, which counts as a reader."""
    def silent(state, pod, ctx):
        return make_policy("FGDScore")(state, pod, ctx)

    policies = [(silent if n is None else make_policy(n), 1000) for n in names]
    assert affinity_readers(policies) == want
    assert policies_read_affinity(policies) == (want > 0)


def test_the_probe_sees_a_read():
    """The probe itself: a kernel that only adds aff_cnt into its score is
    seen, one that carries the state through untouched is not."""
    fgd = make_policy("FGDScore")

    def peeks(state, pod, ctx):
        res = fgd(state, pod, ctx)
        return res._replace(raw_scores=res.raw_scores + state.aff_cnt[:, 0])

    assert _uses_aff_cnt(peeks) and not _uses_aff_cnt(fgd)


@pytest.mark.parametrize("lanes", [None, 3], ids=["standalone", "vmapped"])
def test_the_commits_scope_is_a_name_and_no_operation(lanes):
    """COMMIT_AFFINITY_SCOPE names the add into aff_cnt where it stands:
    `scoped` changes the program's debug info and nothing else, so a
    program lowered with it is the module (and the compile-cache entry) it
    was without it. PR 45's first form moved the add and every standing
    cell ran another program than its parent's."""
    from tpusim.sim import step

    state, _, _ = _abstract_operands()
    pods = 5
    operands = (
        state,
        jax.ShapeDtypeStruct((pods + 1,), jnp.int32),
        jax.ShapeDtypeStruct((pods + 1, 8), jnp.bool_),
        jax.ShapeDtypeStruct((pods + 1,), jnp.bool_),
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     step.no_pending_commit(pods)),
    )
    if lanes:
        operands = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((lanes,) + a.shape, a.dtype),
            operands)

    def lowered(scoped, debug_info):
        def commit(*args):
            return step.apply_commit(*args, scoped=scoped)

        fn = jax.vmap(commit) if lanes else commit
        return jax.jit(fn).lower(*operands).as_text(debug_info=debug_info)

    assert lowered(True, False) == lowered(False, False)
    assert step.COMMIT_AFFINITY_SCOPE in lowered(True, True)
    assert step.COMMIT_AFFINITY_SCOPE not in lowered(False, True)
