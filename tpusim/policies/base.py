"""Policy-kernel plumbing shared by all scoring policies.

The reference runs each enabled ScorePlugin over the feasible node list,
optionally min-max normalizes (plugin_utils.go:48-74 NormalizeScore), applies
the config weight, sums, and picks the max-score node with
smallest-node-name tie-breaking (vendored generic_scheduler.go:185-210
selectHost). Here each policy is a function over the whole NodeState
struct-of-arrays producing

    raw_scores: i32[N]  — the plugin's Score() output per node
    share_dev:  i32[N]  — per node, the device the policy would hand a
                          share-GPU pod at Reserve time (-1 = none); whole-GPU
                          pods always use allocate_exclusive at bind
                          (open_gpu_share.go:285-343 + AllocateExclusiveGpuId)

and the framework semantics (normalize over feasible nodes only, integer
division, weighting, argmax with a fixed random tie-break permutation
standing in for the reference's random node-name prefixes,
simulator.go:584-588) live in tpusim.sim.step.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp

from tpusim.constants import MAX_NODE_SCORE
from tpusim.types import NodeState, PodSpec, TypicalPods


class ScoreContext(NamedTuple):
    """Dynamic inputs every policy may consume.

    feasible: bool[N] Filter-phase mask — normalization reductions and the
    Random policy's node draw only look at feasible nodes, like the vendored
    framework which scores feasible nodes only.
    """

    tp: TypicalPods
    feasible: jnp.ndarray  # bool[N]
    rng: jnp.ndarray  # jax PRNG key (Random policy, random gpu-sel)


class PolicyResult(NamedTuple):
    raw_scores: jnp.ndarray  # i32[N]
    share_dev: jnp.ndarray  # i32[N], -1 = no share-GPU choice


# A policy is (state, pod, ctx) -> PolicyResult, plus a `normalize` mode
# consumed by the step: "none" | "minmax" | "pwr", and `reads_affinity`:
# whether the kernel (any of its `branches` too) reads NodeState.aff_cnt.
# The flat table replay leaves the per-event add into that leaf out of its
# event loop where no kernel of the program reads it (policies_read_affinity
# below); tests/test_affinity_readers.py holds every registered kernel's
# declaration to its jaxpr.
PolicyFn = Callable[[NodeState, PodSpec, ScoreContext], PolicyResult]


def affinity_readers(policies) -> int:
    """How many kernels of [(policy_fn, weight)] read NodeState.aff_cnt
    (SweepRecord.affinity_readers). A kernel that says nothing counts as a
    reader."""
    return sum(bool(getattr(fn, "reads_affinity", True)) for fn, _ in policies)


def policies_read_affinity(policies) -> bool:
    """Whether some kernel of [(policy_fn, weight)] reads
    NodeState.aff_cnt."""
    return affinity_readers(policies) > 0


def feasible_min_max(scores, feasible):
    """(lo, hi) over feasible entries — the reduction half of the min-max
    normalizations, split out so sharded callers can feed pmin/pmax-combined
    global extrema into the same scaling core."""
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    lo = jnp.min(jnp.where(feasible, scores, big))
    hi = jnp.max(jnp.where(feasible, scores, -big))
    return lo, hi


def minmax_scale_i32(scores, feasible, lo, hi, degenerate):
    """The scaling core of the reference's integer NormalizeScore
    (plugin_utils.go:48-74): rescale to [0, MAX_NODE_SCORE] against the
    supplied extrema; a zero range maps everything to `degenerate`.
    Infeasible rows pass through untouched (the reference never sees them);
    callers mask them out before use."""
    rng = hi - lo
    scaled = jnp.where(
        rng == 0, degenerate,
        (scores - lo) * MAX_NODE_SCORE // jnp.maximum(rng, 1),
    )
    return jnp.where(feasible, scaled, scores)


def minmax_normalize_i32(scores, feasible):
    """Integer min-max rescale to [0, 100] over feasible nodes
    (ref: plugin_utils.go:48-74). oldRange == 0 → all MinNodeScore(0)."""
    lo, hi = feasible_min_max(scores, feasible)
    return minmax_scale_i32(scores, feasible, lo, hi, 0)


def pwr_normalize_i32(scores, feasible):
    """PWR's own NormalizeScore (pwr_score.go:104-139): min-max to [0,100]
    but the degenerate all-equal case maps to 100, not 0."""
    lo, hi = feasible_min_max(scores, feasible)
    return minmax_scale_i32(scores, feasible, lo, hi, MAX_NODE_SCORE)


# zero-range (all-equal) value per normalize mode — what block-reducing
# callers pass as `degenerate` to minmax_scale_i32 so their apply half
# matches minmax_normalize_i32 / pwr_normalize_i32 exactly
NORMALIZE_DEGENERATE = {"minmax": 0, "pwr": MAX_NODE_SCORE}
