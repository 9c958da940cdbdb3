"""FGD — Fragmentation Gradient Descent (ref: plugin/fgd_score.go).

score(node) = trunc(sigmoid((frag(node) − frag(node ⊖ pod)) / 1000) × 100)

For a share-GPU pod the hypothetical placement is tried on every fitting
device and the best per-device score wins (fgd_score.go:111-134, first device
on ties); for whole-GPU / CPU-only pods the placement is NodeResource.Sub
(fgd_score.go:137-148). Reserve re-runs the same computation to pick the
device (allocateGpuIdBasedOnFGDScore, fgd_score.go:153-156).

Implementation note (TPU): the naive form evaluates the full frag score on
9 hypothetical node states per node (current + 8 per-device). Because the
frag score decomposes as

    score = Σ_t freq_t × (isQ3_t ? total_left − fitsum_t : total_left)
    fitsum_t = Σ_e [g_e ≥ milli_t]·g_e ,  isQ3 from fit counts + cpu

a per-device hypothetical only perturbs one device's fit/fitsum term, so all
8 hypotheticals are derived from one [T, 8] precompute instead of 8 full
evaluations (~4× fewer element-ops). The share and whole branches are split
behind a lax.cond on the (scalar, per-pod) branch predicate so only the
branch the pod actually needs is executed. Equivalence with the direct form
is pinned by tests/test_policies.py golden values and the cross-check test.

The whole branch also comes in two steps (branches["whole_split"]): Sub's
hypothetical device vector and its fit terms depend on the pod through
(gpu_milli, gpu_num) alone, so a caller that scores every pod type of a trace
on one node (the table engine's column, every event) evaluates them once a
distinct request and finishes each type from its request's terms. The shipped
pod lists hold five requests among 25-329 whole-branch types.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpusim.constants import MAX_NODE_SCORE
from tpusim.ops.frag import node_frag_score
from tpusim.ops.resource import first_max, is_accessible, sub_devices, sub_pod
from tpusim.policies.base import PolicyResult, ScoreContext
from tpusim.types import NodeState, PodSpec


def _sigmoid_score(cur, new):
    """trunc(sigmoid((cur-new)/1000) * MaxNodeScore) — fgd_score.go:124."""
    s = jax.nn.sigmoid((cur - new) / 1000.0)
    return jnp.floor(s * MAX_NODE_SCORE).astype(jnp.int32)


def _share_terms(gpu_left, tp):
    """fit[T,8], fitcnt[T], fitsum[T] for the current device vector.

    fitcnt/fitsum come out of ONE stacked [T,8,2] reduction instead of two —
    on TPU each reduction is a fusion barrier (its own kernel launch inside
    the replay scan body), so merging reductions is the lever here, not
    FLOPs. Counts stay exact in f32 (<= 8)."""
    fit = (gpu_left[None, :] >= tp.gpu_milli[:, None]) & (tp.gpu_milli[:, None] > 0)
    g = gpu_left[None, :].astype(jnp.float32)
    both = jnp.stack(
        [fit.astype(jnp.float32), jnp.where(fit, g, 0.0)], axis=-1
    ).sum(1)  # [T, 2]
    return fit, both[:, 0], both[:, 1]


def _fgd_share_node(cpu_left, gpu_left, gpu_type, pod: PodSpec, tp):
    """Share-GPU branch: best per-device hypothetical (fgd_score.go:111-134).

    The current score and the 8 per-device hypotheticals reduce over T in a
    single [T, 9] sum (see _share_terms on why reductions are merged)."""
    acc = is_accessible(gpu_type, tp.gpu_mask)  # [T]
    gpu_pod = tp.gpu_milli > 0  # [T]
    fit, fitcnt, fitsum = _share_terms(gpu_left, tp)
    total = gpu_left.sum().astype(jnp.float32)

    # current frag score term per typical pod
    isq3 = gpu_pod & acc & (fitcnt >= tp.gpu_num) & (cpu_left >= tp.cpu)
    cur_t = tp.freq * jnp.where(isq3, total - fitsum, total)  # [T]

    # hypothetical on device d: only device d's fit/fitsum terms change
    p = pod.gpu_milli
    g = gpu_left[None, :].astype(jnp.float32)
    fitp = ((gpu_left[None, :] - p) >= tp.gpu_milli[:, None]) & (
        tp.gpu_milli[:, None] > 0
    )  # [T,8]
    fitcnt_h = fitcnt[:, None] - fit + fitp  # [T,8]
    fitsum_h = fitsum[:, None] - jnp.where(fit, g, 0.0) + jnp.where(fitp, g - p, 0.0)
    total_h = total - p
    cpu_ok_h = (cpu_left - pod.cpu) >= tp.cpu  # [T]
    isq3_h = (
        gpu_pod[:, None] & acc[:, None] & (fitcnt_h >= tp.gpu_num[:, None])
        & cpu_ok_h[:, None]
    )
    new_t = tp.freq[:, None] * jnp.where(isq3_h, total_h - fitsum_h, total_h)

    sums = jnp.concatenate([cur_t[:, None], new_t], axis=1).sum(0)  # f32[9]
    cur, new_per_dev = sums[0], sums[1:]

    fits = gpu_left >= p
    dev_scores = jnp.where(fits, _sigmoid_score(cur, new_per_dev), jnp.int32(-1))
    best_score, best_dev = first_max(dev_scores)
    ok = best_score >= 0  # == fits.any(): fitting devices always score >= 0
    score = jnp.where(ok, best_score, 0)
    dev = jnp.where(ok, best_dev, -1).astype(jnp.int32)
    return score, dev


def _device_terms(gpu_left, tp):
    """What the decomposed score reads of a device vector: (fitcnt[T],
    fitsum[T], total). Counts and milli sums are whole numbers under 2^24,
    exact in f32."""
    _, fitcnt, fitsum = _share_terms(gpu_left, tp)
    return fitcnt, fitsum, gpu_left.sum().astype(jnp.float32)


def _score_of_terms(cpu_left, gpu_type, terms, tp):
    """The decomposed score of a node whose device vector has `terms`."""
    fitcnt, fitsum, total = terms
    acc = is_accessible(gpu_type, tp.gpu_mask)
    isq3 = (tp.gpu_milli > 0) & acc & (fitcnt >= tp.gpu_num) & (cpu_left >= tp.cpu)
    return (tp.freq * jnp.where(isq3, total - fitsum, total)).sum()


def _decomposed_score(cpu_left, gpu_left, gpu_type, tp):
    """node_frag_score via the fit/fitsum decomposition (same value; pinned
    against ops.frag.node_frag_score by tests/test_policies.py)."""
    return _score_of_terms(cpu_left, gpu_type, _device_terms(gpu_left, tp), tp)


def _fgd_whole_node(cpu_left, mem_left, gpu_left, gpu_type, pod: PodSpec, tp):
    """Whole-GPU / CPU-only branch: Sub hypothetical (fgd_score.go:137-148)."""
    cur = _decomposed_score(cpu_left, gpu_left, gpu_type, tp)
    c2, _, g2, _, _ = sub_pod(cpu_left, mem_left, gpu_left, pod)
    score = _sigmoid_score(cur, _decomposed_score(c2, g2, gpu_type, tp))
    return score, jnp.int32(-1)


# _fgd_whole_node in two steps (the module docstring says for whom): its own
# expressions, evaluated once where they repeated bit for bit
# (tests/test_policies.py holds the two equal).


def _fgd_request_node(gpu_left, gpu_milli, gpu_num, tp):
    """The hypothetical: the terms of the device vector Sub leaves behind a
    (gpu_milli, gpu_num) request, fitting or not (as _fgd_whole_node, which
    never reads Sub's `ok`)."""
    g2, _, _ = sub_devices(gpu_left, gpu_milli, gpu_num)
    return _device_terms(g2, tp)


def _fgd_finish_node(cpu_left, gpu_left, gpu_type, pod_cpu, terms, tp):
    """The finish: one pod's score from its request's terms, against the
    node's own score (which reads nothing of the pod: under a vmap over pods
    it is computed once)."""
    cur = _decomposed_score(cpu_left, gpu_left, gpu_type, tp)
    new = _score_of_terms(cpu_left - pod_cpu, gpu_type, terms, tp)
    return _sigmoid_score(cur, new), jnp.int32(-1)


_share_nodes = jax.vmap(_fgd_share_node, in_axes=(0, 0, 0, None, None))
_whole_nodes = jax.vmap(_fgd_whole_node, in_axes=(0, 0, 0, 0, None, None))
_request_nodes = jax.vmap(_fgd_request_node, in_axes=(0, None, None, None))
_finish_nodes = jax.vmap(_fgd_finish_node, in_axes=(0, 0, 0, None, 0, None))


def _fgd_share(state: NodeState, pod: PodSpec, ctx: ScoreContext) -> PolicyResult:
    scores, dev = _share_nodes(
        state.cpu_left, state.gpu_left, state.gpu_type, pod, ctx.tp
    )
    return PolicyResult(scores, dev)


def _fgd_whole(state: NodeState, pod: PodSpec, ctx: ScoreContext) -> PolicyResult:
    scores, dev = _whole_nodes(
        state.cpu_left, state.mem_left, state.gpu_left, state.gpu_type, pod, ctx.tp
    )
    return PolicyResult(scores, dev)


def _fgd_whole_request(state: NodeState, gpu_milli, gpu_num, ctx: ScoreContext):
    """Every node's terms after one request: (fitcnt[N,T], fitsum[N,T],
    total[N])."""
    return _request_nodes(state.gpu_left, gpu_milli, gpu_num, ctx.tp)


def _fgd_whole_finish(
    state: NodeState, pod: PodSpec, terms, ctx: ScoreContext
) -> PolicyResult:
    """_fgd_whole's result for `pod`, given _fgd_whole_request's terms of
    (pod.gpu_milli, pod.gpu_num)."""
    scores, dev = _finish_nodes(
        state.cpu_left, state.gpu_left, state.gpu_type, pod.cpu, terms, ctx.tp
    )
    return PolicyResult(scores, dev)


def fgd_score(state: NodeState, pod: PodSpec, ctx: ScoreContext) -> PolicyResult:
    # pod.is_gpu_share() is a scalar (per-pod) predicate, so the cond stays a
    # real branch under the node vmap — only one branch's work is executed.
    return jax.lax.cond(
        pod.is_gpu_share(),
        lambda: _fgd_share(state, pod, ctx),
        lambda: _fgd_whole(state, pod, ctx),
    )


fgd_score.normalize = "none"
fgd_score.policy_name = "FGDScore"
fgd_score.reads_affinity = False
# branch-specialized kernels for callers that know the pod's branch
# statically (the table engine partitions pod types host-side, avoiding the
# cond→select duplication under a type-axis vmap)
# "whole_split": the whole branch in two steps, (request, finish), for a
# caller that scores a whole SET of pod types on the same nodes and knows the
# set's distinct (gpu_milli, gpu_num) requests (PodTypes.requests):
# finish(state, pod, request(state, pod.gpu_milli, pod.gpu_num, ctx), ctx) is
# branches["whole"](state, pod, ctx). A policy without it goes pod by pod.
fgd_score.branches = {
    "share": _fgd_share,
    "whole": _fgd_whole,
    "whole_split": (_fgd_whole_request, _fgd_whole_finish),
}
