"""DotProduct (Tetris) scoring (ref: plugin/dot_product_score.go + the
virtual-resource expansion in pkg/type/resource.go:246-381 and
pkg/utils/utils.go:1274-1342 GenerateSchedulingMatchGroups).

score = trunc(100 × max over match groups of (1 − normalized dot product)).

The reference materializes virtual node/pod vector lists per dim-extension
method; here each method is a fixed-shape masked kernel over 9 virtual slots
(8 per-device slots + 1 idle-GPU pool), vmapped over nodes:

  merge  — one [cpu_left, Σgpu_left] vector per node
  share  — one slot per partially-used fitting device + the idle pool,
           CPU shared across slots
  divide — like share but CPU prorated by the slot's share of idle GPU milli
  extend — node vector lifted to per-group GPU dims (shared devices
           individually + merged idle pool), pod vector one-hot per group

Norm methods divide both vectors by node capacity / pod request / max spec
(NormalizeVector zeroes elements whose divisor ≤ 0); `pod` norm additionally
squashes with tanh(x/10) (dot_product_score.go:76-83).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpusim.constants import (
    MAX_GPUS_PER_NODE,
    MAX_NODE_SCORE,
    MAX_SPEC_CPU,
    MAX_SPEC_GPU,
    MILLI,
)
from tpusim.ops.resource import first_max
from tpusim.policies.base import PolicyResult, ScoreContext
from tpusim.types import NodeState, PodSpec

_NEG = np.float32(-np.inf)


def _safe_div(v, n):
    """NormalizeVector semantics (utils.go:1221-1244): v/n, 0 when n <= 0."""
    return jnp.where(n > 0, v / jnp.where(n > 0, n, 1.0), 0.0)


def _first_free_dev(gpu_left):
    """First fully-free device id (AllocateExclusiveGpuId head, for share
    pods that win the idle-pool slot)."""
    free = gpu_left == MILLI
    return jnp.where(free.any(), jnp.argmax(free), -1).astype(jnp.int32)


def _merge_node(row: NodeState, pod: PodSpec, norm: str):
    total_left = row.gpu_left.sum().astype(jnp.float32)
    node_vec = jnp.stack([row.cpu_left.astype(jnp.float32), total_left])
    pod_vec = jnp.stack(
        [pod.cpu.astype(jnp.float32), pod.total_gpu_milli().astype(jnp.float32)]
    )
    if norm == "node":
        div = jnp.stack(
            [row.cpu_cap.astype(jnp.float32), (row.gpu_cnt * MILLI).astype(jnp.float32)]
        )
    elif norm == "pod":
        div = pod_vec
    else:  # max
        div = jnp.asarray([MAX_SPEC_CPU, MAX_SPEC_GPU], jnp.float32)
    dot = (_safe_div(node_vec, div) * _safe_div(pod_vec, div)).sum() / 2.0
    if norm == "pod":
        dot = jnp.tanh(dot / 10.0)
    score = jnp.where(row.cpu_left >= pod.cpu, 1.0 - dot, _NEG)
    return score, jnp.int32(-1)


def _share_divide_node(row: NodeState, pod: PodSpec, norm: str, divide: bool):
    total_req = pod.total_gpu_milli()
    total_left = row.gpu_left.sum()
    idle_cnt = (row.gpu_left == MILLI).sum()
    slot_real = jnp.arange(MAX_GPUS_PER_NODE) < row.gpu_cnt

    # 8 per-device slots: partially-used fitting devices, share branch only
    # (resource.go:315-341); slot 8: the idle-GPU pool (resource.go:344-365).
    dev_active = (
        (total_req < MILLI)
        & slot_real
        & (row.gpu_left < MILLI)
        & (row.gpu_left >= total_req)
    )
    pool_active = total_req <= idle_cnt * MILLI
    pool_gpu = (idle_cnt * MILLI).astype(jnp.float32)

    slot_gpu = jnp.concatenate([row.gpu_left.astype(jnp.float32), pool_gpu[None]])
    active = jnp.concatenate([dev_active, pool_active[None]])
    cpu_f = row.cpu_left.astype(jnp.float32)
    if divide:
        slot_cpu = _safe_div(cpu_f * slot_gpu, total_left.astype(jnp.float32))
    else:
        slot_cpu = jnp.full(MAX_GPUS_PER_NODE + 1, cpu_f)

    pod_vec = jnp.stack(
        [pod.cpu.astype(jnp.float32), total_req.astype(jnp.float32)]
    )
    if norm == "node":
        div_cpu = row.cpu_cap.astype(jnp.float32)
        div_gpu = (row.gpu_cnt * MILLI).astype(jnp.float32)
    elif norm == "pod":
        div_cpu = pod_vec[0]
        div_gpu = pod_vec[1]
    else:
        div_cpu = jnp.float32(MAX_SPEC_CPU)
        div_gpu = jnp.float32(MAX_SPEC_GPU)

    dots = (
        _safe_div(slot_cpu, div_cpu) * _safe_div(pod_vec[0], div_cpu)
        + _safe_div(slot_gpu, div_gpu) * _safe_div(pod_vec[1], div_gpu)
    ) / 2.0
    if norm == "pod":
        dots = jnp.tanh(dots / 10.0)
    scores = jnp.where((row.cpu_left >= pod.cpu) & active, 1.0 - dots, _NEG)
    best_score, best = first_max(scores)
    share_dev = jnp.where(
        best < MAX_GPUS_PER_NODE, best, _first_free_dev(row.gpu_left)
    )
    return best_score, jnp.where(best_score == _NEG, -1, share_dev)


def _extend_node(row: NodeState, pod: PodSpec, norm: str):
    total_req = pod.total_gpu_milli()
    idle_cnt = (row.gpu_left == MILLI).sum()
    slot_real = jnp.arange(MAX_GPUS_PER_NODE) < row.gpu_cnt

    # Formalized groups (resource.go:217-244): devices with 0 < left < MILLI
    # individually, plus one merged idle group.
    dev_group = slot_real & (row.gpu_left > 0) & (row.gpu_left < MILLI)
    pool_group = idle_cnt > 0
    group_active = jnp.concatenate([dev_group, pool_group[None]])
    group_left = jnp.concatenate(
        [row.gpu_left.astype(jnp.float32), (idle_cnt * MILLI).astype(jnp.float32)[None]]
    )
    n_groups = group_active.sum().astype(jnp.float32)

    # One pod vector per group with enough room (resource.go:263-287); each
    # match group's dot = cpu term + that group's gpu term; vector length for
    # the /len(podVec) normalization is 1 + n_groups.
    cand = group_active & (group_left >= total_req.astype(jnp.float32))
    if norm == "node":
        div_cpu = row.cpu_cap.astype(jnp.float32)
        div_gpu = (row.gpu_cnt * MILLI).astype(jnp.float32)
    elif norm == "pod":
        div_cpu = pod.cpu.astype(jnp.float32)
        div_gpu = total_req.astype(jnp.float32)
    else:
        div_cpu = jnp.float32(MAX_SPEC_CPU)
        div_gpu = jnp.float32(MAX_SPEC_GPU)

    cpu_term = _safe_div(row.cpu_left.astype(jnp.float32), div_cpu) * _safe_div(
        pod.cpu.astype(jnp.float32), div_cpu
    )
    gpu_terms = _safe_div(group_left, div_gpu) * _safe_div(
        total_req.astype(jnp.float32), div_gpu
    )
    dots = (cpu_term + gpu_terms) / jnp.maximum(1.0 + n_groups, 1.0)
    if norm == "pod":
        dots = jnp.tanh(dots / 10.0)
    scores = jnp.where((row.cpu_left >= pod.cpu) & cand, 1.0 - dots, _NEG)
    best_score, best = first_max(scores)
    share_dev = jnp.where(
        best < MAX_GPUS_PER_NODE, best, _first_free_dev(row.gpu_left)
    )
    return best_score, jnp.where(best_score == _NEG, -1, share_dev)


from functools import lru_cache


@lru_cache(maxsize=None)
def make_dotprod(dim_ext: str = "share", norm: str = "max"):
    """Build the DotProduct policy for a (dimExtMethod, normMethod) config
    (ref: example scheduler configs use share/max). Cached per config so
    repeated Simulator constructions share one kernel object (and therefore
    one jit cache entry for the replay engines built around it)."""
    assert dim_ext in ("merge", "share", "divide", "extend"), dim_ext
    assert norm in ("node", "pod", "max"), norm

    def per_node(row: NodeState, pod: PodSpec):
        if dim_ext == "merge":
            s, dev = _merge_node(row, pod, norm)
        elif dim_ext in ("share", "divide"):
            s, dev = _share_divide_node(row, pod, norm, dim_ext == "divide")
        else:
            s, dev = _extend_node(row, pod, norm)
        # empty match-group set → MinNodeScore (dot_product_score.go:96-98);
        # int64() conversion truncates toward zero.
        raw = jnp.where(
            s == _NEG, 0, (MAX_NODE_SCORE * s).astype(jnp.int32)
        )
        return raw, dev

    nodes = jax.vmap(per_node, in_axes=(NodeState(0, 0, 0, 0, 0, 0, 0, 0, 0), None))

    def dotprod_score(state: NodeState, pod: PodSpec, ctx: ScoreContext) -> PolicyResult:
        scores, share_dev = nodes(state, pod)
        return PolicyResult(scores, share_dev)

    dotprod_score.normalize = "none"
    dotprod_score.policy_name = "DotProductScore"
    dotprod_score.reads_affinity = False
    dotprod_score.dim_ext = dim_ext
    dotprod_score.norm = norm
    return dotprod_score
