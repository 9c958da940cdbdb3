"""GpuPacking scoring (ref: plugin/gpu_packing_score.go:67-117), 3 tiers:

  case-1 share used GPUs:          max(100 − Σ trunc(left·100/1000)/10, 50)
  case-2 dip into fully-free GPUs: max(50 − #fullyFreeUsed, 33)
  case-3 fully-free node:          max(33 − #freeGpus, #freeGpus)

Allocation simulation mirrors Sub: fitting devices taken least-free-first
(stable by index) until gpu_num are found.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpusim.constants import MAX_NODE_SCORE, MILLI
from tpusim.ops.resource import select_devices_packed
from tpusim.policies.base import PolicyResult, ScoreContext
from tpusim.types import NodeState, PodSpec

_T3 = MAX_NODE_SCORE // 3  # 33
_T2 = MAX_NODE_SCORE // 2  # 50


def _packing_node(gpu_left, gpu_cnt, pod: PodSpec):
    fully_free = (gpu_left == MILLI).sum().astype(jnp.int32)

    # case-3: every device on the node is idle (gpu_packing_score.go:76-81)
    case3 = jnp.maximum(_T3 - fully_free, fully_free)

    # simulate the ascending-packed allocation (gpu_packing_score.go:83-100)
    dev_mask, ok = select_devices_packed(gpu_left, pod.gpu_milli, pod.gpu_num)
    free_used = (dev_mask & (gpu_left == MILLI)).sum().astype(jnp.int32)

    # case-2: had to consume fully-free devices
    case2 = jnp.maximum(_T2 - free_used, _T3)

    # case-1: only shared (partially-used) devices
    ratio = jnp.where(dev_mask, gpu_left * 100 // MILLI, 0).sum().astype(jnp.int32)
    case1 = jnp.maximum(MAX_NODE_SCORE - ratio // 10, _T2)

    score = jnp.where(
        fully_free == gpu_cnt,
        case3,
        jnp.where(~ok, 0, jnp.where(free_used > 0, case2, case1)),
    )
    # non-GPU pods score MinNodeScore (gpu_packing_score.go:36-39)
    return jnp.where(pod.total_gpu_milli() > 0, score, 0).astype(jnp.int32)


_packing_nodes = jax.vmap(_packing_node, in_axes=(0, 0, None))


def packing_score(state: NodeState, pod: PodSpec, ctx: ScoreContext) -> PolicyResult:
    scores = _packing_nodes(state.gpu_left, state.gpu_cnt, pod)
    share_dev = jnp.full(state.num_nodes, -1, jnp.int32)
    return PolicyResult(scores, share_dev)


packing_score.normalize = "none"
packing_score.policy_name = "GpuPackingScore"
packing_score.reads_affinity = False
