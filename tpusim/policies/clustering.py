"""GpuClustering scoring (ref: plugin/gpu_clustering_score.go:32-56).

Quartile by the node's GPU-affinity profile vs the pod's affinity class
(share-gpu / N-gpu, open-gpu-share/utils/pod.go:111-123), plus an
integer-arithmetic packing term 25·(8000 − totalGpuLeft)//8000 inside each
quartile:

  (75,100] node whose only affinity class equals the pod's
  (50, 75] node with several classes including the pod's
  (25, 50] idle node (no GPU pods at all)
  ( 0, 25] node with only different classes
  0        pod requests no GPU
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpusim.constants import MAX_NODE_SCORE, MAX_SPEC_GPU, MILLI
from tpusim.policies.base import PolicyResult, ScoreContext
from tpusim.types import NodeState, PodSpec

_Q = MAX_NODE_SCORE // 4  # 25


def pod_affinity_class(pod: PodSpec):
    """share-gpu → 0, N whole GPUs → N, no GPU → -1 (ref: pod.go:111-123)."""
    share = (pod.gpu_num == 1) & (pod.gpu_milli < MILLI)
    cls = jnp.where(share, 0, pod.gpu_num)
    return jnp.where(pod.gpu_num == 0, -1, cls).astype(jnp.int32)


def clustering_score(state: NodeState, pod: PodSpec, ctx: ScoreContext) -> PolicyResult:
    cls = pod_affinity_class(pod)
    counts = state.aff_cnt  # i32[N, 9]
    n_classes = (counts > 0).sum(-1)  # len(GpuAffinity)
    has_cls = jnp.take_along_axis(
        counts, jnp.maximum(cls, 0)[None].repeat(counts.shape[0], 0)[:, None], axis=1
    )[:, 0] > 0

    pack = _Q * (MAX_SPEC_GPU - state.total_gpu_left()) // MAX_SPEC_GPU  # i32[N]
    base = jnp.where(
        has_cls,
        jnp.where(n_classes == 1, 3 * _Q, 2 * _Q),
        jnp.where(n_classes == 0, _Q, 0),
    )
    scores = jnp.where(cls < 0, 0, base + pack).astype(jnp.int32)
    share_dev = jnp.full(state.num_nodes, -1, jnp.int32)
    return PolicyResult(scores, share_dev)


clustering_score.normalize = "none"
clustering_score.policy_name = "GpuClusteringScore"
clustering_score.reads_affinity = True
