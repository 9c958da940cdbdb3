"""Simon scoring (ref: plugin/simon.go:47-71).

score = round(100 × max over resource dims of share(podReq_d, alloc_d − req_d))
with share(a, t) = a/t, or 1 when t == 0 and a > 0 (algo/greed.go:78-91).
Dims: milli-CPU, memory MiB, total milli-GPU. NOTE the reference reads
`node.Status.Allocatable` — static CAPACITY, which the fake cluster never
decrements on binding (usage lives in pod objects) — so the score base is
capacity, not free resources. Min-max normalized by the shared
NormalizeScore extension.
"""

from __future__ import annotations

import jax.numpy as jnp

from tpusim.constants import MAX_NODE_SCORE, MILLI
from tpusim.policies.base import PolicyResult, ScoreContext
from tpusim.types import NodeState, PodSpec


def _share(alloc, total):
    return jnp.where(
        total == 0,
        jnp.where(alloc == 0, 0.0, 1.0),
        alloc / jnp.where(total == 0, 1.0, total),
    )


def simon_score(state: NodeState, pod: PodSpec, ctx: ScoreContext) -> PolicyResult:
    req = [
        pod.cpu.astype(jnp.float32),
        pod.mem.astype(jnp.float32),
        pod.total_gpu_milli().astype(jnp.float32),
    ]
    alloc = [
        state.cpu_cap.astype(jnp.float32),
        state.mem_cap.astype(jnp.float32),
        (state.gpu_cnt * MILLI).astype(jnp.float32),
    ]
    res = jnp.zeros(state.num_nodes, jnp.float32)
    for a, f in zip(req, alloc):
        res = jnp.maximum(res, _share(a, f - a))
    scores = jnp.round(MAX_NODE_SCORE * res).astype(jnp.int32)
    share_dev = jnp.full(state.num_nodes, -1, jnp.int32)
    return PolicyResult(scores, share_dev)


simon_score.normalize = "minmax"
simon_score.policy_name = "Simon"
simon_score.reads_affinity = False
