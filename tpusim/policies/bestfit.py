"""BestFit scoring (ref: plugin/best_fit_score.go:66-97).

score = trunc((1 − Σ_i w_i (free_i − req_i)/maxSpec_i) × 100), dims = {cpu,
gpu-milli}, w = 0.5/0.5, maxSpec = 128000 milli-CPU / 8000 milli-GPU.
Min-max normalized by the shared NormalizeScore extension.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpusim.constants import MAX_NODE_SCORE, MAX_SPEC_CPU, MAX_SPEC_GPU
from tpusim.policies.base import PolicyResult, ScoreContext
from tpusim.types import NodeState, PodSpec


def bestfit_score(state: NodeState, pod: PodSpec, ctx: ScoreContext) -> PolicyResult:
    free_cpu = state.cpu_left.astype(jnp.float32)
    free_gpu = state.total_gpu_left().astype(jnp.float32)
    req_cpu = pod.cpu.astype(jnp.float32)
    req_gpu = pod.total_gpu_milli().astype(jnp.float32)
    s = (free_cpu - req_cpu) / MAX_SPEC_CPU * 0.5 + (free_gpu - req_gpu) / MAX_SPEC_GPU * 0.5
    scores = jnp.floor((1.0 - s) * MAX_NODE_SCORE).astype(jnp.int32)
    # free < req would be a framework error post-Filter (best_fit_score.go:79);
    # masked rows never win anyway.
    share_dev = jnp.full(state.num_nodes, -1, jnp.int32)
    return PolicyResult(scores, share_dev)


bestfit_score.normalize = "minmax"
bestfit_score.policy_name = "BestFitScore"
bestfit_score.reads_affinity = False
