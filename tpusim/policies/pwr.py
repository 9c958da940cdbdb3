"""PWR — power-aware scoring (ref: plugin/pwr_score.go).

score(node) = trunc(oldPower − newPower) after hypothetically placing the pod
(per fitting device for share-GPU pods, pwr_score.go:150-200; Sub for
whole-GPU / CPU-only, pwr_score.go:204-218). Raw scores are ≤ 0 watts-deltas;
the plugin's own NormalizeScore maps them to [0, 100] with the all-equal case
pinned to 100 (pwr_score.go:104-139).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpusim.constants import MILLI
from tpusim.ops.energy import cpu_power_watts, gpu_busy_delta_watts, gpu_power_watts
from tpusim.ops.resource import first_max, sub_pod
from tpusim.policies.base import PolicyResult, ScoreContext
from tpusim.types import NodeState, PodSpec

_NEG_INF = np.int32(-(2**31) + 1)  # stands in for Go's math.MinInt64 init


def _pwr_node(row: NodeState, pod: PodSpec):
    """Placing a pod changes power through exactly two channels: the CPU
    package count (recomputed once from cpu_left − pod.cpu) and devices
    flipping from fully-idle to working. Per-device hypotheticals are thus
    derived without re-running the whole power model 9 times; watt tables
    times small integer counts are exact in f32, so the scores equal the
    direct form (randomized old-vs-new equivalence in
    tests/test_policies.py::test_pwr_matches_direct_form)."""
    cpu_old = cpu_power_watts(row.cpu_left, row.cpu_cap, row.cpu_type)
    gpu_old = gpu_power_watts(row.gpu_left, row.gpu_cnt, row.gpu_type)
    old = cpu_old + gpu_old
    cpu_new = cpu_power_watts(row.cpu_left - pod.cpu, row.cpu_cap, row.cpu_type)
    busy_delta = gpu_busy_delta_watts(row.gpu_type)

    # share-GPU: device d flips idle->working iff it was fully idle AND the
    # pod actually takes milli from it (zero-milli share pods — num_gpu=1
    # with a sanitized-to-0 request — change nothing)
    was_idle = row.gpu_left == MILLI
    new_per_dev = cpu_new + gpu_old + jnp.where(
        was_idle & (pod.gpu_milli > 0), busy_delta, 0.0
    )
    fits = row.gpu_left >= pod.gpu_milli
    dev_scores = jnp.where(fits, (old - new_per_dev).astype(jnp.int32), _NEG_INF)
    best_score, best_dev = first_max(dev_scores)
    share_score = jnp.where(fits.any(), best_score, _NEG_INF)
    share_dev = jnp.where(fits.any(), best_dev, -1).astype(jnp.int32)

    # whole-GPU / CPU-only: Sub's taken devices flip iff previously idle
    _, _, _, dev_mask, _ = sub_pod(row.cpu_left, row.mem_left, row.gpu_left, pod)
    flips = (dev_mask & was_idle).sum().astype(jnp.float32)
    whole_score = (old - (cpu_new + gpu_old + flips * busy_delta)).astype(jnp.int32)

    is_share = pod.is_gpu_share()
    return (
        jnp.where(is_share, share_score, whole_score),
        jnp.where(is_share, share_dev, -1).astype(jnp.int32),
    )


_pwr_nodes = jax.vmap(_pwr_node, in_axes=(NodeState(0, 0, 0, 0, 0, 0, 0, 0, 0), None))


def pwr_score(state: NodeState, pod: PodSpec, ctx: ScoreContext) -> PolicyResult:
    scores, share_dev = _pwr_nodes(state, pod)
    return PolicyResult(scores, share_dev)


pwr_score.normalize = "pwr"
pwr_score.policy_name = "PWRScore"
pwr_score.reads_affinity = False
