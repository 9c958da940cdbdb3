"""Random scoring (ref: plugin/random_score.go:42-68).

PreScore draws one node uniformly; Score gives it 100 and everyone else 0.
The reference draws from the PreScore node list (the feasible set), so the
draw here is uniform over ctx.feasible.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpusim.constants import MAX_NODE_SCORE
from tpusim.policies.base import PolicyResult, ScoreContext
from tpusim.types import NodeState, PodSpec


def random_score(state: NodeState, pod: PodSpec, ctx: ScoreContext) -> PolicyResult:
    n = state.num_nodes
    u = jax.random.uniform(ctx.rng, (n,))
    pick = jnp.argmax(jnp.where(ctx.feasible, u, -1.0))
    scores = jnp.where(jnp.arange(n) == pick, MAX_NODE_SCORE, 0).astype(jnp.int32)
    share_dev = jnp.full(n, -1, jnp.int32)
    return PolicyResult(scores, share_dev)


random_score.normalize = "none"
random_score.policy_name = "RandomScore"
random_score.reads_affinity = False
