"""The learned scorer as a first-class policy (ISSUE 14).

PR 8 tunes a weight vector over FIXED built-in policies; this module
makes the policy itself learnable while keeping every engine contract
intact, by exploiting one identity: a LINEAR model over a per-node
feature row IS a weight vector over per-feature score kernels. Each
feature is a policy-kernel-shaped pure function of (node state, pod
spec) — exactly the quantities the score tables and the series plane
already compute (free/total GPU & CPU milli, per-device free-mask
stats, frag-category terms, the DOWN flag) — emitting i32 raw scores in
the [0, MAX_NODE_SCORE] score-table vocabulary with normalize="none".
A learned policy is then the family

    policies = [("LearnedScore[f]", theta_f) for f in features]

and the model parameters theta ARE the engines' traced i32 weight
operand (ISSUE 6): the sequential, flat-table, blocked-table, and
shard_map engines replay the learned policy bit-identically like any
built-in (their tables hold the feature rows; selectHost consumes
sum theta_f * feature_f), a parameter change is a device call, not a
recompile, `run_sweep` vmaps a POPULATION of parameter vectors in one
compiled scan (the ES trainer's rollout, learn.loop), the decision
flight recorder's raw/norm columns become per-FEATURE contributions
(`tpusim explain` attributes a learned choice exactly like a built-in's,
sum weight*norm == the recorded selectHost total), and the svc job plane
serves it unchanged (policies are just [name, weight] pairs).

The optional BUCKETED form appends indicator features (100 iff the
node's GPU occupancy falls in bucket k — the series plane's 10-bucket
node-utilization vocabulary, obs.series.UTIL_BUCKETS): linear over
indicators is a small-table/piecewise-constant model, same machinery.

Artifacts: a trained parameter vector persists as a digest-signed JSON
document (io.storage.write_signed_json — the lease/result discipline;
torn or edited files fail loudly) carrying the feature vocabulary it was
trained over, so `apply --policy LearnedScore:file.json`,
`serve --policy-preset NAME=file.json`, and submit jobs all replay the
exact same i32 family.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from tpusim.constants import MAX_GPUS_PER_NODE, MAX_NODE_SCORE, MILLI
from tpusim.ops.frag import frag_class, node_frag_score
from tpusim.policies.base import PolicyResult, ScoreContext
from tpusim.policies.fgd import fgd_score

POLICY_SCHEMA = "tpusim-learned-policy/1"

LEARNED_PREFIX = "LearnedScore["

# i32 parameter bounds of the learned family: features are <= 100, so
# |theta| <= 4000 keeps any total well inside i32 (4000 * 100 * F). The
# sign is meaningful — "more free GPU" can hurt a packing objective —
# which is why the learned lane's default bounds are symmetric where the
# built-in weight lane's are [0, 4000].
THETA_LO = -4000
THETA_HI = 4000


def _pct(num, den):
    """floor(100 * num / den) clipped into the [0, MAX_NODE_SCORE] score
    vocabulary — exact integer math, no f32 in the elementwise features."""
    val = num * MAX_NODE_SCORE // jnp.maximum(den, 1)
    return jnp.clip(val, 0, MAX_NODE_SCORE).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Feature kernels: i32[N] in [0, 100], pure in (node row, pod spec, tp)
# ---------------------------------------------------------------------------


def _free_gpu_pct(state, pod, ctx):
    """Idle GPU milli as a percent of the node's GPU capacity (DOWN
    nodes carry gpu_left == 0, so they read 0 without special casing)."""
    return _pct(state.gpu_left.sum(-1), state.gpu_cnt * MILLI)


def _free_cpu_pct(state, pod, ctx):
    return _pct(jnp.maximum(state.cpu_left, 0), state.cpu_cap)


def _free_mem_pct(state, pod, ctx):
    """mem_left == -1 is the DOWN sentinel — clipped to 0 here; the
    dedicated down feature carries the flag itself."""
    return _pct(jnp.maximum(state.mem_left, 0), state.mem_cap)


def _free_gpus_pct(state, pod, ctx):
    """Fully idle devices as a percent of the node's device count — the
    per-device free-mask statistic the PWR/packing family reduces."""
    return _pct((state.gpu_left == MILLI).sum(-1), state.gpu_cnt)


def _fit_dev_pct(state, pod, ctx):
    """Devices that could host one unit of this pod's per-GPU request,
    over the fixed MAX_GPUS_PER_NODE width (pad devices hold 0 milli and
    never fit). 0 for CPU-only pods."""
    need = jnp.maximum(pod.gpu_milli, 1)
    fits = (state.gpu_left >= need).sum(-1)
    return jnp.where(
        pod.total_gpu_milli() > 0,
        _pct(fits, jnp.int32(MAX_GPUS_PER_NODE)),
        0,
    ).astype(jnp.int32)


def _max_dev_free_pct(state, pod, ctx):
    """Largest per-device idle share — distinguishes one-nearly-free
    device from the same milli spread thin (what the share-GPU packers
    care about)."""
    return _pct(state.gpu_left.max(-1), jnp.int32(MILLI))


def _q3_sat_pct(state, pod, ctx):
    """Percent of the typical-pod frequency mass this node can host
    outright (frag class Q3) — the satisfaction half of the FGD frag
    decomposition, per node."""
    from tpusim.constants import Q3_SATISFIED

    def one(cpu_left, gpu_left, gpu_type):
        cls = frag_class(cpu_left, gpu_left, gpu_type, ctx.tp)
        sat = jnp.where(cls == Q3_SATISFIED, ctx.tp.freq, 0.0).sum()
        return jnp.clip(
            jnp.floor(sat * MAX_NODE_SCORE), 0, MAX_NODE_SCORE
        ).astype(jnp.int32)

    return jax.vmap(one)(state.cpu_left, state.gpu_left, state.gpu_type)


def _frag_pct(state, pod, ctx):
    """The node's own frag score (every class but Q3) as a percent of
    its idle GPU milli — the frag-category term of the series plane,
    normalized per node so it lives in the score vocabulary."""

    def one(cpu_left, gpu_left, gpu_type):
        total = gpu_left.sum().astype(jnp.float32)
        score = node_frag_score(cpu_left, gpu_left, gpu_type, ctx.tp)
        pct = jnp.floor(
            score * MAX_NODE_SCORE / jnp.maximum(total, 1.0)
        )
        return jnp.clip(pct, 0, MAX_NODE_SCORE).astype(jnp.int32)

    return jax.vmap(one)(state.cpu_left, state.gpu_left, state.gpu_type)


def _down(state, pod, ctx):
    """100 on a DOWN node (the mem_left == -1 fault sentinel). Filter
    already rejects DOWN nodes, so this never flips a selection — it
    exists so the vocabulary is complete for disruption-aware objectives
    and for explain's attribution rows."""
    return jnp.where(state.mem_left < 0, MAX_NODE_SCORE, 0).astype(jnp.int32)


def _frag_delta(state, pod, ctx):
    """The FGD frag-gradient term: how much placing THIS pod here
    improves the cluster frag outlook (the sigmoid-scored frag delta,
    policies.fgd). The one pod-interaction feature — a learned theta
    putting all mass here IS the FGDScore argmax, which is what makes
    imitation of an FGD teacher exactly representable."""
    return fgd_score(state, pod, ctx)


def _util_bucket(k: int):
    """Indicator feature (0 | 100) of GPU-occupancy bucket k — the
    series plane's node-utilization histogram math (obs.series
    cluster_stats: bucket = used * B // cap), restricted to UP GPU
    nodes. Linear over the 10 indicators = a bucketed table model."""
    from tpusim.obs.series import UTIL_BUCKETS

    def kernel(state, pod, ctx):
        cap = state.gpu_cnt * MILLI
        used = cap - state.gpu_left.sum(-1)
        bucket = jnp.clip(
            used * UTIL_BUCKETS // jnp.maximum(cap, 1), 0, UTIL_BUCKETS - 1
        )
        live = (state.mem_left >= 0) & (state.gpu_cnt > 0)
        return jnp.where(
            live & (bucket == k), MAX_NODE_SCORE, 0
        ).astype(jnp.int32)

    return kernel


_FEATURE_IMPLS = {
    "frag_delta": _frag_delta,
    "free_gpu_pct": _free_gpu_pct,
    "free_cpu_pct": _free_cpu_pct,
    "free_mem_pct": _free_mem_pct,
    "free_gpus_pct": _free_gpus_pct,
    "fit_dev_pct": _fit_dev_pct,
    "max_dev_free_pct": _max_dev_free_pct,
    "q3_sat_pct": _q3_sat_pct,
    "frag_pct": _frag_pct,
    "down": _down,
}
for _k in range(10):
    _FEATURE_IMPLS[f"util_bucket{_k}"] = _util_bucket(_k)

# the two shipped vocabularies; artifacts name their features explicitly
# so future vocabulary growth cannot silently re-interpret old thetas
LINEAR_FEATURES = (
    "frag_delta", "free_gpu_pct", "free_cpu_pct", "free_mem_pct",
    "free_gpus_pct", "fit_dev_pct", "max_dev_free_pct", "q3_sat_pct",
    "frag_pct", "down",
)
BUCKETED_FEATURES = LINEAR_FEATURES + tuple(
    f"util_bucket{k}" for k in range(10)
)
FEATURE_SETS = {"linear": LINEAR_FEATURES, "bucketed": BUCKETED_FEATURES}

FEATURE_NAMES = tuple(_FEATURE_IMPLS)

_KERNEL_CACHE: dict = {}


def learned_policy_name(feature: str) -> str:
    return f"{LEARNED_PREFIX}{feature}]"


def parse_learned_name(name: str):
    """'LearnedScore[feat]' -> 'feat', or None for non-learned names."""
    if name.startswith(LEARNED_PREFIX) and name.endswith("]"):
        return name[len(LEARNED_PREFIX):-1]
    return None


def is_learned_name(name: str) -> bool:
    feat = parse_learned_name(name)
    return feat is not None and feat in _FEATURE_IMPLS


def feature_policy(feature: str):
    """The singleton policy kernel of one feature — singletons because
    the engine caches key on kernel object identity (the make_policy
    contract every built-in honors). normalize='none': the raw feature
    value IS what the weighted sum consumes, which keeps the blocked /
    shard selects on their cheap none-normalize paths and makes
    explain's per-feature arithmetic exact by construction."""
    if feature not in _FEATURE_IMPLS:
        raise KeyError(
            f"unknown learned feature {feature!r} (known: "
            f"{', '.join(FEATURE_NAMES)})"
        )
    if feature not in _KERNEL_CACHE:
        impl = _FEATURE_IMPLS[feature]

        def kernel(state, pod, ctx: ScoreContext,
                   _impl=impl) -> PolicyResult:
            res = _impl(state, pod, ctx)
            if isinstance(res, PolicyResult):
                return res
            return PolicyResult(
                res, jnp.full(state.num_nodes, -1, jnp.int32)
            )

        kernel.normalize = "none"
        kernel.policy_name = learned_policy_name(feature)
        kernel.reads_affinity = False
        if feature == "frag_delta":
            # branch-specialized halves for the table engine's static
            # share/whole type partition (the fgd idiom)
            kernel.branches = dict(fgd_score.branches)
        _KERNEL_CACHE[feature] = kernel
    return _KERNEL_CACHE[feature]


def default_theta(features) -> list:
    """The FGD-equivalent starting point: all mass on the frag-gradient
    feature. Its argmax is FGDScore's argmax exactly (same raw rows,
    same tie-break), so it doubles as the tuned-vs-default baseline the
    holdout report compares against."""
    return [1000 if f == "frag_delta" else 0 for f in features]


def learned_policies(theta=None, features=LINEAR_FEATURES):
    """[(name, theta_f)] pairs — the SimulatorConfig.policies /
    TuneConfig form of a learned policy. theta None = default_theta."""
    features = tuple(features)
    for f in features:
        if f not in _FEATURE_IMPLS:
            raise ValueError(
                f"unknown learned feature {f!r} (known: "
                f"{', '.join(FEATURE_NAMES)})"
            )
    if theta is None:
        theta = default_theta(features)
    theta = [int(t) for t in theta]
    if len(theta) != len(features):
        raise ValueError(
            f"theta has {len(theta)} entries for {len(features)} features"
        )
    for t in theta:
        if not THETA_LO <= t <= THETA_HI:
            raise ValueError(
                f"theta entry {t} outside the i32 export bounds "
                f"[{THETA_LO}, {THETA_HI}]"
            )
    return [(learned_policy_name(f), t) for f, t in zip(features, theta)]


# ---------------------------------------------------------------------------
# The digest-signed policy artifact
# ---------------------------------------------------------------------------


def save_policy_artifact(path: str, theta, features=LINEAR_FEATURES,
                         meta=None) -> str:
    """Persist a trained parameter vector as a signed artifact (atomic,
    payload-digest header — a torn/edited file fails loudly on load).
    The document is exactly what load_policy_artifact hands back, and
    the features list pins the vocabulary the theta indexes."""
    from tpusim.io import storage

    pairs = learned_policies(theta, features)  # validates
    doc = {
        "features": [str(f) for f in features],
        "theta": [int(w) for _, w in pairs],
        "meta": dict(meta or {}),
    }
    return storage.write_signed_json(
        path, {"schema": POLICY_SCHEMA}, doc
    )


def load_policy_artifact(path: str):
    """(features tuple, theta list, meta dict) from a signed artifact;
    raises ValueError on torn/edited/wrong-schema files or unknown
    features (a vocabulary-drifted artifact must not silently score
    different quantities)."""
    from tpusim.io import storage

    _, doc = storage.read_signed_json(path, POLICY_SCHEMA)
    features = tuple(str(f) for f in doc.get("features", ()))
    theta = [int(t) for t in doc.get("theta", ())]
    learned_policies(theta, features)  # validates names/bounds/length
    return features, theta, dict(doc.get("meta") or {})


def policies_from_artifact(path: str):
    """Artifact file -> the [(name, weight)] policy pairs every config
    surface consumes (SimulatorConfig, job documents, tune)."""
    features, theta, _ = load_policy_artifact(path)
    return learned_policies(theta, features)


def parse_policy_spec(spec: str):
    """One `--policy` value -> [(name, weight)] pairs.

    Forms: 'LearnedScore:PATH' (a signed artifact), 'learned' /
    'learned-bucketed' (the default-theta families), or a built-in
    policy name at weight 1000 (the reference's single-plugin form)."""
    from tpusim.policies import POLICY_NAMES

    if spec.startswith("LearnedScore:"):
        path = spec[len("LearnedScore:"):]
        if not os.path.isfile(path):
            raise ValueError(f"--policy artifact not found: {path!r}")
        return policies_from_artifact(path)
    if spec in ("learned", "learned-linear"):
        return learned_policies()
    if spec == "learned-bucketed":
        return learned_policies(features=BUCKETED_FEATURES)
    if spec in POLICY_NAMES:
        return [(spec, 1000)]
    raise ValueError(
        f"unknown --policy {spec!r}: want LearnedScore:FILE.json, "
        "learned, learned-bucketed, or a built-in policy name "
        f"({', '.join(POLICY_NAMES)})"
    )
