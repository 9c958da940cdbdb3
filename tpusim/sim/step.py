"""One scheduling cycle as a pure function (replaces vendored
scheduleOne: Filter → Score → Normalize → selectHost → Reserve → Bind,
generic_scheduler.go:143-210 + plugin/open_gpu_share.go Reserve).

The reference's per-cycle node parallelism (a 16-way parallelize helper over
nodes) becomes a vmap over the node axis; the annotation/patch round-trips of
Reserve/Bind become a scatter update of the NodeState arrays.
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpusim.constants import MAX_GPUS_PER_NODE, MILLI
from tpusim.ops.resource import (
    allocate_share_best,
    allocate_share_random,
    allocate_share_worst,
    allocate_two_pointer,
    can_allocate,
    is_accessible,
)
from tpusim.policies import ScoreContext, minmax_normalize_i32, pwr_normalize_i32
from tpusim.policies.clustering import pod_affinity_class
from tpusim.sim.lane_write import add_entry, add_row, set_row
from tpusim.types import NodeState, PodSpec

_INT_MAX = np.int32(np.iinfo(np.int32).max)


def resolve_weights(policies, weights=None) -> jnp.ndarray:
    """The per-policy weight vector as an i32[num_pol] OPERAND (ISSUE 6).

    Weights used to be trace-time Python constants (`jnp.int32(weight)`
    baked into every engine's jaxpr), so each what-if weight change paid
    a full recompile. Every engine now multiplies by this traced vector
    instead; None resolves to the static weights carried in `policies`,
    which is bit-identical to the former baked form (the same i32
    multiply on the same values — only the jaxpr's operand/constant
    split moves). The config-axis sweep vmaps over a [B, num_pol] stack
    of these."""
    if weights is None:
        return jnp.asarray([w for _, w in policies], jnp.int32)
    w = jnp.asarray(weights, jnp.int32)
    if w.shape != (len(policies),):
        raise ValueError(
            f"weights shape {w.shape} does not match the {len(policies)} "
            "configured policies"
        )
    return w


# Score policies whose kernel hands its own Reserve-phase GPU choice to the
# gpuSelMethod machinery (ref: the allocateGpuIdFunc registry,
# plugin/open_gpu_share.go:39 + fgd_score.go:36 / pwr_score.go:41 /
# dot_product_score.go:37)
SELF_SELECT_POLICIES = frozenset({"FGDScore", "PWRScore", "DotProductScore"})


def filter_nodes(state: NodeState, pod: PodSpec) -> jnp.ndarray:
    """Filter phase → bool[N] feasibility.

    Combines the default NodeResourcesFit (cpu/mem request fit) with the
    Open-Gpu-Share Filter (open_gpu_share.go:81-118): GPU pods need a GPU
    node, a matching GPU model, and an AllocateGpuId packing
    (gpunodeinfo.go:136-204 — can_allocate reproduces its feasibility).
    """
    # node-axis padding rows (parallel.pad_nodes) need no special casing:
    # they carry mem_left == -1, failing the mem check for every request
    fit = (state.cpu_left >= pod.cpu) & (state.mem_left >= pod.mem)
    # nodeSelector pinning (snapshot re-bind, export.go:44-58): a pinned pod
    # is only feasible on its pinned node; pinned == -1 means unconstrained.
    n = state.num_nodes
    fit = fit & (
        (pod.pinned < 0) | (jnp.arange(n, dtype=jnp.int32) == pod.pinned)
    )
    gpu_ok = (
        (state.gpu_cnt > 0)
        & is_accessible(state.gpu_type, pod.gpu_mask)
        & jax.vmap(can_allocate, in_axes=(0, None, None))(
            state.gpu_left, pod.gpu_milli, pod.gpu_num
        )
    )
    needs_gpu = pod.total_gpu_milli() > 0
    return fit & (~needs_gpu | gpu_ok)


class Placement(NamedTuple):
    """Result of one cycle. node == -1 → unschedulable (the reference marks
    the pod condition and deletes it, simulator.go:444-455)."""

    node: jnp.ndarray  # i32, -1 = failed
    dev_mask: jnp.ndarray  # bool[8] devices taken (all False for CPU pods)


def _choose_share_device(gpu_left, pod, policy_dev, gpu_sel: str, key):
    """Reserve-phase device choice for a share-GPU pod
    (open_gpu_share.go:252-343): the configured gpuSelMethod either delegates
    to the scoring policy's own pick or uses best/worst/random fit."""
    if gpu_sel == "best":
        return allocate_share_best(gpu_left, pod.gpu_milli)
    if gpu_sel == "worst":
        return allocate_share_worst(gpu_left, pod.gpu_milli)
    if gpu_sel == "random":
        return allocate_share_random(gpu_left, pod.gpu_milli, key)
    # policy-provided (FGDScore / PWRScore / DotProductScore): fall back to
    # best-fit if the policy had no pick (defensive; post-Filter it has one).
    return jnp.where(
        policy_dev >= 0, policy_dev, allocate_share_best(gpu_left, pod.gpu_milli)
    )


def choose_devices(gpu_left, pod, policy_dev_scalar, gpu_sel: str, key):
    """Reserve-phase device mask for one node row: share-GPU pods go through
    the gpuSelMethod machinery (_choose_share_device), whole/multi-GPU pods
    through the two-pointer pack in device-index order (gpunodeinfo.go:
    182-201; == first fully-free devices when milli == 1000). Shared by the
    global select_and_bind and the shard_map engine's owner-local bind."""
    share_dev = _choose_share_device(gpu_left, pod, policy_dev_scalar, gpu_sel, key)
    share_mask = jax.nn.one_hot(share_dev, MAX_GPUS_PER_NODE, dtype=jnp.bool_) & (
        share_dev >= 0
    )
    units, _ = allocate_two_pointer(gpu_left, pod.gpu_milli, pod.gpu_num)
    whole_mask = units > 0
    is_share = pod.is_gpu_share()
    has_gpu = pod.total_gpu_milli() > 0
    return jnp.where(has_gpu, jnp.where(is_share, share_mask, whole_mask), False)


def packed_argmax(
    total: jnp.ndarray,  # i32[M] scores (any granularity: nodes or blocks)
    valid: jnp.ndarray,  # bool[M]
    rank: jnp.ndarray,  # i32[M] tie-break rank (smaller wins)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """selectHost's lexicographic (max score, min tie-break rank) argmax —
    the ONE packed-key reduction shared by the sequential oracle, the flat
    table engine, and the blocked table engine (which runs it twice: per
    block over nodes, then globally over block summaries; identical combine
    in, bit-identical winner out). Returns (index, best_score, ok).

    Two reductions: max score over valid entries, then argmax of -rank
    among the winners (= min rank); validity of the result is read off the
    winner key instead of a third reduction
    (generic_scheduler.go:187-212)."""
    best = jnp.max(jnp.where(valid, total, -_INT_MAX))
    wkey = jnp.where(valid & (total == best), -rank, -_INT_MAX)
    idx = jnp.argmax(wkey).astype(jnp.int32)
    ok = wkey[idx] != -_INT_MAX
    return idx, best, ok


def packed_topk(
    total: jnp.ndarray,  # i32[M] scores (nodes, blocks, or merge candidates)
    valid: jnp.ndarray,  # bool[M]
    rank: jnp.ndarray,  # i32[M] tie-break rank (smaller wins)
    k: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """K-extension of packed_argmax for the decision flight recorder
    (ISSUE 4): the first k entries of selectHost's (max score, min
    tie-break rank) selection order — entry 0 IS the packed_argmax
    winner, entries 1.. are the runner-ups. Returns (pos i32[k],
    total i32[k], rank i32[k], ok bool[k]); invalid tail entries carry
    pos/rank -1, total 0. Exact by construction: k iterated
    packed_argmax reductions, each masking the previous winner out, so
    the ordering cannot drift from the single-winner combine any engine
    selects with."""
    m = total.shape[0]
    iota = jnp.arange(m, dtype=jnp.int32)
    pos, tot, rnk, oks = [], [], [], []
    v = valid
    for _ in range(k):
        idx, best, ok = packed_argmax(total, v, rank)
        pos.append(jnp.where(ok, idx, -1).astype(jnp.int32))
        tot.append(jnp.where(ok, best, 0).astype(jnp.int32))
        rnk.append(jnp.where(ok, rank[idx], -1).astype(jnp.int32))
        oks.append(ok)
        v = v & (iota != idx)
    return jnp.stack(pos), jnp.stack(tot), jnp.stack(rnk), jnp.stack(oks)


def build_decision(
    node: jnp.ndarray,  # i32 committed winner (-1 = no feasible node)
    raws: jnp.ndarray,  # i32[num_pol, M] per-policy raw score rows
    norms: jnp.ndarray,  # i32[num_pol, M] per-policy NORMALIZED rows
    total: jnp.ndarray,  # i32[M] weighted totals (what selectHost reduced)
    feasible: jnp.ndarray,  # bool[M] Filter mask incl. pinning
    rank: jnp.ndarray,  # i32[M] tie-break rank
):
    """DecisionRecord for one create event from full per-policy score
    rows — the ONE record builder shared by the sequential oracle and the
    flat/blocked table engines (the shard engine reproduces the same
    record through its collective merge), so the captured provenance is
    engine-invariant by construction. Positions in the row arrays must be
    global node ids (the blocked path's sentinel pad columns are
    infeasible and rank-INT_MAX, so they can never enter the top-K).
    `block` is left at -1; blocked selects overwrite it with the winning
    block id (an engine-specific slot, like the counters' `rebuilds`)."""
    from tpusim.obs.decisions import DECISION_TOPK, DecisionRecord

    ok = node >= 0
    sel = jnp.maximum(node, 0)
    pos, tot, rnk, oks = packed_topk(total, feasible, rank, DECISION_TOPK)
    return DecisionRecord(
        node=node.astype(jnp.int32),
        total=jnp.where(ok, total[sel], 0).astype(jnp.int32),
        raw=jnp.where(ok, raws[:, sel], 0).astype(jnp.int32),
        norm=jnp.where(ok, norms[:, sel], 0).astype(jnp.int32),
        topk_node=pos,
        topk_total=tot,
        topk_rank=rnk,
        feasible=feasible.sum().astype(jnp.int32),
        block=jnp.int32(-1),
    )


def block_reduce(tot: jnp.ndarray, rank: jnp.ndarray):
    """Per-block (max total, min tie-break rank among the maxima, argmax)
    over the trailing axis — the in-block half of the blocked two-level
    selectHost, shared by the single-device blocked table engine and the
    shard_map engine's blocked local select so the combine cannot drift
    between them. `tot` uses -INT_MAX as the infeasible/empty sentinel;
    rows whose max stays at the sentinel are discarded by the global
    combine's validity gate, so their (rank, argmax) outputs are
    don't-cares. `rank` broadcasts against `tot`."""
    m = tot.max(-1)
    wkey = jnp.where(tot == m[..., None], -rank, -_INT_MAX)
    a = jnp.argmax(wkey, -1).astype(jnp.int32)
    r = jnp.take_along_axis(
        jnp.broadcast_to(rank, tot.shape), a[..., None], -1
    )[..., 0]
    return m, r, a


def bind_selected(
    state: NodeState,
    pod: PodSpec,
    node: jnp.ndarray,  # i32 chosen node index in [0, N) (ignored when ~ok)
    ok: jnp.ndarray,  # bool — selection succeeded
    policy_dev_scalar: jnp.ndarray,  # i32 policy device pick at `node`
    gpu_sel: str,
    key,
) -> Tuple[NodeState, Placement]:
    """Reserve + Bind for an already-selected node — the post-selectHost
    half of the cycle, shared by every engine so the scatter semantics
    cannot diverge."""
    # Reserve: concrete device allocation on the chosen node.
    dev_mask = choose_devices(state.gpu_left[node], pod, policy_dev_scalar, gpu_sel, key)
    dev_mask = dev_mask & ok

    # Bind: scatter-commit the placement.
    cls = pod_affinity_class(pod)
    new_state = state._replace(
        cpu_left=add_row(state.cpu_left, node, jnp.where(ok, -pod.cpu, 0)),
        mem_left=add_row(state.mem_left, node, jnp.where(ok, -pod.mem, 0)),
        gpu_left=add_row(
            state.gpu_left, node, -dev_mask.astype(jnp.int32) * pod.gpu_milli
        ),
        aff_cnt=add_row(
            state.aff_cnt, (node, jnp.maximum(cls, 0)),
            jnp.where(ok & (cls >= 0), 1, 0),
        ),
    )
    return new_state, Placement(jnp.where(ok, node, -1).astype(jnp.int32), dev_mask)


class PendingCommit(NamedTuple):
    """One event's deferred effects, applied at the START of the next scan
    iteration (or in the post-scan epilogue for the last event).

    The table engines software-pipeline every carried-buffer write by one
    event: within a scan body, a buffer read scheduled before a write to
    the same buffer forces XLA to preserve the old value — a whole-buffer
    copy per event (at 100k nodes the state copies alone cost more than
    the actual per-event compute on the CPU backend). Deferring the commit
    makes every body strictly write-then-read: apply the previous event's
    scatters first, then read state/tables freely. Bit-identical by
    construction — the same scatters land before anything reads them.

    The writes are sim/lane_write.py's add_row / set_row. Standalone they
    lower to the `.at[]` updates they always were. Under a sweep's vmap the
    module's batching rule keeps them the batched scatters vmap derives
    (in place on the carry's layout) and changes the READS of the same
    leaves (the dirty row, the selected row, the dirty block) into gathers
    windowed along the node axis only: it was a reader wanting another
    layout, not a write, that cost a whole-buffer copy an event there
    (ENGINES.md, PR 27; per-lane update loops and a layout constraint
    were tried and deleted).

    What a commit adds into aff_cnt is commit_affinity(node, cls, rs). The
    flat table replay does not make that add event by event where no kernel
    of its program reads the leaf (apply_commit's static `affinity`): its
    run_chunk sums the commits its scan applied, the incoming register and
    its own events but the last, after the scan
    (table_engine.chunk_affinity), from the scan's own record: `node` is
    the event_node it emits, `rs` the event's kind, `cls` its pod's class.
    The register itself means what it always did: the last event's commit
    lands whole in the next chunk or in finish, so a carry written by
    either form resumes under the other.

    node == -1 encodes a no-op state commit (failed create / skip / the
    pre-first-event initial value). pod_write is the bookkeeping row index
    (the P-th dummy row for skip events); failed_write is the row for the
    ever-failed flag (dummy unless the event was a creation attempt)."""

    node: jnp.ndarray  # i32 touched node, -1 = none
    dev_mask: jnp.ndarray  # bool[8]
    rs: jnp.ndarray  # i32 +1 delete (returns resources), -1 create
    cpu: jnp.ndarray  # i32 pod milli-CPU
    mem: jnp.ndarray  # i32 pod MiB
    gpu_milli: jnp.ndarray  # i32 pod per-GPU milli
    cls: jnp.ndarray  # i32 affinity class (-1 none)
    pod_write: jnp.ndarray  # i32 row for placed/masks ([P] = dummy)
    placed_val: jnp.ndarray  # i32 value for placed[pod_write]
    mask_val: jnp.ndarray  # bool[8] value for masks[pod_write]
    failed_write: jnp.ndarray  # i32 row for failed ([P] = dummy)
    failed_val: jnp.ndarray  # bool


def no_pending_commit(num_pods: int) -> "PendingCommit":
    """The inert pre-first-event PendingCommit (all writes hit dummies)."""
    z = jnp.int32(0)
    return PendingCommit(
        node=jnp.int32(-1),
        dev_mask=jnp.zeros(MAX_GPUS_PER_NODE, jnp.bool_),
        rs=jnp.int32(-1), cpu=z, mem=z, gpu_milli=z, cls=jnp.int32(-1),
        pod_write=jnp.int32(num_pods), placed_val=jnp.int32(-1),
        mask_val=jnp.zeros(MAX_GPUS_PER_NODE, jnp.bool_),
        failed_write=jnp.int32(num_pods), failed_val=jnp.bool_(False),
    )


def make_pending_commit(
    kind: jnp.ndarray,  # i32 clipped event kind: 0 create, 1 delete, 2 skip
    idx: jnp.ndarray,  # i32 pod index of the event
    node: jnp.ndarray,  # i32 touched node (-1 = none: failed create / skip)
    dev_mask: jnp.ndarray,  # bool[8] devices touched
    pod: PodSpec,
    num_pods: int,
) -> "PendingCommit":
    """Encode one event's effects for the next iteration's apply_commit.

    Semantics match the former in-branch commits exactly: a successful
    create consumes (node, dev_mask); a delete returns the recorded
    resources (node/dev_mask are the freed placement); failed creates and
    skips are state-inert via node == -1; placed/masks are written for
    create (the placement / -1 on failure) and delete (-1/False) but not
    skip; the ever-failed flag is only written by creation attempts
    (simulator.go:444-455)."""
    is_create = kind == 0
    is_skip = kind == 2
    return PendingCommit(
        node=node,
        dev_mask=dev_mask,
        rs=jnp.where(kind == 1, 1, -1),  # delete returns, create consumes
        cpu=pod.cpu, mem=pod.mem, gpu_milli=pod.gpu_milli,
        cls=pod_affinity_class(pod),
        pod_write=jnp.where(is_skip, num_pods, idx).astype(jnp.int32),
        placed_val=jnp.where(is_create, node, -1).astype(jnp.int32),
        mask_val=jnp.where(is_create, dev_mask, False),
        failed_write=jnp.where(is_create, idx, num_pods).astype(jnp.int32),
        failed_val=node < 0,
    )


# the commit's add into aff_cnt where an event loop keeps it: named inside
# tpusim.commit so that a by-scope reading of a device trace finds the one
# add a program whose kernels read the counts cannot defer
COMMIT_AFFINITY_SCOPE = "tpusim.commit.affinity"


def apply_commit(state: NodeState, placed, masks, failed, p: "PendingCommit",
                 affinity: bool = True, scoped: bool = False):
    """Apply a PendingCommit's scatters — the write-only half of the
    pipelined event loop. placed/masks/failed carry one extra dummy row
    ([P]) that absorbs skip-event writes. The global view of
    apply_commit_sharded (offset 0, the full node window), so the commit
    arithmetic exists exactly once."""
    return apply_commit_sharded(
        state, placed, masks, failed, p, jnp.int32(0), state.num_nodes,
        affinity, scoped,
    )


def commit_affinity(node, cls, rs):
    """What a commit (PendingCommit.node / .cls / .rs) adds into
    aff_cnt[node, max(cls, 0)]: +1 for a bind, -1 for a release, 0 where
    it touched no node or the pod has no affinity class. ONE definition
    for the per-event add below and for the flat table replay's sum over
    a chunk's events (table_engine.chunk_affinity)."""
    return jnp.where((node >= 0) & (cls >= 0), -rs, 0)


def _commit_row(p: "PendingCommit", offset, nloc: int):
    """(owns, sel): whether the window of `nloc` rows from global node id
    `offset` holds the commit's node, and its row there (clipped into the
    window, so that a commit the window does not own adds 0 to a row that
    exists)."""
    li = p.node - offset
    owns = (p.node >= 0) & (li >= 0) & (li < nloc)
    return owns, jnp.clip(li, 0, nloc - 1)


def _affinity_entry(p: "PendingCommit", owns):
    """(class, delta) of the commit's one add into the affinity counts, at
    its row of the window that `owns` speaks for."""
    return jnp.maximum(p.cls, 0), jnp.where(
        owns, commit_affinity(p.node, p.cls, p.rs), 0)


def add_commit_affinity(aff_t, p: "PendingCommit"):
    """apply_commit's add into aff_cnt for an event loop that carries the
    counts NODES MINOR beside its state (`aff_t` i32[classes, N]: the flat
    table body where a kernel reads them, table_engine._run_chunk_impl) and
    commits the rest with affinity=False: the same entry, the same delta,
    under the same scope."""
    owns, sel = _commit_row(p, jnp.int32(0), aff_t.shape[1])
    cls, delta = _affinity_entry(p, owns)
    with jax.named_scope(COMMIT_AFFINITY_SCOPE):
        return add_entry(aff_t, cls, sel, delta)


def apply_commit_sharded(state: NodeState, placed, masks, failed,
                         p: "PendingCommit", offset, nloc: int,
                         affinity: bool = True, scoped: bool = False):
    """apply_commit for a node-axis-sharded carry (the shard_map engine's
    software pipeline, ISSUE 11): `p.node` is a GLOBAL node id, so each
    shard lands the state scatters owner-masked on its local row window
    (`offset` = this shard's first global id, `nloc` rows) while the
    [P+1] bookkeeping writes — replicated by construction — apply
    identically on every shard. Strictly write-only on every touched
    buffer, like apply_commit, so the scatters alias in place under scan.
    With offset == 0 and nloc == N this IS apply_commit on a global view
    (the shard engine's finish epilogue uses apply_commit directly).

    `affinity` (static) False leaves the add into aff_cnt out: the flat
    table replay's event loop, where no kernel of its program reads that
    leaf, makes it once a chunk from the events' own record. Every other
    caller commits whole. `scoped` (static) names the add's operations
    COMMIT_AFFINITY_SCOPE: the table bodies' event loops ask for it
    (table_engine._scoped_commit), an epilogue's commit does not. A name
    is all it is: the operations and their order are the same either way."""
    owns, sel = _commit_row(p, offset, nloc)
    state = state._replace(
        cpu_left=add_row(
            state.cpu_left, sel, jnp.where(owns, p.rs * p.cpu, 0)
        ),
        mem_left=add_row(
            state.mem_left, sel, jnp.where(owns, p.rs * p.mem, 0)
        ),
        gpu_left=add_row(
            state.gpu_left, sel,
            jnp.where(owns, p.rs, 0) * p.dev_mask.astype(jnp.int32)
            * p.gpu_milli,
        ),
    )
    if affinity:
        with (jax.named_scope(COMMIT_AFFINITY_SCOPE) if scoped
              else contextlib.nullcontext()):
            cls, delta = _affinity_entry(p, owns)
            state = state._replace(
                aff_cnt=add_row(state.aff_cnt, (sel, cls), delta))
    placed = set_row(placed, p.pod_write, p.placed_val)
    masks = set_row(masks, p.pod_write, p.mask_val)
    failed = set_row(failed, p.failed_write, p.failed_val)
    return state, placed, masks, failed


def select_and_bind(
    state: NodeState,
    pod: PodSpec,
    feasible: jnp.ndarray,  # bool[N]
    total: jnp.ndarray,  # i32[N] weighted scores
    policy_dev: jnp.ndarray,  # i32[N] per-node policy device pick (-1 none)
    gpu_sel: str,
    key,
    tiebreak_rank: jnp.ndarray,
) -> Tuple[NodeState, Placement]:
    """selectHost + Reserve + Bind for already-computed scores — the single
    source of truth shared by the sequential engine (schedule_one) and the
    incremental table engine, so the two stay bit-identical by construction.
    Composed from packed_argmax (selectHost) + bind_selected (Reserve/Bind)
    so the blocked table engine can reuse both halves around its
    block-summary reduction."""
    node, _, ok = packed_argmax(total, feasible, tiebreak_rank)
    return bind_selected(state, pod, node, ok, policy_dev[node], gpu_sel, key)


def score_pod_rows(
    state: NodeState,
    pod: PodSpec,
    k_rand,
    policies: Sequence[Tuple[object, int]],
    gpu_sel: str = "best",
    tp=None,
    weights=None,
):
    """score_pod with the per-policy breakdown kept: returns
    (feasible bool[N], total i32[N], policy_share_dev i32[N],
    raws i32[num_pol, N], norms i32[num_pol, N]) where `norms` are the
    normalized rows the weighted sum consumed (== raws for
    normalize-'none' policies). The decision flight recorder gathers the
    winner's columns out of raws/norms; callers that only need the total
    (score_pod) let XLA dead-code the stacks.

    `weights` is the traced i32[num_pol] weight operand (resolve_weights;
    None = the static config weights) — engines pass it through so one
    jaxpr serves every weight vector of a policy family."""
    n = state.num_nodes
    feasible = filter_nodes(state, pod)
    ctx = ScoreContext(tp=tp, feasible=feasible, rng=k_rand)
    wts = resolve_weights(policies, weights)

    total = jnp.zeros(n, jnp.int32)
    policy_share_dev = jnp.full(n, -1, jnp.int32)
    raws, norms = [], []
    for i, (fn, _) in enumerate(policies):
        res = fn(state, pod, ctx)
        raw = res.raw_scores
        if fn.normalize == "minmax":
            nrm = minmax_normalize_i32(raw, feasible)
        elif fn.normalize == "pwr":
            nrm = pwr_normalize_i32(raw, feasible)
        else:
            nrm = raw
        raws.append(raw)
        norms.append(nrm)
        total = total + wts[i] * nrm
        if gpu_sel == fn.policy_name and fn.policy_name in SELF_SELECT_POLICIES:
            policy_share_dev = res.share_dev
    return feasible, total, policy_share_dev, jnp.stack(raws), jnp.stack(norms)


def score_pod(
    state: NodeState,
    pod: PodSpec,
    k_rand,
    policies: Sequence[Tuple[object, int]],
    gpu_sel: str = "best",
    tp=None,
    weights=None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Filter + Score + Normalize for one pod — the pre-selection half of
    the cycle, shared by schedule_one and the extender host loop (which
    splices HTTP extender filter/prioritize results between this and
    select_and_bind, mirroring where the vendored generic_scheduler calls
    its extenders, generic_scheduler.go:143-210 + 520-560). Returns
    (feasible bool[N], total i32[N] weighted scores, policy_share_dev
    i32[N])."""
    feasible, total, policy_share_dev, _, _ = score_pod_rows(
        state, pod, k_rand, policies, gpu_sel, tp, weights
    )
    return feasible, total, policy_share_dev


def schedule_one(
    state: NodeState,
    pod: PodSpec,
    key,
    policies: Sequence[Tuple[object, int]],
    gpu_sel: str = "best",
    tp=None,
    tiebreak_rank=None,
    weights=None,
) -> Tuple[NodeState, Placement]:
    """Run one full scheduling cycle for `pod` and commit the binding.

    policies: [(policy_fn, weight)] — the enabled Score plugins with their
    config weights (policy selection in the reference = one plugin at weight
    1000, §5.6). tiebreak_rank: i32[N] fixed per-run permutation. This models
    the reference exactly: its vendored selectHost REPLACES upstream k8s's
    random reservoir sampling with "smallest lexicographic name among ties"
    (generic_scheduler.go:187-212, the rand.Intn branch is commented out),
    and node names carry a random 4-digit per-run prefix
    (simulator.go:584-588) — i.e. a fixed random permutation as tie-break
    order. A per-pod random draw instead costs ~2pt of FGD allocation ratio
    (spreads load across tied idle nodes instead of packing).
    """
    n = state.num_nodes
    k_rand, k_sel = jax.random.split(key)
    if tiebreak_rank is None:
        tiebreak_rank = jnp.arange(n, dtype=jnp.int32)
    feasible, total, policy_share_dev = score_pod(
        state, pod, k_rand, policies, gpu_sel, tp, weights
    )
    return select_and_bind(
        state, pod, feasible, total, policy_share_dev, gpu_sel, k_sel,
        tiebreak_rank,
    )


def schedule_one_recorded(
    state: NodeState,
    pod: PodSpec,
    key,
    policies: Sequence[Tuple[object, int]],
    gpu_sel: str = "best",
    tp=None,
    tiebreak_rank=None,
    weights=None,
):
    """schedule_one plus its DecisionRecord — identical trajectory (same
    key splits, same score/select/bind kernels in the same order; the
    extra gathers feed only the record), so a recording replay's
    placements are bit-identical to an unrecorded one. Returns
    (new_state, Placement, DecisionRecord)."""
    n = state.num_nodes
    k_rand, k_sel = jax.random.split(key)
    if tiebreak_rank is None:
        tiebreak_rank = jnp.arange(n, dtype=jnp.int32)
    feasible, total, policy_share_dev, raws, norms = score_pod_rows(
        state, pod, k_rand, policies, gpu_sel, tp, weights
    )
    new_state, placement = select_and_bind(
        state, pod, feasible, total, policy_share_dev, gpu_sel, k_sel,
        tiebreak_rank,
    )
    dec = build_decision(
        placement.node, raws, norms, total, feasible, tiebreak_rank
    )
    return new_state, placement, dec


def unschedule(state: NodeState, pod: PodSpec, placement: Placement) -> NodeState:
    """Evict a placed pod, returning resources to its recorded devices
    (ref: deletePod → cache removal + NodeResource.Add, simulator.go:334-357,
    resource.go:482-531)."""
    node = jnp.maximum(placement.node, 0)
    placed = placement.node >= 0
    cls = pod_affinity_class(pod)
    return state._replace(
        cpu_left=add_row(state.cpu_left, node, jnp.where(placed, pod.cpu, 0)),
        mem_left=add_row(state.mem_left, node, jnp.where(placed, pod.mem, 0)),
        gpu_left=add_row(
            state.gpu_left, node,
            jnp.where(placed, placement.dev_mask.astype(jnp.int32) * pod.gpu_milli, 0),
        ),
        aff_cnt=add_row(
            state.aff_cnt, (node, jnp.maximum(cls, 0)),
            jnp.where(placed & (cls >= 0), -1, 0),
        ),
    )
