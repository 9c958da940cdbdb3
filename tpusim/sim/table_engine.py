"""Incremental score-table replay engine — the throughput path.

Exact-equivalent reformulation of tpusim.sim.engine.make_replay (which
mirrors the reference's strictly serial scheduleOne loop,
vendor .../scheduler/scheduler.go:441): every policy used here scores a node
as a pure function of (that node's state, the pod's resource spec), and one
scheduling/deletion event mutates exactly ONE node. So instead of re-scoring
all N nodes for every event, keep tables

    score_tbl[policy, K, N]  raw plugin scores per (pod type, node)
    sharedev_tbl[K, N]       the gpu_sel policy's Reserve device pick
    feas_tbl[K, N]           Filter-phase feasibility

over the K distinct pod resource types in the trace (openb default: K≈150 vs
N=1523 nodes), and per event recompute only the previously-mutated node's
column before gathering the current pod type's row. Results (placements,
device masks, final state) are bit-identical to the sequential engine — the
same kernels run, just at different times; tests/test_table_engine.py pins
equality on the full openb trace prefix and randomized create/delete mixes.

RandomScore (a per-event PRNG draw over the feasible mask,
plugin/random_score.go:42-68) is NOT table-izable — its score row changes
every event — but since round 5 it runs here anyway: the replay body
follows the sequential engine's key-split discipline exactly (one split
per event, then (k_rand, k_sel) off the sub-key), so the per-event draw is
recomputed in do_create from the same key and the same feasible mask the
oracle sees, bit-identically. The same holds for gpu_sel='random' (the
Reserve-phase draw consumes k_sel in both engines). Only the fused Pallas
engine still rejects per-event randomness (reject_randomized).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpusim.constants import MAX_GPUS_PER_NODE
from tpusim.obs import heartbeat as obs_heartbeat
from tpusim.obs import series as obs_series
from tpusim.obs.counters import counter_delta, zero_counters
from tpusim.obs.decisions import no_decision
from tpusim.policies import (
    NORMALIZE_DEGENERATE,
    ScoreContext,
    minmax_normalize_i32,
    minmax_scale_i32,
    policies_read_affinity,
    pwr_normalize_i32,
)
from tpusim.policies.clustering import pod_affinity_class
from tpusim.sim import lane_write
from tpusim.sim.engine import EV_RETRY, ReplayResult
from tpusim.sim.step import (
    SELF_SELECT_POLICIES,
    PendingCommit,
    add_commit_affinity,
    apply_commit,
    block_reduce,
    build_decision,
    choose_devices,
    commit_affinity,
    filter_nodes,
    make_pending_commit,
    no_pending_commit,
    packed_argmax,
)
from tpusim.types import NodeState, PodSpec

_INT_MAX = np.int32(np.iinfo(np.int32).max)

# Below this node count the flat O(N) select wins: the blocked path's extra
# per-event fixed costs (dirty-block refresh + two-level combine) outweigh
# the reduction savings, and openb-scale traces (N=1523) must not regress.
BLOCKED_MIN_NODES = 8192

# At sweep width the flat step writes its dirty columns a group of this many
# events at a time (LateColumns): one access of each table every
# FLAT_GROUP_EVENTS events, the rows read in between patched from the
# pending block. Vmapped over many lanes on a short node axis that access is
# a pass over the whole lane-batched table, so its cost falls with the
# group; the patch costs one compare and select a slot on every row read,
# so it rises with it (PERF.md section 6, PR 29: 16 beat 8 and 32 on the
# chip at 2,560 lanes x K = 61; PR 33: 16 and 32 level, 64 behind by 3 %,
# at 600 lanes of a trace each x K = 400, so the size reads no K). A
# replay that is not vmapped, or over few lanes, is a chain of
# small operations and the group only adds to it (the standalone openb
# replay: 49 us an event as it is, 110 grouped by 16), so it keeps writing
# every event: flat_group_events() decides from the sweep's own shapes.
FLAT_GROUP_EVENTS = 16
FLAT_GROUP_MIN_LANES = 64


def flat_group_events(lanes: int, nodes: int) -> int:
    """Events the flat step of a `lanes`-wide vmapped replay on `nodes`
    nodes writes its dirty columns at a time; 1: every event, the plain
    body. Static: the sweep's wrapper reads both off its operands' shapes
    (driver._sweep_engine), no option selects it."""
    if nodes < BLOCKED_MIN_NODES and lanes >= FLAT_GROUP_MIN_LANES:
        return FLAT_GROUP_EVENTS
    return 1


# Where no kernel of the program reads NodeState.aff_cnt the flat replay
# does not add into it every event: chunk_affinity sums the chunk's events
# once, this many at a time, so that their one-hot over the nodes
# ([lanes, AFFINITY_EVENTS, N] in a sweep) never stands whole.
AFFINITY_EVENTS = 128


def _scoped_commit(state, placed, masks, failed, pend, affinity: bool = True):
    """apply_commit as an event loop's bodies call it: under the scope
    tpusim.commit, the add into aff_cnt (where the program keeps it in the
    loop: `affinity`) under step.COMMIT_AFFINITY_SCOPE inside it. The
    epilogue's commit (finish) is outside both."""
    with jax.named_scope("tpusim.commit"):
        return apply_commit(
            state, placed, masks, failed, pend, affinity=affinity,
            scoped=True)


def chunk_affinity(pend: PendingCommit, pods: PodSpec, ev_kind, ev_pod,
                   event_node, num_nodes: int, classes: int):
    """i32[N, classes]: what the commits that one chunk's scan APPLIED add
    to NodeState.aff_cnt. The commit is one event deep, so those are the
    incoming `pend` and the chunk's own events but the last (whose commit
    leaves in the outgoing pend, for the next chunk or finish): node_e is
    the scan's own record (`event_node`, the value that went into
    PendingCommit.node), the sign the event's kind, the class its pod's.
    Integer counts in any order, so bit for bit what a per-event
    apply_commit(affinity=True) leaves.

    One expression at every width, with no batching rule: a contraction of
    two one-hots over the event axis, aff[n, c] = sum_e [node_e == n] * s_e
    * [cls_e == c], int8 operands (0, +-1) accumulated in int32, cut into
    blocks of AFFINITY_EVENTS events. No scatter and no gather with an index
    row a lane (sim/lane_write.py: a `while` over the lanes of a sweep)."""
    events = ev_kind.shape[0]
    zero = jnp.zeros((num_nodes, classes), jnp.int32)
    if not events:  # nothing ran: the incoming pend is still pending
        return zero
    with jax.named_scope("tpusim.affinity"):
        cls_e = pod_affinity_class(pods)[ev_pod[:-1]]
        rs_e = jnp.where(jnp.clip(ev_kind[:-1], 0, 2) == 1, 1, -1)
        node = jnp.concatenate([pend.node[None], event_node[:-1]])
        cls = jnp.concatenate([pend.cls[None], cls_e])
        rs = jnp.concatenate([pend.rs[None], rs_e])
        sign = commit_affinity(node, cls, rs).astype(jnp.int8)
        # whole blocks: the pad touches no node and adds 0
        pad = -events % AFFINITY_EVENTS
        blocks = [
            jnp.pad(a, (0, pad), constant_values=fill).reshape(
                -1, AFFINITY_EVENTS)
            for a, fill in ((node, -1), (cls, -1), (sign, 0))
        ]
        node_iota = jax.lax.iota(jnp.int32, num_nodes)
        cls_iota = jax.lax.iota(jnp.int32, classes)

        def add_block(acc, block):
            node_b, cls_b, sign_b = block
            # the sign rides the node one-hot: with one shared trace the
            # class one-hot is then the same for every lane of a sweep
            hot_n = jnp.where(
                node_b[:, None] == node_iota, sign_b[:, None], jnp.int8(0))
            hot_c = (cls_b[:, None] == cls_iota).astype(jnp.int8)
            return acc + jax.lax.dot_general(
                hot_n, hot_c, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32), None

        return jax.lax.scan(add_block, zero, tuple(blocks))[0]


def resolve_block_size(block_size: int, num_nodes: int, num_types: int) -> int:
    """Static block-size decision for the blocked table engine.

    block_size > 0 forces that block size, < 0 forces the flat path, and 0
    (auto) picks a balanced ~sqrt block: the per-event cost is
    O(K*B) dirty-block aggregate refresh + O(N/B) block-summary combine, so
    the balance point is B ~ sqrt(N/K) (the plain ~sqrt(N) rule, refined by
    the pod-type count K that multiplies the refresh), rounded to a power
    of two and clamped to [16, 1024]. Auto stays flat below
    BLOCKED_MIN_NODES. Returns 0 for "run the flat path"."""
    if block_size < 0:
        return 0
    if block_size > 0:
        return min(block_size, num_nodes)
    if num_nodes < BLOCKED_MIN_NODES:
        return 0
    import math

    b = int(math.sqrt(3.0 * num_nodes / max(num_types, 1)))
    b = max(16, min(1024, 1 << max(b - 1, 1).bit_length()))
    return min(b, num_nodes)


class PodTypes(NamedTuple):
    """Distinct (cpu, mem, gpu_milli, gpu_num, gpu_mask) specs in a trace,
    partitioned by scoring branch: share-GPU types first (indices
    [0, Ks)), whole-GPU / CPU-only types after ([Ks, Ks+Kw)). The static
    partition lets branch-aware policies (fgd_score.branches) run each
    group through its specialized kernel instead of a cond→select that
    computes both branches for every type.

    `requests` / `request_of` are the whole group's distinct (gpu_milli,
    gpu_num) pairs and each whole type's row among them (a function of
    `whole`: _whole_requests). Sub's hypothetical device vector reads nothing
    else of a pod, so a kernel that offers the split
    (fgd_score.branches["whole_split"]) tries it once a request, G times a
    column where it was Kw (the shipped pod lists: 5 requests among 25-329
    whole types). Both broadcast over the lanes of a sweep as `share` and
    `whole` do; a PodTypes built without them (None) goes type by type."""

    share: PodSpec  # [Ks] arrays, pinned == -1
    whole: PodSpec  # [Kw] arrays, pinned == -1
    type_id: jnp.ndarray  # i32[P] pod -> global type index
    requests: jnp.ndarray = None  # i32[G, 2] distinct (gpu_milli, gpu_num)
    request_of: jnp.ndarray = None  # i32[Kw] whole type -> its row of requests


def _to_specs(uniq: np.ndarray) -> PodSpec:
    k = uniq.shape[0]
    return PodSpec(
        cpu=jnp.asarray(uniq[:, 0].astype(np.int32)),
        mem=jnp.asarray(uniq[:, 1].astype(np.int32)),
        gpu_milli=jnp.asarray(uniq[:, 2].astype(np.int32)),
        gpu_num=jnp.asarray(uniq[:, 3].astype(np.int32)),
        gpu_mask=jnp.asarray(uniq[:, 4].astype(np.int32)),
        pinned=jnp.full(k, -1, jnp.int32),
    )


def _type_cols(specs: PodSpec) -> np.ndarray:
    """The [P, 5] dedup key matrix (pinned is deliberately not part of the
    type key — node pinning is a per-event feasibility mask, not a property
    the score tables see)."""
    return np.stack(
        [
            np.asarray(specs.cpu),
            np.asarray(specs.mem),
            np.asarray(specs.gpu_milli),
            np.asarray(specs.gpu_num),
            np.asarray(specs.gpu_mask),
        ],
        axis=1,
    )


def num_pod_types(specs: PodSpec) -> int:
    """Distinct pod resource types in a spec set (the K the table engine's
    amortization heuristic weighs against the event count)."""
    return int(np.unique(_type_cols(specs), axis=0).shape[0])


# The distinct requests go to a bucket of their own: the shipped pod lists
# have five, so every type set of theirs carries eight, whichever shuffle,
# seed or depth it was cut from (G is a shape of the compiled program)
REQUEST_BUCKET = 8


def _whole_requests(whole: PodSpec):
    """(requests i32[G, 2], request_of i32[Kw]): host-side dedup of the whole
    group's (gpu_milli, gpu_num) pairs, G on its bucket: the rows past the
    distinct pairs are inert (0, 0) and no type points at them."""
    pairs = np.stack(
        [np.asarray(whole.gpu_milli), np.asarray(whole.gpu_num)], axis=1)
    uniq, inv = np.unique(pairs, axis=0, return_inverse=True)
    g = -(-uniq.shape[0] // REQUEST_BUCKET) * REQUEST_BUCKET
    uniq = np.concatenate(
        [uniq, np.zeros((g - uniq.shape[0], 2), uniq.dtype)])
    return (jnp.asarray(uniq.astype(np.int32)),
            jnp.asarray(inv.reshape(-1).astype(np.int32)))


def build_pod_types(specs: PodSpec) -> PodTypes:
    """Host-side dedup of pod resource specs, and of the whole group's GPU
    requests."""
    cols = _type_cols(specs)
    uniq, inv = np.unique(cols, axis=0, return_inverse=True)
    # is_gpu_share (types.py): exactly one GPU, fractional milli
    is_share = (uniq[:, 3] == 1) & (uniq[:, 2] > 0) & (uniq[:, 2] < 1000)
    order = np.concatenate([np.flatnonzero(is_share), np.flatnonzero(~is_share)])
    rank = np.empty_like(order)
    rank[order] = np.arange(order.size)
    whole = _to_specs(uniq[~is_share])
    return PodTypes(
        _to_specs(uniq[is_share]),
        whole,
        jnp.asarray(rank[inv].astype(np.int32)),
        *_whole_requests(whole),
    )


def pad_pod_types(types: PodTypes, multiple: int = 16) -> PodTypes:
    """Pad each type group to a `multiple` with inert dummy types so sweeps
    over seeds/traces (whose K varies slightly) share one compiled replay.
    Dummies request 2^30 milli-CPU — infeasible on any node — and are never
    referenced by type_id, so they only cost dead table columns. The whole
    group's requests are those of the padded group: the dummies are the
    CPU-only request they ask for."""

    def pad_group(spec: PodSpec, share: bool) -> PodSpec:
        k = int(spec.cpu.shape[0])
        k2 = -(-k // multiple) * multiple
        if k2 == k:  # includes k == 0: empty groups keep their static skip
            return spec
        pad = k2 - k
        big = jnp.full(pad, 2**30, jnp.int32)
        return PodSpec(
            cpu=jnp.concatenate([spec.cpu, big]),
            mem=jnp.concatenate([spec.mem, big]),
            gpu_milli=jnp.concatenate(
                [spec.gpu_milli, jnp.full(pad, 1 if share else 0, jnp.int32)]
            ),
            gpu_num=jnp.concatenate(
                [spec.gpu_num, jnp.full(pad, 1 if share else 0, jnp.int32)]
            ),
            gpu_mask=jnp.concatenate([spec.gpu_mask, jnp.zeros(pad, jnp.int32)]),
            pinned=jnp.concatenate([spec.pinned, jnp.full(pad, -1, jnp.int32)]),
        )

    # type_id indexes share types at [0, Ks) and whole types at [Ks, K);
    # padding shifts the whole-group base, so remap ids past the share group
    ks = int(types.share.cpu.shape[0])
    share2 = pad_group(types.share, True)
    ks2 = int(share2.cpu.shape[0])
    tid = types.type_id
    tid = jnp.where(tid >= ks, tid + (ks2 - ks), tid)
    whole2 = pad_group(types.whole, False)
    return PodTypes(share2, whole2, tid, *_whole_requests(whole2))


def _num_types(types: PodTypes) -> int:
    """K: the rows of the tables `types` index (both groups, pads too)."""
    return int(types.share.cpu.shape[0]) + int(types.whole.cpu.shape[0])


def _row_state(state: NodeState, node, aff_t=None) -> NodeState:
    """1-node slice of the cluster state at a dynamic index. With `aff_t`
    (the affinity counts as the event loop carries them, [classes, N]) the
    slice's aff_cnt is that leaf's column and state.aff_cnt is not read."""
    if aff_t is None:
        return jax.tree.map(lambda a: lane_write.read_row(a, node), state)
    row = _row_state(state._replace(aff_cnt=None), node)
    return row._replace(aff_cnt=lane_write.read_column(aff_t, node))


def _pad_rank(rank: jnp.ndarray, n_pad: int) -> jnp.ndarray:
    """Tie-break rank padded to the blocked layout's node count; sentinel
    rows carry rank INT_MAX so a pad column can never win a tie."""
    n = rank.shape[0]
    if n_pad == n:
        return rank
    return jnp.pad(
        rank, (0, n_pad - n), constant_values=jnp.iinfo(jnp.int32).max
    )


class FlatTableCarry(NamedTuple):
    """Complete engine state between two events of the FLAT table replay —
    the lax.scan carry, promoted to a serializable pytree so a run can be
    cut at any event boundary, round-tripped through host memory / a
    checkpoint file (tpusim.io.storage.save_checkpoint), and resumed
    bit-identically: the scan body is a pure function of (carry, event), so
    `scan(body, c, ev[:k]); scan(body, ·, ev[k:])` IS `scan(body, c, ev)`.

    All leaves are exact dtypes (i32 / bool / u32 PRNG key) — serialization
    cannot perturb them.

    `state` holds every commit but the one in `pend`, aff_cnt included,
    whether the event loop added into that leaf event by event or
    _run_chunk_impl added the chunk's counts after its scan
    (chunk_affinity: a program whose kernels do not read the leaf): the
    two forms hand each other the same carry at every event boundary."""

    state: NodeState
    score_tbl: jnp.ndarray  # i32[num_pol, K, N]
    sdev_tbl: jnp.ndarray  # i32[K, N]
    feas_tbl: jnp.ndarray  # bool[K, N]
    pend: PendingCommit  # the software-pipeline register (one event deep)
    dirty: jnp.ndarray  # i32 node whose column the next event refreshes
    placed: jnp.ndarray  # i32[P+1] (dummy row absorbs skip writes)
    masks: jnp.ndarray  # bool[P+1, 8]
    failed: jnp.ndarray  # bool[P+1]
    arr_cpu: jnp.ndarray  # i32 arrived milli-CPU so far
    arr_gpu: jnp.ndarray  # i32 arrived milli-GPU so far
    key: jnp.ndarray  # PRNG key after the events consumed so far
    ctr: jnp.ndarray  # i32[obs.NUM_COUNTERS] exact in-scan counters


class LateColumns(NamedTuple):
    """The flat step's pending block: the dirty columns of one group of G
    events, computed but not yet written into the tables. It lives inside
    _run_chunk_impl only: every group ends in a flush
    (lane_write.write_columns), so no FlatTableCarry a caller sees has
    columns pending. (`late`, not `pend`: that is the carry's
    PendingCommit.)"""

    idx: jnp.ndarray  # i32[G] each slot's dirty node; -1 until its event
    score: jnp.ndarray  # i32[G, num_pol, K]
    sdev: jnp.ndarray  # i32[G, K]
    feas: jnp.ndarray  # bool[G, K]


class BlockedTableCarry(NamedTuple):
    """FlatTableCarry plus the blocked select-phase aggregates
    (tables/summaries padded to a whole number of B-node blocks). Same
    resume contract; the extra leaves are exactly the per-(policy, type,
    block) summaries ENGINES.md round 6 describes."""

    state: NodeState
    score_tbl: jnp.ndarray  # i32[num_pol, K, n_pad]
    sdev_tbl: jnp.ndarray  # i32[K, n_pad]
    feas_tbl: jnp.ndarray  # bool[K, n_pad]
    bt: jnp.ndarray  # i32[K, N/B] per-block max weighted total
    br: jnp.ndarray  # i32[K, N/B] min tie-break rank among the maxima
    bn: jnp.ndarray  # i32[K, N/B] the block winner's global node id
    brmin: jnp.ndarray  # i32[pn, K, N/B] block raw-score minima (normalizers)
    brmax: jnp.ndarray  # i32[pn, K, N/B] block raw-score maxima
    slo: jnp.ndarray  # i32[pn, K] stored per-type lo extrema
    shi: jnp.ndarray  # i32[pn, K] stored per-type hi extrema
    pend: PendingCommit
    dirty: jnp.ndarray
    placed: jnp.ndarray
    masks: jnp.ndarray
    failed: jnp.ndarray
    arr_cpu: jnp.ndarray
    arr_gpu: jnp.ndarray
    key: jnp.ndarray
    ctr: jnp.ndarray  # i32[obs.NUM_COUNTERS]; [5] counts summary rebuilds


_TABLE_REPLAY_CACHE = {}
# heavy jitted machinery keyed WITHOUT weights (ISSUE 6): the per-policy
# weight vector is a traced i32[num_pol] operand, so every weight config
# of a (kernels, gpu_sel, layout, obs-flags) family shares one jaxpr —
# the marginal what-if weight change is a device call, not a ~5 s
# recompile, and the config-axis sweep vmaps straight over the operand
_TABLE_ENGINE_CACHE = {}


def reject_randomized(policies, gpu_sel: str):
    """Guard for the fused Pallas engine: per-event PRNG draws cannot run
    inside the fused kernel (no jax.random there), so randomized configs
    stay on the table/sequential engines (which replay them
    bit-identically to each other since round 5)."""
    for fn, _ in policies:
        if fn.policy_name == "RandomScore":
            raise ValueError(
                "RandomScore draws per-event randomness; use the table or "
                "sequential engine for it"
            )
    if gpu_sel == "random":
        raise ValueError(
            "gpu_sel='random' draws per-event randomness; use the table or "
            "sequential engine for it"
        )


def selector_index(policies, gpu_sel: str) -> int:
    """Index of the policy whose Reserve-phase device pick the configured
    gpuSelMethod delegates to (-1 = none; the allocateGpuIdFunc registry,
    plugin/open_gpu_share.go:39)."""
    return next(
        (
            i
            for i, (fn, _) in enumerate(policies)
            if gpu_sel == fn.policy_name and fn.policy_name in SELF_SELECT_POLICIES
        ),
        -1,
    )


def _group_fn(fn, which: str):
    """Branch-specialized kernel when the policy provides one (the type
    partition makes the branch static), else the generic kernel."""
    return getattr(fn, "branches", {}).get(which, fn)


def _whole_split(fn):
    """The policy's whole branch in two steps, (request, finish), where it
    offers them (policies/fgd.py), else None."""
    return getattr(fn, "branches", {}).get("whole_split")


def _fills_its_table(fn) -> bool:
    """False for RandomScore: its score row is a per-event draw the replay
    body recomputes, and the table slot is never read."""
    return fn.policy_name != "RandomScore"


def sub_requests(policies, types: PodTypes) -> int:
    """Sub hypotheticals ONE column computation evaluates (a lane, an
    event): the type set's distinct requests G where every kernel that
    scores the whole group takes them by request, the group's size where one
    goes type by type (SweepRecord.sub_requests)."""
    kw = int(types.whole.cpu.shape[0])
    by_request = types.request_of is not None and all(
        _whole_split(fn) is not None
        for fn, _ in policies if _fills_its_table(fn))
    return int(types.requests.shape[0]) if by_request else kw


def _take_request(terms, r):
    """A whole type's terms out of the [G, ...] terms of the distinct
    requests. Under the column's vmap over the types `r` is the group's
    i32[Kw] index, shared by the lanes of a sweep."""
    return jax.tree.map(lambda a: a[r], terms)


def make_table_builders(policies, sel_idx: int):
    """(columns, init_tables) score-table constructors for a static policy
    list — single-sourced table builders for the incremental engine.

    columns(state1, types, tp, key): one node's scores for all K pod types
      -> (scores i32[num_pol, K], sharedev i32[K], feas bool[K]).
    init_tables(state, types, tp, key): full [*, K, N] tables via a K-serial
      map (bounds peak memory to one node-sweep's intermediates per type).

    Both map ONE definition (group) over a type group: a kernel that offers
    its whole branch in two steps evaluates Sub's hypothetical once a
    distinct request of the type set (types.requests, G rows) and each type
    finishes from its request's terms; every other kernel, and a type set
    without the index, goes type by type. The values are the same either
    way (tests/test_table_engine.py).
    """

    def group(state: NodeState, types: PodTypes, tp, key, which: str, over):
        """(scores [k, num_pol, N], sharedev [k, N], feas [k, N]) of one
        type group. `over(f)(xs)` maps f over the leading axis of xs:
        jax.vmap for one node's column, a serial lax.map for whole tables."""
        ctx_feas = jnp.ones(state.num_nodes, jnp.bool_)
        ctx = ScoreContext(tp=tp, feasible=ctx_feas, rng=key)
        request_of = types.request_of if which == "whole" else None
        # policy index -> (finish, its [G, ...] terms of the requests)
        by_request = {}
        if request_of is not None:
            for i, (fn, _) in enumerate(policies):
                split = _whole_split(fn)
                if split is not None and _fills_its_table(fn):
                    request, finish = split
                    by_request[i] = finish, over(
                        lambda req, request=request: request(
                            state, req[0], req[1], ctx)
                    )(types.requests)

        def one_type(tpod_r):
            tpod, r = tpod_r
            feas = filter_nodes(state, tpod)
            scores = []
            sdev = jnp.full(state.num_nodes, -1, jnp.int32)
            for i, (fn, _) in enumerate(policies):
                if not _fills_its_table(fn):
                    scores.append(jnp.zeros(state.num_nodes, jnp.int32))
                    continue
                if i in by_request:
                    finish, terms = by_request[i]
                    res = finish(state, tpod, _take_request(terms, r), ctx)
                else:
                    res = _group_fn(fn, which)(state, tpod, ctx)
                scores.append(res.raw_scores)
                if i == sel_idx:
                    sdev = res.share_dev
            return jnp.stack(scores), sdev, feas

        return over(one_type)((getattr(types, which), request_of))

    def groups(state, types, tp, key, over):
        outs = [
            group(state, types, tp, key, which, over)
            for which in ("share", "whole")
            if getattr(types, which).cpu.shape[0]
        ]
        return [jnp.concatenate([o[i] for o in outs], 0) for i in range(3)]

    def columns(state1: NodeState, types: PodTypes, tp, key):
        scores, sdev, feas = groups(state1, types, tp, key, jax.vmap)
        return scores[:, :, 0].T, sdev[:, 0], feas[:, 0]  # [π,K], [K], [K]

    @jax.named_scope("tpusim.table_build")
    def init_tables(state: NodeState, types: PodTypes, tp, key):
        scores, sdev, feas = groups(
            state, types, tp, key,
            lambda f: functools.partial(jax.lax.map, f))
        return jnp.swapaxes(scores, 0, 1), sdev, feas  # [π,K,N], [K,N] x 2

    return columns, init_tables


def make_table_replay(
    policies, gpu_sel: str = "best", report: bool = False,
    block_size: int = 0, heartbeat_every: int = 0,
    decisions: bool = False, series_every: int = 0,
    faults: bool = False, fault_frag: bool = False,
    unswitched: bool = False,
):
    """Build the jitted incremental replayer for a static policy config.

    policies: [(policy_fn, weight)] — all must be table-izable (raw score a
    pure function of node state + pod spec; RandomScore is not).

    block_size selects the select-phase data layout (resolve_block_size):
    0 (auto) runs the blocked incremental-reduction path at large N and the
    flat path elsewhere; > 0 forces that block size; < 0 forces flat.
    Configs containing RandomScore always run flat — its score row is a
    per-event draw over all N feasible nodes, so there is nothing
    incremental to reduce. The blocked path maintains, per
    (policy, type, block-of-B-nodes), the block min/max feeding the
    normalizers plus the block's (max total, min tie-break rank, node)
    summary, refreshes only the touched node's block per event (O(B)) and
    reduces the final selectHost over N/B block summaries (O(N/B)) —
    bit-identical to the flat path because the same packed_argmax combine
    consumes exact block maxima (max/min are associative) and the same
    minmax_scale_i32 apply consumes exact global extrema.

    The replay is metric-free: per-event report rows (the reference
    recomputes frag/alloc/power cluster-wide after every event,
    simulator.go:426-427, its dominant cost) are reconstructed from the
    emitted (event_node, event_dev) telemetry by the shared vectorized
    post-pass, tpusim.sim.metrics.compute_event_metrics — identical across
    engines by construction. `report` is accepted for signature
    compatibility and must be False.

    The returned replayer also exposes the checkpoint/resume surface the
    driver's chunked dispatch uses (ENGINES.md "Checkpoint/resume"):

        carry = replay.init_carry(state, pods, types, tp, key, rank)
        carry, (nodes, devs) = replay.run_chunk(
            carry, pods, types, ev_kind_seg, ev_pod_seg, tp, rank)   # × S
        state, placed, masks, failed = replay.finish(carry)

    is bit-identical to one replay(...) call over the concatenated
    segments, for any segmentation — including a host/disk round-trip of
    the carry between run_chunk calls (Flat/BlockedTableCarry hold only
    exact-dtype leaves).

    Observability (tpusim.obs): the carry's `ctr` leaf counts events
    applied/bound/failed/deleted/skipped (and blocked summary rebuilds)
    with the shared obs.counters.counter_delta, so the counts are exact,
    engine-invariant, and — being carry state — transparent to
    checkpoint/resume. heartbeat_every > 0 additionally fires a
    jax.debug.callback progress tick (obs.heartbeat) every that many
    processed events from inside the scan; it is part of the engine
    cache key because it is baked into the jaxpr, and it never touches
    the trajectory (pure side output).

    `replay(..., tables=...)` / `init_carry(..., tables=...)` accept
    precomputed (score_tbl, sdev_tbl, feas_tbl) arrays — the driver's
    content-keyed init_tables cache (io.storage) feeds these to skip the
    K-node-sweep build on repeat runs; `replay.build_tables` is the
    jitted builder whose output that cache persists. Results are
    bit-identical either way (the aggregates are pure functions of the
    tables).

    decisions=True (ISSUE 4) makes the scan additionally emit a
    DecisionRecord per event (tpusim.obs.decisions): run_chunk/replay
    ys become (node, dev, dec). The trajectory is untouched — the flat
    path records out of the score rows the select already computed; the
    blocked path reconstructs the event type's full totals row from the
    score/feas tables with direct normalization (the same
    minmax/pwr_normalize_i32 the flat path and the oracle apply), which
    is exactly what its two-level select is bit-identical to — so the
    records are engine-invariant by construction. Recording costs O(N)
    gathers per create event (plus DECISION_TOPK extra packed_argmax
    reductions), which is why it is a static build flag, not always on.

    series_every > 0 (ISSUE 5) makes the scan additionally emit one
    tpusim.obs.series.SeriesSample per event — a real sample of the
    committed pre-event cluster state whenever the processed-event count
    sits on the stride, a pos == -1 sentinel elsewhere. The sample is
    assembled from the score/feas tables the dirty refresh just brought
    current (== fn(state, ·) for every node by the table invariant), so
    it is bit-identical to the sequential engine's recomputed sample; it
    rides the ys, not the carry, so the checkpoint layout is unchanged.
    ys become (node, dev[, dec][, ser]) in that order.

    Weights as operands (ISSUE 6): replay / init_carry / run_chunk all
    accept `weights=` — the i32[num_pol] traced weight vector
    (sim.step.resolve_weights; None = the static config weights, which
    is bit-identical to the former baked `jnp.int32(weight)` constants).
    The underlying jitted machinery is cached WITHOUT the weight values
    (`replay.engine`), so replayers of one policy family share one
    jaxpr across every weight vector; the tables themselves are
    weight-independent (raw per-policy scores), and the blocked
    summaries `bt/br/bn` are built in-scan FROM the weight operand —
    which is why the whole blocked path works off traced weights with
    zero layout change. A carry initialized under weight vector W must
    be resumed with the same W (the driver's run digest covers that).
    """
    if report:
        raise ValueError(
            "the table engine replays metric-free; build the report series "
            "with tpusim.sim.metrics.compute_event_metrics"
        )
    if faults and (decisions or series_every or heartbeat_every):
        raise ValueError(
            "the in-scan fault plane (faults=True) does not combine with "
            "decisions/series/heartbeat builds; run those through the "
            "segmented fault path (Simulator fault_mode='segments')"
        )
    cache_key = (tuple((fn, w) for fn, w in policies), gpu_sel, report,
                 int(block_size), int(heartbeat_every), bool(decisions),
                 int(series_every), bool(faults), bool(fault_frag),
                 bool(unswitched))
    if cache_key in _TABLE_REPLAY_CACHE:
        return _TABLE_REPLAY_CACHE[cache_key]
    engine_key = (tuple(fn for fn, _ in policies), gpu_sel,
                  int(block_size), int(heartbeat_every), bool(decisions),
                  int(series_every), bool(faults), bool(fault_frag),
                  bool(unswitched))
    eng = _TABLE_ENGINE_CACHE.get(engine_key)
    if eng is None:
        eng = _make_table_engine(
            policies, gpu_sel, block_size, heartbeat_every, decisions,
            series_every, faults, fault_frag, unswitched,
        )
        _TABLE_ENGINE_CACHE[engine_key] = eng

    from tpusim.sim.step import resolve_weights

    def replay(state, pods, types, ev_kind, ev_pod, tp, key,
               tiebreak_rank=None, tables=None, weights=None,
               fault_ops=None, fault_carry0=None) -> ReplayResult:
        if faults:
            return eng.replay(
                state, pods, types, ev_kind, ev_pod, tp, key,
                resolve_weights(policies, weights), tiebreak_rank, tables,
                fault_ops, fault_carry0,
            )
        return eng.replay(
            state, pods, types, ev_kind, ev_pod, tp, key,
            resolve_weights(policies, weights), tiebreak_rank, tables,
        )

    def init_carry(state, pods, types, tp, key, tiebreak_rank=None,
                   tables=None, weights=None, fault_carry0=None):
        if faults:
            return eng.init_carry(
                state, pods, types, tp, key,
                resolve_weights(policies, weights), tiebreak_rank, tables,
                fault_carry0,
            )
        return eng.init_carry(
            state, pods, types, tp, key,
            resolve_weights(policies, weights), tiebreak_rank, tables,
        )

    def run_chunk(carry, pods, types, ev_kind, ev_pod, tp,
                  tiebreak_rank=None, weights=None, fault_ops=None):
        if faults:
            return eng.run_chunk(
                carry, pods, types, ev_kind, ev_pod, tp,
                resolve_weights(policies, weights), tiebreak_rank,
                fault_ops,
            )
        return eng.run_chunk(
            carry, pods, types, ev_kind, ev_pod, tp,
            resolve_weights(policies, weights), tiebreak_rank,
        )

    def run_chunk_donated(carry, pods, types, ev_kind, ev_pod, tp,
                          tiebreak_rank=None, weights=None,
                          fault_ops=None):
        """run_chunk with the input carry DONATED to the outputs
        (ISSUE 11): the segment scan reuses the carry's buffers instead
        of reallocating the O(N*K) tables every chunk. The passed carry
        is consumed — snapshot it (np.asarray) first if it must survive,
        which is exactly the driver checkpoint loop's save-then-advance
        order."""
        if faults:
            return eng.run_chunk_donate(
                carry, pods, types, ev_kind, ev_pod, tp,
                resolve_weights(policies, weights), tiebreak_rank,
                fault_ops,
            )
        return eng.run_chunk_donate(
            carry, pods, types, ev_kind, ev_pod, tp,
            resolve_weights(policies, weights), tiebreak_rank,
        )

    # the compiled-executable census of the donating entry (the
    # mesh-chaos gate's one-executable hard check reads it)
    run_chunk_donated._cache_size = eng.run_chunk_donate._cache_size

    # the chunk-resume surface (driver checkpointing, ENGINES.md
    # "Checkpoint/resume"): replay == finish ∘ run_chunk* ∘ init_carry
    replay.init_carry = init_carry
    replay.run_chunk = run_chunk
    replay.run_chunk_donated = run_chunk_donated
    replay.finish = eng.finish
    # the standalone table builder the driver's content-keyed cache
    # persists (io.storage.save_tables); feeding its output back through
    # `tables=` skips the K-node-sweep init bit-identically. The build
    # never reads weights, so one cached table set serves every weight
    # vector of the family.
    replay.build_tables = eng.build_tables
    # the shared weight-operand machinery (the config-axis sweep vmaps
    # eng.replay over stacked weights/keys/ranks)
    replay.engine = eng
    _TABLE_REPLAY_CACHE[cache_key] = replay
    return replay


class _TableEngine(NamedTuple):
    """The weight-operand jitted surface one policy family shares:
    every callable takes the i32[num_pol] weight vector as a traced
    argument (never baked), so the family compiles once.

    `replay` is also the vmap target of a sweep with one trace a lane
    (ISSUE 7, driver._sweep_engine): pods, types.type_id, and the event
    streams batch per lane while types.share/types.whole — the distinct
    type set the tables index — broadcast, so tuned trace variants are
    data, not jaxpr structure. Nothing in the engine reads type_id
    except as a per-pod gather key, which is what makes the lift
    possible without touching the scan body."""

    replay: object  # (state, pods, types, evk, evp, tp, key, wts, rank, tables)
    init_carry: object  # (state, pods, types, tp, key, wts, rank, tables)
    run_chunk: object  # (carry, pods, types, evk, evp, tp, wts, rank)
    run_chunk_donate: object  # run_chunk with the carry donated (ISSUE 11)
    finish: object  # (carry)
    build_tables: object  # (state, types, tp, key) — weight-independent
    # what build_tables reads beside its operands (make_table_builders:
    # the policy kernels and the selector index): two engines that agree
    # here build the same tables from the same operands, whatever else
    # they differ in (faults, heartbeat, block size). The driver keeps a
    # sweep's tables on the device under it (Simulator._sweep_tables)
    closes_over: tuple
    # (num_nodes, types) -> bool: whether run_chunk / replay on a cluster
    # and type set of that size leaves the add into aff_cnt out of the event
    # loop and makes it once a chunk (chunk_affinity): the flat body of a
    # program in which no kernel reads that leaf and no fault step
    # rewrites it (SweepRecord.affinity_deferred)
    affinity_deferred: object
    # (num_nodes, types) -> bool: whether that event loop keeps the add and
    # holds the leaf nodes minor, [classes, N] a lane: the flat body of a
    # program in which a kernel reads the leaf and no fault step rewrites
    # it (SweepRecord.affinity_nodes_minor)
    affinity_nodes_minor: object


def _make_table_engine(
    policies, gpu_sel: str, block_size: int, heartbeat_every: int,
    decisions: bool, series_every: int, faults: bool = False,
    fault_frag: bool = False, unswitched: bool = False,
) -> _TableEngine:
    """Build the jitted weight-operand machinery make_table_replay wraps.
    The closed-over `policies` weights are deliberately never read — only
    the kernel objects and their normalize/name metadata are static; the
    numeric weights always arrive as the `wts` operand.

    faults=True (ISSUE 10) builds the fault-plane variant: the scan
    consumes the MERGED stream (base + fault + retry-slot steps,
    tpusim.sim.fault_lane) with three extra xs (pos/arg/aux), the carry
    becomes (table carry, FaultCarry) — the retry queue rides the same
    checkpoint/resume surface as every other leaf — and fault kinds
    apply as masked one-node ops AFTER the event switch (they clip to
    EV_SKIP inside it, so the base machinery is untouched). Fault
    transitions touch exactly one node, so the existing dirty-column /
    dirty-block refresh keeps the tables exact; DOWN rows carry the
    mem_left == -1 sentinel the Filter already rejects."""
    num_pol = len(policies)
    if faults:
        from tpusim.sim import fault_lane as _fl
    sel_idx = selector_index(policies, gpu_sel)
    _columns, _init_tables = make_table_builders(policies, sel_idx)
    has_random = any(fn.policy_name == "RandomScore" for fn, _ in policies)
    # The flat body's commit leaves aff_cnt alone and _run_chunk_impl adds
    # the chunk's counts after its scan where nothing reads the leaf
    # between events: no kernel of the program (read off the kernels'
    # own declaration) and no fault step (fault_lane zeroes and rewrites
    # aff_cnt rows mid-scan, and overwrites the record of the touched
    # node). Never a caller's choice.
    defer_affinity = not faults and not policies_read_affinity(policies)
    # Where a kernel DOES read it (GpuClustering) the add and the read stay
    # in the flat event loop, which then holds the leaf NODES MINOR,
    # i32[classes, N] a lane, beside its table carry (_run_chunk_impl
    # transposes once on entry and once after the scan): at sweep width the
    # dense add and the dense column read pass over full tiles, where
    # i32[lanes, N, 9] uses nine of a tile's 128 minor entries (PERF.md
    # section 6, PR 46). Fault steps rewrite [N, 9] rows: they keep that
    # form, as the blocked body does. `beside`: the flat body's carry is a
    # pair (table carry, what rides beside it).
    nodes_minor_affinity = not faults and policies_read_affinity(policies)
    beside = faults or nodes_minor_affinity

    def block_size_of(num_nodes: int, num_types: int) -> int:
        """The block size init_carry lays the carry out with; 0: flat."""
        return 0 if has_random else resolve_block_size(
            block_size, num_nodes, num_types)

    # policies whose normalizer needs global (lo, hi) extrema over feasible
    # nodes; the blocked path maintains these via block min/max aggregates
    norm_idx = [
        i for i, (fn, _) in enumerate(policies)
        if fn.normalize in ("minmax", "pwr")
    ]
    norm_deg = [
        NORMALIZE_DEGENERATE[policies[i][0].normalize] for i in norm_idx
    ]

    def _late_row(row, late, cols_of, t_id):
        """`row` (row t_id of a table, [N]) as it reads once the pending
        block's columns `cols_of(late)` ([G, K]) are written; the row
        itself with nothing pending (`late` None: the plain body). Every
        read of a table row in the flat body goes through here, the one
        entry read through lane_write.patch_entry."""
        if late is None:
            return row
        return lane_write.patch_row(
            row, late.idx, lane_write.read_pending(cols_of(late), t_id))

    def _sample_from_tables(state, score_tbl, feas_tbl, t_id, tp, ctr,
                            late=None):
        """One in-scan SeriesSample off the just-refreshed tables — the
        flat and blocked bodies share it. The dirty refresh has already
        made score_tbl/feas_tbl equal to a full rebuild on the committed
        state, so gathering the event type's row is bit-identical to the
        sequential engine recomputing it; blocked pad columns are
        infeasible, so the normalized extrema cannot see them. The
        RandomScore slot is a zero table row and score_stats zeroes it
        anyway — the sample never consumes PRNG. The flat body passes its
        pending block (`late`): the rows are read with it applied."""
        processed = ctr[0] + ctr[3] + ctr[4]

        def build():
            raws = jax.lax.dynamic_index_in_dim(score_tbl, t_id, 1, False)
            feas = jax.lax.dynamic_index_in_dim(feas_tbl, t_id, 0, False)
            if late is not None:
                raws = jnp.stack([
                    _late_row(raws[i], late, lambda l: l.score[:, i], t_id)
                    for i in range(num_pol)
                ])
                feas = _late_row(feas, late, lambda l: l.feas, t_id)
            return obs_series.build_sample(
                state, tp, raws, feas, policies, processed
            )

        return obs_series.emit_from_scan(
            series_every, processed, build, num_pol
        )

    def _totals(raws, feas, slo, shi, wts):
        """Weighted normalized totals with a -INT_MAX sentinel at
        infeasible entries. raws: i32[num_pol, ..., X]; feas: bool[..., X];
        slo/shi: i32[len(norm_idx), ...] stored extrema per normalized
        policy; wts: the i32[num_pol] weight operand. The apply half is
        the shared minmax_scale_i32, so feasible entries match the
        oracle's minmax/pwr_normalize_i32 bit-for-bit whenever slo/shi
        equal the current feasible extrema."""
        with jax.named_scope("tpusim.normalize"):
            tot = jnp.zeros(feas.shape, jnp.int32)
            for i, (fn, _) in enumerate(policies):
                raw = raws[i]
                if fn.normalize in ("minmax", "pwr"):
                    j = norm_idx.index(i)
                    raw = minmax_scale_i32(
                        raw, feas, slo[j][..., None], shi[j][..., None],
                        norm_deg[j],
                    )
                tot = tot + wts[i] * raw
            return jnp.where(feas, tot, -_INT_MAX)

    def make_blocked_body(
        pods, type_id, types, tp, rank_p, n, num_pods, bsz, k_types, nblk,
        offs, wts, fault_ops=None,
    ):
        """Scan body of the blocked O(B + N/B) select path: tables padded
        to a whole number of B-node blocks (sentinel columns: infeasible,
        rank INT_MAX), plus the incremental aggregates

            brmin/brmax[pn, K, N/B]  block raw-score extrema over feasible
                                     nodes per normalized policy (their
                                     min/max over blocks == the global
                                     feasible_min_max extrema exactly)
            bt/br/bn[K, N/B]         per block: max weighted total, min
                                     tie-break rank among the maxima, and
                                     that winner's node id — the block
                                     summaries the final packed_argmax
                                     reduces over

        bt rows are built with *stored* per-type extrema (slo/shi); a
        per-event drift check against the current blocked extrema rebuilds
        one type's summary row (inside a cond, so the O(N) rebuild only
        costs when an extremum actually moved) before the select consumes
        it — which is what keeps normalized policies bit-identical to the
        flat path."""
        n_norm = len(norm_idx)

        def body(carry, ev):
            if faults:
                carry, fc = carry
                kind, idx, fpos, farg, faux = ev
            (state, score_tbl, sdev_tbl, feas_tbl, bt, br, bn,
             brmin, brmax, slo, shi, pend, dirty,
             placed, masks, failed, arr_cpu, arr_gpu, key, ctr) = carry
            if not faults:
                kind, idx = ev
                kc = jnp.clip(kind, 0, 2)
            else:
                is_slot = kind == EV_RETRY
                fc, has_pop, rpod = _fl.pop_retry(fc, is_slot, fpos, farg)
                idx = jnp.where(has_pop, rpod, idx)
                kc = jnp.where(
                    is_slot, jnp.where(has_pop, 0, 2),
                    jnp.clip(kind, 0, 2),
                )
            pod = jax.tree.map(lambda a: a[idx], pods)
            t_id = type_id[idx]
            # identical key-split discipline to the flat path / oracle
            key, sub = jax.random.split(key)
            k_rand, k_sel = jax.random.split(sub)

            # apply the PREVIOUS event's deferred scatters first — every
            # carried buffer is written before anything reads it, so all
            # updates alias in place (PendingCommit)
            state, placed, masks, failed = _scoped_commit(
                state, placed, masks, failed, pend)

            # dirty-column refresh — same kernels, same order as the flat
            # path; dirty < n always, so sentinel columns are never written
            with jax.named_scope("tpusim.refresh"):
                col_scores, col_sdev, col_feas = _columns(
                    _row_state(state, dirty), types, tp, k_rand
                )
                blk = dirty // bsz
                j0 = blk * bsz
                score_tbl, raw_blk = lane_write.write_column(
                    score_tbl, col_scores, dirty, block=(j0, bsz)
                )
                sdev_tbl = lane_write.write_column(sdev_tbl, col_sdev, dirty)
                feas_tbl, feas_blk = lane_write.write_column(
                    feas_tbl, col_feas, dirty, block=(j0, bsz)
                )

            # in-scan series sample (ISSUE 5): committed state + current
            # tables, on the processed-event stride
            ser = (
                _sample_from_tables(state, score_tbl, feas_tbl, t_id, tp,
                                    ctr)
                if series_every else ()
            )

            # dirty-block aggregate refresh for ALL K types: O(K*B)
            with jax.named_scope("tpusim.summary"):
                rank_blk = jax.lax.dynamic_slice(rank_p, (j0,), (bsz,))
                if n_norm:
                    with jax.named_scope("tpusim.normalize"):
                        selb = jnp.stack([raw_blk[i] for i in norm_idx])
                        mn = jnp.where(feas_blk, selb, _INT_MAX).min(-1)
                        mx = jnp.where(feas_blk, selb, -_INT_MAX).max(-1)
                    brmin = jax.lax.dynamic_update_slice(
                        brmin, mn[:, :, None], (0, 0, blk)
                    )
                    brmax = jax.lax.dynamic_update_slice(
                        brmax, mx[:, :, None], (0, 0, blk)
                    )
                # block totals use the STORED extrema — consistent with every
                # other block of each type's summary row by construction
                tot_blk = _totals(raw_blk, feas_blk, slo, shi, wts)
                bm, brk, bar = block_reduce(tot_blk, rank_blk)
                bt = jax.lax.dynamic_update_slice(bt, bm[:, None], (0, blk))
                br = jax.lax.dynamic_update_slice(br, brk[:, None], (0, blk))
                bn = jax.lax.dynamic_update_slice(
                    bn, (j0 + bar)[:, None], (0, blk)
                )

                # extrema drift check + conditional summary-row rebuild for
                # this event's type — outside the event switch, so only [N/B]
                # rows (never whole tables) cross a cond/switch boundary
                rebuilt = None  # obs: did this event pay the O(N) rebuild?
                if n_norm:
                    brmin_row = jax.lax.dynamic_index_in_dim(
                        brmin, t_id, 1, False
                    )
                    brmax_row = jax.lax.dynamic_index_in_dim(
                        brmax, t_id, 1, False
                    )
                    slo_col = jax.lax.dynamic_index_in_dim(slo, t_id, 1, False)
                    shi_col = jax.lax.dynamic_index_in_dim(shi, t_id, 1, False)
                    with jax.named_scope("tpusim.normalize"):
                        lo_cur = brmin_row.min(-1)
                        hi_cur = brmax_row.max(-1)
                        changed = jnp.any(
                            (lo_cur != slo_col) | (hi_cur != shi_col)
                        )
                    rebuilt = changed

                    def rebuild():
                        raws = jax.lax.dynamic_index_in_dim(
                            score_tbl, t_id, 1, False
                        )  # [num_pol, n_pad]
                        fr = jax.lax.dynamic_index_in_dim(
                            feas_tbl, t_id, 0, False
                        )
                        tot = _totals(
                            raws[:, None, :], fr[None, :],
                            lo_cur[:, None], hi_cur[:, None], wts,
                        )[0]
                        m2, r2, a2 = block_reduce(
                            tot.reshape(nblk, bsz), rank_p.reshape(nblk, bsz)
                        )
                        return m2, r2, offs + a2, lo_cur, hi_cur

                    def keep():
                        return (
                            jax.lax.dynamic_index_in_dim(bt, t_id, 0, False),
                            jax.lax.dynamic_index_in_dim(br, t_id, 0, False),
                            jax.lax.dynamic_index_in_dim(bn, t_id, 0, False),
                            slo_col,
                            shi_col,
                        )

                    bt_row, br_row, bn_row, lo_new, hi_new = jax.lax.cond(
                        changed, rebuild, keep
                    )
                    bt = jax.lax.dynamic_update_slice(
                        bt, bt_row[None], (t_id, 0)
                    )
                    br = jax.lax.dynamic_update_slice(
                        br, br_row[None], (t_id, 0)
                    )
                    bn = jax.lax.dynamic_update_slice(
                        bn, bn_row[None], (t_id, 0)
                    )
                    slo = jax.lax.dynamic_update_slice(
                        slo, lo_new[:, None], (0, t_id)
                    )
                    shi = jax.lax.dynamic_update_slice(
                        shi, hi_new[:, None], (0, t_id)
                    )
                else:
                    bt_row = jax.lax.dynamic_index_in_dim(bt, t_id, 0, False)
                    br_row = jax.lax.dynamic_index_in_dim(br, t_id, 0, False)
                    bn_row = jax.lax.dynamic_index_in_dim(bn, t_id, 0, False)

            @jax.named_scope("tpusim.select")
            def do_create():
                # selectHost over N/B block summaries — the same
                # packed_argmax combine the oracle runs over N nodes
                blk_i, _, okb = packed_argmax(
                    bt_row, bt_row != -_INT_MAX, br_row
                )
                cand = bn_row[blk_i]
                # nodeSelector-pinned pods have exactly one candidate: the
                # winner is the pinned node iff Filter passes there (score
                # values cannot matter with a single candidate), matching
                # the oracle's per-event pinned feasibility mask. An
                # out-of-range pin (unknown nodeSelector name — trace.py
                # encodes it as index n) can never be feasible.
                pin = jnp.clip(pod.pinned, 0, n - 1)
                pin_feas = (
                    lane_write.read_entry(feas_tbl, t_id, pin)
                    & (pod.pinned < n)
                )
                node = jnp.where(
                    pod.pinned >= 0,
                    jnp.where(pin_feas, pin, -1),
                    jnp.where(okb, cand, -1),
                ).astype(jnp.int32)
                ok = node >= 0
                sel = jnp.maximum(node, 0)
                dev_scalar = lane_write.read_entry(sdev_tbl, t_id, sel)
                dmask = choose_devices(
                    lane_write.read_row(state.gpu_left, sel, keepdims=False),
                    pod, dev_scalar, gpu_sel, k_sel,
                ) & ok
                node_f = jnp.where(ok, sel, -1).astype(jnp.int32)
                if not decisions:
                    return node_f, dmask
                # provenance: rebuild this type's full totals row with
                # DIRECT normalization over the pin-masked feasibility —
                # exactly the computation the flat path selects with (and
                # what the blocked two-level select is bit-identical to),
                # so the record cannot depend on the engine. Sentinel pad
                # columns are infeasible + rank INT_MAX: never in the topk.
                raws_row = jax.lax.dynamic_index_in_dim(
                    score_tbl, t_id, 1, False
                )  # [num_pol, n_pad]
                feas_row = jax.lax.dynamic_index_in_dim(
                    feas_tbl, t_id, 0, False
                )
                n_pad_l = feas_row.shape[0]
                pin_m = (pod.pinned < 0) | (
                    jnp.arange(n_pad_l, dtype=jnp.int32) == pod.pinned
                )
                feas_d = feas_row & pin_m
                norm_rows = []
                tot_d = jnp.zeros(n_pad_l, jnp.int32)
                for i, (fn, _) in enumerate(policies):
                    raw = raws_row[i]
                    if fn.normalize == "minmax":
                        nrm = minmax_normalize_i32(raw, feas_d)
                    elif fn.normalize == "pwr":
                        nrm = pwr_normalize_i32(raw, feas_d)
                    else:
                        nrm = raw
                    norm_rows.append(nrm)
                    tot_d = tot_d + wts[i] * nrm
                dec = build_decision(
                    node_f, raws_row, jnp.stack(norm_rows), tot_d, feas_d,
                    rank_p,
                )
                # the engine-specific slot: which block won the two-level
                # select (a pinned pod bypasses blocks — its node's block)
                win_blk = jnp.where(
                    ok,
                    jnp.where(pod.pinned >= 0, pin // bsz, blk_i),
                    -1,
                ).astype(jnp.int32)
                return node_f, dmask, dec._replace(block=win_blk)

            def do_delete():
                base = (lane_write.read_pod(placed, idx),
                        lane_write.read_pod(masks, idx))
                return base + ((no_decision(num_pol),) if decisions else ())

            def do_skip():
                base = (
                    jnp.int32(-1), jnp.zeros(MAX_GPUS_PER_NODE, jnp.bool_)
                )
                return base + ((no_decision(num_pol),) if decisions else ())

            outs = jax.lax.switch(kc, [do_create, do_delete, do_skip])
            if decisions:
                node, dev, dec = outs
            else:
                node, dev = outs
            # defer this event's scatters to the next iteration
            pend = make_pending_commit(kc, idx, node, dev, pod, num_pods)
            arr_cpu = arr_cpu + jnp.where(kc == 0, pod.cpu, 0)
            arr_gpu = arr_gpu + jnp.where(kc == 0, pod.total_gpu_milli(), 0)
            dirty = jnp.where(kc == 2, dirty, jnp.maximum(node, 0))
            ctr = ctr + counter_delta(kc, node, rebuilt)
            if heartbeat_every:
                obs_heartbeat.emit_from_scan(
                    ctr[0] + ctr[3] + ctr[4], heartbeat_every
                )
            if faults:
                pend = pend._replace(failed_val=jnp.where(
                    is_slot, failed[idx] | (node < 0), node < 0
                ))
                (state, placed, masks, failed, fc, ftouch, fy) = (
                    _fl.apply_fault_step(
                        state, placed, masks, failed, fc, pods, kind,
                        farg, faux, fpos, fault_ops, tp,
                        jnp.arange(n, dtype=jnp.int32), fault_frag,
                    )
                )
                fc, lat, _ = _fl.commit_retry(
                    fc, has_pop, rpod, node, fpos, farg, fault_ops.params
                )
                fy = fy._replace(
                    rpod=jnp.where(has_pop, rpod, -1).astype(jnp.int32),
                    lat=lat,
                )
                dirty = jnp.where(ftouch >= 0, ftouch, dirty)
                node = jnp.where(ftouch >= 0, ftouch, node)
            new_carry = BlockedTableCarry(
                state, score_tbl, sdev_tbl, feas_tbl, bt, br, bn,
                brmin, brmax, slo, shi, pend, dirty,
                placed, masks, failed, arr_cpu, arr_gpu, key, ctr,
            )
            ys = (
                (node, dev)
                + ((dec,) if decisions else ())
                + ((ser,) if series_every else ())
            )
            if faults:
                return (new_carry, fc), ys + (fy,)
            return new_carry, ys

        return body

    def make_flat_body(pods, type_id, types, tp, tiebreak_rank, n, num_pods,
                       wts, fault_ops=None, grouped: bool = False):
        """Scan body of the flat O(N) select path.

        Under defer_affinity its commit leaves the add into aff_cnt to
        _run_chunk_impl's epilogue: the leaf passes through the loop unread
        and unwritten. Under nodes_minor_affinity it passes through the
        same way, and the carry is (table carry, aff_t): the commit's add
        and the dirty node's counts for the column kernel go to aff_t, the
        leaf with the nodes on its last axis.

        `grouped` (a wide sweep: flat_group_events) makes it one event of
        a group (_run_flat_group). Its carry is then (table carry,
        LateColumns) and its xs lead with the event's slot in the group:
        the step stores its dirty column in that slot and leaves the tables
        as they are, every read of a table row applies the pending block
        (_late_row, lane_write.patch_entry), and the group's flush writes
        the block down. Same integers in the same order as a write every
        event: event e reads rows that hold every column computed up to e,
        its own included (the slot is stored before the select reads), and
        of two slots naming one node the later wins, in the patch and in
        the flush.

        Round 18 ports the shard engine's Round-15 unconditional-select
        restructure back here as an A/B layout knob (`unswitched`, the
        shard engine's `pipelined` pattern): with it ON, the select runs
        UNCONDITIONALLY every event (score/feas rows never cross a
        branch boundary — the branch-capture class the shard engine
        shed) and only the small (node, dev[, dec]) results merge by
        kind. Bit-identical to the switch form by construction — the
        same create_result closure runs either inside the switch branch
        or inline, with the same pre-split k_rand/k_sel (pinned by
        tests/test_table_engine.py::test_unswitched_flat_bit_identity).
        MEASURED at N=100k on the CPU backend (bench_scale --nodes
        100000 --block-size -1, creates-only stream): the switch form
        wins, ~5.3 vs ~6.8 ms/event — XLA:CPU lowers the in-branch row
        reads as plain gathers (no whole-table copy), so removing the
        branch only adds merge selects. The default therefore stays on
        the switch; the unswitched layout exists for accelerator
        backends where conditionals serialize the stream (the Round 15
        motivation) and for A/B measurement."""

        def body(carry, ev):
            late = None
            if grouped:
                carry, late = carry
                slot, *ev = ev
            aff_t = None
            if faults:
                carry, fc = carry
                kind, idx, fpos, farg, faux = ev
            elif nodes_minor_affinity:
                carry, aff_t = carry
            (state, score_tbl, sdev_tbl, feas_tbl, pend, dirty,
             placed, masks, failed, arr_cpu, arr_gpu, key, ctr) = carry
            if not faults:
                kind, idx = ev
                kc = jnp.clip(kind, 0, 2)
            else:
                # retry slots pop the earliest due evicted pod and run it
                # through the ordinary create branch; fault kinds clip to
                # skip here and apply as masked ops after the switch
                is_slot = kind == EV_RETRY
                fc, has_pop, rpod = _fl.pop_retry(fc, is_slot, fpos, farg)
                idx = jnp.where(has_pop, rpod, idx)
                kc = jnp.where(
                    is_slot, jnp.where(has_pop, 0, 2),
                    jnp.clip(kind, 0, 2),
                )
            pod = jax.tree.map(lambda a: a[idx], pods)
            t_id = type_id[idx]
            # the sequential oracle's split discipline exactly (engine.py
            # body: key, sub = split(key); schedule_one: k_rand, k_sel =
            # split(sub)) — this is what makes the per-event random draws
            # below bit-identical to the oracle's
            key, sub = jax.random.split(key)
            k_rand, k_sel = jax.random.split(sub)

            # apply the PREVIOUS event's deferred scatters first: every
            # carried buffer is written before anything reads it this
            # iteration, so all updates alias in place (PendingCommit)
            state, placed, masks, failed = _scoped_commit(
                state, placed, masks, failed, pend, affinity=faults)
            if nodes_minor_affinity:
                with jax.named_scope("tpusim.commit"):
                    aff_t = add_commit_affinity(aff_t, pend)

            # refresh the one column whose node changed last event (from
            # the just-committed state). Grouped, the tables are not
            # written here: the column goes into this event's slot of the
            # pending block (an index the lanes of a sweep share), every
            # read below applies the block, and the group's flush writes it
            with jax.named_scope("tpusim.refresh"):
                col_scores, col_sdev, col_feas = _columns(
                    _row_state(state, dirty, aff_t), types, tp, k_rand
                )
                if grouped:
                    late = LateColumns(*(
                        jax.lax.dynamic_update_index_in_dim(
                            arr, val, slot, 0)
                        for arr, val in zip(
                            late, (dirty, col_scores, col_sdev, col_feas))
                    ))
                else:
                    score_tbl = lane_write.write_column(
                        score_tbl, col_scores, dirty
                    )
                    sdev_tbl = lane_write.write_column(
                        sdev_tbl, col_sdev, dirty)
                    feas_tbl = lane_write.write_column(
                        feas_tbl, col_feas, dirty)

            # in-scan series sample (ISSUE 5): committed state + current
            # tables, on the processed-event stride
            ser = (
                _sample_from_tables(state, score_tbl, feas_tbl, t_id, tp,
                                    ctr, late=late)
                if series_every else ()
            )

            @jax.named_scope("tpusim.select")
            def create_result():
                """The full create computation — ONE definition serving
                both select layouts below (Round 18)."""
                feasible = _late_row(
                    feas_tbl[t_id], late, lambda l: l.feas, t_id
                ) & (
                    (pod.pinned < 0)
                    | (jnp.arange(n, dtype=jnp.int32) == pod.pinned)
                )
                total = jnp.zeros(n, jnp.int32)
                raw_rows, norm_rows = [], []
                for i, (fn, _) in enumerate(policies):
                    if fn.policy_name == "RandomScore":
                        # per-event draw, recomputed instead of
                        # table-read — through the ONE canonical kernel
                        # (the oracle's schedule_one calls the same fn
                        # with the same feasible mask and k_rand)
                        ctx = ScoreContext(
                            tp=tp, feasible=feasible, rng=k_rand
                        )
                        raw = fn(state, pod, ctx).raw_scores
                    else:
                        raw = _late_row(
                            score_tbl[i, t_id], late,
                            lambda l: l.score[:, i], t_id,
                        )
                    # the plugin's NormalizeScore (feasible extrema, then
                    # the scale) and the framework's weighted total
                    with jax.named_scope("tpusim.normalize"):
                        if fn.normalize == "minmax":
                            nrm = minmax_normalize_i32(raw, feasible)
                        elif fn.normalize == "pwr":
                            nrm = pwr_normalize_i32(raw, feasible)
                        else:
                            nrm = raw
                        total = total + wts[i] * nrm
                    if decisions:
                        raw_rows.append(raw)
                        norm_rows.append(nrm)
                # the oracle's selectHost + Reserve halves; the Bind
                # scatter is deferred via PendingCommit
                sel, _, ok = packed_argmax(total, feasible, tiebreak_rank)
                left = lane_write.read_row(
                    state.gpu_left, sel, keepdims=False)
                dev_scalar = lane_write.read_entry(sdev_tbl, t_id, sel)
                if grouped:
                    dev_scalar = lane_write.patch_entry(
                        dev_scalar, sel, late.idx,
                        lane_write.read_pending(late.sdev, t_id),
                    )
                dmask = choose_devices(
                    left, pod, dev_scalar, gpu_sel, k_sel,
                ) & ok
                node_f = jnp.where(ok, sel, -1).astype(jnp.int32)
                if not decisions:
                    return node_f, dmask
                # provenance off the very rows the select consumed
                dec = build_decision(
                    node_f, jnp.stack(raw_rows), jnp.stack(norm_rows),
                    total, feasible, tiebreak_rank,
                )
                return node_f, dmask, dec

            if unswitched:
                # the shard engine's Round-15 form: the select runs
                # UNCONDITIONALLY (table rows never cross a branch
                # boundary) and only the small (node, dev[, dec])
                # results merge by kind
                outs_c = create_result()
                is_create = kc == 0
                is_delete = kc == 1
                node = jnp.where(
                    is_create, outs_c[0],
                    jnp.where(is_delete, lane_write.read_pod(placed, idx),
                              jnp.int32(-1)),
                ).astype(jnp.int32)
                dev = jnp.where(
                    is_create, outs_c[1],
                    jnp.where(is_delete, lane_write.read_pod(masks, idx),
                              jnp.zeros(MAX_GPUS_PER_NODE, jnp.bool_)),
                )
                if decisions:
                    dec = jax.tree.map(
                        lambda a, b: jnp.where(is_create, a, b),
                        outs_c[2], no_decision(num_pol),
                    )
            else:
                # the event switch (the measured-faster layout on the
                # single-device CPU flat path — ENGINES.md Round 18)

                def do_delete():
                    base = (lane_write.read_pod(placed, idx),
                            lane_write.read_pod(masks, idx))
                    return base + (
                        (no_decision(num_pol),) if decisions else ()
                    )

                def do_skip():
                    base = (
                        jnp.int32(-1),
                        jnp.zeros(MAX_GPUS_PER_NODE, jnp.bool_),
                    )
                    return base + (
                        (no_decision(num_pol),) if decisions else ()
                    )

                outs = jax.lax.switch(
                    kc, [create_result, do_delete, do_skip]
                )
                if decisions:
                    node, dev, dec = outs
                else:
                    node, dev = outs
            # defer this event's scatters to the next iteration; arrived
            # counters accumulate per creation event regardless of outcome
            # (simulator.go:406-408)
            pend = make_pending_commit(kc, idx, node, dev, pod, num_pods)
            arr_cpu = arr_cpu + jnp.where(kc == 0, pod.cpu, 0)
            arr_gpu = arr_gpu + jnp.where(kc == 0, pod.total_gpu_milli(), 0)
            dirty = jnp.where(kc == 2, dirty, jnp.maximum(node, 0))
            ctr = ctr + counter_delta(kc, node)
            if heartbeat_every:
                obs_heartbeat.emit_from_scan(
                    ctr[0] + ctr[3] + ctr[4], heartbeat_every
                )
            if faults:
                # retry creates accumulate ever-failed with OR (the
                # segmented path's per-segment `|=`); base creates still
                # overwrite (they run once per pod)
                pend = pend._replace(failed_val=jnp.where(
                    is_slot, failed[idx] | (node < 0), node < 0
                ))
                (state, placed, masks, failed, fc, ftouch, fy) = (
                    _fl.apply_fault_step(
                        state, placed, masks, failed, fc, pods, kind,
                        farg, faux, fpos, fault_ops, tp,
                        jnp.arange(n, dtype=jnp.int32), fault_frag,
                    )
                )
                fc, lat, _ = _fl.commit_retry(
                    fc, has_pop, rpod, node, fpos, farg, fault_ops.params
                )
                fy = fy._replace(
                    rpod=jnp.where(has_pop, rpod, -1).astype(jnp.int32),
                    lat=lat,
                )
                dirty = jnp.where(ftouch >= 0, ftouch, dirty)
                node = jnp.where(ftouch >= 0, ftouch, node)
            new_carry = FlatTableCarry(
                state, score_tbl, sdev_tbl, feas_tbl, pend, dirty,
                placed, masks, failed, arr_cpu, arr_gpu, key, ctr,
            )
            ys = (
                (node, dev)
                + ((dec,) if decisions else ())
                + ((ser,) if series_every else ())
            )
            if faults:
                new_carry, ys = (new_carry, fc), ys + (fy,)
            elif nodes_minor_affinity:
                new_carry = (new_carry, aff_t)
            return ((new_carry, late) if grouped else new_carry), ys

        return body

    def _run_flat_group(body, carry, xs, slots: int):
        """One group of the flat replay: `slots` events (xs leaves
        [slots, ...]) through `body` with their dirty columns held in a
        fresh pending block, then the flush: each table takes the group's
        columns in one access. The carry that comes back has nothing
        pending."""
        base = carry[0] if beside else carry
        n_pol, k_types = base.score_tbl.shape[:2]
        held = max(slots, 1)  # an empty segment still traces the body
        late = LateColumns(
            jnp.full(held, -1, jnp.int32),
            jnp.zeros((held, n_pol, k_types), jnp.int32),
            jnp.zeros((held, k_types), jnp.int32),
            jnp.zeros((held, k_types), jnp.bool_),
        )
        # unroll amortizes per-iteration fixed costs (~20% wall on the openb
        # replay); higher factors showed no further gain
        (carry, late), ys = jax.lax.scan(
            body, (carry, late),
            (jnp.arange(slots, dtype=jnp.int32),) + tuple(xs), unroll=4,
        )
        base = carry[0] if beside else carry
        with jax.named_scope("tpusim.refresh"):
            base = base._replace(
                score_tbl=lane_write.write_columns(
                    base.score_tbl, late.score, late.idx),
                sdev_tbl=lane_write.write_columns(
                    base.sdev_tbl, late.sdev, late.idx),
                feas_tbl=lane_write.write_columns(
                    base.feas_tbl, late.feas, late.idx),
            )
        return ((base, carry[1]) if beside else base), ys

    # FaultCarry pod-axis pad/trim to the carry's P+1 bookkeeping rows —
    # shared with the shard engine (fault_lane.pad/trim_fault_carry)
    def _pad_fc(fc0):
        from tpusim.sim import fault_lane as _fl

        return _fl.pad_fault_carry(fc0)

    def _trim_fc(fc):
        from tpusim.sim import fault_lane as _fl

        return _fl.trim_fault_carry(fc)

    @jax.jit
    def init_carry(state, pods, types, tp, key, wts, tiebreak_rank=None,
                   tables=None, fault_carry0=None):
        """Engine state at event 0: score/sdev/feas tables from the
        committed state + an inert pipeline register (and, on the blocked
        path, the per-(policy, type, block) aggregates built from the
        `wts` weight operand).

        `tables` short-circuits the K-node-sweep build with precomputed
        (score_tbl, sdev_tbl, feas_tbl) — the driver's content-keyed
        cache path; every downstream aggregate derives from them, so a
        cached init is bit-identical to a built one.

        The event key chain must stay byte-for-byte the sequential
        oracle's (it never burns a split before its scan), so the random
        replay path sees identical per-event keys; no table-ized column
        kernel consumes rng, so init can reuse the root key as-is."""
        n = state.num_nodes
        num_pods = pods.cpu.shape[0]
        k_types = _num_types(types)
        bsz = block_size_of(n, k_types)
        if tiebreak_rank is None:
            tiebreak_rank = jnp.arange(n, dtype=jnp.int32)
        if tables is None:
            score_tbl, sdev_tbl, feas_tbl = _init_tables(state, types, tp, key)
        else:
            score_tbl, sdev_tbl, feas_tbl = tables

        # one extra dummy row absorbs skip-event writes of the pipelined
        # commit (PendingCommit.pod_write); sliced off by finish()
        placed = jnp.full(num_pods + 1, -1, jnp.int32)
        masks = jnp.zeros((num_pods + 1, MAX_GPUS_PER_NODE), jnp.bool_)
        failed = jnp.zeros(num_pods + 1, jnp.bool_)
        pend = no_pending_commit(num_pods)
        z = jnp.int32(0)
        if not bsz:
            flat = FlatTableCarry(
                state, score_tbl, sdev_tbl, feas_tbl, pend, z,
                placed, masks, failed, z, z, key, zero_counters(),
            )
            return (flat, _pad_fc(fault_carry0)) if faults else flat

        nblk = -(-n // bsz)
        n_pad = nblk * bsz
        n_norm = len(norm_idx)
        rank_p = _pad_rank(tiebreak_rank, n_pad)
        if n_pad != n:
            pad = n_pad - n
            score_tbl = jnp.pad(score_tbl, ((0, 0), (0, 0), (0, pad)))
            sdev_tbl = jnp.pad(
                sdev_tbl, ((0, 0), (0, pad)), constant_values=-1
            )
            feas_tbl = jnp.pad(feas_tbl, ((0, 0), (0, pad)))
        offs = jnp.arange(nblk, dtype=jnp.int32) * bsz

        if n_norm:
            sel0 = jnp.stack([score_tbl[i] for i in norm_idx])
            brmin = jnp.where(feas_tbl, sel0, _INT_MAX).reshape(
                n_norm, k_types, nblk, bsz
            ).min(-1)
            brmax = jnp.where(feas_tbl, sel0, -_INT_MAX).reshape(
                n_norm, k_types, nblk, bsz
            ).max(-1)
            slo = brmin.min(-1)  # [pn, K] == per-row feasible_min_max
            shi = brmax.max(-1)
        else:
            brmin = jnp.zeros((0, k_types, nblk), jnp.int32)
            brmax = jnp.zeros((0, k_types, nblk), jnp.int32)
            slo = jnp.zeros((0, k_types), jnp.int32)
            shi = jnp.zeros((0, k_types), jnp.int32)

        tot0 = _totals(score_tbl, feas_tbl, slo, shi, wts)  # [K, n_pad]
        bt, br, ba = block_reduce(
            tot0.reshape(k_types, nblk, bsz), rank_p.reshape(nblk, bsz)
        )
        bn = offs[None, :] + ba  # [K, nblk] global winner node ids
        blocked = BlockedTableCarry(
            state, score_tbl, sdev_tbl, feas_tbl, bt, br, bn,
            brmin, brmax, slo, shi, pend, z,
            placed, masks, failed, z, z, key, zero_counters(),
        )
        return (blocked, _pad_fc(fault_carry0)) if faults else blocked

    def _run_chunk_impl(carry, pods, types, ev_kind, ev_pod, tp, wts,
                        tiebreak_rank=None, fault_ops=None, group: int = 1):
        """Advance `carry` over a segment of the event stream; returns
        (carry', (event_node, event_dev)) for the segment — extended with
        a per-event DecisionRecord element when the engine was built with
        decisions=True, then a per-event SeriesSample element when built
        with series_every > 0. Chaining
        run_chunk calls over any partition of the stream is bit-identical
        to one replay() over the whole stream — the scan body is a pure
        function of (carry, event), and every carry leaf is an exact dtype
        (i32/bool/u32), so even a host/disk round-trip between chunks
        cannot perturb the trajectory. `wts` must be the weight vector
        the carry was initialized under (the blocked summaries embed it).
        `group` (static; flat_group_events) makes the flat step write its
        columns that many events at a time; the carry that comes back is
        the same either way, and so is its aff_cnt where the flat body
        left the per-event add out (defer_affinity): the segment's counts
        go in here, after its scan, so every carry a caller sees, between
        two chunks, in a checkpoint or into finish, holds the leaf the
        per-event commit would have left at that event. Where the flat
        loop keeps the add (nodes_minor_affinity) it holds the leaf
        [classes, N], transposed here on the way in and on the way out:
        the carry a caller sees keeps aff_cnt [N, classes]."""
        base = carry[0] if faults else carry
        n = base.state.num_nodes
        num_pods = pods.cpu.shape[0]
        if tiebreak_rank is None:
            tiebreak_rank = jnp.arange(n, dtype=jnp.int32)
        type_id = types.type_id
        xs = (
            (ev_kind, ev_pod, fault_ops.pos, fault_ops.arg, fault_ops.aux)
            if faults else (ev_kind, ev_pod)
        )
        blocked = isinstance(base, BlockedTableCarry)
        if blocked:
            group = 1  # its column writes hand back the block it reduces
            k_types, nblk = base.bt.shape
            bsz = base.score_tbl.shape[2] // nblk
            rank_p = _pad_rank(tiebreak_rank, nblk * bsz)
            offs = jnp.arange(nblk, dtype=jnp.int32) * bsz
            body = make_blocked_body(
                pods, type_id, types, tp, rank_p, n, num_pods, bsz,
                k_types, nblk, offs, wts, fault_ops,
            )
        else:
            body = make_flat_body(
                pods, type_id, types, tp, tiebreak_rank, n, num_pods, wts,
                fault_ops, grouped=group > 1,
            )
            if nodes_minor_affinity:
                carry = (carry, base.state.aff_cnt.T)
        out, ys = _scan_events(body, carry, xs, group)
        if defer_affinity and not blocked:
            state = out.state
            out = out._replace(state=state._replace(
                aff_cnt=state.aff_cnt + chunk_affinity(
                    base.pend, pods, ev_kind, ev_pod, ys[0], n,
                    state.aff_cnt.shape[1])))
        elif nodes_minor_affinity and not blocked:
            out, aff_t = out
            out = out._replace(state=out.state._replace(aff_cnt=aff_t.T))
        return out, ys

    def _scan_events(body, carry, xs, group: int):
        """The segment's events through `body`: one scan, or the flat
        body's groups."""
        if group <= 1:
            # unroll amortizes per-iteration fixed costs (~20% wall on the
            # openb replay); higher factors showed no further gain
            return jax.lax.scan(body, carry, xs, unroll=4)
        # the grouped flat replay: E = q * G + r events are q groups of G
        # and one of r, each ending in its flush. No skip events are padded
        # in: every event splits the key, and the chain must stay the
        # oracle's
        q, r = divmod(xs[0].shape[0], group)
        parts = []
        if q:
            carry, ys = jax.lax.scan(
                lambda c, x: _run_flat_group(body, c, x, group), carry,
                jax.tree.map(
                    lambda a: a[:q * group].reshape(
                        (q, group) + a.shape[1:]),
                    xs),
            )
            parts.append(jax.tree.map(
                lambda a: a.reshape((q * group,) + a.shape[2:]), ys))
        if r or not q:
            carry, ys = _run_flat_group(
                body, carry, jax.tree.map(lambda a: a[q * group:], xs), r)
            parts.append(ys)
        if len(parts) == 1:
            return carry, parts[0]
        return carry, jax.tree.map(lambda *a: jnp.concatenate(a), *parts)

    run_chunk = jax.jit(_run_chunk_impl, static_argnames="group")
    # the donating twin (ISSUE 11): identical jaxpr, but the input carry's
    # buffers are donated to the outputs, so a long chunked replay stops
    # reallocating its O(N*K) score tables every segment. The caller must
    # treat the input carry as CONSUMED (the driver's _run_chunked takes
    # its host checkpoint copy before the next chunk dispatch); callers
    # that reuse a carry (tests probing arbitrary cut points) stay on the
    # non-donating entry.
    run_chunk_donate = jax.jit(
        _run_chunk_impl, donate_argnums=0, static_argnames="group")

    @jax.jit
    def finish(carry):
        """Post-scan epilogue: apply the last event's still-pending commit
        and strip the dummy bookkeeping row. Returns (state, placed,
        masks, failed). A finished carry must not be resumed — the pending
        commit has landed."""
        if faults:
            carry = carry[0]
        state, placed, masks, failed = apply_commit(
            carry.state, carry.placed, carry.masks, carry.failed, carry.pend
        )
        return state, placed[:-1], masks[:-1], failed[:-1]

    @functools.partial(jax.jit, static_argnames="group")
    def _replay_impl(
        state: NodeState,
        pods: PodSpec,  # [P]
        types: PodTypes,  # host-side build_pod_types(pods)
        ev_kind: jnp.ndarray,  # i32[E]
        ev_pod: jnp.ndarray,  # i32[E]
        tp,
        key,
        wts,  # i32[num_pol] traced weight operand
        tiebreak_rank=None,
        tables=None,
        fault_ops=None,
        fault_carry0=None,
        group: int = 1,  # static: flat_group_events
    ) -> ReplayResult:
        carry = init_carry(
            state, pods, types, tp, key, wts, tiebreak_rank, tables,
            fault_carry0,
        )
        carry, ys = run_chunk(
            carry, pods, types, ev_kind, ev_pod, tp, wts, tiebreak_rank,
            fault_ops, group=group,
        )
        state, placed, masks, failed = finish(carry)
        nodes, devs = ys[0], ys[1]
        rest = list(ys[2:])
        decs = rest.pop(0) if decisions else None
        sers = rest.pop(0) if series_every else None
        if faults:
            base, fc = carry
            return ReplayResult(
                state, placed, masks, failed, None, nodes, devs, base.ctr,
                None, None, rest.pop(0), _trim_fc(fc),
            )
        return ReplayResult(
            state, placed, masks, failed, None, nodes, devs, carry.ctr,
            decs, sers,
        )

    def flat(num_nodes: int, types: PodTypes) -> bool:
        return not block_size_of(num_nodes, _num_types(types))

    return _TableEngine(
        replay=_replay_impl,
        init_carry=init_carry,
        run_chunk=run_chunk,
        run_chunk_donate=run_chunk_donate,
        finish=finish,
        build_tables=jax.jit(
            lambda state, types, tp, key: _init_tables(state, types, tp, key)
        ),
        closes_over=(tuple(fn for fn, _ in policies), sel_idx),
        affinity_deferred=lambda num_nodes, types: (
            defer_affinity and flat(num_nodes, types)),
        affinity_nodes_minor=lambda num_nodes, types: (
            nodes_minor_affinity and flat(num_nodes, types)),
    )
