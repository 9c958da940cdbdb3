"""Explicit-collective sharded replay (shard_map) — flat per-event cost.

The first sharded engine (tpusim.parallel.sharding) re-jits the table engine
with node-axis in_shardings and lets XLA's SPMD partitioner insert the
collectives. That proves equality, but the partitioner turns the per-event
dynamic gathers/scatters at the winning node's index (state.gpu_left[node],
.at[node].add, the dirty-column refresh) into whole-array movement, so
us/event GROWS with mesh size (MULTICHIP round-2 table: 2751 -> 9731 us/event
from 1 -> 8 virtual devices).

This engine writes the communication by hand with jax.shard_map, the way the
scaling-book recipe says to when the partitioner's choices matter:

  - Filter/Score/table refresh are LOCAL: each shard owns N/D node rows and
    the matching [K, N/D] score-table shard; the dirty-node column refresh
    runs on every shard but only the owner's masked write lands.
  - selectHost is a local argmax + THREE scalar collectives: pmax of the
    best local score, pmin of the winning tie-break rank among score-tied
    shards, psum of the winner's global node id (ranks are a permutation,
    so exactly one shard contributes). Lexicographically identical to the
    global (max score, min rank) selection in sim.step.select_and_bind.
  - Reserve/Bind are OWNER-LOCAL: the owning shard computes the device mask
    from its local row (sim.step.choose_devices — the same helper the
    global engine binds with) and applies the row update; one [8]-wide psum
    publishes the device mask for the replicated bookkeeping arrays.
  - Per-event metrics never touch the loop at all: like every engine since
    round 5, the replay is metric-free and the report series is
    reconstructed from the replicated (event_node, event_dev) telemetry by
    the shared post-pass (tpusim.sim.metrics) — byte-identical to the
    single-device engines by construction, vs the reference recomputing
    cluster metrics after every event (simulator.go:426-427).

Per-event collective payload: 3 scalars + one 8-lane mask, independent of
N and D — the us/event curve stays flat as the mesh grows (MULTICHIP.md).
Placements are bit-identical to the single-device table engine.

Since ISSUE 11 the step body is SOFTWARE-PIPELINED one event deep, the
way Round 6 restructured the single-device table engine: each iteration
first applies the PREVIOUS event's deferred commit (the replicated
`sim.step.PendingCommit` register riding ShardTableCarry — owner-masked
state scatters via `apply_commit_sharded`, replicated [P+1] bookkeeping
writes) and only then reads state/tables, so every carried buffer is
written before it is read and XLA aliases the scatters in place instead
of taking a whole-buffer defensive copy per event. Under the fault lane
the fault step kinds flow through the same discipline: the DECISION
(victim draw, queue bookkeeping — fc is read-modify-write in-line, it is
small) happens at the event, while the state/bookkeeping WRITES ride a
second register (`fault_lane.FaultPending`) applied right after the bind
commit at the top of the next iteration. The collective payload is
untouched and placements/telemetry/counters are bit-identical to the
unpipelined body by construction (the same scatters land before anything
reads them); `pipelined=False` keeps the old in-body commit for A/B
measurement (bench_multichip --scale-lane). At nloc = N/D >= ~10k the
eliminated copies dominate the loop — the 1M-node lane headline
(MULTICHIP.md "The 1M-node lane").
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tpusim.constants import MAX_GPUS_PER_NODE, MAX_NODE_SCORE
from tpusim.obs import series as obs_series
from tpusim.obs.counters import counter_delta, zero_counters
from tpusim.obs.decisions import DECISION_TOPK, DecisionRecord, no_decision
from tpusim.policies.base import (
    NORMALIZE_DEGENERATE,
    feasible_min_max,
    minmax_scale_i32,
)
from tpusim.sim.engine import ReplayResult
from tpusim.sim.step import (
    PendingCommit,
    apply_commit,
    apply_commit_sharded,
    block_reduce,
    choose_devices,
    make_pending_commit,
    no_pending_commit,
    packed_argmax,
    packed_topk,
)
from tpusim.sim.table_engine import (
    PodTypes,
    _pad_rank,
    _row_state,
    make_table_builders,
    reject_randomized,
    resolve_block_size,
    selector_index,
)
from tpusim.types import NodeState, PodSpec

from tpusim.parallel.sharding import NODE_AXIS

_INT_MAX = np.int32(np.iinfo(np.int32).max)


class ShardTableCarry(NamedTuple):
    """Complete sharded-engine state between two events — the shard_map
    scan carry, promoted to a pytree the driver can gather to host
    (np.asarray on each leaf collects the shards), checkpoint, and feed
    back in; jit re-shards it against the same mesh on resume, so the
    continued scan is bit-identical to the uninterrupted one. state and
    the packed table / block summaries are node-axis sharded; everything
    else is replicated (identical on every shard by construction)."""

    state: NodeState  # node-axis sharded, [nloc] rows per shard
    packed_tbl: jnp.ndarray  # i32[K, nloc(_p), npol+2] scores|sdev|feas
    lt: jnp.ndarray  # i32[K, nloc/B] block max totals ([0,0] when flat)
    lr: jnp.ndarray  # i32[K, nloc/B] block min winner ranks
    lwn: jnp.ndarray  # i32[K, nloc/B] block winner LOCAL node indices
    # the software-pipeline register (ISSUE 11): the previous event's
    # deferred commit, replicated (node is the GLOBAL winner id); inert
    # no_pending_commit forever on pipelined=False builds
    pend: PendingCommit
    dirty: jnp.ndarray  # i32 global node id to refresh next (replicated)
    placed: jnp.ndarray  # i32[P+1] (replicated; dummy row absorbs the
    #                      pipelined commit's skip writes, like the table
    #                      engines — finish() strips it)
    masks: jnp.ndarray  # bool[P+1, 8]
    failed: jnp.ndarray  # bool[P+1]
    arr_cpu: jnp.ndarray  # i32
    arr_gpu: jnp.ndarray  # i32
    key: jnp.ndarray  # PRNG key after the events consumed so far
    # i32[obs.NUM_COUNTERS] exact in-scan counters (tpusim.obs.counters)
    # — replicated: every shard adds the same delta from the replicated
    # (kind, node) decision. `rebuilds` stays 0 here (block summaries
    # refresh unconditionally; there is no drift-cond to count).
    ctr: jnp.ndarray


def make_shardmap_table_replay(policies, mesh, gpu_sel: str = "best",
                               report: bool = False, block_size: int = 0,
                               decisions: bool = False,
                               series_every: int = 0,
                               faults: bool = False,
                               pipelined: bool = True):
    """Build the explicit-collective sharded replayer. The node count must
    already be padded to a multiple of the mesh size (parallel.pad_nodes)
    and `state`/`tiebreak_rank` sharded over it (parallel.shard_state).
    Metric-free like every engine; build the report series with
    tpusim.sim.metrics.compute_event_metrics over the replicated
    telemetry.

    block_size (resolve_block_size over the PER-DEVICE node count) turns
    on blocked local selectHost inputs for configs whose policies all use
    normalize == "none": each shard keeps per-(type, block-of-B) summaries
    (max total, min tie-break rank, winner node) refreshed only at the
    touched node's block, so the per-device selectHost reduction consumes
    nloc/B block maxima instead of nloc node rows. The cross-device
    collective payload itself was already N-independent (3 scalars + one
    8-lane mask) and is unchanged — the block maxima shrink what each
    device reduces before contributing its scalar. Normalized policies
    (minmax/pwr need global extrema collectives per event) keep the flat
    local path regardless of block_size.

    decisions=True (ISSUE 4) additionally emits the per-event
    DecisionRecord stream. The top-K summaries CROSS the collective: each
    shard reduces its local score rows to its top-DECISION_TOPK
    (total, rank, global node id) candidates, an all_gather collects the
    D×K summaries, and the replicated merge reruns the SAME packed-key
    top-K over them — exact because the global k-th best always lies
    within its own shard's local top-K, and the (max total, min rank)
    combine is the one every engine selects with. The winner's
    per-policy raw/normalized columns and the feasible count cross as
    owner-masked psums. Per-event collective payload grows by
    3×DECISION_TOPK i32 lanes + (2×num_policies + 1) scalars — still
    independent of N and D.

    series_every > 0 (ISSUE 5) additionally emits the in-scan
    SeriesSample stream (tpusim.obs.series). Every sample field is an
    integer reduction, so the shard decomposition is exact: util
    histogram / DOWN count / per-category frag cross as psums of
    per-shard integer partials (cluster_stats rounds each NODE's frag
    row to whole milli BEFORE summing, so the total cannot depend on the
    node partition); normalized score extrema cross as the same
    pmin/pmax pair the flat select path normalizes with, then the
    per-policy hi/lo cross as one pmax/pmin each. Mesh pad rows are
    masked by their rank == INT_MAX sentinel (they carry the DOWN
    nodes' mem_left == -1 and must count as neither). Samples land only
    at stride points (a replicated cond), so the extra collective
    payload amortizes to O(1/series_every) per event. ys become
    (node, dev[, dec][, ser]) in that order, like the table engine.

    pipelined=True (ISSUE 11, the default) software-pipelines the step
    body one event deep (module docstring): the Bind scatter and — under
    faults — the fault-step row writes ride pending registers applied at
    the top of the next iteration, so the body is strictly
    write-then-read and the per-event whole-buffer state copies vanish.
    Bit-identical to pipelined=False (the pre-ISSUE-11 in-body commit,
    kept for A/B measurement) for every policy/mix/gpu_sel and under the
    fault lane; both paths share one carry layout ([P+1] bookkeeping +
    the — possibly inert — pend register), so the driver's chunked
    checkpoint dispatch is knob-agnostic."""
    if report:
        raise ValueError(
            "the shard_map engine replays metric-free; build the report "
            "series with tpusim.sim.metrics.compute_event_metrics"
        )
    if faults and (decisions or series_every):
        raise ValueError(
            "the in-scan fault plane (faults=True) does not combine with "
            "decisions/series builds on the shard engine"
        )
    if faults:
        # fault transitions touch exactly one node row, so the DOWN
        # masking IS the mem_left == -1 pad sentinel the local Filter
        # already rejects; the requeue scatter and disruption counters
        # are replicated bookkeeping (identical on every shard), and the
        # state row resets/returns are owner-masked via the global-id
        # row mask. The recover frag-delta capture stays OFF here — a
        # psum of f32 partials cannot be bit-equal to the single-device
        # cluster sum (ENGINES.md Round 14).
        from tpusim.sim import fault_lane as _fl
    reject_randomized(policies, gpu_sel)
    sel_idx = selector_index(policies, gpu_sel)
    _columns, _init_tables = make_table_builders(policies, sel_idx)
    npol = len(policies)
    n_dev = mesh.shape[NODE_AXIS]
    all_none_norm = all(fn.normalize == "none" for fn, _ in policies)

    def _local_totals(rows, wts):
        """Weighted totals with -INT_MAX at infeasible entries from a
        packed-layout slice [..., C] (none-normalize configs only).
        `wts` is the traced i32[num_pol] weight operand (ISSUE 6)."""
        tot = jnp.zeros(rows.shape[:-1], jnp.int32)
        for i in range(npol):
            tot = tot + wts[i] * rows[..., i]
        return jnp.where(rows[..., npol + 1] != 0, tot, -_INT_MAX)

    def _resolve_bsz(nloc: int, k_types: int) -> int:
        return (
            resolve_block_size(block_size, nloc, k_types)
            if all_none_norm else 0
        )

    def _init_shard(state, rank, pods, types, tp, key, wts,
                    fault_carry0=None):
        """Per-shard carry at event 0: local table shards + blocked local
        summaries + replicated bookkeeping (state/rank are the LOCAL node
        rows; wts is the replicated weight operand)."""
        nloc = state.num_nodes
        num_pods = pods.cpu.shape[0]

        key, k_init = jax.random.split(key)
        s0, d0, f0 = _init_tables(state, types, tp, k_init)
        packed_tbl = jnp.concatenate(
            [jnp.moveaxis(s0, 0, -1), d0[..., None],
             f0.astype(jnp.int32)[..., None]],
            axis=-1,
        )  # [K, nloc, C]

        k_types = int(types.share.cpu.shape[0]) + int(types.whole.cpu.shape[0])
        bsz = _resolve_bsz(nloc, k_types)

        if bsz:
            nbl = -(-nloc // bsz)
            nloc_p = nbl * bsz
            if nloc_p != nloc:
                # sentinel columns: feas 0 -> -INT_MAX totals, never chosen
                packed_tbl = jnp.pad(
                    packed_tbl, ((0, 0), (0, nloc_p - nloc), (0, 0))
                )
            rank_p = _pad_rank(rank, nloc_p)
            loffs = jnp.arange(nbl, dtype=jnp.int32) * bsz
            lt, lr, la = block_reduce(
                _local_totals(packed_tbl, wts).reshape(k_types, nbl, bsz),
                rank_p.reshape(nbl, bsz),
            )
            lwn = loffs[None, :] + la  # [K, nbl] local winner node indices
        else:
            lt = lr = lwn = jnp.zeros((0, 0), jnp.int32)

        # one extra dummy row absorbs skip-event writes of the pipelined
        # commit (PendingCommit.pod_write); sliced off by finish(). The
        # unpipelined path shares the layout (its in-body writes never
        # touch the dummy row), so both knobs run one carry shape.
        placed = jnp.full(num_pods + 1, -1, jnp.int32)
        masks = jnp.zeros((num_pods + 1, MAX_GPUS_PER_NODE), jnp.bool_)
        failed = jnp.zeros(num_pods + 1, jnp.bool_)
        z = jnp.int32(0)
        base = ShardTableCarry(
            state, packed_tbl, lt, lr, lwn, no_pending_commit(num_pods),
            z, placed, masks, failed, z, z, key, zero_counters(),
        )
        if not faults:
            return base
        fcp = _fl.pad_fault_carry(fault_carry0)
        if pipelined:
            return (base, fcp, _fl.no_fault_pending(num_pods + 1))
        return (base, fcp)

    def _chunk_shard(carry, rank, pods, types, ev_kind, ev_pod, tp, wts,
                     fault_ops=None):
        """Advance a per-shard carry over one event segment (the scan the
        one-shot replay runs over the whole stream). `wts` must be the
        weight vector the carry was initialized under (the blocked local
        summaries embed it)."""
        base0 = carry[0] if faults else carry
        nloc = base0.state.num_nodes
        me = jax.lax.axis_index(NODE_AXIS)
        offset = (me * nloc).astype(jnp.int32)
        gids = offset + jnp.arange(nloc, dtype=jnp.int32)
        num_pods = pods.cpu.shape[0]
        type_id = types.type_id
        k_types = int(types.share.cpu.shape[0]) + int(types.whole.cpu.shape[0])
        bsz = _resolve_bsz(nloc, k_types)
        rank_p = (
            _pad_rank(rank, base0.packed_tbl.shape[1]) if bsz else rank
        )

        def body(carry, ev):
            fpend = None
            if faults:
                if pipelined:
                    carry, fc, fpend = carry
                else:
                    carry, fc = carry
                kind, idx, fpos, farg, faux = ev
            (state, packed_tbl, lt, lr, lwn, pend, dirty, placed, masks,
             failed, arr_cpu, arr_gpu, key, ctr) = carry
            if pipelined:
                # apply the PREVIOUS event's deferred scatters first —
                # every carried buffer is written before anything reads
                # it this iteration, so all updates alias in place
                # (sim.step.PendingCommit; the state half is owner-masked
                # on this shard's local row window)
                state, placed, masks, failed = apply_commit_sharded(
                    state, placed, masks, failed, pend, offset, nloc
                )
                if faults:
                    # ... then the previous event's fault writes (row
                    # reset / evict return / victim clearing) — the same
                    # in-line order the unpipelined body commits in
                    state, placed, masks, failed = _fl.apply_fault_pending(
                        state, placed, masks, failed, fpend, offset, nloc
                    )
            if not faults:
                kind, idx = ev
                kc = jnp.clip(kind, 0, 2)
            else:
                from tpusim.sim.engine import EV_RETRY

                is_slot = kind == EV_RETRY
                fc, has_pop, rpod = _fl.pop_retry(fc, is_slot, fpos, farg)
                idx = jnp.where(has_pop, rpod, idx)
                kc = jnp.where(
                    is_slot, jnp.where(has_pop, 0, 2),
                    jnp.clip(kind, 0, 2),
                )
            pod = jax.tree.map(lambda a: a[idx], pods)
            t_id = type_id[idx]
            key, k_col, k_sel = jax.random.split(key, 3)

            # dirty-column refresh: ONLY the owning shard computes (a real
            # lax.cond branch — non-owners skip the K-type scoring sweep
            # entirely, which also keeps the single-host virtual mesh from
            # paying D redundant refreshes per event)
            li = dirty - offset
            owns_d = (li >= 0) & (li < nloc)
            lic = jnp.clip(li, 0, nloc - 1)

            if pipelined:
                # no whole-buffer operand may cross the cond boundary
                # (ISSUE 11): XLA copies big buffers captured by branch
                # computations, so the cond closes over only the
                # PRE-GATHERED one-node row, and the column write is an
                # owner-masked OOB-drop scatter — non-owners write
                # nothing instead of reading back the old column. No
                # packed_tbl read, no DUS: the update touches exactly
                # one column's elements.
                row1 = _row_state(state, lic)

                def refresh_col_p():
                    cs, cd, cf = _columns(row1, types, tp, k_col)
                    return jnp.concatenate(
                        [cs.T, cd[:, None],
                         cf.astype(jnp.int32)[:, None]],
                        axis=-1,
                    )  # [K, C]

                col = jax.lax.cond(
                    owns_d,
                    refresh_col_p,
                    lambda: jnp.zeros(
                        (k_types, npol + 2), jnp.int32
                    ),
                )
                tgt_col = jnp.where(
                    owns_d, lic, packed_tbl.shape[1]
                )
                packed_tbl = packed_tbl.at[:, tgt_col, :].set(
                    col, mode="drop"
                )
            else:
                # the cond computes only the [K, 1, C] column (non-owners
                # reuse the old slice); the table write itself stays
                # OUTSIDE the cond so XLA can alias the
                # dynamic_update_slice in place — a cond returning the
                # whole table forces a full-buffer copy per event
                def refresh_col():
                    cs, cd, cf = _columns(
                        _row_state(state, lic), types, tp, k_col
                    )
                    return jnp.concatenate(
                        [cs.T, cd[:, None],
                         cf.astype(jnp.int32)[:, None]],
                        axis=-1,
                    )[:, None, :]

                new_col = jax.lax.cond(
                    owns_d,
                    refresh_col,
                    lambda: jax.lax.dynamic_slice_in_dim(
                        packed_tbl, lic, 1, axis=1
                    ),
                )
                packed_tbl = jax.lax.dynamic_update_slice_in_dim(
                    packed_tbl, new_col, lic, axis=1
                )

            if bsz:
                # dirty-block summary refresh for all K types: non-owner
                # shards recompute an unchanged block (idempotent), owners
                # fold the refreshed column in — O(K*B) either way
                blk = lic // bsz
                j0 = blk * bsz
                rows_blk = jax.lax.dynamic_slice(
                    packed_tbl, (0, j0, 0),
                    (k_types, bsz, npol + 2),
                )
                rank_blk = jax.lax.dynamic_slice(rank_p, (j0,), (bsz,))
                bm, brk, bar = block_reduce(
                    _local_totals(rows_blk, wts), rank_blk
                )
                lt = jax.lax.dynamic_update_slice(lt, bm[:, None], (0, blk))
                lr = jax.lax.dynamic_update_slice(lr, brk[:, None], (0, blk))
                lwn = jax.lax.dynamic_update_slice(
                    lwn, (j0 + bar)[:, None], (0, blk)
                )

            if series_every:
                # in-scan series sample (ISSUE 5): replicated stride
                # clock; every field crosses the mesh as an exact integer
                # collective (module docstring). All shards take the same
                # cond branch (processed is replicated), so the
                # collectives inside it always pair up.
                processed = ctr[0] + ctr[3] + ctr[4]

                def _build_sample():
                    real = rank < _INT_MAX  # mesh pad rows: rank sentinel
                    hist_l, down_l, frag_l = obs_series.cluster_stats(
                        state, tp, node_mask=real
                    )
                    hist = jax.lax.psum(hist_l, NODE_AXIS)
                    down = jax.lax.psum(down_l, NODE_AXIS)
                    frag = jax.lax.psum(frag_l, NODE_AXIS)
                    rows_t = jax.lax.dynamic_index_in_dim(
                        packed_tbl, t_id, 0, False
                    )  # [nloc(_p), C]; block pad columns are infeasible
                    feas_l = rows_t[:, npol + 1] != 0
                    feas_cnt = jax.lax.psum(
                        feas_l.sum().astype(jnp.int32), NODE_AXIS
                    )
                    any_f = feas_cnt > 0
                    his, los = [], []
                    for i, (fn, _) in enumerate(policies):
                        raw = rows_t[:, i]
                        if fn.normalize in ("minmax", "pwr"):
                            # local extrema + pmin/pmax = the global
                            # reduction, scaled by the same core the
                            # unsharded engines normalize with
                            lo_l, hi_l = feasible_min_max(raw, feas_l)
                            nrm = minmax_scale_i32(
                                raw, feas_l,
                                jax.lax.pmin(lo_l, NODE_AXIS),
                                jax.lax.pmax(hi_l, NODE_AXIS),
                                NORMALIZE_DEGENERATE[fn.normalize],
                            )
                        else:  # RandomScore cannot reach the shard engine
                            nrm = raw
                        hi_i = jax.lax.pmax(
                            jnp.max(jnp.where(feas_l, nrm, -_INT_MAX)),
                            NODE_AXIS,
                        )
                        lo_i = jax.lax.pmin(
                            jnp.min(jnp.where(feas_l, nrm, _INT_MAX)),
                            NODE_AXIS,
                        )
                        his.append(jnp.where(any_f, hi_i, 0))
                        los.append(jnp.where(any_f, lo_i, 0))
                    return obs_series.SeriesSample(
                        pos=processed.astype(jnp.int32),
                        util_hist=hist,
                        nodes_down=down,
                        feasible=feas_cnt,
                        frag=frag,
                        score_hi=jnp.stack(his).astype(jnp.int32),
                        score_lo=jnp.stack(los).astype(jnp.int32),
                    )

                ser = obs_series.emit_from_scan(
                    series_every, processed, _build_sample, npol
                )
            else:
                ser = ()

            def do_create():
                if bsz:
                    # blocked local selectHost: reduce nloc/B block
                    # summaries instead of nloc rows; the 3-scalar
                    # collective combine below is unchanged
                    lt_row = jax.lax.dynamic_index_in_dim(lt, t_id, 0, False)
                    lr_row = jax.lax.dynamic_index_in_dim(lr, t_id, 0, False)
                    lw_row = jax.lax.dynamic_index_in_dim(lwn, t_id, 0, False)
                    blk_i, best_l, okb = packed_argmax(
                        lt_row, lt_row != -_INT_MAX, lr_row
                    )
                    am_l = lw_row[blk_i]
                    rank_l = jnp.where(okb, lr_row[blk_i], _INT_MAX)
                    # pinned pods: exactly one candidate, owned by exactly
                    # one shard — the winner is the pinned node iff Filter
                    # passes there (the flat path encodes the same through
                    # its feasibility mask)
                    pin_l = pod.pinned - offset
                    owns_pin = (pin_l >= 0) & (pin_l < nloc)
                    pin_c = jnp.clip(pin_l, 0, nloc - 1)
                    pin_row = jax.lax.dynamic_slice(
                        packed_tbl, (t_id, pin_c, 0), (1, 1, npol + 2)
                    )[0, 0]
                    pin_ok = owns_pin & (pin_row[npol + 1] != 0)
                    pin_tot = jnp.zeros((), jnp.int32)
                    for i in range(npol):
                        pin_tot = pin_tot + wts[i] * pin_row[i]
                    pinned = pod.pinned >= 0
                    best_l = jnp.where(
                        pinned, jnp.where(pin_ok, pin_tot, -_INT_MAX), best_l
                    )
                    rank_l = jnp.where(
                        pinned, jnp.where(pin_ok, rank[pin_c], _INT_MAX),
                        rank_l,
                    )
                    am_l = jnp.where(pinned, pin_c, am_l)
                    if decisions:
                        # full local rows for the provenance capture
                        # (none-normalize configs only: norm == raw)
                        rows_t = jax.lax.dynamic_index_in_dim(
                            packed_tbl, t_id, 0, False
                        )  # [nloc_p, C]
                        nloc_p = rows_t.shape[0]
                        gids_p = offset + jnp.arange(nloc_p, dtype=jnp.int32)
                        d_raws = rows_t[:, :npol].T
                        d_norms = d_raws
                        d_feas = (rows_t[:, npol + 1] != 0) & (
                            (pod.pinned < 0) | (gids_p == pod.pinned)
                        )
                        d_tot = _local_totals(rows_t, wts)
                        d_rank = rank_p
                else:
                    row = packed_tbl[t_id]  # [nloc, C]
                    feasible = (row[:, npol + 1] != 0) & (
                        (pod.pinned < 0) | (gids == pod.pinned)
                    )
                    total = jnp.zeros(nloc, jnp.int32)
                    d_raw_rows, d_norm_rows = [], []
                    for i, (fn, _) in enumerate(policies):
                        raw = row[:, i]
                        nrm = raw
                        if fn.normalize in ("minmax", "pwr"):
                            # local extrema + pmin/pmax = the global
                            # reduction; the scaling core is the same code
                            # the unsharded engines normalize with
                            lo_l, hi_l = feasible_min_max(raw, feasible)
                            lo = jax.lax.pmin(lo_l, NODE_AXIS)
                            hi = jax.lax.pmax(hi_l, NODE_AXIS)
                            nrm = minmax_scale_i32(
                                raw, feasible, lo, hi,
                                0 if fn.normalize == "minmax"
                                else MAX_NODE_SCORE,
                            )
                        if decisions:
                            d_raw_rows.append(raw)
                            d_norm_rows.append(nrm)
                        total = total + wts[i] * nrm

                    # selectHost: local argmax + 3 scalar collectives
                    best_l = jnp.max(jnp.where(feasible, total, -_INT_MAX))
                    wkey = jnp.where(
                        feasible & (total == best_l), -rank, -_INT_MAX
                    )
                    am_l = jnp.argmax(wkey).astype(jnp.int32)
                    rank_l = -wkey[am_l]  # INT_MAX when no candidate
                    if decisions:
                        d_raws = jnp.stack(d_raw_rows)
                        d_norms = jnp.stack(d_norm_rows)
                        d_feas = feasible
                        d_tot = total
                        d_rank = rank
                g_best = jax.lax.pmax(best_l, NODE_AXIS)
                g_rank = jax.lax.pmin(
                    jnp.where(best_l == g_best, rank_l, _INT_MAX), NODE_AXIS
                )
                ok = g_best != -_INT_MAX
                win = ok & (best_l == g_best) & (rank_l == g_rank)
                gnode = jax.lax.psum(
                    jnp.where(win, offset + am_l, 0), NODE_AXIS
                ).astype(jnp.int32)

                # Reserve: owner-local device choice; one [8] psum
                # publishes the device mask for the replicated bookkeeping
                # (the Bind scatter runs outside the switch — see below)
                ln = jnp.clip(gnode - offset, 0, nloc - 1)
                owner = (gnode >= offset) & (gnode < offset + nloc)
                if bsz:
                    pdev = jax.lax.dynamic_slice(
                        packed_tbl, (t_id, ln, npol), (1, 1, 1)
                    )[0, 0, 0]
                else:
                    pdev = row[ln, npol]
                dmask_l = choose_devices(
                    state.gpu_left[ln], pod, pdev, gpu_sel, k_sel
                ) & ok
                dev_mask = (
                    jax.lax.psum(
                        jnp.where(owner, dmask_l, False).astype(jnp.int32),
                        NODE_AXIS,
                    )
                    > 0
                )
                node_f = jnp.where(ok, gnode, -1).astype(jnp.int32)
                if not decisions:
                    return node_f, dev_mask
                # ---- decision provenance (replicated) ----
                # local top-K candidates -> (total, rank, global id)
                # summaries across the collective -> replicated merge with
                # the same packed-key top-K every engine orders by. Exact:
                # the global k-th best is inside its shard's local top-K.
                lpos, ltot, lrnk, lok = packed_topk(
                    d_tot, d_feas, d_rank, DECISION_TOPK
                )
                lgid = jnp.where(lok, offset + lpos, -1).astype(jnp.int32)
                ag = jax.lax.all_gather(
                    jnp.stack([ltot, lrnk, lgid]), NODE_AXIS
                )  # [D, 3, K]
                gtot = ag[:, 0, :].reshape(-1)
                grnk = ag[:, 1, :].reshape(-1)
                ggid = ag[:, 2, :].reshape(-1)
                mpos, mtot, mrnk, mok = packed_topk(
                    gtot, ggid >= 0, grnk, DECISION_TOPK
                )
                mnode = jnp.where(
                    mok, ggid[jnp.maximum(mpos, 0)], -1
                ).astype(jnp.int32)
                # winner columns + feasible count: owner-masked psums
                win_raw = jax.lax.psum(
                    jnp.where(owner & ok, d_raws[:, ln], 0), NODE_AXIS
                ).astype(jnp.int32)
                win_norm = jax.lax.psum(
                    jnp.where(owner & ok, d_norms[:, ln], 0), NODE_AXIS
                ).astype(jnp.int32)
                feas_cnt = jax.lax.psum(
                    d_feas.sum().astype(jnp.int32), NODE_AXIS
                )
                if bsz:
                    nbl = lt.shape[1]
                    blk_g = jax.lax.psum(
                        jnp.where(owner & ok, me * nbl + ln // bsz, 0),
                        NODE_AXIS,
                    ).astype(jnp.int32)
                    win_blk = jnp.where(ok, blk_g, -1).astype(jnp.int32)
                else:
                    win_blk = jnp.int32(-1)
                dec = DecisionRecord(
                    node=node_f,
                    total=jnp.where(ok, g_best, 0).astype(jnp.int32),
                    raw=win_raw,
                    norm=win_norm,
                    topk_node=mnode,
                    topk_total=mtot,
                    topk_rank=mrnk,
                    feasible=feas_cnt,
                    block=win_blk,
                )
                return node_f, dev_mask, dec

            def do_delete():
                base = placed[idx], masks[idx]
                return base + ((no_decision(npol),) if decisions else ())

            def do_skip():
                base = (
                    jnp.int32(-1), jnp.zeros(MAX_GPUS_PER_NODE, jnp.bool_)
                )
                return base + ((no_decision(npol),) if decisions else ())

            # either way the event decision is only the replicated
            # (node, dev_mask[, dec]) — a carried buffer returned from a
            # branch cannot alias the carry (the round-6 restructure);
            # the pipelined path goes further and drops the switch itself
            if pipelined:
                # no lax.switch around the create path (ISSUE 11): branch
                # computations capture the score-table/state buffers, and
                # XLA materializes whole-buffer copies for captured
                # conditional operands — the dominant per-event cost at
                # nloc >= ~100k. The create computation is pure (the
                # commit is deferred through the register), so it runs
                # UNCONDITIONALLY and the small (node, dev[, dec])
                # results merge by event kind. Collectives now run on
                # every event (delete/skip included) with the same
                # per-event payload; all shards agree on kc, so they
                # always pair up.
                outs_c = do_create()
                outs_d = do_delete()
                outs_s = do_skip()
                outs = tuple(
                    jax.tree.map(
                        lambda a, b, c: jnp.where(
                            kc == 0, a, jnp.where(kc == 1, b, c)
                        ),
                        oc, od, os_,
                    )
                    for oc, od, os_ in zip(outs_c, outs_d, outs_s)
                )
            else:
                outs = jax.lax.switch(kc, [do_create, do_delete, do_skip])
            if decisions:
                node, dev, dec = outs
            else:
                node, dev = outs
            is_create = kc == 0
            is_delete = kc == 1
            if pipelined:
                # defer this event's scatters to the next iteration: the
                # register is replicated (node is the GLOBAL winner id);
                # apply_commit_sharded owner-masks the state half
                pend = make_pending_commit(kc, idx, node, dev, pod,
                                           num_pods)
                if faults:
                    # retry creates accumulate ever-failed with OR (the
                    # segmented path's per-segment `|=`); base creates
                    # still overwrite (they run once per pod)
                    pend = pend._replace(failed_val=jnp.where(
                        is_slot, failed[idx] | (node < 0), node < 0
                    ))
            else:
                lbind = jnp.clip(node - offset, 0, nloc - 1)
                apply = (node >= 0) & (node >= offset) & (
                    node < offset + nloc
                )
                rs = jnp.where(is_delete, 1, -1)  # delete returns
                from tpusim.policies.clustering import pod_affinity_class

                cls = pod_affinity_class(pod)
                state = state._replace(
                    cpu_left=state.cpu_left.at[lbind].add(
                        jnp.where(apply, rs * pod.cpu, 0)
                    ),
                    mem_left=state.mem_left.at[lbind].add(
                        jnp.where(apply, rs * pod.mem, 0)
                    ),
                    gpu_left=state.gpu_left.at[lbind].add(
                        jnp.where(apply, rs, 0)
                        * dev.astype(jnp.int32) * pod.gpu_milli
                    ),
                    aff_cnt=state.aff_cnt.at[lbind, jnp.maximum(cls, 0)].add(
                        jnp.where(apply & (cls >= 0), -rs, 0)
                    ),
                )
                placed = placed.at[idx].set(
                    jnp.where(is_create, node,
                              jnp.where(is_delete, -1, placed[idx]))
                )
                masks = masks.at[idx].set(
                    jnp.where(is_create, dev,
                              jnp.where(is_delete, False, masks[idx]))
                )
                failed = failed.at[idx].set(
                    jnp.where(
                        is_create,
                        # retry attempts accumulate ever-failed with OR
                        # (the segmented path's per-segment `|=`)
                        (failed[idx] & is_slot & is_create) | (node < 0)
                        if faults else node < 0,
                        failed[idx],
                    )
                )
            arr_cpu = arr_cpu + jnp.where(is_create, pod.cpu, 0)
            arr_gpu = arr_gpu + jnp.where(is_create, pod.total_gpu_milli(), 0)
            # node == -1 (failed create) leaves no owner, so every shard
            # skips the next refresh — same as the pre-restructure behavior
            dirty = jnp.where(kc == 2, dirty, node)
            ctr = ctr + counter_delta(kc, node)
            if faults:
                if pipelined:
                    # decide the fault step now (it reads only committed
                    # bookkeeping — the current event can never both bind
                    # AND fault), defer its writes one iteration
                    fpend, fc, ftouch, fy = _fl.plan_fault_step(
                        placed, masks, fc, pods, kind, farg, faux, fpos,
                        fault_ops,
                    )
                else:
                    # masked fault transitions: state row ops owner-masked
                    # by the global-id row mask, bookkeeping replicated
                    (state, placed, masks, failed, fc, ftouch, fy) = (
                        _fl.apply_fault_step(
                            state, placed, masks, failed, fc, pods, kind,
                            farg, faux, fpos, fault_ops, tp, gids, False,
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
            new_carry = ShardTableCarry(
                state, packed_tbl, lt, lr, lwn, pend, dirty, placed,
                masks, failed, arr_cpu, arr_gpu, key, ctr,
            )
            ys = (
                (node, dev)
                + ((dec,) if decisions else ())
                + ((ser,) if series_every else ())
            )
            if faults:
                if pipelined:
                    return (new_carry, fc, fpend), ys + (fy,)
                return (new_carry, fc), ys + (fy,)
            return new_carry, ys

        xs = (
            (ev_kind, ev_pod, fault_ops.pos, fault_ops.arg, fault_ops.aux)
            if faults else (ev_kind, ev_pod)
        )
        carry, ys = jax.lax.scan(body, carry, xs)
        return (carry,) + tuple(ys)

    state_specs = NodeState(*([P(NODE_AXIS)] * len(NodeState._fields)))
    spec_r = PodSpec(*([P()] * 6))
    types_specs = PodTypes(spec_r, spec_r, P(), P(), P())
    from tpusim.types import TypicalPods

    tp_specs = TypicalPods(*([P()] * len(TypicalPods._fields)))
    # the carry's table shards / block summaries live on the node axis;
    # bookkeeping — the pipeline register included — is replicated
    # (identical on every shard by construction)
    pend_specs = PendingCommit(*([P()] * len(PendingCommit._fields)))
    carry_specs = ShardTableCarry(
        state=state_specs,
        packed_tbl=P(None, NODE_AXIS),
        lt=P(None, NODE_AXIS), lr=P(None, NODE_AXIS), lwn=P(None, NODE_AXIS),
        pend=pend_specs,
        dirty=P(), placed=P(), masks=P(), failed=P(),
        arr_cpu=P(), arr_gpu=P(), key=P(), ctr=P(),
    )

    def _wrap(fn, in_specs, out_specs):
        return jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    # decision records and series samples are replicated outputs
    # (collective-merged topk / psummed integer reductions), like the
    # (node, dev) telemetry
    dec_specs = DecisionRecord(*([P()] * len(DecisionRecord._fields)))
    ser_specs = obs_series.SeriesSample(
        *([P()] * len(obs_series.SeriesSample._fields))
    )
    if faults:
        # retry queue, disruption counters, streams, fault telemetry, and
        # the deferred fault register are all replicated — identical on
        # every shard by construction
        fc_specs = _fl.FaultCarry(*([P()] * len(_fl.FaultCarry._fields)))
        fops_specs = _fl.FaultOps(*([P()] * len(_fl.FaultOps._fields)))
        fy_specs = _fl.FaultY(*([P()] * len(_fl.FaultY._fields)))
        if pipelined:
            fp_specs = _fl.FaultPending(
                *([P()] * len(_fl.FaultPending._fields))
            )
            carry_specs = (carry_specs, fc_specs, fp_specs)
        else:
            carry_specs = (carry_specs, fc_specs)
    mapped_init = _wrap(
        _init_shard,
        (state_specs, P(NODE_AXIS), spec_r, types_specs, tp_specs, P(),
         P()) + ((fc_specs,) if faults else ()),
        carry_specs,
    )
    mapped_chunk = _wrap(
        _chunk_shard,
        (carry_specs, P(NODE_AXIS), spec_r, types_specs, P(), P(), tp_specs,
         P()) + ((fops_specs,) if faults else ()),
        (carry_specs, P(), P())
        + ((dec_specs,) if decisions else ())
        + ((ser_specs,) if series_every else ())
        + ((fy_specs,) if faults else ()),
    )

    from tpusim.sim.step import resolve_weights

    @jax.jit
    def _init_carry_j(state, pods, types, tp, key, tiebreak_rank, wts,
                      fault_carry0=None):
        if faults:
            return mapped_init(state, tiebreak_rank, pods, types, tp, key,
                               wts, fault_carry0)
        return mapped_init(state, tiebreak_rank, pods, types, tp, key, wts)

    def _run_chunk_impl(carry, pods, types, ev_kind, ev_pod, tp,
                        tiebreak_rank, wts, fault_ops=None):
        if faults:
            outs = mapped_chunk(
                carry, tiebreak_rank, pods, types, ev_kind, ev_pod, tp,
                wts, fault_ops,
            )
        else:
            outs = mapped_chunk(
                carry, tiebreak_rank, pods, types, ev_kind, ev_pod, tp, wts
            )
        return outs[0], tuple(outs[1:])

    _run_chunk_j = jax.jit(_run_chunk_impl)
    # the donating twin (ISSUE 11): the input carry's shards are donated
    # to the outputs, so a chunked 1M-node replay stops reallocating its
    # O(N*K) table shards every segment; the caller must treat the input
    # carry as consumed (the driver snapshots to host before advancing)
    _run_chunk_don = jax.jit(_run_chunk_impl, donate_argnums=0)

    # weights resolve OUTSIDE the jitted functions (ISSUE 6): the weight
    # vector is always a traced operand, never a baked constant, so one
    # compiled shard_map scan serves every weight vector of the family
    def init_carry(state, pods, types, tp, key, tiebreak_rank,
                   weights=None, fault_carry0=None):
        if faults:
            return _init_carry_j(
                state, pods, types, tp, key, tiebreak_rank,
                resolve_weights(policies, weights), fault_carry0,
            )
        return _init_carry_j(
            state, pods, types, tp, key, tiebreak_rank,
            resolve_weights(policies, weights),
        )

    def run_chunk(carry, pods, types, ev_kind, ev_pod, tp, tiebreak_rank,
                  weights=None, fault_ops=None):
        if faults:
            return _run_chunk_j(
                carry, pods, types, ev_kind, ev_pod, tp, tiebreak_rank,
                resolve_weights(policies, weights), fault_ops,
            )
        return _run_chunk_j(
            carry, pods, types, ev_kind, ev_pod, tp, tiebreak_rank,
            resolve_weights(policies, weights),
        )

    def run_chunk_donated(carry, pods, types, ev_kind, ev_pod, tp,
                          tiebreak_rank, weights=None, fault_ops=None):
        """run_chunk with the input carry DONATED to the outputs
        (ISSUE 11): the chunk scan reuses the carry's table/state shards
        instead of reallocating them every segment. The passed carry is
        consumed — snapshot it first if it must survive."""
        if faults:
            return _run_chunk_don(
                carry, pods, types, ev_kind, ev_pod, tp, tiebreak_rank,
                resolve_weights(policies, weights), fault_ops,
            )
        return _run_chunk_don(
            carry, pods, types, ev_kind, ev_pod, tp, tiebreak_rank,
            resolve_weights(policies, weights),
        )

    run_chunk_donated._cache_size = _run_chunk_don._cache_size

    def _finish_impl(carry):
        """Post-scan epilogue: apply the last event's still-pending
        commit(s) on the gathered GLOBAL view (pend.node is a global id,
        so sim.step.apply_commit applies directly; the registers are
        inert no-ops on pipelined=False builds) and strip the dummy
        bookkeeping row. A finished carry must not be resumed."""
        fpend_f = None
        if faults:
            if pipelined:
                carry, _fc, fpend_f = carry
            else:
                carry, _fc = carry
        state, placed, masks, failed = apply_commit(
            carry.state, carry.placed, carry.masks, carry.failed,
            carry.pend,
        )
        if fpend_f is not None:
            state, placed, masks, failed = _fl.apply_fault_pending(
                state, placed, masks, failed, fpend_f, 0,
                state.num_nodes,
            )
        return state, placed[:-1], masks[:-1], failed[:-1]

    finish = jax.jit(_finish_impl)

    @jax.jit
    def _replay_impl(state, pods, types, ev_kind, ev_pod, tp, key,
                     tiebreak_rank, wts, fault_ops=None,
                     fault_carry0=None) -> ReplayResult:
        carry = _init_carry_j(state, pods, types, tp, key, tiebreak_rank,
                              wts, fault_carry0)
        carry, ys = _run_chunk_j(
            carry, pods, types, ev_kind, ev_pod, tp, tiebreak_rank, wts,
            fault_ops,
        )
        state_f, placed, masks, failed = _finish_impl(carry)
        nodes, devs = ys[0], ys[1]
        rest = list(ys[2:])
        decs = rest.pop(0) if decisions else None
        sers = rest.pop(0) if series_every else None
        if faults:
            base = carry[0]
            fc = carry[1]
            return ReplayResult(
                state_f, placed, masks, failed, None,
                nodes, devs, base.ctr, None, None, rest.pop(0),
                _fl.trim_fault_carry(fc),
            )
        return ReplayResult(
            state_f, placed, masks, failed, None,
            nodes, devs, carry.ctr, decs, sers,
        )

    def replay(state, pods, types, ev_kind, ev_pod, tp, key,
               tiebreak_rank, weights=None, fault_ops=None,
               fault_carry0=None) -> ReplayResult:
        if faults:
            return _replay_impl(
                state, pods, types, ev_kind, ev_pod, tp, key,
                tiebreak_rank, resolve_weights(policies, weights),
                fault_ops, fault_carry0,
            )
        return _replay_impl(
            state, pods, types, ev_kind, ev_pod, tp, key, tiebreak_rank,
            resolve_weights(policies, weights),
        )

    # checkpoint/resume surface (driver chunked dispatch): a host gather of
    # the carry (np.asarray per leaf) is the snapshot; jit re-shards it on
    # the way back in, and the continued scan is bit-identical
    replay.init_carry = init_carry
    replay.run_chunk = run_chunk
    replay.run_chunk_donated = run_chunk_donated
    replay.finish = finish
    replay.engine = _replay_impl  # the weight-operand jitted impl
    return replay
