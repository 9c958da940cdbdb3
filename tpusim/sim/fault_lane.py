"""The in-scan fault plane (ISSUE 10): fault schedules as sweep operands.

PR 2's fault injection splits the base trace host-side and replays the
segments between host-applied fault transitions — a shape-changing Python
loop that cannot vmap, so every fault what-if costs one full replay
(ROADMAP: "the last named config scalar" keeping robustness off the
one-compile sweep axis). This module moves the whole fault vocabulary
INSIDE the compiled scan:

  1. `compile_fault_plan` merges a fault schedule into the base event
     stream host-side: EV_NODE_FAIL / EV_NODE_RECOVER / EV_EVICT become
     ordinary scan steps at their trace positions, and fixed blocks of
     EV_RETRY slots are inserted at every position a queued retry could
     possibly become due (the backoff chains are a pure function of the
     schedule — attempt k of an eviction at e fires at e + Σ backoff(1..k)
     — so the slot positions are computable without knowing outcomes; a
     slot with nothing due is an inert skip). The merged stream plus the
     pre-drawn eviction tables are fixed-shape per-lane OPERANDS, so a
     B-lane disruption frontier vmaps onto ONE compiled scan.

  2. `FaultCarry` holds the retry queue as i32 carry arrays with the
     exact `queues.RetryQueue` semantics: capped exponential backoff,
     FIFO ties ((ready, seq) lexicographic pops), and a dead list
     (attempt > max_retries, or queue overflow at the static capacity —
     both terminal "max-retries-exceeded"). Because it is carry state it
     survives chunked scans and checkpoint round-trips bit-identically.

  3. Random eviction victims stay bit-identical to the host path's
     numpy PCG64 draw: `pick_eviction_victim` draws
     default_rng(seed + pos*K).integers(0, size) where size is the
     placed-pod count AT REPLAY TIME — unknowable host-side — but the
     draw for EVERY possible size is precomputable, so each EV_EVICT
     event ships a [P+1] draw row and the scan gathers draws[row, size].

Equivalence contract: under a deterministic config (no RandomScore /
gpu_sel random — the PRNG chain differs from the segmented path by
construction) and sufficient queue capacity, the in-scan lane reproduces
the segmented PR 2 path's placements, DisruptionMetrics, and final state
exactly; `Simulator.run_with_faults` dispatches here by default and
tests/test_fault_lane.py pins the equality per engine.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpusim.constants import MAX_GPUS_PER_NODE, MILLI
from tpusim.sim.engine import (
    EV_CREATE,
    EV_EVICT,
    EV_NODE_FAIL,
    EV_NODE_RECOVER,
    EV_RETRY,
    EV_SKIP,
)

_INT_MAX = np.int32(np.iinfo(np.int32).max)
_VICTIM_MIX = 2654435761  # pick_eviction_victim's Knuth multiplier

# dctr layout (i32[7] disruption counters carried in-scan)
D_EVICTED = 0
D_RETRIES_ENQ = 1
D_RESCHEDULED = 2
D_FAILURES = 3
D_RECOVERIES = 4
D_FN_GPU_EVENTS = 5
D_DEAD = 6
NUM_DCTR = 7


class FaultOps(NamedTuple):
    """Per-lane fault operands of one fault-enabled replay. The first
    three ride the scan as xs beside (ev_kind, ev_pod); draws/params are
    gathered constants. Everything is data — two lanes with different
    schedules share one jaxpr as long as the padded shapes match."""

    pos: jnp.ndarray  # i32[E_m] base-trace position of each merged step
    arg: jnp.ndarray  # i32[E_m] node (fail/recover) | explicit pod
    #                   (evict, -1 = drawn) | flush round (retry slots)
    aux: jnp.ndarray  # i32[E_m] eviction draw-table row (-1 otherwise)
    draws: jnp.ndarray  # i32[n_rows(>=1), P+1] pre-drawn victim ranks
    params: jnp.ndarray  # i32[4]: backoff base, cap, max_retries, E
    gcnt: jnp.ndarray  # i32[N] global per-node GPU counts (broadcast in
    #                    sweeps; the dark-capacity clock needs the global
    #                    row even on the sharded engine)


class FaultPlan(NamedTuple):
    """Host-side compilation of one fault schedule (numpy arrays — the
    driver uploads/stacks them into FaultOps)."""

    kind: np.ndarray  # i32[E_m] merged stream kinds (0..6)
    idx: np.ndarray  # i32[E_m] base pod index (0 on non-base steps)
    pos: np.ndarray  # i32[E_m]
    arg: np.ndarray  # i32[E_m]
    aux: np.ndarray  # i32[E_m]
    draws: np.ndarray  # i32[n_rows, P+1]
    params: np.ndarray  # i32[4]
    capacity: int  # static retry-queue capacity R
    num_events: int  # base trace length E
    has_recover: bool  # static: arm the frag-delta capture


class FaultCarry(NamedTuple):
    """Retry queue + disruption bookkeeping as exact-dtype carry arrays
    (the queues.RetryQueue semantics; checkpoint/resume transparent like
    every other carry leaf). Invalid queue slots carry pod == -1 and
    ready == seq == INT_MAX so lexicographic pops never see them."""

    q_ready: jnp.ndarray  # i32[R]
    q_seq: jnp.ndarray  # i32[R]
    q_pod: jnp.ndarray  # i32[R]
    q_att: jnp.ndarray  # i32[R]
    q_era: jnp.ndarray  # i32[R] flush round the entry was pushed in (0 =
    #                     during the trace); round r pops only era < r
    seq: jnp.ndarray  # i32 next insertion sequence number
    attempts: jnp.ndarray  # i32[Pp] consecutive failed attempts so far
    evicted_at: jnp.ndarray  # i32[Pp] eviction position (-1 = not evicted)
    dead: jnp.ndarray  # bool[Pp] terminal max-retries-exceeded
    down_at: jnp.ndarray  # i32[N] failure position per node (-1 = up)
    dctr: jnp.ndarray  # i32[NUM_DCTR] disruption counters


class FaultY(NamedTuple):
    """Per-merged-event fault telemetry (scan ys): enough for the host
    to reconstruct every DisruptionMetrics list, the [Fault] log lines,
    creation ranks, and the true event count."""

    rpod: jnp.ndarray  # i32 popped retry pod (-1 = no pop this step)
    lat: jnp.ndarray  # i32 reschedule latency on retry success (-1 else)
    vpod: jnp.ndarray  # i32 EV_EVICT victim (-1 none)
    vnode: jnp.ndarray  # i32 the evict victim's node (-1 none)
    nvict: jnp.ndarray  # i32 pods evicted at this step (fail/evict)
    rec: jnp.ndarray  # i32 1 = recover applied this step
    fb: jnp.ndarray  # f32 cluster frag before a recover (frag flag only)
    fa: jnp.ndarray  # f32 cluster frag after a recover


def no_fault_y():
    z = jnp.int32(-1)
    return FaultY(z, z, z, z, jnp.int32(0), jnp.int32(0),
                  jnp.float32(0), jnp.float32(0))


# ---------------------------------------------------------------------------
# Host-side plan compilation
# ---------------------------------------------------------------------------


def resolve_capacity(fcfg, num_pods: int) -> int:
    """Static retry-queue capacity R: the explicit knob, else
    min(num_pods, 256) — enough that the host RetryQueue (unbounded)
    and the in-carry queue never diverge on realistic schedules; an
    overflowing eviction wave goes terminal instead of corrupting."""
    cap = int(getattr(fcfg, "queue_capacity", 0) or 0)
    if cap > 0:
        return cap
    return max(1, min(int(num_pods), 256))


def _backoffs(fcfg) -> List[int]:
    return [
        min(fcfg.backoff_base * (1 << max(k - 1, 0)), fcfg.backoff_cap)
        for k in range(1, max(fcfg.max_retries, 0) + 1)
    ]


def _victim_draw_row(seed: int, pos: int, num_pods: int) -> np.ndarray:
    """draws[size] = the host path's PCG64 pick for every possible
    placed-count `size` (pick_eviction_victim: a FRESH generator per
    (seed, pos), first draw). Row 0 is -1 (nothing placed)."""
    row = np.full(num_pods + 1, -1, np.int32)
    base = np.uint64(seed) + np.uint64(pos) * np.uint64(_VICTIM_MIX)
    for s in range(1, num_pods + 1):
        row[s] = int(np.random.default_rng(base).integers(0, s))
    return row


def compile_fault_plan(
    ev_kind: np.ndarray,
    ev_pod: np.ndarray,
    faults: Sequence,
    fcfg,
    num_nodes: int,
    num_pods: int,
    capacity: int = 0,
) -> FaultPlan:
    """Merge a fault schedule into the base stream (module docstring).

    The merged order reproduces the segmented host loop exactly: base
    events run to each boundary position, faults clamped to that
    position fire first (schedule order), then one block of EV_RETRY
    slots pops the retries due there (FIFO (ready, seq) order); after
    the trace and fault stream drain, max_retries flush rounds pop the
    queue regardless of backoff, era-gated so each round only sees
    entries pushed before it — the host loop's thresh=inf semantics."""
    from tpusim.sim.faults import validate_fault_schedule

    ev_kind = np.asarray(ev_kind, np.int32)
    ev_pod = np.asarray(ev_pod, np.int32)
    e = int(ev_kind.shape[0])
    faults = sorted(faults, key=lambda f: f.pos)  # stable like the host
    validate_fault_schedule(faults, num_nodes, num_pods)
    if fcfg.backoff_cap > (1 << 20):
        raise ValueError(
            f"backoff_cap {fcfg.backoff_cap} > 2^20: the in-scan backoff "
            "is computed in f32-exact integer range"
        )
    cap_r = capacity or resolve_capacity(fcfg, num_pods)
    bos = _backoffs(fcfg)

    # potential retry boundaries: attempt k of an eviction at source e0
    # fires at e0 + Σ backoff(1..k); chains past the trace end land in
    # the flush rounds. Slot multiplicity per position: 1 per reaching
    # EVICT chain, capacity per reaching FAIL chain (victim counts are
    # outcome-dependent), capped at capacity (<= queue occupancy).
    slot_need: dict = {}
    any_evict_src = False
    for f in faults:
        if f.kind not in (EV_NODE_FAIL, EV_EVICT):
            continue
        any_evict_src = True
        mult = cap_r if f.kind == EV_NODE_FAIL else 1
        t = min(f.pos, e)
        for b in bos:
            t = t + b
            if t >= e:
                break
            slot_need[t] = min(cap_r, slot_need.get(t, 0) + mult)

    boundaries = sorted(
        set(min(f.pos, e) for f in faults) | set(slot_need)
    )

    kinds: List[int] = []
    idxs: List[int] = []
    poss: List[int] = []
    args: List[int] = []
    auxs: List[int] = []
    draw_rows: List[np.ndarray] = []

    def emit(kind, idx=0, pos=0, arg=0, aux=-1):
        kinds.append(kind)
        idxs.append(idx)
        poss.append(pos)
        args.append(arg)
        auxs.append(aux)

    fi = 0
    cursor = 0
    for p in boundaries:
        p = min(p, e)
        # base events up to the boundary
        for i in range(cursor, p):
            emit(int(ev_kind[i]), int(ev_pod[i]), pos=i)
        cursor = max(cursor, p)
        # faults clamped to this boundary, in schedule order
        while fi < len(faults) and min(faults[fi].pos, e) <= p:
            f = faults[fi]
            fi += 1
            if f.kind == EV_EVICT:
                row = -1
                if f.pod < 0:
                    row = len(draw_rows)
                    draw_rows.append(
                        _victim_draw_row(fcfg.seed, p, num_pods)
                    )
                emit(EV_EVICT, pos=p, arg=int(f.pod), aux=row)
            else:
                emit(int(f.kind), pos=p, arg=int(f.node))
        # due-retry slots (normal mode: ready <= pos gate)
        for _ in range(slot_need.get(p, 0)):
            emit(EV_RETRY, pos=p, arg=0)
    # trace tail + faults clamped past the end
    for i in range(cursor, e):
        emit(int(ev_kind[i]), int(ev_pod[i]), pos=i)
    while fi < len(faults):
        f = faults[fi]
        fi += 1
        if f.kind == EV_EVICT:
            row = -1
            if f.pod < 0:
                row = len(draw_rows)
                draw_rows.append(_victim_draw_row(fcfg.seed, e, num_pods))
            emit(EV_EVICT, pos=e, arg=int(f.pod), aux=row)
        else:
            emit(int(f.kind), pos=e, arg=int(f.node))
    # flush rounds: pop everything queued before the round, regardless
    # of backoff (the host loop's end-of-trace thresh=inf drain)
    if any_evict_src:
        for r in range(1, max(fcfg.max_retries, 1) + 1):
            for _ in range(cap_r):
                emit(EV_RETRY, pos=e, arg=r)

    draws = (
        np.stack(draw_rows)
        if draw_rows else np.full((1, num_pods + 1), -1, np.int32)
    )
    has_rec = any(f.kind == EV_NODE_RECOVER for f in faults)
    return FaultPlan(
        kind=np.asarray(kinds, np.int32),
        idx=np.asarray(idxs, np.int32),
        pos=np.asarray(poss, np.int32),
        arg=np.asarray(args, np.int32),
        aux=np.asarray(auxs, np.int32),
        draws=draws.astype(np.int32),
        params=np.asarray(
            [fcfg.backoff_base, fcfg.backoff_cap, fcfg.max_retries, e],
            np.int32,
        ),
        capacity=cap_r,
        num_events=e,
        has_recover=has_rec,
    )


def pad_fault_plans(
    plans: Sequence[FaultPlan], bucket: int = 256, min_stream: int = 0,
    min_rows: int = 0,
) -> Tuple[np.ndarray, ...]:
    """Pad B per-lane plans to common shapes for the vmapped chaos sweep:
    streams to a shared bucketed length (EV_SKIP padding — inert steps),
    draw tables to a shared row count. Returns stacked
    (kind, idx, pos, arg, aux, draws, params) arrays plus the unified
    static (capacity, has_recover). Capacities must already agree (the
    driver resolves one capacity for the whole sweep)."""
    caps = {p.capacity for p in plans}
    if len(caps) != 1:
        raise ValueError(
            f"chaos-sweep lanes must share one queue capacity, got {caps}"
        )
    # power-of-two shape classes above the base bucket: merged-stream
    # lengths and draw-table rows vary with every schedule, and a shape
    # change IS a recompile — rounding up to the next power of two keeps
    # consecutive waves of similar-size schedules on one executable
    # (padding is inert EV_SKIP steps / unused draw rows). min_stream /
    # min_rows are the caller's sticky high-water floors (the svc
    # worker's min_pods/min_events discipline): a later smaller wave on
    # the same Simulator must not land on a smaller shape and recompile.
    em = max(
        max(int(p.kind.shape[0]) for p in plans), int(min_stream)
    )
    em = bucket if em <= bucket else (1 << (em - 1).bit_length())
    rows = max(
        max(int(p.draws.shape[0]) for p in plans), int(min_rows)
    )
    # 64-row floor: random-evict counts jitter wave to wave (they follow
    # the schedule's geometric draws), and a [64, P+1] i32 table is
    # noise-sized — a generous floor keeps typical waves in ONE class
    rows = max(64, 1 << max(rows - 1, 0).bit_length())
    pp = max(int(p.draws.shape[1]) for p in plans)

    def pad_stream(a, fill):
        out = np.full(em, fill, np.int32)
        out[: a.shape[0]] = a
        return out

    kinds, idxs, poss, args, auxs, draws, params = [], [], [], [], [], [], []
    for p in plans:
        kinds.append(pad_stream(p.kind, EV_SKIP))
        idxs.append(pad_stream(p.idx, 0))
        poss.append(pad_stream(p.pos, p.num_events))
        args.append(pad_stream(p.arg, 0))
        auxs.append(pad_stream(p.aux, -1))
        d = np.full((rows, pp), -1, np.int32)
        d[: p.draws.shape[0], : p.draws.shape[1]] = p.draws
        draws.append(d)
        params.append(p.params)
    return (
        np.stack(kinds), np.stack(idxs), np.stack(poss), np.stack(args),
        np.stack(auxs), np.stack(draws), np.stack(params),
        plans[0].capacity, any(p.has_recover for p in plans),
    )


# ---------------------------------------------------------------------------
# In-scan carry + queue ops
# ---------------------------------------------------------------------------


def init_fault_carry(num_pods: int, num_nodes: int, capacity: int) -> FaultCarry:
    r = int(capacity)
    return FaultCarry(
        q_ready=jnp.full(r, _INT_MAX, jnp.int32),
        q_seq=jnp.full(r, _INT_MAX, jnp.int32),
        q_pod=jnp.full(r, -1, jnp.int32),
        q_att=jnp.zeros(r, jnp.int32),
        q_era=jnp.zeros(r, jnp.int32),
        seq=jnp.int32(0),
        attempts=jnp.zeros(num_pods, jnp.int32),
        evicted_at=jnp.full(num_pods, -1, jnp.int32),
        dead=jnp.zeros(num_pods, jnp.bool_),
        down_at=jnp.full(num_nodes, -1, jnp.int32),
        dctr=jnp.zeros(NUM_DCTR, jnp.int32),
    )


def pad_fault_carry(fc0: FaultCarry) -> FaultCarry:
    """Size the FaultCarry's pod axis to the engines' P+1 bookkeeping
    rows (the dummy row absorbing the pipelined commit's skip writes can
    never be evicted — placed[P] stays -1 — so the pad rows are inert).
    Shared by the table and shard_map fault builds; trim_fault_carry is
    the inverse the ReplayResult applies."""
    return fc0._replace(
        attempts=jnp.pad(fc0.attempts, (0, 1)),
        evicted_at=jnp.pad(fc0.evicted_at, (0, 1), constant_values=-1),
        dead=jnp.pad(fc0.dead, (0, 1)),
    )


def trim_fault_carry(fc: FaultCarry) -> FaultCarry:
    return fc._replace(
        attempts=fc.attempts[:-1],
        evicted_at=fc.evicted_at[:-1],
        dead=fc.dead[:-1],
    )


def backoff_of(att, base, cap):
    """min(base * 2^(att-1), cap) with traced operands, exact: the shift
    is clamped so base << s stays in i32 (and once it exceeds cap — which
    compile_fault_plan bounds at 2^20 — the min snaps to cap anyway)."""
    s = jnp.maximum(att - 1, 0)
    lb = jnp.floor(
        jnp.log2(jnp.maximum(base, 1).astype(jnp.float32))
    ).astype(jnp.int32)
    s = jnp.minimum(s, jnp.maximum(29 - lb, 0))
    return jnp.minimum(base << s, cap)


def pop_retry(fc: FaultCarry, is_slot, pos, flush_round):
    """One EV_RETRY slot's pop: the earliest (ready, seq) entry that is
    due (normal slots: ready <= pos) or era-eligible (flush round r:
    pushed before round r). Returns (fc', has, pod). Inert when nothing
    qualifies — extra slots are skips by construction."""
    eligible = (fc.q_pod >= 0) & jnp.where(
        flush_round > 0, fc.q_era < flush_round, fc.q_ready <= pos
    )
    any_e = eligible.any()
    rmin = jnp.min(jnp.where(eligible, fc.q_ready, _INT_MAX))
    cand = eligible & (fc.q_ready == rmin)
    slot = jnp.argmin(jnp.where(cand, fc.q_seq, _INT_MAX)).astype(jnp.int32)
    has = is_slot & any_e
    pod = jnp.where(has, fc.q_pod[slot], 0).astype(jnp.int32)
    fc = fc._replace(
        q_pod=fc.q_pod.at[slot].set(jnp.where(has, -1, fc.q_pod[slot])),
        q_ready=fc.q_ready.at[slot].set(
            jnp.where(has, _INT_MAX, fc.q_ready[slot])
        ),
        q_seq=fc.q_seq.at[slot].set(
            jnp.where(has, _INT_MAX, fc.q_seq[slot])
        ),
    )
    return fc, has, pod


def _queue_push_mask(fc: FaultCarry, vm, att, pos, era, params):
    """Push every pod in mask `vm` (ascending pod order = FIFO seq
    order, the host's flatnonzero discipline) for attempt vector `att`.
    Entries with att > max_retries go dead instead (RetryQueue.push ->
    None); overflow past the static capacity also goes dead (the
    documented divergence from the unbounded host heap). Returns
    (fc', pushed bool[Pp], dead_now bool[Pp])."""
    r = fc.q_pod.shape[0]
    base, cap, maxr = params[0], params[1], params[2]
    dead_now = vm & (att > maxr)
    want = vm & ~dead_now
    rank = jnp.cumsum(want.astype(jnp.int32)) - 1
    free = fc.q_pod < 0
    nfree = free.sum()
    free_order = jnp.argsort(~free)  # free slots first, index order
    fits = want & (rank < nfree)
    tgt = jnp.where(fits, free_order[jnp.clip(rank, 0, r - 1)], r)
    pods_iota = jnp.arange(vm.shape[0], dtype=jnp.int32)
    ready = pos + backoff_of(att, base, cap)
    fc = fc._replace(
        q_pod=fc.q_pod.at[tgt].set(pods_iota, mode="drop"),
        q_att=fc.q_att.at[tgt].set(att, mode="drop"),
        q_ready=fc.q_ready.at[tgt].set(ready, mode="drop"),
        q_seq=fc.q_seq.at[tgt].set(fc.seq + rank, mode="drop"),
        q_era=fc.q_era.at[tgt].set(
            jnp.broadcast_to(era, pods_iota.shape).astype(jnp.int32),
            mode="drop",
        ),
        seq=fc.seq + fits.sum(),
    )
    return fc, fits, dead_now | (want & ~fits)


def _evict_into_queue(fc: FaultCarry, vm, pos, era, params):
    """evict_bookkeep for a victim mask: attempts += 1, eviction clock
    stamped, push-or-dead, disruption counters. Returns
    (fc', newly_dead bool[Pp])."""
    att = jnp.where(vm, fc.attempts + 1, 0)
    fc, pushed, dead_now = _queue_push_mask(fc, vm, att, pos, era, params)
    nd = vm & dead_now
    fc = fc._replace(
        attempts=jnp.where(vm, att, fc.attempts),
        evicted_at=jnp.where(vm, pos, fc.evicted_at),
        dead=fc.dead | nd,
        dctr=fc.dctr.at[D_EVICTED].add(vm.sum().astype(jnp.int32))
        .at[D_RETRIES_ENQ].add(pushed.sum().astype(jnp.int32))
        .at[D_DEAD].add(nd.sum().astype(jnp.int32)),
    )
    return fc, nd


# ---------------------------------------------------------------------------
# Masked fault-step application (shared by all engines)
# ---------------------------------------------------------------------------


def _frag_scalar(state, tp):
    from tpusim.ops.frag import cluster_frag_amounts, frag_sum_except_q3

    return frag_sum_except_q3(cluster_frag_amounts(state, tp).sum(0))


def _fault_decisions(placed, fc: FaultCarry, kind, arg, aux, ops: FaultOps):
    """The decision half of one fault step, shared by the in-line apply
    (apply_fault_step) and the pipelined plan (plan_fault_step): which
    transition fires and on what — (do_fail, do_rec, do_evict, node,
    victim, vnode). Reads only committed bookkeeping; writes nothing."""
    is_fail = kind == EV_NODE_FAIL
    is_rec = kind == EV_NODE_RECOVER
    is_evict = kind == EV_EVICT
    node = jnp.clip(arg, 0, fc.down_at.shape[0] - 1)
    node_down = fc.down_at[node] >= 0
    do_fail = is_fail & ~node_down
    do_rec = is_rec & node_down

    # ---- EV_EVICT victim selection (host pick_eviction_victim, exact:
    # the PCG64 draw per placed-count is pre-tabulated in ops.draws)
    placed_ok = placed >= 0
    size = placed_ok.sum().astype(jnp.int32)
    row = jnp.clip(aux, 0, ops.draws.shape[0] - 1)
    j = ops.draws[row, jnp.clip(size, 0, ops.draws.shape[1] - 1)]
    ranks = jnp.cumsum(placed_ok.astype(jnp.int32)) - 1
    vsel = placed_ok & (ranks == j)
    drawn = jnp.argmax(vsel).astype(jnp.int32)
    use_explicit = is_evict & (arg >= 0)
    exp_c = jnp.clip(arg, 0, placed.shape[0] - 1)
    victim = jnp.where(use_explicit, exp_c, drawn)
    found = jnp.where(
        use_explicit, placed_ok[exp_c], (aux >= 0) & (j >= 0)
    )
    do_evict = is_evict & found
    vnode = jnp.where(do_evict, placed[victim], -1)
    return do_fail, do_rec, do_evict, node, victim, vnode


def _fault_bookkeep(fc: FaultCarry, placed, node, victim, do_fail, do_rec,
                    do_evict, pos, ops: FaultOps):
    """The FaultCarry half of one fault step (victim requeue, down clock,
    disruption counters) — shared by apply_fault_step and
    plan_fault_step so the queue trajectory cannot depend on whether the
    state writes were in-line or deferred. `placed` must be the
    PRE-clearing bookkeeping (vm derives from it). Returns
    (fc', vm victim mask, newly_dead mask)."""
    params = ops.params
    # node-fail evicts every pod on the node, evict exactly one; both
    # requeue through the carry queue in ascending pod order (the host's
    # flatnonzero discipline)
    vm = (do_fail & (placed == node)) | (
        do_evict & (jnp.arange(placed.shape[0]) == victim)
    )
    fc, newly_dead = _evict_into_queue(fc, vm, pos, jnp.int32(0), params)

    # ---- down clock + recover accounting
    fc = fc._replace(
        down_at=fc.down_at.at[node].set(
            jnp.where(do_fail, pos,
                      jnp.where(do_rec, -1, fc.down_at[node]))
        ),
        dctr=fc.dctr.at[D_FAILURES].add(do_fail.astype(jnp.int32))
        .at[D_RECOVERIES].add(do_rec.astype(jnp.int32))
        .at[D_FN_GPU_EVENTS].add(
            jnp.where(
                do_rec,
                ops.gcnt[node] * (pos - fc.down_at[node]),
                0,
            )
        ),
    )
    return fc, vm, newly_dead


def apply_fault_step(
    state,
    placed,
    masks,
    failed,
    fc: FaultCarry,
    specs,
    kind,
    arg,
    aux,
    pos,
    ops: FaultOps,
    tp,
    node_ids,
    frag_delta: bool,
):
    """Apply one EV_NODE_FAIL / EV_NODE_RECOVER / EV_EVICT step as masked
    whole-array updates (at most one kind fires; non-fault steps are
    exact no-ops). `state` may be a LOCAL node shard: `node_ids` carries
    each local row's global id (arange(N) on one device), and the
    replicated bookkeeping (placed/masks/failed/fc) updates identically
    on every shard. Returns (state, placed, masks, failed, fc, touched
    global node id (-1 none), FaultY minus the retry fields)."""
    do_fail, do_rec, do_evict, node, victim, vnode = _fault_decisions(
        placed, fc, kind, arg, aux, ops
    )

    # ---- frag-before capture (recover events; static flag)
    if frag_delta:
        fb = jax.lax.cond(
            do_rec, lambda: _frag_scalar(state, tp),
            lambda: jnp.float32(0),
        )
    else:
        fb = jnp.float32(0)

    # ---- node row reset (fail -> DOWN sentinel, recover -> empty):
    # the faults._reset_node encoding as a masked row op
    do_reset = do_fail | do_rec
    rowm = (node_ids == node) & do_reset
    gpu_full = (
        jnp.arange(MAX_GPUS_PER_NODE, dtype=jnp.int32)[None, :]
        < state.gpu_cnt[:, None]
    ).astype(jnp.int32) * MILLI
    new_mem = jnp.where(do_fail, jnp.full_like(state.mem_cap, -1),
                        state.mem_cap)
    state = state._replace(
        cpu_left=jnp.where(rowm, state.cpu_cap, state.cpu_left),
        mem_left=jnp.where(rowm, new_mem, state.mem_left),
        gpu_left=jnp.where(rowm[:, None], gpu_full, state.gpu_left),
        aff_cnt=jnp.where(rowm[:, None], 0, state.aff_cnt),
    )

    # ---- EV_EVICT resource return (deschedule.evict semantics) at the
    # victim's node, owner-masked via node_ids
    vpod_spec = jax.tree.map(lambda a: a[victim], specs)
    from tpusim.policies.clustering import pod_affinity_class

    cls = pod_affinity_class(vpod_spec)
    vrow = (node_ids == vnode) & do_evict
    colm = (
        jnp.arange(state.aff_cnt.shape[1], dtype=jnp.int32)
        == jnp.maximum(cls, 0)
    ) & (cls >= 0)
    state = state._replace(
        cpu_left=state.cpu_left + jnp.where(vrow, vpod_spec.cpu, 0),
        mem_left=state.mem_left + jnp.where(vrow, vpod_spec.mem, 0),
        gpu_left=state.gpu_left + jnp.where(
            vrow[:, None],
            masks[victim].astype(jnp.int32) * vpod_spec.gpu_milli,
            0,
        ),
        aff_cnt=state.aff_cnt - jnp.where(
            vrow[:, None] & colm[None, :], 1, 0
        ),
    )

    if frag_delta:
        fa = jax.lax.cond(
            do_rec, lambda s=state: _frag_scalar(s, tp),
            lambda: jnp.float32(0),
        )
    else:
        fa = jnp.float32(0)

    # ---- victim bookkeeping (shared _fault_bookkeep: requeue through
    # the carry queue in ascending pod order, down clock, counters)
    fc, vm, newly_dead = _fault_bookkeep(
        fc, placed, node, victim, do_fail, do_rec, do_evict, pos, ops
    )
    placed = jnp.where(vm, -1, placed)
    masks = jnp.where(vm[:, None], False, masks)
    # a pod out of retries AT EVICTION marks ever-failed explicitly (the
    # host's evict_bookkeep; retry failures mark it via the create path)
    failed = failed | newly_dead

    touched = jnp.where(
        do_reset, node, jnp.where(do_evict, vnode, -1)
    ).astype(jnp.int32)
    y = FaultY(
        rpod=jnp.int32(-1),
        lat=jnp.int32(-1),
        vpod=jnp.where(do_evict, victim, -1).astype(jnp.int32),
        vnode=jnp.where(do_evict, vnode, -1).astype(jnp.int32),
        nvict=vm.sum().astype(jnp.int32),
        rec=do_rec.astype(jnp.int32),
        fb=fb,
        fa=fa,
    )
    return state, placed, masks, failed, fc, touched, y


class FaultPending(NamedTuple):
    """One fault step's deferred write set — the fault half of the
    shard engine's software pipeline (ISSUE 11): the DECISION (victim
    draw, row targets, queue bookkeeping) happens in-line at the event —
    it only reads committed bookkeeping — while every state/placed/
    masks/failed WRITE is encoded here and applied at the top of the
    NEXT scan iteration by apply_fault_pending, keeping the body
    strictly write-then-read. All node ids are GLOBAL; fields are inert
    (-1 / zeros) on non-fault steps."""

    reset_node: jnp.ndarray  # i32 node to reset (-1 none)
    reset_fail: jnp.ndarray  # bool: True -> DOWN sentinel, False -> empty
    evict_node: jnp.ndarray  # i32 node returning an evicted pod's
    #                          resources (-1 none)
    evict_cpu: jnp.ndarray  # i32
    evict_mem: jnp.ndarray  # i32
    evict_milli: jnp.ndarray  # i32 per-GPU milli of the victim
    evict_mask: jnp.ndarray  # bool[8] the victim's recorded device mask
    evict_cls: jnp.ndarray  # i32 affinity class (-1 none)
    clear: jnp.ndarray  # bool[Pp] rows cleared in placed/masks
    dead_or: jnp.ndarray  # bool[Pp] OR-ed into ever-failed


def no_fault_pending(num_rows: int) -> FaultPending:
    z = jnp.int32(0)
    return FaultPending(
        reset_node=jnp.int32(-1), reset_fail=jnp.bool_(False),
        evict_node=jnp.int32(-1), evict_cpu=z, evict_mem=z, evict_milli=z,
        evict_mask=jnp.zeros(MAX_GPUS_PER_NODE, jnp.bool_),
        evict_cls=jnp.int32(-1),
        clear=jnp.zeros(num_rows, jnp.bool_),
        dead_or=jnp.zeros(num_rows, jnp.bool_),
    )


def plan_fault_step(
    placed,
    masks,
    fc: FaultCarry,
    specs,
    kind,
    arg,
    aux,
    pos,
    ops: FaultOps,
):
    """apply_fault_step with the state/bookkeeping WRITES deferred: runs
    the same decision + queue bookkeeping (shared _fault_decisions /
    _fault_bookkeep, so the trajectory is bit-identical by construction)
    but returns the write set as a FaultPending instead of mutating the
    buffers. The recover frag-delta capture is unsupported here (the
    post-reset state is never materialized at the event) — the shard
    engine, the only pipelined-fault consumer, never captures it anyway
    (ENGINES.md Round 14). Returns (FaultPending, fc', touched global
    node id, FaultY minus the retry fields)."""
    do_fail, do_rec, do_evict, node, victim, vnode = _fault_decisions(
        placed, fc, kind, arg, aux, ops
    )
    vpod_spec = jax.tree.map(lambda a: a[victim], specs)
    from tpusim.policies.clustering import pod_affinity_class

    cls = pod_affinity_class(vpod_spec)
    fc, vm, newly_dead = _fault_bookkeep(
        fc, placed, node, victim, do_fail, do_rec, do_evict, pos, ops
    )
    do_reset = do_fail | do_rec
    fp = FaultPending(
        reset_node=jnp.where(do_reset, node, -1).astype(jnp.int32),
        reset_fail=do_fail,
        evict_node=jnp.where(do_evict, vnode, -1).astype(jnp.int32),
        evict_cpu=vpod_spec.cpu,
        evict_mem=vpod_spec.mem,
        evict_milli=vpod_spec.gpu_milli,
        evict_mask=masks[victim],
        evict_cls=cls,
        clear=vm,
        dead_or=newly_dead,
    )
    touched = jnp.where(
        do_reset, node, jnp.where(do_evict, vnode, -1)
    ).astype(jnp.int32)
    y = FaultY(
        rpod=jnp.int32(-1),
        lat=jnp.int32(-1),
        vpod=jnp.where(do_evict, victim, -1).astype(jnp.int32),
        vnode=jnp.where(do_evict, vnode, -1).astype(jnp.int32),
        nvict=vm.sum().astype(jnp.int32),
        rec=do_rec.astype(jnp.int32),
        fb=jnp.float32(0),
        fa=jnp.float32(0),
    )
    return fp, fc, touched, y


def apply_fault_pending(state, placed, masks, failed, fp: FaultPending,
                        offset, nloc: int):
    """Apply one FaultPending's deferred writes — strictly write-only on
    every touched buffer: the node-row effects land as one-row scatters
    with out-of-range-drop owner masking (`offset`/`nloc` select this
    shard's local window; 0/N on a gathered global view), the [Pp]
    bookkeeping as masked whole-row selects. The value reads touch only
    the never-written capacity leaves (cpu_cap/mem_cap/gpu_cnt), so the
    scatters alias in place under scan exactly like apply_commit's."""
    # ---- node row reset (fail -> DOWN sentinel, recover -> empty)
    lres = fp.reset_node - offset
    owns_r = (fp.reset_node >= 0) & (lres >= 0) & (lres < nloc)
    ri = jnp.clip(lres, 0, nloc - 1)
    tgt_r = jnp.where(owns_r, ri, nloc)  # nloc = out of range -> dropped
    gpu_full = (
        jnp.arange(MAX_GPUS_PER_NODE, dtype=jnp.int32) < state.gpu_cnt[ri]
    ).astype(jnp.int32) * MILLI
    state = state._replace(
        cpu_left=state.cpu_left.at[tgt_r].set(
            state.cpu_cap[ri], mode="drop"
        ),
        mem_left=state.mem_left.at[tgt_r].set(
            jnp.where(fp.reset_fail, jnp.int32(-1), state.mem_cap[ri]),
            mode="drop",
        ),
        gpu_left=state.gpu_left.at[tgt_r].set(gpu_full, mode="drop"),
        aff_cnt=state.aff_cnt.at[tgt_r].set(0, mode="drop"),
    )

    # ---- EV_EVICT resource return at the victim's node
    lev = fp.evict_node - offset
    owns_e = (fp.evict_node >= 0) & (lev >= 0) & (lev < nloc)
    ei = jnp.clip(lev, 0, nloc - 1)
    tgt_e = jnp.where(owns_e, ei, nloc)
    state = state._replace(
        cpu_left=state.cpu_left.at[tgt_e].add(fp.evict_cpu, mode="drop"),
        mem_left=state.mem_left.at[tgt_e].add(fp.evict_mem, mode="drop"),
        gpu_left=state.gpu_left.at[tgt_e].add(
            fp.evict_mask.astype(jnp.int32) * fp.evict_milli, mode="drop"
        ),
        aff_cnt=state.aff_cnt.at[
            tgt_e, jnp.maximum(fp.evict_cls, 0)
        ].add(jnp.where(fp.evict_cls >= 0, -1, 0), mode="drop"),
    )

    placed = jnp.where(fp.clear, -1, placed)
    masks = jnp.where(fp.clear[:, None], False, masks)
    failed = failed | fp.dead_or
    return state, placed, masks, failed


def commit_retry(fc: FaultCarry, has, pod, node, pos, era, params):
    """Post-create bookkeeping of one popped retry: success resets the
    consecutive-failure budget and records the reschedule latency;
    failure burns an attempt and re-enqueues (or goes dead). Returns
    (fc', lat i32 — the latency on success, -1 otherwise, dead_mask)."""
    success = has & (node >= 0)
    failn = has & (node < 0)
    v = jnp.clip(pod, 0, fc.attempts.shape[0] - 1)
    lat = jnp.where(success, pos - fc.evicted_at[v], -1).astype(jnp.int32)
    att_v = fc.attempts[v] + 1
    vm = failn & (jnp.arange(fc.attempts.shape[0]) == v)
    att_vec = jnp.where(vm, att_v, 0)
    fc, pushed, dead_now = _queue_push_mask(
        fc, vm, att_vec, pos, era, params
    )
    nd = vm & dead_now
    fc = fc._replace(
        attempts=jnp.where(
            vm, att_v,
            jnp.where(
                success & (jnp.arange(fc.attempts.shape[0]) == v),
                0, fc.attempts,
            ),
        ),
        evicted_at=jnp.where(
            success & (jnp.arange(fc.evicted_at.shape[0]) == v),
            -1, fc.evicted_at,
        ),
        dead=fc.dead | nd,
        dctr=fc.dctr.at[D_RESCHEDULED].add(success.astype(jnp.int32))
        .at[D_RETRIES_ENQ].add(pushed.sum().astype(jnp.int32))
        .at[D_DEAD].add(nd.sum().astype(jnp.int32)),
    )
    return fc, lat, nd


# ---------------------------------------------------------------------------
# Host-side result assembly
# ---------------------------------------------------------------------------


def assemble_disruption(plan: FaultPlan, ys: FaultY, final_fc,
                        gpu_cnt: np.ndarray, frag_delta: bool = True):
    """(DisruptionMetrics, dead_pods bool[Pp], retry attempt count) from
    the scan's fault telemetry — the exact numbers the segmented host
    loop accumulates, including the end-of-trace dark-capacity clock for
    nodes still down when the trace ends. frag_delta=False (the shard
    engine, whose replay cannot capture it) leaves
    post_recovery_frag_delta EMPTY instead of reporting the ys' zero
    placeholders as if they were measured deltas (ISSUE 11 satellite —
    the driver pairs this with a [Degrade] warning + obs counter)."""
    from tpusim.sim.metrics import DisruptionMetrics

    dctr = np.asarray(final_fc.dctr, np.int64)
    dm = DisruptionMetrics(
        node_failures=int(dctr[D_FAILURES]),
        node_recoveries=int(dctr[D_RECOVERIES]),
        evicted_pods=int(dctr[D_EVICTED]),
        retries_enqueued=int(dctr[D_RETRIES_ENQ]),
        rescheduled_pods=int(dctr[D_RESCHEDULED]),
        unscheduled_after_retries=int(dctr[D_DEAD]),
        failed_node_gpu_events=int(dctr[D_FN_GPU_EVENTS]),
    )
    down = np.asarray(final_fc.down_at, np.int64)
    gpu_cnt = np.asarray(gpu_cnt, np.int64)
    # the shard path's down_at spans the mesh-PADDED node axis while the
    # caller's gpu_cnt may be the real cluster's — pad rows can never be
    # down (fault targets are validated < num_nodes), so trimming to the
    # common prefix is exact
    n = min(down.shape[0], gpu_cnt.shape[0])
    down = down[:n]
    still = down >= 0
    dm.failed_node_gpu_events += int(
        (gpu_cnt[:n][still]
         * np.maximum(plan.num_events - down[still], 0)).sum()
    )
    lat = np.asarray(ys.lat, np.int64)
    dm.reschedule_latency_events = [int(x) for x in lat[lat >= 0]]
    if frag_delta:
        rec = np.asarray(ys.rec) > 0
        fb = np.asarray(ys.fb, np.float64)
        fa = np.asarray(ys.fa, np.float64)
        dm.post_recovery_frag_delta = [
            float(fa[i]) - float(fb[i]) for i in np.flatnonzero(rec)
        ]
    else:
        dm.post_recovery_frag_delta = []
    dead = np.asarray(final_fc.dead, bool)
    attempts_run = int((np.asarray(ys.rpod) >= 0).sum())
    return dm, dead, attempts_run


def fault_creation_rank(plan: FaultPlan, ys: FaultY,
                        num_pods: int) -> np.ndarray:
    """Per-pod creation rank over the merged stream: base creations and
    actual retry attempts rank in replay order, later attempts
    overwrite — the segmented path's state_box['rank'] bookkeeping."""
    kind = plan.kind
    rpod = np.asarray(ys.rpod)[: kind.shape[0]]
    cand = np.where(
        kind == EV_CREATE, plan.idx,
        np.where((kind == EV_RETRY) & (rpod >= 0), rpod, -1),
    )
    rank = np.full(num_pods, -1, np.int64)
    hits = np.flatnonzero(cand >= 0)
    for r, i in enumerate(hits):
        rank[cand[i]] = r
    return rank
