"""Fused whole-replay Pallas engine — one kernel for the entire event loop.

Round-3 profiling (ENGINES.md) showed the incremental table replay is
KERNEL-LAUNCH-BOUND: ~40 small fused kernels per event plus a ~15 us/iteration
`lax.scan` floor put a hard ceiling of ~16.6k events/s on one chip, while the
per-event math itself is only ~1-2 us of VPU work. This engine removes both
overheads at once: the WHOLE replay is a single `pl.pallas_call` with
`grid=(E,)` and sequential ("arbitrary") dimension semantics. The score /
feasibility / device tables, the cluster state, and the placement bookkeeping
all live in VMEM across grid steps (~6 MB total); one grid step = one event =
the same filter -> score-column refresh -> selectHost -> Reserve -> Bind cycle
the table engine runs (mirroring the reference's per-pod cycle,
vendor .../scheduler/scheduler.go:441 scheduleOne + the simon plugin set),
executed as straight-line VPU code with zero kernel launches per event.

Mosaic constraints shape the implementation (probed on the target chip):
scalars cannot be stored to VMEM and dynamic lane-dim slicing is not
lowerable — but dynamic slicing on LEADING and SUBLANE dims is. So the node
and event axes are chunked as (C, 128) and the tables as [K, C, 128]:
  row gather   score_tbl[t_id]     -> score[pl.ds(tid,1), :, :]   (free)
  col update   tbl[:, node] = col  -> rmw of tbl[:, pl.ds(c,1), :]
                                      masked on lane == node % 128
  scalar read  placed[idx]         -> sum(where(lane_iota == idx, placed, 0))
                                      (pod-axis arrays stay flat [1, P] —
                                      the masked full-row op is ~45 KB)
Each update touches one (.., 1, 128) chunk instead of a whole [K, N] table
(~12x less masked-write traffic than the round-4 v1 flat layout).

Exactness: the kernel computes the same integer scores from the same integer
state as the table engine; the only divergence channel is f32 reduction order
inside the FGD frag sums (floor(sigmoid(.)*100) can flip an integer score when
a sum lands exactly on a truncation boundary). Placements are asserted
identical to the table engine on the full openb trace in the TPU lane
(tests/test_tpu.py); the CPU lane pins interpreter-mode equality on
randomized small traces (tests/test_pallas_engine.py).

Scope: configurations where EVERY enabled Score plugin has a column kernel
in PALLAS_COLUMNS — FGD, BestFit, GpuPacking, GpuClustering, PWR, and
DotProduct (all 4 dim-extension methods) — with gpu_sel in {best, worst,
enabled self-select policy}. That covers the reference's full experiment
protocol: the single-plugin-at-weight-1000 rows (SURVEY.md §5.6) AND the
PWR+FGD weighted mixes (generate_run_scripts.py rows 08/11/12), whose
Σ wᵢ·normalizeᵢ(colᵢ) accumulation runs fused since round 5. Per-event
reporting configs run here too: the kernel replays metric-free and the
shared post-pass (tpusim.sim.metrics) reconstructs the report series from
the emitted (event_node, event_dev) telemetry. driver.run_events picks
this engine automatically on TPU backends and falls back to the
table/sequential engines otherwise.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpusim.constants import (
    CPU_FULL_W,
    CPU_IDLE_W,
    CPU_NCORES,
    GPU_FULL_W,
    GPU_IDLE_W,
    MAX_GPUS_PER_NODE,
    MAX_NODE_SCORE,
    MAX_SPEC_CPU,
    MAX_SPEC_GPU,
    MILLI,
)
from tpusim.ops.energy import cpu_package_watts
from tpusim.sim.engine import ReplayResult
from tpusim.sim.step import SELF_SELECT_POLICIES
from tpusim.sim.table_engine import PodTypes, reject_randomized
from tpusim.types import NodeState, PodSpec

_INT_MAX = np.int32(np.iinfo(np.int32).max)

_EV_FIELDS = 12  # packed per-event row size (see _pack_events)

# VMEM budget the fused kernel's resident set must fit in: the compiler's
# default scoped-VMEM allowance of 16 MiB less headroom for Mosaic's own
# scratch — not the core's physical VMEM. On the 'TPU v5 lite' of PR 22's
# chip run (pltpu.get_tpu_info(): 128 MiB of VMEM per core) a 12.7 MiB
# estimate (N = 8,192, K = 128) compiled and ran, and 18.9 MiB (N = 12,288)
# failed in XLA with "RESOURCE_EXHAUSTED: Ran out of memory in memory
# space vmem while allocating on stack". driver.run_events probes
# fits_vmem()/fits_hbm() first and routes a shape neither tier fits to the
# blocked table engine. Using more of the core needs
# CompilerParams(vmem_limit_bytes=...) as well as a larger budget (ROADMAP
# S5). Override with TPUSIM_PALLAS_VMEM_BYTES.
DEFAULT_VMEM_BUDGET = 14 * 2**20


def vmem_resident_bytes(
    n_nodes: int, k_types: int, num_pol: int, num_pods: int, num_events: int
) -> int:
    """Estimated VMEM-resident footprint of the fused kernel: the
    score/sdev/feas tables ([K, N] i32 per policy + 2), the node state
    (~14 i32 lanes per node), the packed event rows ([_EV_FIELDS, E] i32),
    and the pod-axis bookkeeping ([1, P] rows). The node axis is padded to
    a 128 multiple like make_pallas_replay does."""
    n = -(-n_nodes // 128) * 128
    tables = (num_pol + 2) * k_types * n * 4
    state = 14 * n * 4
    events = _EV_FIELDS * num_events * 4
    pods = 12 * num_pods * 4
    return tables + state + events + pods


def vmem_budget() -> int:
    """The per-core VMEM budget the residency probes test against:
    TPUSIM_PALLAS_VMEM_BYTES or DEFAULT_VMEM_BUDGET. A malformed value
    fails LOUDLY naming the variable (ISSUE 15 satellite, the shared
    tpusim.envutil helper): it used to fall back silently, which could
    re-open the degradation path — or un-gate a kernel that then dies
    with an opaque Mosaic allocation failure — without the operator
    ever learning their override was ignored."""
    from tpusim.envutil import int_env

    return int_env("TPUSIM_PALLAS_VMEM_BYTES", DEFAULT_VMEM_BUDGET,
                   minimum=1)


def fits_vmem(
    n_nodes: int, k_types: int, num_pol: int, num_pods: int, num_events: int
) -> bool:
    """Whether the fused kernel's FULLY-VMEM-RESIDENT set fits the
    budget — tier 1 of the driver's pre-dispatch residency probe
    (ENGINES.md spill list: the measured ceiling is N ≤ 4096 at K = 151
    on a 16 MiB core). Tier 2 is fits_hbm: the HBM-resident-table
    layout whose VMEM footprint drops to O(K·B + row scratch)."""
    return vmem_resident_bytes(
        n_nodes, k_types, num_pol, num_pods, num_events
    ) <= vmem_budget()


def vmem_resident_bytes_hbm(
    n_nodes: int, k_types: int, num_pol: int, num_pods: int,
    num_events: int, num_norm: int = 1,
) -> int:
    """Estimated VMEM-resident footprint of the HBM-residency kernel
    (ENGINES.md Round 19). The [K, N] score/sdev/feas tables and the
    mutable node state live in HBM (`pl.ANY`); what stays
    VMEM-resident is

      blocked summaries   bt/br/bn [N/B, K] + brmin/brmax
                          [N/B, nn·K] + slo/shi — (3 + 2·nn)·K·4 bytes
                          per 128-node block (nn = max(num_norm, 1))
      tie-break rank      [N/B, 128] i32 (the drift rebuild reduces it)
      row scratch         the event type's double-buffered score rows +
                          feas row: (2·num_pol + 2)·N·4 bytes
      column scratch      the dirty node's double-buffered table column
                          chunks: (num_pol + 2)·K·2·128·4 bytes
      state/chunk scratch one retained state chunk + read-only chunk +
                          the winner's sdev chunk (~24 rows of 128 i32)
      events + pods       the packed event rows, per-event telemetry,
                          and pod bookkeeping — unchanged from the
                          VMEM-resident layout

    so the per-node cost falls from (num_pol + 2)·K·4 + ~56 bytes to
    (3 + 2·nn)·K/32 + (2·num_pol + 2 + 1)·4 bytes and the ceiling moves
    from N ≤ 4096 to ≥ 256k at K = 151 (see hbm_ceiling_nodes)."""
    n = -(-n_nodes // 128) * 128
    nc = n // 128
    nn = max(int(num_norm), 1)
    summaries = (3 + 2 * nn) * k_types * nc * 4 + 2 * nn * k_types * 4
    rank = n * 4
    rows = (2 * num_pol + 2) * n * 4
    cols = (num_pol + 2) * k_types * 2 * 128 * 4
    state_scratch = 24 * 128 * 4
    events = (_EV_FIELDS + 2) * num_events * 4
    pods = 12 * num_pods * 4
    return summaries + rank + rows + cols + state_scratch + events + pods


def fits_hbm(
    n_nodes: int, k_types: int, num_pol: int, num_pods: int,
    num_events: int, num_norm: int = 1,
) -> bool:
    """Tier 2 of the residency probe: whether the HBM-residency
    kernel's VMEM-resident set (vmem_resident_bytes_hbm) fits the
    budget. The tables themselves are HBM-bounded, so this is the only
    VMEM constraint left."""
    return vmem_resident_bytes_hbm(
        n_nodes, k_types, num_pol, num_pods, num_events, num_norm
    ) <= vmem_budget()


def select_residency(
    n_nodes: int, k_types: int, num_pol: int, num_pods: int,
    num_events: int, num_norm: int = 1,
):
    """The two-tier residency auto-select the driver dispatches on:
    'vmem' when the whole table set fits on-core (the original fused
    kernel — fastest, zero DMA), else 'hbm' when the HBM-resident
    layout's VMEM working set fits, else None (degrade to the blocked
    table engine — the [Degrade] path, now narrowed to genuinely
    VMEM-impossible shapes)."""
    if fits_vmem(n_nodes, k_types, num_pol, num_pods, num_events):
        return "vmem"
    if fits_hbm(n_nodes, k_types, num_pol, num_pods, num_events, num_norm):
        return "hbm"
    return None


def hbm_ceiling_nodes(
    k_types: int, num_pol: int, num_norm: int = 1, num_pods: int = 2048,
    num_events: int = 4096, budget: int = None,
) -> int:
    """Largest node count (128-multiple) whose HBM-residency VMEM
    working set fits the budget at this (K, num_pol, num_norm) shape and
    a reference workload size — the documented ceiling
    `bench_scale --pallas-ceiling` sweeps and the gate pins ≥ 256k at
    K = 151 (ENGINES.md Round 19 footprint math)."""
    if budget is None:
        budget = vmem_budget()

    def fits(blocks: int) -> bool:
        return vmem_resident_bytes_hbm(
            blocks * 128, k_types, num_pol, num_pods, num_events, num_norm
        ) <= budget

    lo, hi = 0, 1
    while fits(hi) and hi < 2 ** 24:
        lo, hi = hi, hi * 2
    while lo + 1 < hi:
        mid = (lo + hi) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid
    return lo * 128


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _node_bit(gtyp):
    """GPU-model bit of a node's gpu_type id (-1 = no GPU -> no bit).
    ref: utils.go:957-1005 IsNodeAccessibleToPod."""
    return jnp.where(gtyp >= 0, jax.lax.shift_left(1, jnp.maximum(gtyp, 0)), 0)


def _sigmoid_score_f32(cur, new):
    """trunc(sigmoid((cur-new)/1000) * MaxNodeScore) — fgd_score.go:124."""
    s = jax.nn.sigmoid((cur - new) / 1000.0)
    return jnp.floor(s * MAX_NODE_SCORE).astype(jnp.int32)


def _cumsum8_lanes(u):
    """Inclusive prefix sum of a (1,8) lane vector (no cumsum in Mosaic)."""
    sub = _iota((8, 8), 0)
    lane = _iota((8, 8), 1)
    a = jnp.where(lane <= sub, u, 0)  # (8,8): row d = prefix of u
    return a.sum(axis=1, keepdims=True).T  # (1,8)


# ---------------------------------------------------------------------------
# Policy column kernels: score ONE node (scalars + (8,1) device vector)
# against every pod type at once. Signature:
#   col_fn(node: _NodeScalars, types: _TypeCols, tp: _TpRows)
#     -> (score_col i32[K,1], sdev_col i32[K,1])
# Registered per policy name; policies without an entry fall back to the
# table engine.
# ---------------------------------------------------------------------------


class _NodeScalars(NamedTuple):
    cpu: jnp.ndarray  # scalar i32 cpu_left
    mem: jnp.ndarray  # scalar i32 mem_left
    cap: jnp.ndarray  # scalar i32 cpu_cap
    gcnt: jnp.ndarray  # scalar i32 gpu count
    gtyp: jnp.ndarray  # scalar i32 gpu model id (-1 none)
    ctyp: jnp.ndarray  # scalar i32 cpu model id
    g8: jnp.ndarray  # (8,1) i32 per-device milli left
    aff9: jnp.ndarray  # (9,1) i32 pods per GPU-affinity class


class _EnergyRows(NamedTuple):
    """Energy model tables as (1,M) rows (ref: open-gpu-share/utils/
    const.go:48-121; tpusim.constants CPU_*/GPU_* arrays)."""

    gidle: jnp.ndarray  # (1,Mg) f32 idle watts per GPU model
    gfull: jnp.ndarray  # (1,Mg) f32 full watts per GPU model
    cidle: jnp.ndarray  # (1,Mc) f32 idle watts per CPU package
    cfull: jnp.ndarray  # (1,Mc) f32 full watts per CPU package
    ncores: jnp.ndarray  # (1,Mc) f32 physical cores per CPU package


class _TypeCols(NamedTuple):
    """Pod-type spec columns, share-group rows [0,Ks) then whole [Ks,K)."""

    cpu: jnp.ndarray  # (K,1) i32
    mem: jnp.ndarray  # (K,1) i32
    milli: jnp.ndarray  # (K,1) i32
    num: jnp.ndarray  # (K,1) i32
    mask: jnp.ndarray  # (K,1) i32
    ks: int  # static share-group size


class _TpRows(NamedTuple):
    """Typical-pod distribution as (1,T) rows (ref: frag.go:285-380)."""

    cpu: jnp.ndarray  # (1,T) i32
    milli: jnp.ndarray  # (1,T) i32
    numf: jnp.ndarray  # (1,T) f32
    mask: jnp.ndarray  # (1,T) i32
    freq: jnp.ndarray  # (1,T) f32


def _packed_take(node: _NodeScalars, milli, num):
    """select_devices_packed for (K,1) type columns on one node: fitting
    devices taken least-free-first, stable by index, until `num` are found
    (ref: resource.go:454-480). Returns (take (K,8) bool, ok (K,1) bool)."""
    gT = node.g8.T  # (1,8)
    kdim = milli.shape[0]
    sub8 = _iota((8, 8), 0)  # d
    lane8b = _iota((8, 8), 1)  # e
    lt = (gT < node.g8) | ((gT == node.g8) & (lane8b < sub8))  # [d,e]
    rank8 = lt.astype(jnp.int32).sum(axis=1, keepdims=True)  # (8,1)
    fit = (gT >= milli) & (milli > 0)  # (K,8)
    # taken = fitting, with < num fitting devices ahead in sorted order
    earlier = fit.reshape(kdim, 1, 8) & (
        rank8.T.reshape(1, 1, 8) < rank8.reshape(1, 8, 1)
    )  # [k,d,e]
    cnt = earlier.astype(jnp.int32).sum(axis=2)  # (K,8)
    take = fit & (cnt < num)
    ok = take.astype(jnp.int32).sum(axis=1, keepdims=True) >= num
    return take, ok


def _frag_terms(node: _NodeScalars, tp: _TpRows):
    """Shared frag ingredients for one node: the fit/fitcnt/fitsum
    decomposition of NodeGpuShareFragAmountScore (frag.go:148-203) that
    policies/fgd.py uses, here against (8,T)-shaped broadcasts."""
    gf = node.g8.astype(jnp.float32)  # (8,1)
    fit = (node.g8 >= tp.milli) & (tp.milli > 0)  # (8,T)
    fitf = fit.astype(jnp.float32)
    fitcnt = fitf.sum(axis=0, keepdims=True)  # (1,T)
    fitsum = jnp.where(fit, gf, 0.0).sum(axis=0, keepdims=True)  # (1,T)
    total = gf.sum()
    acc = (tp.mask == 0) | ((tp.mask & _node_bit(node.gtyp)) != 0)  # (1,T)
    gpu_pod = tp.milli > 0
    return fit, fitf, fitcnt, fitsum, total, acc, gpu_pod


def _fgd_column(node: _NodeScalars, types: _TypeCols, tp: _TpRows, aux):
    """FGD score + Reserve-device column for one node across all pod types
    (ref: plugin/fgd_score.go:99-156; the same fit/fitsum decomposition as
    policies/fgd.py, vectorized over the type axis)."""
    ks = types.ks
    k = types.cpu.shape[0]
    kw = k - ks
    fit, fitf, fitcnt, fitsum, total, acc, gpu_pod = _frag_terms(node, tp)
    isq3 = gpu_pod & acc & (fitcnt >= tp.numf) & (node.cpu >= tp.cpu)
    cur = (tp.freq * jnp.where(isq3, total - fitsum, total)).sum()
    gf = node.g8.astype(jnp.float32)  # (8,1)
    gT = node.g8.T  # (1,8)
    t = tp.cpu.shape[1]

    outs = []
    # --- share branch: best per-device hypothetical (fgd_score.go:111-134)
    if ks:
        p = types.milli[:ks]  # (Ks,1)
        p3 = p.astype(jnp.float32).reshape(ks, 1, 1)
        g3 = gf.reshape(1, 8, 1)
        m3i = tp.milli.reshape(1, 1, t)
        fitp = ((g3 - p3) >= m3i.astype(jnp.float32)) & (m3i > 0)  # (Ks,8,T)
        fit3 = fitf.reshape(1, 8, t)
        fitcnt_h = fitcnt.reshape(1, 1, t) - fit3 + fitp.astype(jnp.float32)
        fitsum_h = (
            fitsum.reshape(1, 1, t)
            - jnp.where(fit.reshape(1, 8, t), g3, 0.0)
            + jnp.where(fitp, g3 - p3, 0.0)
        )
        cpu_ok = (node.cpu - types.cpu[:ks]) >= tp.cpu  # (Ks,T)
        isq3_h = (
            gpu_pod.reshape(1, 1, t)
            & acc.reshape(1, 1, t)
            & (fitcnt_h >= tp.numf.reshape(1, 1, t))
            & cpu_ok.reshape(ks, 1, t)
        )
        total_h = total - p3  # (Ks,1,1)
        new = (
            tp.freq.reshape(1, 1, t)
            * jnp.where(isq3_h, total_h - fitsum_h, total_h)
        ).sum(axis=2)  # (Ks,8)
        fits = gT >= p  # (Ks,8)
        dev_scores = jnp.where(fits, _sigmoid_score_f32(cur, new), -1)
        best_score = jnp.max(dev_scores, axis=1, keepdims=True)  # (Ks,1)
        lane8 = _iota((ks, 8), 1)
        best_dev = jnp.min(
            jnp.where(dev_scores == best_score, lane8, 8), axis=1, keepdims=True
        )
        ok = best_score >= 0  # == fits.any(): fitting devices score >= 0
        outs.append((jnp.where(ok, best_score, 0), jnp.where(ok, best_dev, -1)))

    # --- whole/CPU branch: Sub hypothetical (fgd_score.go:137-148)
    if kw:
        wm = types.milli[ks:]  # (Kw,1)
        wn = types.num[ks:]
        wc = types.cpu[ks:]
        take, _ = _packed_take(node, wm, wn)  # (Kw,8)
        g2 = jnp.where(wn > 0, gT - take * wm, gT)  # (Kw,8)
        g2f = g2.astype(jnp.float32)
        m3i = tp.milli.reshape(1, 1, t)
        fit2 = (g2.reshape(kw, 8, 1) >= m3i) & (m3i > 0)  # (Kw,8,T)
        fitcnt2 = fit2.astype(jnp.float32).sum(axis=1)  # (Kw,T)
        fitsum2 = jnp.where(fit2, g2f.reshape(kw, 8, 1), 0.0).sum(axis=1)
        total2 = g2f.sum(axis=1, keepdims=True)  # (Kw,1)
        isq3_2 = gpu_pod & acc & (fitcnt2 >= tp.numf) & ((node.cpu - wc) >= tp.cpu)
        new_w = (tp.freq * jnp.where(isq3_2, total2 - fitsum2, total2)).sum(
            axis=1, keepdims=True
        )
        outs.append(
            (_sigmoid_score_f32(cur, new_w), jnp.full((kw, 1), -1, jnp.int32))
        )

    if len(outs) == 2:
        return (
            jnp.concatenate([outs[0][0], outs[1][0]], axis=0),
            jnp.concatenate([outs[0][1], outs[1][1]], axis=0),
        )
    return outs[0]


def _first_max_dev(scores, neg):
    """(value, device) of the first maximum over the device lane axis —
    jnp.argmax's first-on-ties semantics via max + min-index."""
    kdim = scores.shape[0]
    best = jnp.max(scores, axis=1, keepdims=True)  # (K,1)
    lane8 = _iota((kdim, 8), 1)
    dev = jnp.min(jnp.where(scores == best, lane8, 8), axis=1, keepdims=True)
    ok = best > neg
    return jnp.where(ok, best, neg), jnp.where(ok, dev, -1)


def _bestfit_column(node: _NodeScalars, types: _TypeCols, tp, aux):
    """BestFit (ref: best_fit_score.go:66-97): weighted free-minus-request
    over {cpu, gpu} dims against max machine specs."""
    gtot = node.g8.sum().astype(jnp.float32)
    s = (
        (node.cpu - types.cpu).astype(jnp.float32) / MAX_SPEC_CPU * 0.5
        + (gtot - (types.milli * types.num).astype(jnp.float32))
        / MAX_SPEC_GPU * 0.5
    )
    score = jnp.floor((1.0 - s) * MAX_NODE_SCORE).astype(jnp.int32)
    return score, jnp.full_like(score, -1)


def _packing_column(node: _NodeScalars, types: _TypeCols, tp, aux):
    """GpuPacking 3-tier scoring (ref: gpu_packing_score.go:67-117;
    mirrors policies/packing.py over the type axis)."""
    gT = node.g8.T  # (1,8)
    fully_free = (node.g8 == MILLI).astype(jnp.int32).sum()
    t3, t2 = MAX_NODE_SCORE // 3, MAX_NODE_SCORE // 2
    case3 = jnp.maximum(t3 - fully_free, fully_free)
    take, ok = _packed_take(node, types.milli, types.num)  # (K,8)
    free_used = (take & (gT == MILLI)).astype(jnp.int32).sum(
        axis=1, keepdims=True
    )
    ratio = jnp.where(take, gT * 100 // MILLI, 0).sum(axis=1, keepdims=True)
    case1 = jnp.maximum(MAX_NODE_SCORE - ratio // 10, t2)
    case2 = jnp.maximum(t2 - free_used, t3)
    score = jnp.where(
        fully_free == node.gcnt,
        case3,
        jnp.where(~ok, 0, jnp.where(free_used > 0, case2, case1)),
    )
    score = jnp.where((types.milli * types.num) > 0, score, 0)
    return score.astype(jnp.int32), jnp.full_like(score, -1)


def _type_affinity_class(types: _TypeCols):
    """pod_affinity_class per type column (ref: pod.go:111-123)."""
    share = (types.num == 1) & (types.milli < MILLI)
    cls = jnp.where(share, 0, types.num)
    return jnp.where(types.num == 0, -1, cls)


def _clustering_column(node: _NodeScalars, types: _TypeCols, tp, aux):
    """GpuClustering quartile scoring (ref: gpu_clustering_score.go:32-56;
    mirrors policies/clustering.py)."""
    q = MAX_NODE_SCORE // 4  # 25
    counts = node.aff9.T  # (1,9)
    n_classes = (counts > 0).astype(jnp.int32).sum()
    cls = _type_affinity_class(types)  # (K,1)
    kdim = cls.shape[0]
    lane9 = _iota((kdim, 9), 1)
    has_cls = jnp.sum(
        jnp.where(lane9 == jnp.maximum(cls, 0), counts, 0),
        axis=1, keepdims=True,
    ) > 0
    gtot = node.g8.sum()
    pack = q * (MAX_SPEC_GPU - gtot) // MAX_SPEC_GPU
    base = jnp.where(
        has_cls,
        jnp.where(n_classes == 1, 3 * q, 2 * q),
        jnp.where(n_classes == 0, q, 0),
    )
    score = jnp.where(cls < 0, 0, base + pack).astype(jnp.int32)
    return score, jnp.full_like(score, -1)


_PWR_NEG = np.int32(-(2**31) + 1)  # policies/pwr.py _NEG_INF


def _pwr_column(node: _NodeScalars, types: _TypeCols, tp, aux: _EnergyRows):
    """PWR watts-delta scoring (ref: pwr_score.go:150-218; mirrors
    policies/pwr.py's two-channel decomposition: the CPU package count and
    devices flipping idle->working)."""
    ks = types.ks
    kdim = types.cpu.shape[0]

    def look(row, idx):
        lane = _iota((1, row.shape[1]), 1)
        return jnp.sum(jnp.where(lane == idx, row, 0.0))

    gidle = jnp.where(node.gtyp >= 0, look(aux.gidle, jnp.maximum(node.gtyp, 0)), 0.0)
    gfull = jnp.where(node.gtyp >= 0, look(aux.gfull, jnp.maximum(node.gtyp, 0)), 0.0)
    busy_delta = gfull - gidle
    cidle = look(aux.cidle, node.ctyp)
    cfull = look(aux.cfull, node.ctyp)
    ncores = look(aux.ncores, node.ctyp)

    def cpu_watts(cpu_left):
        return cpu_package_watts(cpu_left, node.cap, ncores, cidle, cfull)

    was_idle = node.g8.T == MILLI  # (1,8)
    n_idle = was_idle.astype(jnp.float32).sum()
    gpu_old = gidle * n_idle + gfull * (node.gcnt.astype(jnp.float32) - n_idle)
    old = cpu_watts(node.cpu) + gpu_old
    cpu_new = cpu_watts(node.cpu - types.cpu)  # (K,1)

    score = jnp.zeros((kdim, 1), jnp.int32)
    sdev = jnp.full((kdim, 1), -1, jnp.int32)
    sub_k = _iota((kdim, 1), 0)
    if ks:
        # share branch: device flips iff fully idle and the pod takes milli
        new_dev = cpu_new + gpu_old + jnp.where(
            was_idle & (types.milli > 0), busy_delta, 0.0
        )  # (K,8)
        fits = node.g8.T >= types.milli
        dev_scores = jnp.where(fits, (old - new_dev).astype(jnp.int32), _PWR_NEG)
        s_val, s_dev = _first_max_dev(dev_scores, _PWR_NEG)
        in_share = sub_k < ks
        score = jnp.where(in_share, s_val, score)
        sdev = jnp.where(in_share, s_dev, sdev)
    if kdim - ks:
        # whole/CPU branch: Sub's taken devices flip iff previously idle
        take, _ = _packed_take(node, types.milli, types.num)  # (K,8)
        flips = (take & was_idle).astype(jnp.float32).sum(axis=1, keepdims=True)
        w_val = (old - (cpu_new + gpu_old + flips * busy_delta)).astype(jnp.int32)
        in_whole = sub_k >= ks
        score = jnp.where(in_whole, w_val, score)
        sdev = jnp.where(in_whole, -1, sdev)
    return score, sdev


def _make_dotprod_column(dim_ext: str, norm: str):
    """DotProduct column for a (dimExtMethod, normMethod) config (ref:
    dot_product_score.go + the virtual expansion resource.go:246-381;
    mirrors policies/dotprod.py's fixed-slot masked kernels)."""

    def safe_div(v, n):
        return jnp.where(n > 0, v / jnp.where(n > 0, n, 1.0), 0.0)

    def column(node: _NodeScalars, types: _TypeCols, tp, aux):
        kdim = types.cpu.shape[0]
        gT = node.g8.T.astype(jnp.float32)  # (1,8)
        gtot = node.g8.sum().astype(jnp.float32)
        idle_cnt = (node.g8 == MILLI).astype(jnp.int32).sum()
        cpu_f = node.cpu.astype(jnp.float32)
        treq = (types.milli * types.num).astype(jnp.float32)  # (K,1)
        tcpu = types.cpu.astype(jnp.float32)
        cap_f = node.cap.astype(jnp.float32)
        gcap = (node.gcnt * MILLI).astype(jnp.float32)
        neg = jnp.float32(-(2.0**30))

        if norm == "node":
            div_cpu, div_gpu = cap_f, gcap
        elif norm == "pod":
            div_cpu, div_gpu = tcpu, treq
        else:  # max
            div_cpu = jnp.float32(MAX_SPEC_CPU)
            div_gpu = jnp.float32(MAX_SPEC_GPU)

        if dim_ext == "merge":
            dot = (
                safe_div(cpu_f, div_cpu) * safe_div(tcpu, div_cpu)
                + safe_div(gtot, div_gpu) * safe_div(treq, div_gpu)
            ) / 2.0
            if norm == "pod":
                dot = jnp.tanh(dot / 10.0)
            s = jnp.where(node.cpu >= types.cpu, 1.0 - dot, neg)  # (K,1)
            best = s
            dev = jnp.full((kdim, 1), -1, jnp.int32)
        else:
            slot_real = _iota((1, 8), 1) < node.gcnt
            pool_gpu = (idle_cnt * MILLI).astype(jnp.float32)
            first_free = jnp.min(
                jnp.where((node.g8.T == MILLI), _iota((1, 8), 1), 8)
            )
            first_free = jnp.where(idle_cnt > 0, first_free, -1)
            if dim_ext in ("share", "divide"):
                # 8 per-device slots (partially-used fitting devices, share
                # pods only) + the idle pool (resource.go:315-365)
                dev_active = (
                    (treq < MILLI) & slot_real & (gT < MILLI) & (gT >= treq)
                )  # (K,8)
                pool_active = treq <= (idle_cnt * MILLI).astype(jnp.float32)
                slot_gpu9 = jnp.concatenate(
                    [jnp.broadcast_to(gT, (kdim, 8)),
                     jnp.broadcast_to(pool_gpu, (kdim, 1))], axis=1
                )  # (K,9)
                active9 = jnp.concatenate([dev_active, pool_active], axis=1)
                if dim_ext == "divide":
                    slot_cpu9 = safe_div(cpu_f * slot_gpu9, gtot)
                else:
                    slot_cpu9 = jnp.broadcast_to(cpu_f, (kdim, 9))
                dots = (
                    safe_div(slot_cpu9, div_cpu) * safe_div(tcpu, div_cpu)
                    + safe_div(slot_gpu9, div_gpu) * safe_div(treq, div_gpu)
                ) / 2.0
            else:  # extend: formalized groups (resource.go:217-287)
                dev_group = slot_real & (gT > 0) & (gT < MILLI)  # (1,8)
                pool_group = idle_cnt > 0
                group9 = jnp.concatenate(
                    [jnp.broadcast_to(dev_group, (kdim, 8)),
                     jnp.broadcast_to(pool_group, (kdim, 1))], axis=1
                )
                left9 = jnp.concatenate(
                    [jnp.broadcast_to(gT, (kdim, 8)),
                     jnp.broadcast_to(pool_gpu, (kdim, 1))], axis=1
                )
                n_groups = dev_group.astype(jnp.float32).sum() + jnp.where(
                    pool_group, 1.0, 0.0
                )
                active9 = group9 & (left9 >= treq)
                slot_gpu9 = left9
                cpu_term = safe_div(cpu_f, div_cpu) * safe_div(tcpu, div_cpu)
                gpu_terms = safe_div(left9, div_gpu) * safe_div(treq, div_gpu)
                dots = (cpu_term + gpu_terms) / jnp.maximum(1.0 + n_groups, 1.0)
            if norm == "pod":
                dots = jnp.tanh(dots / 10.0)
            s9 = jnp.where((node.cpu >= types.cpu) & active9, 1.0 - dots, neg)
            best = jnp.max(s9, axis=1, keepdims=True)  # (K,1)
            lane9 = _iota((kdim, 9), 1)
            slot = jnp.min(
                jnp.where(s9 == best, lane9, 9), axis=1, keepdims=True
            )
            dev = jnp.where(slot < 8, slot, first_free).astype(jnp.int32)
            dev = jnp.where(best == neg, -1, dev)
        raw = jnp.where(
            best == neg, 0, (MAX_NODE_SCORE * best).astype(jnp.int32)
        )
        return raw, dev

    return column


def _resolve_column(fn):
    """Column kernel for a policy fn, or None if this policy/config has no
    Pallas form (the driver then falls back to the table engine)."""
    name = fn.policy_name
    if name == "FGDScore":
        return _fgd_column
    if name == "BestFitScore":
        return _bestfit_column
    if name == "GpuPackingScore":
        return _packing_column
    if name == "GpuClusteringScore":
        return _clustering_column
    if name == "PWRScore":
        return _pwr_column
    if name == "DotProductScore":
        dim_ext = getattr(fn, "dim_ext", None)
        norm = getattr(fn, "norm", None)
        # a wrapped policy object (e.g. jit_policy) may not carry the
        # config attrs — answer the predicate with "no column" rather
        # than crash
        if dim_ext is None or norm is None:
            return None
        return _make_dotprod_column(dim_ext, norm)
    return None


# policy names with a Pallas column implementation (config resolved by
# _resolve_column; kept as a set for quick membership tests/docs)
PALLAS_COLUMNS = {
    "FGDScore", "BestFitScore", "GpuPackingScore", "GpuClusteringScore",
    "PWRScore", "DotProductScore",
}

_SUPPORTED_GPU_SEL = {"best", "worst"} | SELF_SELECT_POLICIES


def supports(policies, gpu_sel: str) -> bool:
    """Whether make_pallas_replay can run this configuration. Per-event
    reporting is no longer gated here: engines replay metric-free and the
    shared post-pass (tpusim.sim.metrics) reconstructs the report series
    from the telemetry this kernel already emits. Weighted multi-policy
    configs (the reference's PWR+FGD mixes,
    generate_run_scripts.py:39-41) run fused since round 5 — every
    enabled policy needs a column kernel."""
    if not policies:
        return False
    if any(_resolve_column(fn) is None for fn, _ in policies):
        return False
    if gpu_sel not in _SUPPORTED_GPU_SEL:
        return False
    # a self-select gpuSelMethod must name an enabled policy (otherwise
    # there is no sdev source; the reference would fail plugin lookup too)
    if gpu_sel in SELF_SELECT_POLICIES and gpu_sel not in {
        fn.policy_name for fn, _ in policies
    }:
        return False
    return True


def _feas_column(node: _NodeScalars, types: _TypeCols):
    """Filter-phase feasibility for one node x all types (mirrors
    step.filter_nodes minus the per-event pinned-node mask)."""
    gT = node.g8.T  # (1,8)
    fit = (node.cpu >= types.cpu) & (node.mem >= types.mem)  # (K,1)
    units = jnp.where(types.milli > 0, gT // jnp.maximum(types.milli, 1), 0)
    can_alloc = units.sum(axis=1, keepdims=True) >= types.num
    acc = (types.mask == 0) | ((types.mask & _node_bit(node.gtyp)) != 0)
    gpu_ok = (node.gcnt > 0) & acc & can_alloc
    needs_gpu = (types.milli * types.num) > 0
    return (fit & (~needs_gpu | gpu_ok)).astype(jnp.int32)


def _pack_events(specs: PodSpec, type_id, ev_kind, ev_pod):
    """[_EV_FIELDS, E] i32 per-event rows: every pod scalar the kernel
    needs, pre-gathered host/XLA-side so the kernel only does masked lane
    extraction (Mosaic cannot dynamically index the pod axis)."""
    from tpusim.policies.clustering import pod_affinity_class

    pod = jax.tree.map(lambda a: a[ev_pod], specs)
    return jnp.stack(
        [
            ev_kind.astype(jnp.int32),
            ev_pod.astype(jnp.int32),
            type_id[ev_pod].astype(jnp.int32),
            pod.cpu,
            pod.mem,
            pod.gpu_milli,
            pod.gpu_num,
            pod.gpu_mask,
            pod.pinned,
            pod_affinity_class(pod),
            pod.is_gpu_share().astype(jnp.int32),
            pod.total_gpu_milli(),
        ]
    )


_CH = 128  # lane-chunk width: the node/event axes are laid out [*, C, 128]


def _make_kernel(columns, ks, gpu_sel):
    """The fused replay kernel for a static configuration. `columns` is a
    tuple of (column_fn, normalize, weight, is_selector) — one per enabled
    Score plugin; multi-policy rows accumulate Σ wᵢ · normalizeᵢ(colᵢ) in
    i32 exactly like the table engine's do_create (and the vendored
    RunScorePlugins weighted sum). The score table stacks per-policy
    blocks as [n_pol·K, C, 128]; the sdev table carries only the
    gpuSelMethod selector's Reserve picks. See module docstring for the
    masked-op calculus; every step mirrors a line of sim/step.py or
    table_engine.py.

    Layout (round-4 v2): the node axis is chunked as (C, 128) and the
    tables as [K, C, 128], because Mosaic supports dynamic slicing on
    leading and sublane dims (probed) but not the lane dim. Row gathers
    become free leading-dim slices, and column/state updates touch one
    (.., 1, 128) chunk instead of rewriting whole [K, N] tables — ~12x
    less masked-write traffic per event than the v1 flat layout."""
    self_select = gpu_sel in SELF_SELECT_POLICIES
    n_pol = len(columns)

    def kernel(
        ev_ref,  # [F, Ec, 128] i32
        tcpu_ref, tmem_ref, tmilli_ref, tnum_ref, tmask_ref,  # [K,1] i32
        tpcpu_ref, tpmilli_ref, tpnumf_ref, tpmask_ref, tpfreq_ref,  # [1,T]
        gcnt_ref, gtyp_ref, rank_ref,  # (C,128) i32 (read-only)
        cpucap_ref, ctyp_ref,  # (C,128) i32 (read-only; PWR dims)
        gidle_ref, gfull_ref, cidle_ref, cfull_ref, ncores_ref,  # (1,M) f32
        cpu0_ref, mem0_ref, gpu0_ref, aff0_ref,  # initial state (chunked)
        score_ref, sdev_ref, feas_ref,  # [K, C, 128] i32
        cpu_ref, mem_ref,  # (C,128) i32
        gpul_ref,  # [8, C, 128] i32
        aff_ref,  # [9, C, 128] i32
        placed_ref, maskb_ref, failed_ref,  # [1,P] i32
        evnode_ref, evdevb_ref,  # [Ec, 128] i32
        dirty,  # SMEM (1,) i32
    ):
        i = pl.program_id(0)
        kdim, nc, _ = feas_ref.shape  # K types; score_ref is [n_pol*K,..]
        n = nc * _CH
        p = placed_ref.shape[1]

        lane_p = _iota((1, p), 1)
        # node id grid over the chunked layout
        nid = _iota((nc, _CH), 0) * _CH + _iota((nc, _CH), 1)
        lane1 = _iota((1, _CH), 1)

        types = _TypeCols(
            tcpu_ref[:, :], tmem_ref[:, :], tmilli_ref[:, :],
            tnum_ref[:, :], tmask_ref[:, :], ks,
        )
        tp = _TpRows(
            tpcpu_ref[:, :], tpmilli_ref[:, :], tpnumf_ref[:, :],
            tpmask_ref[:, :], tpfreq_ref[:, :],
        )
        aux = _EnergyRows(
            gidle_ref[:, :], gfull_ref[:, :], cidle_ref[:, :],
            cfull_ref[:, :], ncores_ref[:, :],
        )

        def chunk_scalar(ref, c, sel):
            """ref (C,128): ref[c, l] via a one-chunk masked reduce."""
            return jnp.sum(jnp.where(sel, ref[pl.ds(c, 1), :], 0))

        def node_scalars(d):
            c, l = d // _CH, d % _CH
            sel = lane1 == l
            # 3D chunk slices reshape to 2D before reducing — Mosaic's
            # reduction lowering rejects the layout a 3D-sliced operand
            # carries (observed on-chip), while the 2D pattern is the one
            # the v1 layout already proved out
            g8c = gpul_ref[:, pl.ds(c, 1), :].reshape(8, _CH)
            a9c = aff_ref[:, pl.ds(c, 1), :].reshape(9, _CH)
            return _NodeScalars(
                cpu=chunk_scalar(cpu_ref, c, sel),
                mem=chunk_scalar(mem_ref, c, sel),
                cap=chunk_scalar(cpucap_ref, c, sel),
                gcnt=chunk_scalar(gcnt_ref, c, sel),
                gtyp=chunk_scalar(gtyp_ref, c, sel),
                ctyp=chunk_scalar(ctyp_ref, c, sel),
                g8=jnp.sum(jnp.where(sel, g8c, 0), axis=1, keepdims=True),
                aff9=jnp.sum(jnp.where(sel, a9c, 0), axis=1, keepdims=True),
            )

        def refresh_column(d):
            node = node_scalars(d)
            col_scores = []
            col_sdev = jnp.full((kdim, 1), -1, jnp.int32)
            for column_fn, _, _, is_sel in columns:
                cs, cd = column_fn(node, types, tp, aux)
                col_scores.append(cs)
                if is_sel:
                    col_sdev = cd
            col_score = (
                col_scores[0]
                if n_pol == 1
                else jnp.concatenate(col_scores, axis=0)
            )  # (n_pol*K, 1)
            col_feas = _feas_column(node, types)
            c, l = d // _CH, d % _CH
            hit = (lane1 == l).reshape(1, 1, _CH)
            for ref, col in (
                (score_ref, col_score),
                (sdev_ref, col_sdev),
                (feas_ref, col_feas),
            ):
                blk = ref[:, pl.ds(c, 1), :]  # (rows,1,128)
                ref[:, pl.ds(c, 1), :] = jnp.where(
                    hit, col.reshape(col.shape[0], 1, 1), blk
                )

        @pl.when(i == 0)
        def _():
            cpu_ref[:, :] = cpu0_ref[:, :]
            mem_ref[:, :] = mem0_ref[:, :]
            gpul_ref[:, :, :] = gpu0_ref[:, :, :]
            aff_ref[:, :, :] = aff0_ref[:, :, :]
            placed_ref[:, :] = jnp.full(placed_ref.shape, -1, jnp.int32)
            maskb_ref[:, :] = jnp.zeros(placed_ref.shape, jnp.int32)
            failed_ref[:, :] = jnp.zeros(placed_ref.shape, jnp.int32)
            evnode_ref[:, :] = jnp.full(evnode_ref.shape, -1, jnp.int32)
            evdevb_ref[:, :] = jnp.zeros(evnode_ref.shape, jnp.int32)
            dirty[0] = 0

            # build the score/sdev/feas tables column by column from the
            # initial state — the table engine's init_tables, but through
            # the SAME column code path the per-event refresh uses
            def body(d, _):
                refresh_column(d)
                return 0

            jax.lax.fori_loop(0, n, body, 0)

        # refresh the one column whose node changed last event
        # (table_engine.py's per-event column refresh; at i == 0 the tables
        # were just built, so the refresh is subsumed by the init loop)
        @pl.when(i != 0)
        def _():
            refresh_column(dirty[0])

        # ---- this event's packed scalars (one-chunk masked extraction)
        ec, el = i // _CH, i % _CH
        evblk = ev_ref[:, pl.ds(ec, 1), :]  # (F,1,128)
        sel_ev = (lane1 == el).reshape(1, 1, _CH)

        def f(j):
            return jnp.sum(jnp.where(sel_ev, evblk[j : j + 1, :, :], 0))

        kind = f(0)
        idx = f(1)
        tid = f(2)
        pcpu, pmem, pmilli, pnum = f(3), f(4), f(5), f(6)
        ppin, pcls, pshare, ptgm = f(8), f(9), f(10), f(11)
        sel_p = lane_p == idx
        sel_e1 = lane1 == el
        sub8c = _iota((8, 1), 0)

        def state_update(c, delta_fns):
            """Apply masked one-chunk updates: [(ref, hit_mask, delta)] —
            (C,128) refs take a (1,128) mask; [R,C,128] refs take an
            (R,1,128)-broadcastable mask; delta is scalar (or (R,1,1))."""
            for ref, hit, delta in delta_fns:
                if ref.ndim == 2:
                    blk = ref[pl.ds(c, 1), :]
                    ref[pl.ds(c, 1), :] = jnp.where(hit, blk + delta, blk)
                else:
                    blk = ref[:, pl.ds(c, 1), :]
                    ref[:, pl.ds(c, 1), :] = jnp.where(hit, blk + delta, blk)

        # ---- creation: Filter -> Score row -> selectHost -> Reserve -> Bind
        @pl.when(kind == 0)
        def _():
            feas_row = feas_ref[pl.ds(tid, 1), :, :].reshape(nc, _CH) != 0
            # nodeSelector pinning is a per-event mask, not a table column
            feasible = feas_row & ((ppin < 0) | (nid == ppin))
            total = jnp.zeros((nc, _CH), jnp.int32)
            for pi, (_, normalize, weight, _) in enumerate(columns):
                raw = score_ref[pl.ds(tid + pi * kdim, 1), :, :].reshape(
                    nc, _CH
                )
                if normalize in ("minmax", "pwr"):
                    lo = jnp.min(jnp.where(feasible, raw, _INT_MAX))
                    hi = jnp.max(jnp.where(feasible, raw, -_INT_MAX))
                    rngv = hi - lo
                    degen = 0 if normalize == "minmax" else MAX_NODE_SCORE
                    scaled = jnp.where(
                        rngv == 0,
                        degen,
                        (raw - lo) * MAX_NODE_SCORE // jnp.maximum(rngv, 1),
                    )
                    raw = jnp.where(feasible, scaled, raw)
                total = total + weight * raw
            # selectHost: max weighted score, smallest tie-break rank wins
            best = jnp.max(jnp.where(feasible, total, -_INT_MAX))
            wkey = jnp.where(
                feasible & (total == best), -rank_ref[:, :], -_INT_MAX
            )
            m = jnp.max(wkey)
            ok = m != -_INT_MAX
            node = jnp.where(ok, jnp.min(jnp.where(wkey == m, nid, n)), 0)
            c, l = node // _CH, node % _CH
            sel_l = lane1 == l

            # Reserve: device pick on the winner (step.choose_devices)
            g8w = jnp.sum(
                jnp.where(sel_l, gpul_ref[:, pl.ds(c, 1), :].reshape(8, _CH), 0),
                axis=1, keepdims=True,
            )  # (8,1)
            gT = g8w.T  # (1,8)
            lane8 = _iota((1, 8), 1)
            fits = gT >= pmilli
            any_fit = jnp.sum(fits.astype(jnp.int32)) > 0
            # allocate_share_best: min milli-left among fitting, first index
            bkey = jnp.where(fits, gT, _INT_MAX)
            bdev = jnp.min(jnp.where(bkey == jnp.min(bkey), lane8, 8))
            bdev = jnp.where(any_fit, bdev, -1)
            if gpu_sel == "worst":
                wkey8 = jnp.where(fits, gT, -_INT_MAX)
                wdev = jnp.min(jnp.where(wkey8 == jnp.max(wkey8), lane8, 8))
                share_dev = jnp.where(any_fit, wdev, -1)
            elif self_select:
                sdev = jnp.sum(
                    jnp.where(
                        sel_l,
                        sdev_ref[pl.ds(tid, 1), pl.ds(c, 1), :].reshape(1, _CH),
                        0,
                    )
                )
                share_dev = jnp.where(sdev >= 0, sdev, bdev)
            else:  # "best"
                share_dev = bdev
            share_bits = jnp.where(
                share_dev >= 0,
                jax.lax.shift_left(1, jnp.maximum(share_dev, 0)),
                0,
            )
            # allocate_two_pointer for whole/multi-GPU pods
            units = jnp.where(pmilli > 0, gT // jnp.maximum(pmilli, 1), 0)
            prev = _cumsum8_lanes(units) - units
            take_units = jnp.clip(pnum - prev, 0, units)
            whole_bits = jnp.sum(
                jnp.where(take_units > 0, jax.lax.shift_left(1, lane8), 0)
            )
            bits = jnp.where(
                ptgm > 0, jnp.where(pshare != 0, share_bits, whole_bits), 0
            )
            bits = jnp.where(ok, bits, 0)

            # Bind: masked one-chunk scatter-commit (step.select_and_bind)
            okl = sel_l & ok
            mask8 = (jax.lax.shift_right_logical(bits, sub8c) & 1) != 0
            aff_sub = _iota((9, 1), 0) == jnp.maximum(pcls, 0)
            state_update(
                c,
                [
                    (cpu_ref, okl, -pcpu),
                    (mem_ref, okl, -pmem),
                    (
                        gpul_ref,
                        okl.reshape(1, 1, _CH) & mask8.reshape(8, 1, 1),
                        -pmilli,
                    ),
                    (
                        aff_ref,
                        okl.reshape(1, 1, _CH)
                        & aff_sub.reshape(9, 1, 1)
                        & (pcls >= 0),
                        1,
                    ),
                ],
            )

            placed_ref[:, :] = jnp.where(
                sel_p, jnp.where(ok, node, -1), placed_ref[:, :]
            )
            maskb_ref[:, :] = jnp.where(sel_p, bits, maskb_ref[:, :])
            failed_ref[:, :] = jnp.where(
                sel_p, jnp.where(ok, 0, 1), failed_ref[:, :]
            )
            eblk = evnode_ref[pl.ds(ec, 1), :]
            evnode_ref[pl.ds(ec, 1), :] = jnp.where(
                sel_e1, jnp.where(ok, node, -1), eblk
            )
            dblk = evdevb_ref[pl.ds(ec, 1), :]
            evdevb_ref[pl.ds(ec, 1), :] = jnp.where(sel_e1, bits, dblk)
            dirty[0] = jnp.where(ok, node, 0)

        # ---- deletion: return resources to the recorded devices
        # (step.unschedule; simulator.go:334-357)
        @pl.when(kind == 1)
        def _():
            node = jnp.sum(jnp.where(sel_p, placed_ref[:, :], 0))
            bits = jnp.sum(jnp.where(sel_p, maskb_ref[:, :], 0))
            was = node >= 0
            nodee = jnp.maximum(node, 0)
            c, l = nodee // _CH, nodee % _CH
            sel_l = (lane1 == l) & was
            mask8 = (jax.lax.shift_right_logical(bits, sub8c) & 1) != 0
            aff_sub = _iota((9, 1), 0) == jnp.maximum(pcls, 0)
            state_update(
                c,
                [
                    (cpu_ref, sel_l, pcpu),
                    (mem_ref, sel_l, pmem),
                    (
                        gpul_ref,
                        sel_l.reshape(1, 1, _CH) & mask8.reshape(8, 1, 1),
                        pmilli,
                    ),
                    (
                        aff_ref,
                        sel_l.reshape(1, 1, _CH)
                        & aff_sub.reshape(9, 1, 1)
                        & (pcls >= 0),
                        -1,
                    ),
                ],
            )
            placed_ref[:, :] = jnp.where(sel_p, -1, placed_ref[:, :])
            maskb_ref[:, :] = jnp.where(sel_p, 0, maskb_ref[:, :])
            eblk = evnode_ref[pl.ds(ec, 1), :]
            evnode_ref[pl.ds(ec, 1), :] = jnp.where(sel_e1, node, eblk)
            dblk = evdevb_ref[pl.ds(ec, 1), :]
            evdevb_ref[pl.ds(ec, 1), :] = jnp.where(sel_e1, bits, dblk)
            dirty[0] = nodee

        # kind == 2 (EV_SKIP / padding): dirty, outputs unchanged

    return kernel


_PALLAS_REPLAY_CACHE = {}


def num_normalized(policies) -> int:
    """How many enabled policies carry a minmax/pwr NormalizeScore pass —
    the `num_norm` the HBM-residency footprint math sizes its
    brmin/brmax summaries with."""
    return sum(
        1 for fn, _ in policies if fn.normalize in ("minmax", "pwr")
    )


def make_pallas_replay(
    policies, gpu_sel: str = "best", interpret: bool = False,
    residency: str = "vmem",
):
    """Build the fused single-kernel replayer. Same call signature as the
    table engine's replay (state, pods, types, ev_kind, ev_pod, tp, key,
    tiebreak_rank); raises for configurations supports() rejects. `key` is
    accepted but unused — every supported configuration is deterministic
    (reject_randomized guarantees it).

    residency='vmem' is the original layout: every table VMEM-resident
    across grid steps (N ≤ 4096 at K = 151). residency='hbm' is the
    Round-19 layout (ENGINES.md): the [K, N] score/sdev/feas tables and
    the mutable node state live in HBM (`pl.ANY`) and only
    the event's active working set crosses into VMEM by per-event
    double-buffered async DMA; its replay returns
    `(ReplayResult, dma_stats i32[3])` where dma_stats counts the
    kernel's (semaphore waits, DMA starts, extrema-drift summary
    rebuilds) — exact in-kernel counters the driver surfaces in the
    obs run record."""
    if residency not in ("vmem", "hbm"):
        raise ValueError(
            f"residency must be 'vmem' or 'hbm' (got {residency!r})"
        )
    reject_randomized(policies, gpu_sel)
    if not supports(policies, gpu_sel):
        raise ValueError(
            "pallas engine needs a registered column kernel for EVERY "
            "enabled policy and gpu_sel in {best, worst, an enabled "
            "self-select policy}; got "
            f"{[f.policy_name for f, _ in policies]} / gpu_sel={gpu_sel}"
        )
    cache_key = (
        tuple((fn, w) for fn, w in policies), gpu_sel, interpret, residency
    )
    if cache_key in _PALLAS_REPLAY_CACHE:
        return _PALLAS_REPLAY_CACHE[cache_key]
    if residency == "hbm":
        replay = _make_hbm_replay(policies, gpu_sel, interpret)
        _PALLAS_REPLAY_CACHE[cache_key] = replay
        return replay

    # (column_fn, normalize, weight, is_selector) per enabled plugin; the
    # selector is the policy the gpuSelMethod delegates Reserve picks to
    # (the allocateGpuIdFunc registry, plugin/open_gpu_share.go:39)
    columns = tuple(
        (
            _resolve_column(fn),
            fn.normalize,
            int(w),
            gpu_sel == fn.policy_name and fn.policy_name in SELF_SELECT_POLICIES,
        )
        for fn, w in policies
    )
    n_pol = len(columns)

    @jax.jit
    def replay(
        state: NodeState,
        pods: PodSpec,
        types: PodTypes,
        ev_kind,
        ev_pod,
        tp,
        key,
        tiebreak_rank=None,
    ) -> ReplayResult:
        from tpusim.parallel.sharding import pad_nodes

        n0 = state.num_nodes
        if tiebreak_rank is None:
            tiebreak_rank = jnp.arange(n0, dtype=jnp.int32)
        state_p, rank_p = pad_nodes(state, tiebreak_rank, 128)
        n = state_p.num_nodes

        ks = int(types.share.cpu.shape[0])
        kw = int(types.whole.cpu.shape[0])
        kdim = ks + kw

        def col(field):
            return jnp.concatenate(
                [getattr(types.share, field), getattr(types.whole, field)]
            ).reshape(kdim, 1)

        tcols = [col(f) for f in ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask")]
        t = int(tp.cpu.shape[0])
        tprows = [
            tp.cpu.reshape(1, t),
            tp.gpu_milli.reshape(1, t),
            tp.gpu_num.astype(jnp.float32).reshape(1, t),
            tp.gpu_mask.reshape(1, t),
            tp.freq.reshape(1, t),
        ]
        ev = _pack_events(pods, types.type_id, ev_kind, ev_pod)
        e = int(ev.shape[1])
        p = int(pods.cpu.shape[0])
        nc = n // _CH
        # event axis chunked like the node axis; pad with EV_SKIP rows the
        # grid (over the TRUE e) never reads
        epad = (-e) % _CH
        if epad:
            ev = jnp.concatenate(
                [ev, jnp.zeros((ev.shape[0], epad), jnp.int32)
                 .at[0, :].set(2)],
                axis=1,
            )
        ec = (e + epad) // _CH
        ev3 = ev.reshape(ev.shape[0], ec, _CH)

        kernel = _make_kernel(columns, ks, gpu_sel)
        out_shape = (
            jax.ShapeDtypeStruct((n_pol * kdim, nc, _CH), jnp.int32),  # score
            jax.ShapeDtypeStruct((kdim, nc, _CH), jnp.int32),  # sdev
            jax.ShapeDtypeStruct((kdim, nc, _CH), jnp.int32),  # feas
            jax.ShapeDtypeStruct((nc, _CH), jnp.int32),  # cpu_left
            jax.ShapeDtypeStruct((nc, _CH), jnp.int32),  # mem_left
            jax.ShapeDtypeStruct((8, nc, _CH), jnp.int32),  # gpu_left
            jax.ShapeDtypeStruct((9, nc, _CH), jnp.int32),  # aff_cnt
            jax.ShapeDtypeStruct((1, p), jnp.int32),  # placed
            jax.ShapeDtypeStruct((1, p), jnp.int32),  # device mask bits
            jax.ShapeDtypeStruct((1, p), jnp.int32),  # failed
            jax.ShapeDtypeStruct((ec, _CH), jnp.int32),  # event node
            jax.ShapeDtypeStruct((ec, _CH), jnp.int32),  # event dev bits
        )
        energy_rows = [
            jnp.asarray(GPU_IDLE_W).reshape(1, -1),
            jnp.asarray(GPU_FULL_W).reshape(1, -1),
            jnp.asarray(CPU_IDLE_W).reshape(1, -1),
            jnp.asarray(CPU_FULL_W).reshape(1, -1),
            jnp.asarray(CPU_NCORES).reshape(1, -1),
        ]

        def chunk(a):
            return a.reshape(nc, _CH)

        (
            _score, _sdev, _feas, cpu_l, mem_l, gpul, aff,
            placed, maskb, failed, evnode, evdevb,
        ) = pl.pallas_call(
            kernel,
            grid=(e,),
            out_shape=out_shape,
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 25,
            out_specs=tuple([pl.BlockSpec(memory_space=pltpu.VMEM)] * 12),
            scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(
            ev3,
            *tcols,
            *tprows,
            chunk(state_p.gpu_cnt),
            chunk(state_p.gpu_type),
            chunk(rank_p),
            chunk(state_p.cpu_cap),
            chunk(state_p.cpu_type),
            *energy_rows,
            chunk(state_p.cpu_left),
            chunk(state_p.mem_left),
            state_p.gpu_left.T.reshape(8, nc, _CH),
            state_p.aff_cnt.T.reshape(9, nc, _CH),
        )

        bit8 = jnp.arange(MAX_GPUS_PER_NODE, dtype=jnp.int32)
        new_state = state._replace(
            cpu_left=cpu_l.reshape(n)[:n0],
            mem_left=mem_l.reshape(n)[:n0],
            gpu_left=gpul.reshape(8, n)[:, :n0].T,
            aff_cnt=aff.reshape(9, n)[:, :n0].T,
        )
        masks = ((maskb[0, :, None] >> bit8) & 1) != 0  # [P,8] bool
        evnode_f = evnode.reshape(ec * _CH)[:e]
        evdevb_f = evdevb.reshape(ec * _CH)[:e]
        devs = ((evdevb_f[:, None] >> bit8) & 1) != 0  # [E,8] bool
        return ReplayResult(
            new_state, placed[0], masks, failed[0] != 0, None, evnode_f, devs
        )

    _PALLAS_REPLAY_CACHE[cache_key] = replay
    return replay


# ---------------------------------------------------------------------------
# HBM residency (ENGINES.md Round 19): the [K, N] score/sdev/feas tables and
# the mutable node state live in HBM (`pl.BlockSpec(memory_space=pl.ANY)`);
# only the event's ACTIVE working set crosses into VMEM, by per-event async
# DMA (`pltpu.make_async_copy` + DMA
# semaphores — the SNIPPETS.md [2] primitive):
#
#   row slice    the event type's score rows + feas row, double-buffered:
#                event e+1's slice (its type comes from the scalar-
#                prefetched event stream) starts right after event e's
#                dirty-column writeback completes and is waited at the top
#                of body e+1 — DMA overlaps the grid turn-around + the
#                next event's refresh.
#   column chunk the dirty node's (.., 1, 128) table chunks, prefetched the
#                same way (the dirty node is known at the END of the
#                previous body — it IS that body's winner/freed node), and
#                written BACK by a second async copy after the refresh.
#   state chunk  the touched chunk of cpu/mem/gpu/aff, read-modify-written
#                around the Bind; the retained scratch copy doubles as the
#                next event's refresh input (dirty chunk == bound chunk).
#
# selectHost no longer touches the full row: it reduces the VMEM-RESIDENT
# blocked summaries bt/br/bn ([N/B, K]: per 128-node block the max weighted
# total, min tie-break rank among the maxima, and that winner's node id)
# maintained exactly like the blocked table engine's (ENGINES.md Round 6
# math): the dirty block's summary row refreshes each event from the column
# chunk under STORED per-type extrema (slo/shi), brmin/brmax track the
# per-block feasible raw extrema, and an extrema-drift check rebuilds one
# type's summary column (inside pl.when, from the row slice already in
# VMEM) before the select consumes it. Bit-identity with the flat select is
# inherited from the blocked engine's proof; the oracle tests pin it.
#
# Resident VMEM becomes O(K·B + row scratch) instead of O(K·N)
# (vmem_resident_bytes_hbm), moving the ceiling from N <= 4096 to
# HBM-bounded (>= 256k at K = 151 — hbm_ceiling_nodes).
# ---------------------------------------------------------------------------


def _make_hbm_kernel(columns, ks, gpu_sel):
    """The HBM-residency replay kernel for a static configuration. Same
    per-event math as _make_kernel (every line mirrors the blocked table
    engine or the VMEM-resident kernel); what changes is WHERE the tables
    live and the DMA choreography above. Control flow is uniform across
    event kinds — every body runs the same DMA skeleton with masked
    no-op updates — so the in-kernel DMA counters (dctr: waits, starts,
    drift rebuilds) are exact and static per event."""
    self_select = gpu_sel in SELF_SELECT_POLICIES
    n_pol = len(columns)
    norm_idx = [
        i for i, (_, nrm, _, _) in enumerate(columns)
        if nrm in ("minmax", "pwr")
    ]
    n_norm = len(norm_idx)
    nn = max(n_norm, 1)

    def kernel(
        kref, tref,  # scalar-prefetched event kind / type-id streams
        ev_ref,  # [F, Ec, 128] i32 packed event rows
        tcpu_ref, tmem_ref, tmilli_ref, tnum_ref, tmask_ref,  # [K,1] i32
        tpcpu_ref, tpmilli_ref, tpnumf_ref, tpmask_ref, tpfreq_ref,  # [1,T]
        gidle_ref, gfull_ref, cidle_ref, cfull_ref, ncores_ref,  # [1,M] f32
        rank_ref,  # (C,128) i32 VMEM (the drift rebuild reduces it whole)
        gcnt_any, gtyp_any, cap_any, ctyp_any,  # (C,128) i32 HBM read-only
        cpu0_any, mem0_any, gpu0_any, aff0_any,  # initial state, HBM
        # ---- outputs
        score_any, sdev_any, feas_any,  # [*, C, 128] i32 HBM tables
        cpu_any, mem_any, gpu_any, aff_any,  # mutable state, HBM
        bt_ref, br_ref, bn_ref,  # (C, K) i32 VMEM blocked summaries
        brmin_ref, brmax_ref,  # (C, nn*K) i32 block feasible raw extrema
        slo_ref, shi_ref,  # (1, nn*K) i32 stored per-type extrema
        placed_ref, maskb_ref, failed_ref,  # [1,P] i32
        evnode_ref, evdevb_ref,  # [Ec, 128] i32
        dma_ref,  # (1,128) i32: [waits, starts, rebuilds] at lanes 0..2
        # ---- scratch
        rowS,  # (2*n_pol, C, 128) double-buffered event-type score rows
        rowF,  # (2, C, 128) double-buffered event-type feas row
        colS,  # (n_pol*K, 2, 128) double-buffered dirty column chunk
        colD,  # (K, 2, 128)
        colF,  # (K, 2, 128)
        stC, stM,  # (1,128) retained state chunk (cpu / mem)
        stG,  # (8,1,128)
        stA,  # (9,1,128)
        roB,  # (4,128) read-only chunk rows: gcnt/gtyp/cap/ctyp
        sdW,  # (1,1,128) the winner's sdev chunk (self-select Reserve)
        dirty, dctr,  # SMEM (1,) / (4,) i32
        row_sem, colin_sem, colwb_sem,  # DMA sems
        stin_sem, stwb_sem, ro_sem, sd_sem, init_sem,
    ):
        i = pl.program_id(0)
        e = pl.num_programs(0)
        kdim, nc, _ = feas_any.shape
        n = nc * _CH
        p = placed_ref.shape[1]
        slot = jax.lax.rem(i, 2)
        nslot = jax.lax.rem(i + 1, 2)

        lane_p = _iota((1, p), 1)
        nid = _iota((nc, _CH), 0) * _CH + _iota((nc, _CH), 1)
        lane1 = _iota((1, _CH), 1)
        laneK = _iota((nc, kdim), 1)
        lane_nn = _iota((nc, nn * kdim), 1)
        lane_s = _iota((1, nn * kdim), 1)
        blki = _iota((nc, 1), 0)

        types = _TypeCols(
            tcpu_ref[:, :], tmem_ref[:, :], tmilli_ref[:, :],
            tnum_ref[:, :], tmask_ref[:, :], ks,
        )
        tp = _TpRows(
            tpcpu_ref[:, :], tpmilli_ref[:, :], tpnumf_ref[:, :],
            tpmask_ref[:, :], tpfreq_ref[:, :],
        )
        aux = _EnergyRows(
            gidle_ref[:, :], gfull_ref[:, :], cidle_ref[:, :],
            cfull_ref[:, :], ncores_ref[:, :],
        )

        # ---- DMA descriptors (constructed identically at start and wait
        # sites — the make_async_copy contract) + exact counters
        def start(cps):
            for cp in cps:
                dctr[1] = dctr[1] + 1
                cp.start()

        def wait(cps):
            for cp in cps:
                dctr[0] = dctr[0] + 1
                cp.wait()

        def row_dmas(s, t):
            cps = [
                pltpu.make_async_copy(
                    score_any.at[pl.ds(t + pi * kdim, 1), :, :],
                    rowS.at[pl.ds(s * n_pol + pi, 1), :, :],
                    row_sem.at[pi],
                )
                for pi in range(n_pol)
            ]
            cps.append(pltpu.make_async_copy(
                feas_any.at[pl.ds(t, 1), :, :],
                rowF.at[pl.ds(s, 1), :, :],
                row_sem.at[n_pol],
            ))
            return cps

        def colin_dmas(s, c):
            return [
                pltpu.make_async_copy(
                    score_any.at[:, pl.ds(c, 1), :],
                    colS.at[:, pl.ds(s, 1), :], colin_sem.at[0],
                ),
                pltpu.make_async_copy(
                    sdev_any.at[:, pl.ds(c, 1), :],
                    colD.at[:, pl.ds(s, 1), :], colin_sem.at[1],
                ),
                pltpu.make_async_copy(
                    feas_any.at[:, pl.ds(c, 1), :],
                    colF.at[:, pl.ds(s, 1), :], colin_sem.at[2],
                ),
            ]

        def colwb_dmas(s, c):
            return [
                pltpu.make_async_copy(
                    colS.at[:, pl.ds(s, 1), :],
                    score_any.at[:, pl.ds(c, 1), :], colwb_sem.at[0],
                ),
                pltpu.make_async_copy(
                    colD.at[:, pl.ds(s, 1), :],
                    sdev_any.at[:, pl.ds(c, 1), :], colwb_sem.at[1],
                ),
                pltpu.make_async_copy(
                    colF.at[:, pl.ds(s, 1), :],
                    feas_any.at[:, pl.ds(c, 1), :], colwb_sem.at[2],
                ),
            ]

        def state_dmas(c, srcs, inward, sems):
            cpu_r, mem_r, gpu_r, aff_r = srcs
            pairs = [
                (cpu_r.at[pl.ds(c, 1), :], stC),
                (mem_r.at[pl.ds(c, 1), :], stM),
                (gpu_r.at[:, pl.ds(c, 1), :], stG),
                (aff_r.at[:, pl.ds(c, 1), :], stA),
            ]
            return [
                pltpu.make_async_copy(
                    a if inward else b, b if inward else a, sems.at[j]
                )
                for j, (a, b) in enumerate(pairs)
            ]

        def ro_dmas(c):
            return [
                pltpu.make_async_copy(
                    r.at[pl.ds(c, 1), :], roB.at[pl.ds(j, 1), :],
                    ro_sem.at[j],
                )
                for j, r in enumerate(
                    (gcnt_any, gtyp_any, cap_any, ctyp_any)
                )
            ]

        def sd_dmas(t, c):
            return [pltpu.make_async_copy(
                sdev_any.at[pl.ds(t, 1), pl.ds(c, 1), :], sdW,
                sd_sem.at[0],
            )]

        # ---- shared compute helpers (mirror _make_kernel / the blocked
        # table engine line by line)
        def node_scalars_chunk(l):
            """_NodeScalars of lane `l` of the retained state chunk."""
            sel = lane1 == l
            g8c = stG[:, :, :].reshape(8, _CH)
            a9c = stA[:, :, :].reshape(9, _CH)

            def ro(j):
                return jnp.sum(jnp.where(sel, roB[pl.ds(j, 1), :], 0))

            return _NodeScalars(
                cpu=jnp.sum(jnp.where(sel, stC[:, :], 0)),
                mem=jnp.sum(jnp.where(sel, stM[:, :], 0)),
                cap=ro(2),
                gcnt=ro(0),
                gtyp=ro(1),
                ctyp=ro(3),
                g8=jnp.sum(jnp.where(sel, g8c, 0), axis=1, keepdims=True),
                aff9=jnp.sum(jnp.where(sel, a9c, 0), axis=1, keepdims=True),
            )

        def column_for(node):
            col_scores = []
            col_sdev = jnp.full((kdim, 1), -1, jnp.int32)
            for column_fn, _, _, is_sel in columns:
                cs, cd = column_fn(node, types, tp, aux)
                col_scores.append(cs)
                if is_sel:
                    col_sdev = cd
            col_score = (
                col_scores[0]
                if n_pol == 1
                else jnp.concatenate(col_scores, axis=0)
            )
            return col_score, col_sdev, _feas_column(node, types)

        def chunk_totals(score3, feas_b):
            """Weighted normalized totals over one (K, 128) chunk under
            the STORED extrema — the blocked engine's _totals with the
            -INT_MAX infeasible sentinel."""
            tot = jnp.zeros(feas_b.shape, jnp.int32)
            slo_k = slo_ref[:, :].reshape(nn, kdim)
            shi_k = shi_ref[:, :].reshape(nn, kdim)
            for pi, (_, nrm, w, _) in enumerate(columns):
                raw = score3[pi]
                if nrm in ("minmax", "pwr"):
                    j = norm_idx.index(pi)
                    lo = slo_k[j].reshape(kdim, 1)
                    hi = shi_k[j].reshape(kdim, 1)
                    rngv = hi - lo
                    degen = 0 if nrm == "minmax" else MAX_NODE_SCORE
                    scaled = jnp.where(
                        rngv == 0, degen,
                        (raw - lo) * MAX_NODE_SCORE // jnp.maximum(rngv, 1),
                    )
                    raw = jnp.where(feas_b, scaled, raw)
                tot = tot + w * raw
            return jnp.where(feas_b, tot, -_INT_MAX)

        def chunk_block_reduce(tot, rank_row, c):
            """block_reduce over one chunk's lane axis: (max total, min
            tie-break rank among the maxima, winner node id) per type."""
            m = jnp.max(tot, axis=1, keepdims=True)  # (K,1)
            wkey = jnp.where(tot == m, -rank_row, -_INT_MAX)
            mw = jnp.max(wkey, axis=1, keepdims=True)
            lane8k = _iota(tot.shape, 1)
            a = jnp.min(
                jnp.where(wkey == mw, lane8k, _CH), axis=1, keepdims=True
            )
            r = jnp.sum(
                jnp.where(lane8k == a, jnp.broadcast_to(rank_row, tot.shape),
                          0),
                axis=1, keepdims=True,
            )
            return m, r, c * _CH + a

        def col_chunk_views(s):
            score3 = colS[:, pl.ds(s, 1), :].reshape(n_pol, kdim, _CH)
            feas_b = colF[:, pl.ds(s, 1), :].reshape(kdim, _CH) != 0
            return score3, feas_b

        def block_extrema_row(score3, feas_b):
            """(1, nn*K) brmin/brmax rows of one chunk: per normalized
            policy the feasible raw extrema over the 128 lanes."""
            mns, mxs = [], []
            for j in range(nn):
                raw = score3[norm_idx[j]] if n_norm else score3[0]
                mns.append(jnp.min(
                    jnp.where(feas_b, raw, _INT_MAX), axis=1, keepdims=True
                ))
                mxs.append(jnp.max(
                    jnp.where(feas_b, raw, -_INT_MAX), axis=1, keepdims=True
                ))
            mn = jnp.concatenate(mns, axis=0).reshape(1, nn * kdim)
            mx = jnp.concatenate(mxs, axis=0).reshape(1, nn * kdim)
            return mn, mx

        def summary_rows_at(c, s):
            """Refresh brmin/brmax + bt/br/bn row `c` from the column
            chunk in slot `s` (stored extrema — the incremental half of
            the blocked engine's per-event aggregate refresh)."""
            score3, feas_b = col_chunk_views(s)
            if n_norm:
                mn, mx = block_extrema_row(score3, feas_b)
                brmin_ref[pl.ds(c, 1), :] = mn
                brmax_ref[pl.ds(c, 1), :] = mx
            rank_row = rank_ref[pl.ds(c, 1), :]
            tot = chunk_totals(score3, feas_b)
            bm, brk, bar = chunk_block_reduce(tot, rank_row, c)
            bt_ref[pl.ds(c, 1), :] = bm.reshape(1, kdim)
            br_ref[pl.ds(c, 1), :] = brk.reshape(1, kdim)
            bn_ref[pl.ds(c, 1), :] = bar.reshape(1, kdim)

        # dirty[0] is only written from i == 0 onward; mask the SMEM
        # read so a first-event EV_SKIP (t_node falls back to d_prev)
        # cannot derive a garbage chunk index from uninitialized scratch
        # on hardware (interpreter zero-fills and would hide it)
        d_prev = jnp.where(i == 0, 0, dirty[0])
        cd_prev = d_prev // _CH
        ld_prev = jax.lax.rem(d_prev, _CH)
        kind = kref[i]
        tid = tref[i]
        inext = jnp.minimum(i + 1, e - 1)
        tid_next = tref[inext]

        # ================= init (event 0): build everything =============
        @pl.when(i == 0)
        def _():
            dctr[0] = 0
            dctr[1] = 0
            dctr[2] = 0
            dirty[0] = 0
            init_cps = [
                pltpu.make_async_copy(a, b, init_sem.at[j])
                for j, (a, b) in enumerate((
                    (cpu0_any, cpu_any), (mem0_any, mem_any),
                    (gpu0_any, gpu_any), (aff0_any, aff_any),
                ))
            ]
            start(init_cps)
            wait(init_cps)
            placed_ref[:, :] = jnp.full(placed_ref.shape, -1, jnp.int32)
            maskb_ref[:, :] = jnp.zeros(placed_ref.shape, jnp.int32)
            failed_ref[:, :] = jnp.zeros(placed_ref.shape, jnp.int32)
            evnode_ref[:, :] = jnp.full(evnode_ref.shape, -1, jnp.int32)
            evdevb_ref[:, :] = jnp.zeros(evnode_ref.shape, jnp.int32)
            brmin_ref[:, :] = jnp.full(brmin_ref.shape, _INT_MAX, jnp.int32)
            brmax_ref[:, :] = jnp.full(brmax_ref.shape, -_INT_MAX, jnp.int32)
            slo_ref[:, :] = jnp.zeros(slo_ref.shape, jnp.int32)
            shi_ref[:, :] = jnp.zeros(shi_ref.shape, jnp.int32)

            # pass 1: table columns chunk by chunk (through the SAME
            # column code path the per-event refresh uses) + block extrema
            def pass1(c, _c):
                sd = state_dmas(c, (cpu0_any, mem0_any, gpu0_any, aff0_any),
                                True, stin_sem)
                rd = ro_dmas(c)
                start(sd + rd)
                wait(sd + rd)

                def lane_body(l, _l):
                    cs, cdv, cf = column_for(node_scalars_chunk(l))
                    hit = (lane1 == l).reshape(1, 1, _CH)
                    for ref, col in (
                        (colS, cs), (colD, cdv), (colF, cf)
                    ):
                        blk = ref[:, pl.ds(0, 1), :]
                        ref[:, pl.ds(0, 1), :] = jnp.where(
                            hit, col.reshape(col.shape[0], 1, 1), blk
                        )
                    return 0

                jax.lax.fori_loop(0, _CH, lane_body, 0)
                wb = colwb_dmas(0, c)
                start(wb)
                wait(wb)
                if n_norm:
                    score3, feas_b = col_chunk_views(0)
                    mn, mx = block_extrema_row(score3, feas_b)
                    brmin_ref[pl.ds(c, 1), :] = mn
                    brmax_ref[pl.ds(c, 1), :] = mx
                return 0

            jax.lax.fori_loop(0, nc, pass1, 0)
            if n_norm:
                slo_ref[:, :] = jnp.min(brmin_ref[:, :], axis=0,
                                        keepdims=True)
                shi_ref[:, :] = jnp.max(brmax_ref[:, :], axis=0,
                                        keepdims=True)

            # pass 2: bt/br/bn under the just-stored extrema
            def pass2(c, _c):
                cin = colin_dmas(0, c)
                start(cin)
                wait(cin)
                summary_rows_at(c, 0)
                return 0

            jax.lax.fori_loop(0, nc, pass2, 0)
            # event 0's row slice, synchronously, into slot 0
            r0 = row_dmas(0, tid)
            start(r0)
            wait(r0)

        # ============ steady state: wait prefetches, refresh ============
        @pl.when(i != 0)
        def _():
            wait(row_dmas(slot, tid))
            wait(colin_dmas(slot, cd_prev))
            wait(ro_dmas(cd_prev))
            # dirty-column refresh (the table engine's per-event column
            # refresh) on the retained state chunk, into this slot's
            # column scratch, then write back + patch the row slice the
            # prefetch could not have seen (it left HBM before this
            # refresh — the same-block-twice correctness case)
            cs, cdv, cf = column_for(node_scalars_chunk(ld_prev))
            hit = (lane1 == ld_prev).reshape(1, 1, _CH)
            for ref, col in ((colS, cs), (colD, cdv), (colF, cf)):
                blk = ref[:, pl.ds(slot, 1), :]
                ref[:, pl.ds(slot, 1), :] = jnp.where(
                    hit, col.reshape(col.shape[0], 1, 1), blk
                )
            start(colwb_dmas(slot, cd_prev))
            sub_np = _iota((n_pol * kdim, 1), 0)
            for pi in range(n_pol):
                v = jnp.sum(
                    jnp.where(sub_np == tid + pi * kdim, cs, 0)
                )
                old = rowS[pl.ds(slot * n_pol + pi, 1), pl.ds(cd_prev, 1), :]
                rowS[pl.ds(slot * n_pol + pi, 1), pl.ds(cd_prev, 1), :] = (
                    jnp.where(hit, v, old)
                )
            sub_k = _iota((kdim, 1), 0)
            vf = jnp.sum(jnp.where(sub_k == tid, cf, 0))
            oldf = rowF[pl.ds(slot, 1), pl.ds(cd_prev, 1), :]
            rowF[pl.ds(slot, 1), pl.ds(cd_prev, 1), :] = jnp.where(
                hit, vf, oldf
            )
            # dirty-block aggregate refresh for ALL K types (stored
            # extrema — consistent with every other block by construction)
            summary_rows_at(cd_prev, slot)

        # ---- extrema drift check + conditional summary-column rebuild
        # for THIS event's type (the blocked engine's cond, from the row
        # slice already in VMEM)
        if n_norm:
            lo_cur, hi_cur, slo_v, shi_v = [], [], [], []
            for j in range(n_norm):
                msk = lane_nn == (j * kdim + tid)
                lo_cur.append(jnp.min(
                    jnp.where(msk, brmin_ref[:, :], _INT_MAX)
                ))
                hi_cur.append(jnp.max(
                    jnp.where(msk, brmax_ref[:, :], -_INT_MAX)
                ))
                msk_s = lane_s == (j * kdim + tid)
                slo_v.append(jnp.sum(jnp.where(msk_s, slo_ref[:, :], 0)))
                shi_v.append(jnp.sum(jnp.where(msk_s, shi_ref[:, :], 0)))
            changed = jnp.zeros((), jnp.bool_)
            for j in range(n_norm):
                changed = changed | (lo_cur[j] != slo_v[j]) | (
                    hi_cur[j] != shi_v[j]
                )

            @pl.when(changed)
            def _():
                dctr[2] = dctr[2] + 1
                feas_row = rowF[pl.ds(slot, 1), :, :].reshape(nc, _CH) != 0
                tot = jnp.zeros((nc, _CH), jnp.int32)
                for pi, (_, nrm, w, _) in enumerate(columns):
                    raw = rowS[pl.ds(slot * n_pol + pi, 1), :, :].reshape(
                        nc, _CH
                    )
                    if nrm in ("minmax", "pwr"):
                        j = norm_idx.index(pi)
                        rngv = hi_cur[j] - lo_cur[j]
                        degen = 0 if nrm == "minmax" else MAX_NODE_SCORE
                        scaled = jnp.where(
                            rngv == 0, degen,
                            (raw - lo_cur[j]) * MAX_NODE_SCORE
                            // jnp.maximum(rngv, 1),
                        )
                        raw = jnp.where(feas_row, scaled, raw)
                    tot = tot + w * raw
                tot = jnp.where(feas_row, tot, -_INT_MAX)
                rank2 = rank_ref[:, :]
                m = jnp.max(tot, axis=1, keepdims=True)
                wkey = jnp.where(tot == m, -rank2, -_INT_MAX)
                mw = jnp.max(wkey, axis=1, keepdims=True)
                lane2 = _iota((nc, _CH), 1)
                a = jnp.min(
                    jnp.where(wkey == mw, lane2, _CH), axis=1, keepdims=True
                )
                r = jnp.sum(
                    jnp.where(lane2 == a, rank2, 0), axis=1, keepdims=True
                )
                nid_b = blki * _CH + a
                mT = laneK == tid
                bt_ref[:, :] = jnp.where(mT, m, bt_ref[:, :])
                br_ref[:, :] = jnp.where(mT, r, br_ref[:, :])
                bn_ref[:, :] = jnp.where(mT, nid_b, bn_ref[:, :])
                for j in range(n_norm):
                    msk_s = lane_s == (j * kdim + tid)
                    slo_ref[:, :] = jnp.where(msk_s, lo_cur[j],
                                              slo_ref[:, :])
                    shi_ref[:, :] = jnp.where(msk_s, hi_cur[j],
                                              shi_ref[:, :])

        # ---- this event's packed scalars (one-chunk masked extraction)
        ec_i = i // _CH
        el = jax.lax.rem(i, _CH)
        evblk = ev_ref[:, pl.ds(ec_i, 1), :]
        sel_ev = (lane1 == el).reshape(1, 1, _CH)

        def f(j):
            return jnp.sum(jnp.where(sel_ev, evblk[j:j + 1, :, :], 0))

        idx = f(1)
        pcpu, pmem, pmilli, pnum = f(3), f(4), f(5), f(6)
        ppin, pcls, pshare, ptgm = f(8), f(9), f(10), f(11)
        sel_p = lane_p == idx
        sel_e1 = lane1 == el
        sub8c = _iota((8, 1), 0)
        is_c = kind == 0
        is_d = kind == 1

        # ---- create: selectHost over the N/B block summaries (the
        # blocked two-level select; pinned pods bypass it — exactly one
        # candidate, its Filter bit decides)
        mT2 = laneK == tid
        bt_t = jnp.sum(jnp.where(mT2, bt_ref[:, :], 0), axis=1,
                       keepdims=True)
        br_t = jnp.sum(jnp.where(mT2, br_ref[:, :], 0), axis=1,
                       keepdims=True)
        bn_t = jnp.sum(jnp.where(mT2, bn_ref[:, :], 0), axis=1,
                       keepdims=True)
        vld = bt_t != -_INT_MAX
        best = jnp.max(jnp.where(vld, bt_t, -_INT_MAX))
        wkeyb = jnp.where(vld & (bt_t == best), -br_t, -_INT_MAX)
        mwb = jnp.max(wkeyb)
        okb = mwb != -_INT_MAX
        blk_w = jnp.min(jnp.where(wkeyb == mwb, blki, nc))
        cand = jnp.sum(jnp.where(blki == blk_w, bn_t, 0))
        pinc = jnp.clip(ppin, 0, n - 1)
        feas_rowv = rowF[pl.ds(slot, 1), :, :].reshape(nc, _CH)
        pin_feas = (jnp.sum(jnp.where(nid == pinc, feas_rowv, 0)) != 0) & (
            ppin < n
        )
        node_c = jnp.where(
            ppin >= 0,
            jnp.where(pin_feas, pinc, -1),
            jnp.where(okb, cand, -1),
        ).astype(jnp.int32)
        ok_c = node_c >= 0
        sel_c = jnp.maximum(node_c, 0)

        # ---- delete: the recorded placement
        node_d = jnp.sum(jnp.where(sel_p, placed_ref[:, :], 0))
        bits_d = jnp.sum(jnp.where(sel_p, maskb_ref[:, :], 0))
        was_d = node_d >= 0

        # unified touched node -> the state chunk every kind DMAs
        t_node = jnp.where(
            is_c, sel_c, jnp.where(is_d, jnp.maximum(node_d, 0), d_prev)
        )
        ct = t_node // _CH
        lt = jax.lax.rem(t_node, _CH)
        sel_l = lane1 == lt

        # previous event's state writeback must land before this read —
        # and THIS event's dirty-column writeback (started in the
        # refresh above) before the sdev-chunk read below: when the
        # winner lands in the chunk the refresh just wrote (ct ==
        # cd_prev), an unordered read could return the pre-refresh sdev
        # lane on hardware (interpreter DMAs complete at start() and
        # would hide it). The wait also precedes the e+1 prefetches, so
        # the original row/column read-after-writeback ordering holds.
        @pl.when(i != 0)
        def _():
            wait(state_dmas(cd_prev, (cpu_any, mem_any, gpu_any, aff_any),
                            False, stwb_sem))
            wait(colwb_dmas(slot, cd_prev))
        st_in = state_dmas(ct, (cpu_any, mem_any, gpu_any, aff_any),
                           True, stin_sem)
        start(st_in)
        wait(st_in)
        sd_in = sd_dmas(tid, ct)
        start(sd_in)
        wait(sd_in)

        # ---- Reserve: device pick on the winner (step.choose_devices)
        g8w = jnp.sum(
            jnp.where(sel_l, stG[:, :, :].reshape(8, _CH), 0),
            axis=1, keepdims=True,
        )
        gT = g8w.T
        lane8 = _iota((1, 8), 1)
        fits = gT >= pmilli
        any_fit = jnp.sum(fits.astype(jnp.int32)) > 0
        bkey = jnp.where(fits, gT, _INT_MAX)
        bdev = jnp.min(jnp.where(bkey == jnp.min(bkey), lane8, 8))
        bdev = jnp.where(any_fit, bdev, -1)
        if gpu_sel == "worst":
            wkey8 = jnp.where(fits, gT, -_INT_MAX)
            wdev = jnp.min(jnp.where(wkey8 == jnp.max(wkey8), lane8, 8))
            share_dev = jnp.where(any_fit, wdev, -1)
        elif self_select:
            sdev = jnp.sum(
                jnp.where(sel_l, sdW[:, :, :].reshape(1, _CH), 0)
            )
            share_dev = jnp.where(sdev >= 0, sdev, bdev)
        else:  # "best"
            share_dev = bdev
        share_bits = jnp.where(
            share_dev >= 0,
            jax.lax.shift_left(1, jnp.maximum(share_dev, 0)),
            0,
        )
        units = jnp.where(pmilli > 0, gT // jnp.maximum(pmilli, 1), 0)
        prev = _cumsum8_lanes(units) - units
        take_units = jnp.clip(pnum - prev, 0, units)
        whole_bits = jnp.sum(
            jnp.where(take_units > 0, jax.lax.shift_left(1, lane8), 0)
        )
        bits_c = jnp.where(
            ptgm > 0, jnp.where(pshare != 0, share_bits, whole_bits), 0
        )
        bits_c = jnp.where(ok_c, bits_c, 0)

        # ---- Bind: masked read-modify-write of the retained state chunk
        # (one scatter-commit per kind, no-op for skips/failed creates)
        act = jnp.where(
            is_c & ok_c, -1, jnp.where(is_d & was_d, 1, 0)
        ).astype(jnp.int32)
        bits_eff = jnp.where(is_c, bits_c, jnp.where(is_d, bits_d, 0))
        mask8 = (jax.lax.shift_right_logical(bits_eff, sub8c) & 1) != 0
        aff_sub = _iota((9, 1), 0) == jnp.maximum(pcls, 0)
        stC[:, :] = stC[:, :] + jnp.where(sel_l, act * pcpu, 0)
        stM[:, :] = stM[:, :] + jnp.where(sel_l, act * pmem, 0)
        stG[:, :, :] = stG[:, :, :] + jnp.where(
            sel_l.reshape(1, 1, _CH) & mask8.reshape(8, 1, 1),
            act * pmilli, 0,
        )
        stA[:, :, :] = stA[:, :, :] + jnp.where(
            sel_l.reshape(1, 1, _CH) & aff_sub.reshape(9, 1, 1)
            & (pcls >= 0),
            -act, 0,
        )
        start(state_dmas(ct, (cpu_any, mem_any, gpu_any, aff_any),
                         False, stwb_sem))

        # ---- bookkeeping (mirrors _make_kernel's create/delete writes)
        placed_ref[:, :] = jnp.where(
            sel_p & is_c, jnp.where(ok_c, node_c, -1),
            jnp.where(sel_p & is_d, -1, placed_ref[:, :]),
        )
        maskb_ref[:, :] = jnp.where(
            sel_p & is_c, bits_c,
            jnp.where(sel_p & is_d, 0, maskb_ref[:, :]),
        )
        failed_ref[:, :] = jnp.where(
            sel_p & is_c, jnp.where(ok_c, 0, 1), failed_ref[:, :]
        )
        eblk = evnode_ref[pl.ds(ec_i, 1), :]
        evnode_ref[pl.ds(ec_i, 1), :] = jnp.where(
            sel_e1 & is_c, jnp.where(ok_c, node_c, -1),
            jnp.where(sel_e1 & is_d, node_d, eblk),
        )
        dblk = evdevb_ref[pl.ds(ec_i, 1), :]
        evdevb_ref[pl.ds(ec_i, 1), :] = jnp.where(
            sel_e1 & is_c, bits_c,
            jnp.where(sel_e1 & is_d, bits_d, dblk),
        )
        dirty[0] = t_node

        # ---- prefetch event e+1's working set (the double buffer):
        # the column writeback already landed (waited before the
        # state/sdev chunk reads above), so the next row/column reads
        # cannot cover a chunk still being written
        @pl.when(i + 1 < e)
        def _():
            start(colin_dmas(nslot, ct))
            start(row_dmas(nslot, tid_next))
            start(ro_dmas(ct))

        @pl.when(i + 1 == e)
        def _():
            wait(state_dmas(ct, (cpu_any, mem_any, gpu_any, aff_any),
                            False, stwb_sem))

        dma_ref[:, :] = jnp.where(
            lane1 == 0, dctr[0],
            jnp.where(lane1 == 1, dctr[1],
                      jnp.where(lane1 == 2, dctr[2], 0)),
        )

    return kernel


def _make_hbm_replay(policies, gpu_sel: str, interpret: bool):
    """Build the HBM-residency replayer (make_pallas_replay's
    residency='hbm' arm). Returns a jitted `replay(...)` with the table
    engine's call signature that yields `(ReplayResult, dma_stats)` —
    dma_stats = i32[3] (semaphore waits, DMA starts, drift rebuilds)
    counted exactly inside the kernel."""
    columns = tuple(
        (
            _resolve_column(fn),
            fn.normalize,
            int(w),
            gpu_sel == fn.policy_name
            and fn.policy_name in SELF_SELECT_POLICIES,
        )
        for fn, w in policies
    )
    n_pol = len(columns)
    n_norm = sum(1 for _, nrm, _, _ in columns if nrm in ("minmax", "pwr"))
    nn = max(n_norm, 1)

    @jax.jit
    def replay(
        state: NodeState,
        pods: PodSpec,
        types: PodTypes,
        ev_kind,
        ev_pod,
        tp,
        key,
        tiebreak_rank=None,
    ):
        from tpusim.parallel.sharding import pad_nodes

        n0 = state.num_nodes
        if tiebreak_rank is None:
            tiebreak_rank = jnp.arange(n0, dtype=jnp.int32)
        state_p, rank_p = pad_nodes(state, tiebreak_rank, 128)
        n = state_p.num_nodes

        ks = int(types.share.cpu.shape[0])
        kw = int(types.whole.cpu.shape[0])
        kdim = ks + kw

        def col(field):
            return jnp.concatenate(
                [getattr(types.share, field), getattr(types.whole, field)]
            ).reshape(kdim, 1)

        tcols = [col(f) for f in ("cpu", "mem", "gpu_milli", "gpu_num",
                                  "gpu_mask")]
        t = int(tp.cpu.shape[0])
        tprows = [
            tp.cpu.reshape(1, t),
            tp.gpu_milli.reshape(1, t),
            tp.gpu_num.astype(jnp.float32).reshape(1, t),
            tp.gpu_mask.reshape(1, t),
            tp.freq.reshape(1, t),
        ]
        ev = _pack_events(pods, types.type_id, ev_kind, ev_pod)
        e = int(ev.shape[1])
        p = int(pods.cpu.shape[0])
        nc = n // _CH
        epad = (-e) % _CH
        if epad:
            ev = jnp.concatenate(
                [ev, jnp.zeros((ev.shape[0], epad), jnp.int32)
                 .at[0, :].set(2)],
                axis=1,
            )
        ec = (e + epad) // _CH
        ev3 = ev.reshape(ev.shape[0], ec, _CH)
        kinds = jnp.asarray(ev_kind, jnp.int32)
        tids = types.type_id[ev_pod].astype(jnp.int32)

        kernel = _make_hbm_kernel(columns, ks, gpu_sel)
        any_spec = pl.BlockSpec(memory_space=pl.ANY)
        vmem_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
        out_shape = (
            jax.ShapeDtypeStruct((n_pol * kdim, nc, _CH), jnp.int32),
            jax.ShapeDtypeStruct((kdim, nc, _CH), jnp.int32),  # sdev
            jax.ShapeDtypeStruct((kdim, nc, _CH), jnp.int32),  # feas
            jax.ShapeDtypeStruct((nc, _CH), jnp.int32),  # cpu_left
            jax.ShapeDtypeStruct((nc, _CH), jnp.int32),  # mem_left
            jax.ShapeDtypeStruct((8, nc, _CH), jnp.int32),  # gpu_left
            jax.ShapeDtypeStruct((9, nc, _CH), jnp.int32),  # aff_cnt
            jax.ShapeDtypeStruct((nc, kdim), jnp.int32),  # bt
            jax.ShapeDtypeStruct((nc, kdim), jnp.int32),  # br
            jax.ShapeDtypeStruct((nc, kdim), jnp.int32),  # bn
            jax.ShapeDtypeStruct((nc, nn * kdim), jnp.int32),  # brmin
            jax.ShapeDtypeStruct((nc, nn * kdim), jnp.int32),  # brmax
            jax.ShapeDtypeStruct((1, nn * kdim), jnp.int32),  # slo
            jax.ShapeDtypeStruct((1, nn * kdim), jnp.int32),  # shi
            jax.ShapeDtypeStruct((1, p), jnp.int32),  # placed
            jax.ShapeDtypeStruct((1, p), jnp.int32),  # device mask bits
            jax.ShapeDtypeStruct((1, p), jnp.int32),  # failed
            jax.ShapeDtypeStruct((ec, _CH), jnp.int32),  # event node
            jax.ShapeDtypeStruct((ec, _CH), jnp.int32),  # event dev bits
            jax.ShapeDtypeStruct((1, _CH), jnp.int32),  # dma stats
        )
        energy_rows = [
            jnp.asarray(GPU_IDLE_W).reshape(1, -1),
            jnp.asarray(GPU_FULL_W).reshape(1, -1),
            jnp.asarray(CPU_IDLE_W).reshape(1, -1),
            jnp.asarray(CPU_FULL_W).reshape(1, -1),
            jnp.asarray(CPU_NCORES).reshape(1, -1),
        ]

        def chunk(a):
            return a.reshape(nc, _CH)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e,),
            in_specs=[vmem_spec] * 17 + [any_spec] * 8,
            out_specs=tuple([any_spec] * 7 + [vmem_spec] * 13),
            scratch_shapes=[
                pltpu.VMEM((2 * n_pol, nc, _CH), jnp.int32),  # rowS
                pltpu.VMEM((2, nc, _CH), jnp.int32),  # rowF
                pltpu.VMEM((n_pol * kdim, 2, _CH), jnp.int32),  # colS
                pltpu.VMEM((kdim, 2, _CH), jnp.int32),  # colD
                pltpu.VMEM((kdim, 2, _CH), jnp.int32),  # colF
                pltpu.VMEM((1, _CH), jnp.int32),  # stC
                pltpu.VMEM((1, _CH), jnp.int32),  # stM
                pltpu.VMEM((8, 1, _CH), jnp.int32),  # stG
                pltpu.VMEM((9, 1, _CH), jnp.int32),  # stA
                pltpu.VMEM((4, _CH), jnp.int32),  # roB
                pltpu.VMEM((1, 1, _CH), jnp.int32),  # sdW
                pltpu.SMEM((1,), jnp.int32),  # dirty
                pltpu.SMEM((4,), jnp.int32),  # dctr
                pltpu.SemaphoreType.DMA((n_pol + 1,)),  # row_sem
                pltpu.SemaphoreType.DMA((3,)),  # colin_sem
                pltpu.SemaphoreType.DMA((3,)),  # colwb_sem
                pltpu.SemaphoreType.DMA((4,)),  # stin_sem
                pltpu.SemaphoreType.DMA((4,)),  # stwb_sem
                pltpu.SemaphoreType.DMA((4,)),  # ro_sem
                pltpu.SemaphoreType.DMA((1,)),  # sd_sem
                pltpu.SemaphoreType.DMA((4,)),  # init_sem
            ],
        )
        (
            _score, _sdev, _feas, cpu_l, mem_l, gpul, aff,
            _bt, _br, _bn, _bmin, _bmax, _slo, _shi,
            placed, maskb, failed, evnode, evdevb, dma,
        ) = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=interpret,
        )(
            kinds,
            tids,
            ev3,
            *tcols,
            *tprows,
            *energy_rows,
            chunk(rank_p),
            chunk(state_p.gpu_cnt),
            chunk(state_p.gpu_type),
            chunk(state_p.cpu_cap),
            chunk(state_p.cpu_type),
            chunk(state_p.cpu_left),
            chunk(state_p.mem_left),
            state_p.gpu_left.T.reshape(8, nc, _CH),
            state_p.aff_cnt.T.reshape(9, nc, _CH),
        )

        bit8 = jnp.arange(MAX_GPUS_PER_NODE, dtype=jnp.int32)
        new_state = state._replace(
            cpu_left=cpu_l.reshape(n)[:n0],
            mem_left=mem_l.reshape(n)[:n0],
            gpu_left=gpul.reshape(8, n)[:, :n0].T,
            aff_cnt=aff.reshape(9, n)[:, :n0].T,
        )
        masks = ((maskb[0, :, None] >> bit8) & 1) != 0
        evnode_f = evnode.reshape(ec * _CH)[:e]
        evdevb_f = evdevb.reshape(ec * _CH)[:e]
        devs = ((evdevb_f[:, None] >> bit8) & 1) != 0
        result = ReplayResult(
            new_state, placed[0], masks, failed[0] != 0, None, evnode_f,
            devs,
        )
        return result, dma[0, :3]

    replay.residency = "hbm"
    return replay
