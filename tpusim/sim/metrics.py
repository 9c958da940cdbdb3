"""Per-event metrics as a vectorized post-pass over replay telemetry.

The reference recomputes the full cluster frag/alloc/power report after
EVERY event (simulator.go:426-427, analysis.go:24-126) — its dominant cost.
Round 2-4 engines moved that into the replay scan (one touched-node metric
row refresh + a cluster reduce per scan step), which still serializes ~10
kernel launches per event and forced the fused Pallas engine to reject
reporting configs entirely.

This module removes per-event metric work from every engine: a replay runs
metric-free and emits only its placement telemetry — `event_node` i32[E]
(the node each event touched) and `event_dev` bool[E,8] — which all engines
already produce bit-identically (it IS the pinned equality contract). The
per-event metric series is then reconstructed from that telemetry in a few
large batched ops, with no sequential scan:

  1. per-event touched-node states via a segmented (per-node) cumulative
     sum over the event axis — integer arithmetic, exact;
  2. per-event touched-node frag/power rows via the SAME vmapped kernels
     (ops.frag.node_frag_amounts / ops.energy.node_power) the engines'
     in-scan report paths used, batched over all E events at once;
  3. cluster series as initial totals + a cumulative sum of per-event row
     deltas along the event axis.

Exactness: every integer series ([Alloc]/[AllocCPU] lines, arrived
counters) is exact — integer sums in any order. The f32 frag/power series
are deterministic but use a cumulative-delta order instead of the per-event
full re-sum the round-4 scan paths used, so their last ulps differ from
round 4 (drift ~1e-6 relative over a full trace; the analysis CSVs' merged
percent-scale values are unaffected). What matters is byte-identity ACROSS
engines, and that now holds by construction: identical telemetry in →
identical series out, for the sequential, table, fused-Pallas, and batched
paths alike. The sequential oracle keeps its in-scan report mode as a
cross-check (tests/test_metrics.py pins post-pass == in-scan exactly for
integers and to f32 tolerance for the float series).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List

import jax
import jax.numpy as jnp

from tpusim.constants import MILLI
from tpusim.ops.energy import node_power
from tpusim.ops.frag import node_frag_amounts
from tpusim.sim.engine import (
    EV_CREATE,
    EV_DELETE,
    EventMetrics,
    cluster_usage,
    power_rows,
)
from tpusim.types import NodeState, PodSpec


def _segment_inclusive_cumsum(delta_s, head):
    """Inclusive cumulative sum of `delta_s` (leading axis) restarting at
    every True in `head` — the standard cumsum-minus-group-base trick, all
    parallel ops."""
    csum = jnp.cumsum(delta_s, axis=0)
    excl = csum - delta_s
    idx = jnp.arange(head.shape[0])
    head_idx = jax.lax.associative_scan(jnp.maximum, jnp.where(head, idx, 0))
    group_base = excl[head_idx]
    return csum - group_base


def _usage_contrib(cpu_left, gpu_left, cpu_cap, gpu_cnt):
    """One node's contribution to the [Alloc]/[AllocCPU] aggregates
    (cluster_usage semantics, analysis.go:91-99), for batched [E] states."""
    fully_free = (gpu_left == MILLI).sum(-1)
    used = (fully_free < gpu_cnt) | (cpu_left < cpu_cap)
    u = used.astype(jnp.int32)
    return (
        u,
        u * gpu_cnt,
        u * (gpu_cnt * MILLI - gpu_left.sum(-1)),
        u * (cpu_cap - cpu_left),
    )


_frag_rows = jax.vmap(node_frag_amounts, in_axes=(0, 0, 0, None))
_power_rows_b = jax.vmap(node_power)


@jax.jit
@jax.named_scope("tpusim.event_metrics")
def compute_event_metrics(
    init_state: NodeState,
    specs: PodSpec,
    ev_kind: jnp.ndarray,  # i32[E]
    ev_pod: jnp.ndarray,  # i32[E]
    event_node: jnp.ndarray,  # i32[E] touched node (-1 = state untouched)
    event_dev: jnp.ndarray,  # bool[E, 8] touched devices
    tp,
) -> EventMetrics:
    """EventMetrics for a replayed event stream, from telemetry alone."""
    n = init_state.num_nodes
    pod = jax.tree.map(lambda a: a[ev_pod], specs)

    valid = event_node >= 0
    # resources the event TAKES from its node (negative take = release)
    sign = jnp.where(
        valid & (ev_kind == EV_CREATE),
        1,
        jnp.where(valid & (ev_kind == EV_DELETE), -1, 0),
    )
    taken_cpu = sign * pod.cpu  # i32[E]
    taken_gpu = sign[:, None] * event_dev.astype(jnp.int32) * pod.gpu_milli[:, None]

    # ---- group events by touched node (stable: intra-node event order kept)
    key = jnp.where(valid, event_node, n)
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    head = jnp.concatenate([jnp.ones(1, bool), key_s[1:] != key_s[:-1]])
    node_s = jnp.minimum(key_s, n - 1)  # clamped gather index (invalid rows
    # land in the trailing key==n group and are masked out of every delta)
    valid_s = key_s < n

    # ---- per-event post-state of the touched node (integer, exact)
    cum_cpu = _segment_inclusive_cumsum(taken_cpu[order], head)
    cum_gpu = _segment_inclusive_cumsum(taken_gpu[order], head)
    post_cpu_s = init_state.cpu_left[node_s] - cum_cpu
    post_gpu_s = init_state.gpu_left[node_s] - cum_gpu
    pre_cpu_s = post_cpu_s + taken_cpu[order]
    pre_gpu_s = post_gpu_s + taken_gpu[order]
    cap_s = init_state.cpu_cap[node_s]
    gcnt_s = init_state.gpu_cnt[node_s]
    gtyp_s = init_state.gpu_type[node_s]
    ctyp_s = init_state.cpu_type[node_s]

    def to_events(x_s):
        """Scatter a sorted-order series back to event order."""
        return jnp.zeros_like(x_s).at[order].set(x_s)

    # ---- frag series: init totals + cumsum of touched-row deltas
    init_rows = _frag_rows(
        init_state.cpu_left, init_state.gpu_left, init_state.gpu_type, tp
    )  # f32[N, 7]
    new_row_s = _frag_rows(post_cpu_s, post_gpu_s, gtyp_s, tp)  # f32[E, 7]
    prev_row_s = jnp.concatenate(
        [jnp.zeros((1, new_row_s.shape[1]), new_row_s.dtype), new_row_s[:-1]]
    )
    old_row_s = jnp.where(head[:, None], init_rows[node_s], prev_row_s)
    frag_delta = to_events(
        jnp.where(valid_s[:, None], new_row_s - old_row_s, 0.0)
    )
    frag_amounts = init_rows.sum(0)[None, :] + jnp.cumsum(frag_delta, axis=0)

    # ---- power series: same shape, (cpu_watts, gpu_watts) per node
    pc0, pg0 = power_rows(init_state)
    new_pw_s = jnp.stack(
        _power_rows_b(post_cpu_s, cap_s, post_gpu_s, gcnt_s, gtyp_s, ctyp_s),
        axis=-1,
    )  # f32[E, 2]
    init_pw = jnp.stack([pc0, pg0], axis=-1)  # f32[N, 2]
    prev_pw_s = jnp.concatenate(
        [jnp.zeros((1, 2), new_pw_s.dtype), new_pw_s[:-1]]
    )
    old_pw_s = jnp.where(head[:, None], init_pw[node_s], prev_pw_s)
    pw_delta = to_events(jnp.where(valid_s[:, None], new_pw_s - old_pw_s, 0.0))
    pw = init_pw.sum(0)[None, :] + jnp.cumsum(pw_delta, axis=0)

    # ---- usage series ([Alloc]/[AllocCPU]): integer deltas, exact
    init_usage = cluster_usage(init_state)
    post_c = _usage_contrib(post_cpu_s, post_gpu_s, cap_s, gcnt_s)
    pre_c = _usage_contrib(pre_cpu_s, pre_gpu_s, cap_s, gcnt_s)
    usage = [
        i + jnp.cumsum(to_events(jnp.where(valid_s, po - pr, 0)))
        for i, po, pr in zip(init_usage, post_c, pre_c)
    ]

    # ---- arrived counters: accumulate per creation event regardless of
    # outcome (simulator.go:406-408) — failed creations included
    is_create = ev_kind == EV_CREATE
    arr_cpu = jnp.cumsum(jnp.where(is_create, pod.cpu, 0))
    arr_gpu = jnp.cumsum(jnp.where(is_create, pod.total_gpu_milli(), 0))

    return EventMetrics(
        frag_amounts=frag_amounts,
        used_nodes=usage[0],
        used_gpus=usage[1],
        used_gpu_milli=usage[2],
        used_cpu_milli=usage[3],
        arrived_gpu_milli=arr_gpu,
        arrived_cpu_milli=arr_cpu,
        power_cpu=pw[:, 0],
        power_gpu=pw[:, 1],
    )


@dataclass
class DisruptionMetrics:
    """Fault-replay disruption accounting (ISSUE 2; filled by
    Simulator.schedule_pods_with_faults, reported by
    reports.disruption_report_block). The clock is the EVENT counter —
    trace positions, not wall time — so every number is bit-reproducible
    under a fixed fault seed; that reproducibility is itself a pinned
    acceptance criterion (tests/test_faults.py)."""

    node_failures: int = 0
    node_recoveries: int = 0
    evicted_pods: int = 0  # node-crash evictions + single-pod preemptions
    retries_enqueued: int = 0
    rescheduled_pods: int = 0  # evicted pods that found a home again
    unscheduled_after_retries: int = 0  # hit max_retries -> terminal
    # Σ gpu_count × events-down per failed node: "failed-node GPU-hours"
    # with the event counter as the clock
    failed_node_gpu_events: int = 0
    # per rescheduled pod: placement position - eviction position
    reschedule_latency_events: List[int] = field(default_factory=list)
    # per recovery: cluster frag (frag_sum_except_q3 of the amounts row)
    # right after the node returned minus right before — how much
    # fragmentation the re-added empty capacity exposes
    post_recovery_frag_delta: List[float] = field(default_factory=list)

    def mean_reschedule_latency(self) -> float:
        lat = self.reschedule_latency_events
        return float(sum(lat)) / len(lat) if lat else 0.0

    def as_dict(self) -> dict:
        """Scalar summary for the direct-CSV stash / log parsing."""
        return {
            "node_failures": self.node_failures,
            "node_recoveries": self.node_recoveries,
            "evicted_pods": self.evicted_pods,
            "retries_enqueued": self.retries_enqueued,
            "rescheduled_pods": self.rescheduled_pods,
            "unscheduled_after_retries": self.unscheduled_after_retries,
            "failed_node_gpu_events": self.failed_node_gpu_events,
            "mean_reschedule_latency_events": self.mean_reschedule_latency(),
            "max_reschedule_latency_events": (
                max(self.reschedule_latency_events)
                if self.reschedule_latency_events else 0
            ),
            "post_recovery_frag_delta_sum": float(
                sum(self.post_recovery_frag_delta)
            ),
        }
