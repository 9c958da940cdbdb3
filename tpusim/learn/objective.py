"""Scalarized tuning objective over sweep-lane outputs (ISSUE 9).

The paper's own quality metrics are the objective: GPU allocation up,
FGD fragmentation down, unscheduled pods bounded ("Learning to Score",
arxiv 2603.10545, tunes score weights against exactly these). Every term
is already on a `SweepLane` (driver.schedule_pods_sweep) and on a
service result document (svc.worker.summarize_lane), so one rollout —
local vmapped sweep or remote `tpusim submit` loop — yields the same
scalar bit-for-bit:

    J(w) = w_alloc * gpu_alloc_pct
         - w_frag  * frag_pct           (frag gpu-milli / cluster GPU milli)
         - w_unsched * unsched_pct      (unscheduled pods / trace pods)

All three terms are percentages, so the default 1/1/1 weighting is
already scale-sane; the knobs exist because an operator who cares more
about disruption than packing should not have to edit code.

The optional robustness evaluator re-runs a candidate through
`Simulator.run_with_faults` (seeded disruption, ISSUE 2) and scores the
same objective on the faulted outcome — the per-generation held-out
check of the tuning loop.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class ObjectiveConfig:
    """Term weights of the scalarized objective (all terms in percent).

    w_disrupt (ISSUE 10) charges pods PERMANENTLY lost to disruption
    (max-retries-exceeded under a fault schedule) — trainable now that
    fault schedules are sweep operands (the chaos sweep rolls a whole
    population through one faulted compiled scan). 0 keeps the
    pre-fault objective AND the pre-fault log-header bytes (old tuning
    logs stay resumable)."""

    w_alloc: float = 1.0
    w_frag: float = 1.0
    w_unsched: float = 1.0
    w_disrupt: float = 0.0

    def canonical(self) -> list:
        """Deterministic JSON form for the tuning-log header. The
        disruption weight joins only when non-zero so pre-chaos logs
        keep their exact header bytes."""
        base = [float(self.w_alloc), float(self.w_frag),
                float(self.w_unsched)]
        if self.w_disrupt:
            base.append(float(self.w_disrupt))
        return base


def lane_terms(lane) -> dict:
    """SweepLane -> the objective's term dict. Keys and value types match
    terms_from_result exactly (the local-vs-remote bit-identity contract
    of the tuning log): plain ints and floats, JSON-stable."""
    from tpusim.constants import MILLI

    pn = np.asarray(lane.placed_node, np.int32)
    dm = np.asarray(lane.dev_mask, bool)
    h = hashlib.sha256()
    h.update(pn.tobytes())
    h.update(dm.tobytes())
    dis = getattr(lane, "disruption", None)
    return {
        "weights": [int(w) for w in lane.weights],
        "seed": int(lane.seed),
        "events": int(lane.events),
        "pods": int(pn.shape[0]),
        "placed": int(lane.placed),
        "failed": int(lane.failed),
        "unscheduled": int(lane.unscheduled),
        # chaos-sweep lanes (ISSUE 10): pods terminally lost to
        # disruption + total evictions; 0 on fault-free lanes so the
        # vocabulary is one dict either way
        "disrupted": int(dis.unscheduled_after_retries) if dis else 0,
        "evicted": int(dis.evicted_pods) if dis else 0,
        "gpu_total_milli": int(
            np.asarray(lane.state.gpu_cnt, np.int64).sum()
        ) * MILLI,
        "gpu_alloc_pct": float(lane.gpu_alloc_pct),
        "frag_gpu_milli": float(lane.frag_gpu_milli),
        "placements_sha256": h.hexdigest(),
    }


def terms_from_result(doc: dict) -> dict:
    """Service result document (svc.worker.summarize_lane) -> the same
    term dict lane_terms builds locally. JSON floats round-trip exactly
    (repr-faithful), so a remote rollout's terms are byte-identical to
    the local lane's in the tuning log."""
    return {
        "weights": [int(w) for w in doc["weights"]],
        "seed": int(doc["seed"]),
        "events": int(doc["events"]),
        "pods": int(doc["pods"]),
        "placed": int(doc["placed"]),
        "failed": int(doc["failed"]),
        "unscheduled": int(doc["unscheduled"]),
        # absent on pre-chaos service results -> the fault-free value
        "disrupted": int(doc.get("disrupted", 0)),
        "evicted": int(doc.get("evicted", 0)),
        "gpu_total_milli": int(doc["gpu_total_milli"]),
        "gpu_alloc_pct": float(doc["gpu_alloc_pct"]),
        "frag_gpu_milli": float(doc["frag_gpu_milli"]),
        "placements_sha256": str(doc["placements_sha256"]),
    }


def scalarize(terms: dict, cfg: ObjectiveConfig = None) -> float:
    """One term dict -> the scalar objective J(w) (maximize)."""
    cfg = cfg or ObjectiveConfig()
    frag_pct = 100.0 * terms["frag_gpu_milli"] / max(
        terms["gpu_total_milli"], 1
    )
    unsched_pct = 100.0 * terms["unscheduled"] / max(terms["pods"], 1)
    disrupt_pct = 100.0 * terms.get("disrupted", 0) / max(terms["pods"], 1)
    return (
        cfg.w_alloc * terms["gpu_alloc_pct"]
        - cfg.w_frag * frag_pct
        - cfg.w_unsched * unsched_pct
        - cfg.w_disrupt * disrupt_pct
    )


def terms_from_simulate(res, total_gpu_milli: int, typical) -> dict:
    """SimulateResult -> the same term vocabulary, for runs that did not
    go through the sweep (the robustness evaluator's run_with_faults
    outcome). Recomputes gpu_alloc/frag from the final state exactly as
    _slice_sweep_lanes does (there without the slot mask: the pads are 0)."""
    from tpusim.constants import MILLI
    from tpusim.ops.frag import cluster_frag_amounts, frag_sum_except_q3

    import jax

    st = jax.tree.map(np.asarray, res.state)
    slot = (
        np.arange(st.gpu_left.shape[1])[None, :] < st.gpu_cnt[:, None]
    )
    # DOWN nodes park at the mem_left = -1 sentinel with gpu_left zeroed;
    # their slots read as fully allocated, which is what the disruption
    # objective should see (capacity lost to faults is not free capacity)
    denom = max(int(st.gpu_cnt.sum()) * MILLI, 1)
    alloc = 100.0 * float(
        np.where(slot, MILLI - st.gpu_left, 0).sum()
    ) / denom
    amounts = np.asarray(cluster_frag_amounts(res.state, typical).sum(0))
    pn = np.asarray(res.placed_node, np.int32)
    return {
        "weights": [],  # stamped by the caller (the candidate's vector)
        "seed": -1,
        "events": int(res.events),
        "pods": int(pn.shape[0]),
        "placed": int((pn >= 0).sum()),
        "failed": len(res.unscheduled_pods),
        "unscheduled": len(res.unscheduled_pods),
        "gpu_total_milli": int(total_gpu_milli),
        "gpu_alloc_pct": alloc,
        "frag_gpu_milli": float(frag_sum_except_q3(amounts)),
        "placements_sha256": hashlib.sha256(pn.tobytes()).hexdigest(),
    }


def make_robust_eval(nodes, workload_pods, policies, fault_cfg,
                     base_cfg=None):
    """Build the optional per-generation robustness evaluator: a callable
    (weights) -> (terms, objective-ready dict) that replays the workload
    through `run_with_faults` with the candidate weights baked into a
    fresh Simulator config (weights are traced operands since ISSUE 6,
    so the per-candidate Simulator shares the cached engines — no
    recompile) under the SAME seeded fault schedule every generation.
    Local-trace mode only: the remote job plane has no fault operands
    yet (ROADMAP names that lift)."""
    from tpusim.sim.driver import Simulator, SimulatorConfig

    base = base_cfg or SimulatorConfig()

    def evaluate(weights) -> dict:
        cfg = SimulatorConfig(
            policies=tuple(
                (name, int(w)) for (name, _), w in zip(policies, weights)
            ),
            gpu_sel_method=base.gpu_sel_method,
            norm_method=base.norm_method,
            dim_ext_method=base.dim_ext_method,
            engine=base.engine,
            seed=base.seed,
            report_per_event=False,
            shuffle_pod=False,
        )
        sim = Simulator(nodes, cfg)
        sim.set_workload_pods(list(workload_pods))
        res = sim.run_with_faults(fault_cfg)
        terms = terms_from_simulate(
            res, sim.node_total_milli_gpu, sim.typical
        )
        terms["weights"] = [int(w) for w in weights]
        terms["seed"] = int(base.seed)
        return terms

    return evaluate
