"""One lane of a PWR+FGD wave held to the plain reference
(`reference_mix.py`) over ALL its events: `reference_follow.py`'s walk, for
two policies under the lane's own weight row.

Every event is scored by the reference's own functions on the state the
lane's placements have led to (nothing of the program), and the lane's
choice is held to it under one rule. Every integer is exact: feasibility,
the device mask, every state field, PWR's normalized scores and the totals,
which are integer arithmetic given the raw scores. A RAW score may differ
by 1 between the float32 program and this float64 reference only where the
reference's value lies within `reference_fgd.NEAR` of an integer before it
is floored (FGD) or truncated (PWR); such entries are counted and printed.

- FGD's near entries move a node's total by one FGD weight, so a choice
  other than the reference's is ADMITTED where they alone could make it
  (`reference_follow`'s test, the step being the lane's FGD weight), and
  the walk goes on from the lane's choice;
- a near PWR entry would move the extrema and with them every node's
  normalized score. None can occur while the energy tables hold whole
  watts (old - new is then a whole number in any precision); one that did
  would be counted, and a choice differing at its event is not admitted;
- anything else differs: counted, and the walk ends.

At the end the walk's own final state is compared, and the lane's
`power_cpu_w` / `power_gpu_w` with the reference's energy model over that
state (exactly: whole watts, 6,212 GPUs x 400 W under 2^24).
"""

from __future__ import annotations

import numpy as np

from benchmark.lib import reference_fgd as fgd
from benchmark.lib import reference_follow, reference_mix

STATE_FIELDS = reference_follow.STATE_FIELDS


def walk(cluster: dict, pods: dict, typical: dict, rank, lane, weights,
         energy: dict) -> dict:
    """Hold `lane` (placed_node, dev_mask, ever_failed, state, power_cpu_w,
    power_gpu_w) to the reference over every event of the creation trace
    `pods` under the weight row `weights` = (PWR, FGD); inputs as
    `reference_mix.replay` takes them. Returns `events_held`, `differing`
    {field: entries}, `admitted`, `near_entries` (FGD's) and
    `pwr_near_entries`."""
    as_i64 = lambda a: np.asarray(a, np.int64)  # noqa: E731
    cpu_cap, mem_left = as_i64(cluster["cpu_cap"]), as_i64(cluster["mem_cap"])
    cpu_left = cpu_cap.copy()
    gpu_cnt, gpu_type = as_i64(cluster["gpu_cnt"]), as_i64(cluster["gpu_type"])
    cpu_type = as_i64(cluster["cpu_type"])
    n = len(cpu_left)
    gpu_left = ((np.arange(fgd.MAX_GPUS)[None, :] < gpu_cnt[:, None])
                * np.int64(fgd.MILLI))
    aff_cnt = np.zeros((n, fgd.AFFINITY_CLASSES), np.int64)
    rank = as_i64(rank)
    w_fgd = int(weights[1])
    tp = tuple(as_i64(typical[f])
               for f in ("cpu", "gpu_milli", "gpu_num", "gpu_mask")) + (
        np.asarray(typical["freq"], np.float64),)
    fields = [as_i64(pods[f])
              for f in ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask")]
    lane_node = np.asarray(lane.placed_node)
    lane_mask = np.asarray(lane.dev_mask, bool)
    p = len(fields[0])
    failed = np.zeros(p, bool)
    differing = {"placed_node": 0, "dev_mask": 0}
    admitted = near_entries = pwr_near_entries = 0
    held = p

    for e in range(p):
        pod = tuple(int(f[e]) for f in fields)
        cand = np.flatnonzero(fgd.feasible_nodes(
            cpu_left, mem_left, gpu_left, gpu_cnt, gpu_type, pod))
        if cand.size == 0:  # unschedulable: the lane rejected it too
            failed[e] = True
            if lane_node[e] >= 0 or lane_mask[e].any():
                differing["placed_node"] += 1
                held = e
                break
            continue
        total, device, near, pwr_near = reference_mix.score_candidates(
            (cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type), cand,
            pod, tp, weights, energy)
        near_entries += int(near.sum())
        pwr_near_entries += int(pwr_near.sum())
        winners = np.flatnonzero(total == total.max())
        w = int(winners[np.argmin(rank[cand][winners])])
        at = np.flatnonzero(cand == lane_node[e])
        if at.size == 0:  # rejected by the lane, or placed where it cannot be
            differing["placed_node"] += 1
            held = e
            break
        j = int(at[0])
        other = False
        if j != w:
            if pwr_near.any() or not reference_follow._admissible(
                    total, near, rank[cand], w_fgd, j):
                differing["placed_node"] += 1
                held = e
                break
            other = True
        node = int(cand[j])
        mask = fgd.reserve_devices(gpu_left[node], pod, int(device[j]))
        if not np.array_equal(mask, lane_mask[e]):
            # another device of the node: only a share-GPU pod's near FGD
            # score can choose it, and it has to fit there
            took = np.flatnonzero(lane_mask[e])
            if not (near[j] and pod[3] == 1 and pod[2] < fgd.MILLI
                    and took.size == 1
                    and gpu_left[node, took[0]] >= pod[2]):
                differing["dev_mask"] += 1
                held = e
                break
            mask, other = lane_mask[e], True
        admitted += other
        cpu_left[node] -= pod[0]
        mem_left[node] -= pod[1]
        gpu_left[node] -= mask * pod[2]
        cls = fgd.affinity_class(pod)
        if cls >= 0:
            aff_cnt[node, cls] += 1

    if held == p:  # the walk's own final state, flags and watts included
        state = dict(zip(STATE_FIELDS, (cpu_left, mem_left, gpu_left, aff_cnt)))
        differing["ever_failed"] = int(
            (np.asarray(lane.ever_failed) != failed).sum())
        for f in STATE_FIELDS:
            differing[f"state.{f}"] = int(
                (np.asarray(getattr(lane.state, f)) != state[f]).sum())
        watts = reference_mix.cluster_power(
            cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type, energy)
        differing["power_cpu_w, power_gpu_w"] = sum(
            got != want for got, want in
            zip((lane.power_cpu_w, lane.power_gpu_w), watts))
    return {"events_held": held, "differing": differing,
            "admitted": int(admitted), "near_entries": near_entries,
            "pwr_near_entries": pwr_near_entries}
