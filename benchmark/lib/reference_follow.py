"""One lane held to the plain reference over ALL its events, with the
reference's own tolerance applied event by event.

`reference_fgd.replay` replays a trace on its own and names the first event
a near-integer score could decide (`first_undecided`); what follows that
event cannot be compared, because the program (float32) and the reference
(float64) may each have gone the other way there, both within the rule. On
a pod list with many typical pods (gpuspec33: 130) such an event comes early
in one lane of two (event 52 of 512: my chip run, PR 32), and a comparison
that stops there holds a tenth of the lane. So this file walks the lane
beside the reference instead: every event is scored by the reference's own
functions (`feasible_nodes`, `score_nodes`, `reserve_devices`,
`affinity_class`: nothing of the program) on the state the lane's own
placements have led to, and the lane's choice is held to it:

- it IS the reference's choice (best total, then smallest rank; the
  device the score chose): equal;
- it is another node or device, and only scores within `NEAR` of an integer
  taken the other way could make it the choice (`near`, as `score_nodes`
  flags them: with the lane's total raised by one score step where it is
  near, and every other total lowered by one where that one is, the lane's
  node wins by total and then rank): ADMITTED, counted, and the walk goes on
  from the lane's choice;
- anything else differs: counted, and the walk ends (what follows a real
  difference is another replay).

A lane that equals `reference_fgd.replay` event for event is never
admitted anything. The final state is the walk's own and is compared too.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib import reference_fgd as ref

STATE_FIELDS = ("cpu_left", "mem_left", "gpu_left", "aff_cnt")


def _admissible(total, near, rank, weight, j) -> bool:
    """Whether node j can be the choice once every near score may move by
    one step: with its own total at most one step up and every other total
    at most one step down, it beats each other node, by total or, at equal
    totals, by the smaller rank."""
    up = total[j] + weight * near[j]
    down = total - weight * near
    beats = (up > down) | ((up == down) & (rank[j] < rank))
    beats[j] = True
    return bool(beats.all())


def walk(cluster: dict, pods: dict, typical: dict, rank, lane,
         weight: int = 1000) -> dict:
    """Hold `lane` (placed_node, dev_mask, ever_failed, state) to the
    reference over every event of the creation trace `pods`; inputs as
    `reference_fgd.replay` takes them. Returns `events_held` (all of them
    unless an event differs), `differing` {field: entries}, `admitted`
    (events at which the lane's choice was another one the tolerance
    admits), `near_entries`."""
    as_i64 = lambda a: np.asarray(a, np.int64)  # noqa: E731
    cpu_left, mem_left = as_i64(cluster["cpu_cap"]), as_i64(cluster["mem_cap"])
    gpu_cnt, gpu_type = as_i64(cluster["gpu_cnt"]), as_i64(cluster["gpu_type"])
    n = len(cpu_left)
    gpu_left = ((np.arange(ref.MAX_GPUS)[None, :] < gpu_cnt[:, None])
                * np.int64(ref.MILLI))
    aff_cnt = np.zeros((n, ref.AFFINITY_CLASSES), np.int64)
    rank = as_i64(rank)
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
    admitted = near_entries = 0
    held = p

    for e in range(p):
        pod = tuple(int(f[e]) for f in fields)
        cand = np.flatnonzero(ref.feasible_nodes(
            cpu_left, mem_left, gpu_left, gpu_cnt, gpu_type, pod))
        if cand.size == 0:  # unschedulable: the lane rejected it too
            failed[e] = True
            if lane_node[e] >= 0 or lane_mask[e].any():
                differing["placed_node"] += 1
                held = e
                break
            continue
        score, device, near = ref.score_nodes(
            cpu_left[cand], gpu_left[cand], gpu_type[cand], pod, tp)
        near_entries += int(near.sum())
        total = weight * score
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
            if not _admissible(total, near, rank[cand], weight, j):
                differing["placed_node"] += 1
                held = e
                break
            other = True
        node = int(cand[j])
        mask = ref.reserve_devices(gpu_left[node], pod, int(device[j]))
        if not np.array_equal(mask, lane_mask[e]):
            # another device of the node: only a share-GPU pod's near score
            # can choose it, and it has to fit there
            took = np.flatnonzero(lane_mask[e])
            if not (near[j] and pod[3] == 1 and pod[2] < ref.MILLI
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
        cls = ref.affinity_class(pod)
        if cls >= 0:
            aff_cnt[node, cls] += 1

    if held == p:  # the walk's own final state, flags included
        state = dict(zip(STATE_FIELDS, (cpu_left, mem_left, gpu_left, aff_cnt)))
        differing["ever_failed"] = int(
            (np.asarray(lane.ever_failed) != failed).sum())
        for f in STATE_FIELDS:
            differing[f"state.{f}"] = int(
                (np.asarray(getattr(lane.state, f)) != state[f]).sum())
    return {"events_held": held, "differing": differing,
            "admitted": int(admitted), "near_entries": near_entries}
