"""One lane of a create/delete stream held to the plain reference over ALL
its events: `reference_follow.walk`'s rule, with deletions.

`reference_follow.py` walks a creation trace, where a lane's final
`placed_node` IS its choice at every event. A stream with deletions hides
that: a pod deleted later reads -1 at the end. So this walk reads the
lane's RECORD of its events (`event_node`, `event_dev`: the node chosen or
freed and the devices touched at every event) and ties the record to what
the lane returned at the end:

- a creation is scored by the reference's own functions on the state the
  record has led to, and the recorded choice is held to it exactly as
  `reference_follow.walk` holds a lane's: equal, or ADMITTED where only
  scores within `NEAR` of an integer could decide otherwise (counted; the
  walk goes on from the recorded choice), or differing (the walk ends);
- a deletion has no tolerance: the walk's own bookkeeping says where the
  pod sits and on which devices (`reference_clock.replay`'s release, event
  by event); the record has to name that node and those devices, or
  nothing where the pod is not placed, and the walk gives them back;
- after the last event the walk's placed pods, device masks and failure
  flags and every field of its state have to equal the lane's final
  arrays: a record that is not the lane's own history ends on another
  state than the lane did.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib import reference_clock as clock
from benchmark.lib import reference_fgd as ref
from benchmark.lib.reference_follow import STATE_FIELDS, _admissible


def walk(cluster: dict, pods: dict, events, typical: dict, rank, lane,
         record, weight: int = 1000) -> dict:
    """Hold `record` = (event_node [E], event_dev [E, 8]) to the reference
    over every event of `events` = (kind [E], pod [E]), and `lane`'s final
    placed_node, dev_mask, ever_failed and state to where the walk ends;
    the other inputs as `reference_clock.replay` takes them. Returns what
    `reference_follow.walk` returns, and `deletes_held`."""
    as_i64 = lambda a: np.asarray(a, np.int64)  # noqa: E731
    # copies: the two are written, and int64 capacities would alias
    cpu_left = np.array(cluster["cpu_cap"], np.int64)
    mem_left = np.array(cluster["mem_cap"], np.int64)
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
    kinds, idxs = (as_i64(a) for a in events)
    rec_node = np.asarray(record[0])
    rec_dev = np.asarray(record[1], bool)
    p, e_n = len(fields[0]), len(kinds)
    placed = np.full(p, -1, np.int64)
    masks = np.zeros((p, ref.MAX_GPUS), bool)
    failed = np.zeros(p, bool)
    differing = {"event_node": 0, "event_dev": 0}
    admitted = near_entries = deletes_held = 0
    held = e_n

    def bind(node, pod, mask, sign):
        cpu_left[node] -= sign * pod[0]
        mem_left[node] -= sign * pod[1]
        gpu_left[node] -= sign * mask * pod[2]
        cls = ref.affinity_class(pod)
        if cls >= 0:
            aff_cnt[node, cls] += sign

    for e in range(e_n):
        i = int(idxs[e])
        pod = tuple(int(f[i]) for f in fields)
        if kinds[e] == clock.EV_DELETE:
            if rec_node[e] != placed[i]:
                differing["event_node"] += 1
            elif not np.array_equal(rec_dev[e], masks[i]):
                differing["event_dev"] += 1
            if differing["event_node"] or differing["event_dev"]:
                held = e
                break
            if placed[i] >= 0:
                bind(int(placed[i]), pod, masks[i].copy(), -1)
                placed[i], masks[i] = -1, False
                deletes_held += 1
            continue
        if kinds[e] != clock.EV_CREATE:
            continue
        cand = np.flatnonzero(ref.feasible_nodes(
            cpu_left, mem_left, gpu_left, gpu_cnt, gpu_type, pod))
        failed[i] = cand.size == 0
        if cand.size == 0:  # unschedulable: the lane rejected it too
            if rec_node[e] >= 0 or rec_dev[e].any():
                differing["event_node"] += 1
                held = e
                break
            continue
        score, device, near = ref.score_nodes(
            cpu_left[cand], gpu_left[cand], gpu_type[cand], pod, tp)
        near_entries += int(near.sum())
        total = weight * score
        winners = np.flatnonzero(total == total.max())
        w = int(winners[np.argmin(rank[cand][winners])])
        at = np.flatnonzero(cand == rec_node[e])
        if at.size == 0:  # rejected by the lane, or placed where it cannot be
            differing["event_node"] += 1
            held = e
            break
        j = int(at[0])
        other = False
        if j != w:
            if not _admissible(total, near, rank[cand], weight, j):
                differing["event_node"] += 1
                held = e
                break
            other = True
        node = int(cand[j])
        mask = ref.reserve_devices(gpu_left[node], pod, int(device[j]))
        if not np.array_equal(mask, rec_dev[e]):
            # another device of the node: only a share-GPU pod's near score
            # can choose it, and it has to fit there
            took = np.flatnonzero(rec_dev[e])
            if not (near[j] and pod[3] == 1 and pod[2] < ref.MILLI
                    and took.size == 1
                    and gpu_left[node, took[0]] >= pod[2]):
                differing["event_dev"] += 1
                held = e
                break
            mask, other = rec_dev[e].copy(), True
        admitted += other
        bind(node, pod, mask, 1)
        placed[i], masks[i] = node, mask

    if held == e_n:  # where the walk ends against what the lane returned
        state = dict(zip(STATE_FIELDS, (cpu_left, mem_left, gpu_left, aff_cnt)))
        for name, mine in (("placed_node", placed), ("dev_mask", masks),
                           ("ever_failed", failed)):
            differing[name] = int(
                (np.asarray(getattr(lane, name)) != mine).sum())
        for f in STATE_FIELDS:
            differing[f"state.{f}"] = int(
                (np.asarray(getattr(lane.state, f)) != state[f]).sum())
    return {"events_held": held, "differing": differing,
            "admitted": int(admitted), "near_entries": near_entries,
            "deletes_held": deletes_held}
