"""One lane of a LOADED cluster walked beside the plain reference over ALL
its events: the bookkeeping at every event, the full FGD scoring rule at
the events asked, and the reference's own state kept at the events whose
report is to be recomputed.

`reference_follow.walk` scores every event, which costs 11 ms an event on
the loaded 1,213-node cluster (122 s for the 10,811 events of a whole
tuned trace: ISSUE 41's CPU run). A cell that replays traces to their end
cannot pay that for a lane in every run, and most of what a loaded cluster
adds needs no score: that the chosen node passes the reference's Filter on
the reference's state, that the devices taken are ones the reference's
Reserve admits, that EVERY rejected create has no feasible node in the
reference's state, and that the final state is the reference's. So this
walk holds every event to those, and the events in `scored` (drawn by the
caller; all of them, to run the walk whole) to `reference_follow`'s rule
besides: the lane's node IS the reference's choice, or one only scores
within `NEAR` of an integer taken the other way admit.

What the reference replays it makes itself: `tuned_order` is the
artifact's shuffle and tuning (simulator.go:975-1013 SortClusterPods,
:1200-1282 TunePodsByNodeTotalResource) over the CSV's rows, in numpy's
generator discipline (one generator seeded by the tuning seed drives the
shuffle and then the draws), so a trace the program prepared can be held
to it before it is replayed.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib import reference_fgd as ref
from benchmark.lib.reference_follow import STATE_FIELDS, _admissible


def tuned_order(names, gpu_milli, gpu_num, capacity_milli: int, ratio: float,
                seed: int) -> list:
    """Rows of the pod list, in the order a shuffled trace tuned to `ratio`
    x `capacity_milli` creates them: the rows name-sorted and shuffled, then
    random rows removed while the GPU milli asked for exceed the target, or
    random rows of the shuffled list appended as clones while one more
    stays under it. The stopping rule of tuning up is the Go text's, bug
    for bug: the test adds the candidate's PER-GPU milli, the total its
    milli x GPUs (simulator.go:1271-1276)."""
    rng = np.random.default_rng(seed)
    order = sorted(range(len(names)), key=lambda i: names[i])
    rng.shuffle(order)  # a list, as the program shuffles its list of pods
    asked = [int(gpu_milli[i]) * int(gpu_num[i]) for i in range(len(names))]
    total, target = sum(asked[i] for i in order), ratio * capacity_milli
    if ratio <= 0 or total == target:
        return order
    if total > target:
        while total > target:
            at = int(rng.integers(len(order)))
            total -= asked[order.pop(at)]
        return order
    source, out = list(order), list(order)
    while True:
        row = source[int(rng.integers(len(source)))]
        if total + int(gpu_milli[row]) > target:
            return out
        total += asked[row]
        out.append(row)


def load_crossings(gpu_milli, gpu_num, capacity_milli: int) -> list:
    """The events at which the arrived GPU load reaches a new whole per
    cent of the cluster's capacity (the artifact's discrete schema: a point
    a per cent of arrived load), and the last event."""
    arrived = np.cumsum(np.asarray(gpu_milli, np.int64)
                        * np.asarray(gpu_num, np.int64))
    pct = (100 * arrived) // capacity_milli
    first = np.flatnonzero(np.diff(pct, prepend=0) > 0)
    return sorted(set(first.tolist()) | {len(arrived) - 1})


def _devices_admitted(gpu_left, pod, lane_mask) -> bool:
    """Whether the devices a lane took on a node are ones the reference's
    Reserve admits without a score: a share-GPU pod one device that fits it
    (which one is the score's to say), any other pod the devices
    gpunodeinfo.go:182-201 packs it on, in device order."""
    _cpu, _mem, milli, num, _mask = pod
    if num == 1 and 0 < milli < ref.MILLI:
        took = np.flatnonzero(lane_mask)
        return took.size == 1 and gpu_left[took[0]] >= milli
    return np.array_equal(ref.reserve_devices(gpu_left, pod, -1), lane_mask)


def walk(cluster: dict, pods: dict, typical: dict, rank, lane, weight: int,
         scored, keep) -> dict:
    """Hold `lane` (placed_node, dev_mask, ever_failed, state) to the
    reference over every event of the creation trace `pods`; inputs as
    `reference_fgd.replay` takes them. `scored`: the events held to the
    full scoring rule; `keep`: the events after which the reference's
    (cpu_left, gpu_left) are copied into `states`.

    Returns `events_held` (all of them unless an event differs),
    `differing` {field: entries}, `scored` (events the scoring rule held),
    `rejected` (creates with no feasible node, which the lane rejected
    too), `admitted`, `near_entries`, and `states` {event: (cpu_left,
    gpu_left)}."""
    as_i64 = lambda a: np.asarray(a, np.int64)  # noqa: E731
    # the walk's own state: copies, the cluster's capacities stay the caller's
    cpu_left, mem_left = (as_i64(cluster[f]).copy()
                          for f in ("cpu_cap", "mem_cap"))
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
    scored, keep = set(int(e) for e in scored), set(int(e) for e in keep)
    failed = np.zeros(p, bool)
    differing = {"placed_node": 0, "dev_mask": 0}
    admitted = near_entries = held_scored = 0
    states = {}
    held = p

    for e in range(p):
        pod = tuple(int(f[e]) for f in fields)
        cand = np.flatnonzero(ref.feasible_nodes(
            cpu_left, mem_left, gpu_left, gpu_cnt, gpu_type, pod))
        at = np.flatnonzero(cand == lane_node[e])
        if cand.size == 0:  # no feasible node: the lane rejected it too
            failed[e] = True
            if lane_node[e] >= 0 or lane_mask[e].any():
                differing["placed_node"] += 1
                held = e
                break
        elif at.size == 0:  # rejected by the lane, or placed where it cannot
            differing["placed_node"] += 1
            held = e
            break
        else:
            node, mask = int(lane_node[e]), lane_mask[e]
            if e in scored:
                j = int(at[0])
                score, device, near = ref.score_nodes(
                    cpu_left[cand], gpu_left[cand], gpu_type[cand], pod, tp)
                near_entries += int(near.sum())
                total = weight * score
                winners = np.flatnonzero(total == total.max())
                w = int(winners[np.argmin(rank[cand][winners])])
                other = j != w
                if other and not _admissible(
                        total, near, rank[cand], weight, j):
                    differing["placed_node"] += 1
                    held = e
                    break
                want = ref.reserve_devices(gpu_left[node], pod, int(device[j]))
                if not np.array_equal(want, mask):
                    # another device of the node: only a share-GPU pod's
                    # near score can choose it, and it has to fit there
                    if not (near[j] and pod[3] == 1 and pod[2] < ref.MILLI
                            and _devices_admitted(gpu_left[node], pod, mask)):
                        differing["dev_mask"] += 1
                        held = e
                        break
                    other = True
                admitted += other
                held_scored += 1
            elif not _devices_admitted(gpu_left[node], pod, mask):
                differing["dev_mask"] += 1
                held = e
                break
            cpu_left[node] -= pod[0]
            mem_left[node] -= pod[1]
            gpu_left[node] -= mask * pod[2]
            cls = ref.affinity_class(pod)
            if cls >= 0:
                aff_cnt[node, cls] += 1
        if e in keep:
            states[e] = (cpu_left.copy(), gpu_left.copy())

    if held == p:  # the walk's own final state, flags included
        state = dict(zip(STATE_FIELDS,
                         (cpu_left, mem_left, gpu_left, aff_cnt)))
        differing["ever_failed"] = int(
            (np.asarray(lane.ever_failed) != failed).sum())
        for f in STATE_FIELDS:
            differing[f"state.{f}"] = int(
                (np.asarray(getattr(lane.state, f)) != state[f]).sum())
    return {"events_held": held, "differing": differing,
            "scored": held_scored, "rejected": int(failed.sum()),
            "admitted": int(admitted), "near_entries": near_entries,
            "states": states}
