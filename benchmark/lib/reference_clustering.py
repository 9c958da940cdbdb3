"""One lane's replay of a creation trace under GpuClustering with `best`
devices, in numpy alone: the plain reference behind the configuration
openb-clustering (the FGD artifact's row 03-GpuClustering; PERF.md section
4).

This file imports nothing of `tpusim`, not even the other references: the
sequential oracle shares the program's score kernel, its device choice and
its commit, this file shares nothing, and it keeps its OWN affinity counts,
which the score reads at every event. It follows the Go text of the
reference scheduler: the score plugin/gpu_clustering_score.go:32-56, the
pod's affinity class open-gpu-share/utils/pod.go:111-123, the Filter
plugin/open_gpu_share.go:81-118, the device choice
open-gpu-share/cache/gpunodeinfo.go:136-204 (AllocateGpuId), selectHost
generic_scheduler.go:187-212. Every quantity is an integer, so a lane of
the program either equals this replay entry for entry or is wrong: there is
no tolerance and no "near" score.

Inputs are data, not code under test: the cluster and the trace as integer
arrays and the lane's tie-break rank (the reference's random node-name
prefixes, simulator.go:584-588, as a permutation). A copy of this file is
the benchmark's: benchmark/lib/reference_clustering.py.

Departures from the Go text, each for a reason:

- nodes are scored as arrays (one numpy expression over all nodes) where
  Go loops over them 16 at a time; the per-node arithmetic is the Go
  loop's;
- a node's affinity map (`GpuAffinity`, class name -> pods of it) is a row
  of nine counts, share-gpu first and then 1..8 whole GPUs: a class is "on
  the node" while its count is positive, which is when Go keeps its key;
- the score's packing term is Go's integer arithmetic as the program
  states it: 25 * (8000 - total_gpu_left) // 8000 with 8,000 = MaxSpecGpu
  milli, a floor of a non-negative quotient, so numpy's `//` is Go's `/`;
- every create is placed or rejected at once, and a rejected create leaves
  the state untouched (simulator.go:444-455; it still ARRIVED, which is the
  report's to count: simulator.go:406-408); the retry queue and deletion
  events are outside a creation trace;
- a node's GPU model and a pod's `gpu_spec` are an id and a bitmask of ids
  (data/README.md), where Go compares model names
  (utils.go:957-1005 IsNodeAccessibleToPod);
- selectHost's "smallest lexicographic node name among the best" is `rank`,
  smaller wins;
- pods carry no nodeSelector: the recorded trace has none.
"""

from __future__ import annotations

import numpy as np

MILLI = 1000  # one whole GPU
MAX_GPUS = 8  # devices a node row holds; absent devices are 0 milli
MAX_SPEC_GPU = MAX_GPUS * MILLI  # the packing term's denominator
QUARTILE = 25  # MaxNodeScore / 4
AFFINITY_CLASSES = 9  # share-gpu, then 1..8 whole GPUs (pod.go:111-123)
STATE_FIELDS = ("cpu_left", "mem_left", "gpu_left", "aff_cnt")


def affinity_class(pod) -> int:
    """pod.go:111-123: share-gpu 0, N whole GPUs N, no GPU -1."""
    _cpu, _mem, milli, num, _mask = pod
    if num == 0:
        return -1
    return 0 if (num == 1 and milli < MILLI) else int(num)


def _accessible(node_type, pod_mask):
    """utils.go:957-1005: no constraint, or the node's model is allowed."""
    node_bit = np.where(node_type >= 0, 1 << np.maximum(node_type, 0), 0)
    return (pod_mask == 0) | ((pod_mask & node_bit) != 0)


def feasible_nodes(cpu_left, mem_left, gpu_left, gpu_cnt, gpu_type, pod):
    """Filter -> bool[N]: NodeResourcesFit (CPU, memory) and
    open_gpu_share.go:81-118: a GPU pod needs a GPU node of an allowed model
    on which AllocateGpuId finds devices: each yields floor(left / milli)
    units, the pod needs `num`."""
    cpu, mem, milli, num, mask = pod
    ok = (cpu_left >= cpu) & (mem_left >= mem)
    if milli * num > 0:
        units = (gpu_left // milli).sum(-1)
        ok &= (gpu_cnt > 0) & _accessible(gpu_type, mask) & (units >= num)
    return ok


def score_nodes(gpu_left, aff_cnt, pod) -> np.ndarray:
    """gpu_clustering_score.go:32-56 -> i64[N], five bands:

      75 + pack  the pod's class is the ONLY class on the node
      50 + pack  several classes on the node, the pod's among them
      25 + pack  an idle node (no GPU pod on it)
       0 + pack  only other classes on the node
       0         the pod asks for no GPU (whatever the node holds)

    pack = 25 * (8000 - total_gpu_left) // 8000: fuller nodes first inside
    a band, 0 on a node with eight idle GPUs, 25 with none left."""
    cls = affinity_class(pod)
    n = len(gpu_left)
    if cls < 0:
        return np.zeros(n, np.int64)
    classes = (aff_cnt > 0).sum(-1)
    has = aff_cnt[:, cls] > 0
    base = np.where(has, np.where(classes == 1, 3 * QUARTILE, 2 * QUARTILE),
                    np.where(classes == 0, QUARTILE, 0))
    pack = QUARTILE * (MAX_SPEC_GPU - gpu_left.sum(-1)) // MAX_SPEC_GPU
    return (base + pack).astype(np.int64)


def select_host(total, feasible, rank) -> int:
    """generic_scheduler.go:187-212: the best total among the feasible
    nodes, then the smallest rank; -1 with no feasible node."""
    cand = np.flatnonzero(feasible)
    if cand.size == 0:
        return -1
    best = cand[total[cand] == total[cand].max()]
    return int(best[np.argmin(rank[best])])


def reserve_devices(gpu_left, pod) -> np.ndarray:
    """AllocateGpuId (gpunodeinfo.go:136-204) on the chosen node's devices
    -> bool[8], the method `best`: a pod of ONE GPU takes the fitting device
    with the least free milli, the first on ties (:169-181); a pod of
    several is packed greedily in device order, floor(left / milli) units a
    device, until its `num` are found (:182-201)."""
    _cpu, _mem, milli, num, _mask = pod
    mask = np.zeros(MAX_GPUS, bool)
    if milli * num == 0:
        return mask
    if num == 1:
        fits = np.flatnonzero(gpu_left >= milli)
        mask[fits[np.argmin(gpu_left[fits])]] = True  # argmin: first on ties
        return mask
    need = num
    for d in range(MAX_GPUS):
        take = min(need, int(gpu_left[d]) // milli)
        mask[d] = take > 0
        need -= take
    return mask


def replay(cluster: dict, pods: dict, rank, weight: int = 1000, keep=(),
           count_affinity: bool = True) -> dict:
    """Replay `pods` (creations, in order) on the empty `cluster`.

    cluster: cpu_cap, mem_cap, gpu_cnt, gpu_type (model id, -1 none), [N].
    pods: cpu, mem, gpu_milli, gpu_num, gpu_mask (allowed-model bits), [P].
    rank: i[N], the lane's tie-break permutation, smaller wins.
    keep: events after which (cpu_left, gpu_left) are copied into `states`
    (what a report recomputed from scratch reads).
    count_affinity False is the CONTROL, never the reference: a replay whose
    Bind drops the add into the affinity counts, so every node looks idle to
    the score for ever (what a program that defers or loses the add would
    compute); tests hold that it differs.

    Returns placed_node i32[P] (-1 rejected), dev_mask bool[P, 8],
    ever_failed bool[P], the final cpu_left / mem_left / gpu_left / aff_cnt
    (i32), and `states` {event: (cpu_left, gpu_left)}."""
    as_i64 = lambda a: np.asarray(a, np.int64)  # noqa: E731
    cpu_left, mem_left = (as_i64(cluster[f]).copy()
                          for f in ("cpu_cap", "mem_cap"))
    gpu_cnt, gpu_type = as_i64(cluster["gpu_cnt"]), as_i64(cluster["gpu_type"])
    n = len(cpu_left)
    gpu_left = ((np.arange(MAX_GPUS)[None, :] < gpu_cnt[:, None])
                * np.int64(MILLI))
    aff_cnt = np.zeros((n, AFFINITY_CLASSES), np.int64)
    rank = as_i64(rank)
    fields = [as_i64(pods[f])
              for f in ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask")]
    p = len(fields[0])
    placed = np.full(p, -1, np.int32)
    dev_mask = np.zeros((p, MAX_GPUS), bool)
    keep = set(int(e) for e in keep)
    states = {}

    for e in range(p):
        pod = tuple(int(f[e]) for f in fields)
        node = select_host(
            weight * score_nodes(gpu_left, aff_cnt, pod),
            feasible_nodes(cpu_left, mem_left, gpu_left, gpu_cnt, gpu_type,
                           pod), rank)
        if node >= 0:  # else unschedulable: the state stays as it is
            mask = reserve_devices(gpu_left[node], pod)
            # Bind: every field of the node's state, its counts among them
            cpu_left[node] -= pod[0]
            mem_left[node] -= pod[1]
            gpu_left[node] -= mask * pod[2]
            cls = affinity_class(pod)
            if cls >= 0 and count_affinity:
                aff_cnt[node, cls] += 1
            placed[e], dev_mask[e] = node, mask
        if e in keep:
            states[e] = (cpu_left.copy(), gpu_left.copy())

    return {
        "placed_node": placed,
        "dev_mask": dev_mask,
        "ever_failed": placed < 0,
        "cpu_left": cpu_left.astype(np.int32),
        "mem_left": mem_left.astype(np.int32),
        "gpu_left": gpu_left.astype(np.int32),
        "aff_cnt": aff_cnt.astype(np.int32),
        "states": states,
    }


def lane_differences(lane, want: dict):
    """({field: entries that differ}, first event) between a lane of the
    program (placed_node, dev_mask, ever_failed, state.<STATE_FIELDS>) and
    `replay`'s result; the first event is the first whose node, devices or
    flag differ, -1 where none does. A misshapen field counts every
    entry."""
    out, first = {}, -1
    pairs = [(f, getattr(lane, f), want[f])
             for f in ("placed_node", "dev_mask", "ever_failed")]
    pairs += [(f"state.{f}", getattr(lane.state, f), want[f])
              for f in STATE_FIELDS]
    for name, got, ref in pairs:
        got, ref = np.asarray(got), np.asarray(ref)
        if got.shape != ref.shape:
            out[name], first = int(max(got.size, ref.size, 1)), 0
            continue
        differs = got != ref
        out[name] = int(differs.sum())
        if not name.startswith("state.") and differs.any():
            at = int(np.flatnonzero(differs.reshape(len(ref), -1).any(1))[0])
            first = at if first < 0 else min(first, at)
    return out, first
