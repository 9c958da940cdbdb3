"""One lane's replay of a creation trace under FGD, in numpy alone: the plain
reference behind the openb configuration (PERF.md section 4).

Nothing here imports `tpusim.policies`, `tpusim.ops` or `tpusim.sim`: the
sequential oracle shares the program's score kernels, this file shares
nothing. It follows the Go text of the reference scheduler in its DIRECT
form (plugin/fgd_score.go, pkg/utils/frag.go, pkg/type/resource.go,
open-gpu-share's gpunodeinfo.go): every node's fragmentation is evaluated
in full on the current state and again on each hypothetical state, with
none of the program's decompositions, tables or deferred commits, and in
float64 as Go computes it (the program computes in float32).

Inputs are data, not code under test: the cluster and the trace as integer
arrays, the typical pods (frag.go:285-380, the target workload) as arrays,
and the lane's tie-break rank (the reference's random node-name prefixes,
simulator.go:584-588, as a permutation). A copy of this file is the
benchmark's: benchmark/lib/reference_fgd.py.

Departures from the Go text, each for a reason:

- nodes are scored as arrays (one numpy expression over the feasible
  nodes) where Go loops over them 16 at a time; the per-node arithmetic is
  the Go loop's, and a float64 sum over the typical pods runs pairwise
  where Go's runs left to right (differences near 1e-13, see `NEAR`);
- every create is placed or rejected at once; the reference's retry queue
  and deletion events are outside a creation trace;
- a node's GPU model and a pod's `gpu_spec` are an id and a bitmask of ids
  (data/README.md), where Go compares model names
  (utils.go:957-1005 IsNodeAccessibleToPod);
- selectHost's "smallest lexicographic node name among the best" is
  `rank`, smaller wins (generic_scheduler.go:187-212);
- pods carry no nodeSelector: the recorded trace has none.
"""

from __future__ import annotations

import numpy as np

MILLI = 1000  # one whole GPU
MAX_GPUS = 8  # devices a node row holds; absent devices are 0 milli
MAX_NODE_SCORE = 100
AFFINITY_CLASSES = 9  # share-gpu, then 1..8 whole GPUs (pod.go:111-123)

# A score is floor(x) of a float. The program computes x in float32, this
# file in float64; they can differ by 1 only where x lies this close to an
# integer (the float32 path's error in x stays below 1e-4: frag scores
# under 8,000 carry 1e-3 of rounding, divided by 1,000, times a slope of at
# most 25). Such entries are counted, and an event one of them could decide
# is reported, never silently accepted.
NEAR = 1e-3


def _accessible(node_type, pod_mask):
    """utils.go:957-1005: no constraint, or the node's model is allowed."""
    node_bit = np.where(node_type >= 0, 1 << np.maximum(node_type, 0), 0)
    return (pod_mask == 0) | ((pod_mask & node_bit) != 0)


def frag_scores(cpu_left, gpu_left, gpu_type, typical) -> np.ndarray:
    """frag.go:148-203 NodeGpuShareFragAmountScore for M node states:
    f64[M], the expected idle GPU milli a typical pod cannot use.

    cpu_left i64[M], gpu_left i64[M, 8], gpu_type i64[M]. For each typical
    pod (frag.go:460-493 GetNodePodFrag): a pod the node satisfies (Q3)
    leaves only the devices individually too small for it as fragment
    (frag.go:205-213); to any other pod all idle milli is fragment."""
    t_cpu, t_milli, t_num, t_mask, t_freq = typical
    g = gpu_left[:, None, :]  # [M, 1, 8]
    milli = t_milli[None, :, None]  # [1, T, 1]
    total = gpu_left.sum(-1)  # [M]
    can_host = ((g >= milli) & (milli > 0)).sum(-1) >= t_num[None, :]
    satisfied = (
        (t_milli > 0)[None, :]  # a pod without GPU is never Q3 (XL / XR)
        & _accessible(gpu_type[:, None], t_mask[None, :])
        & can_host
        & (cpu_left[:, None] >= t_cpu[None, :])
    )  # [M, T]
    too_small = np.where(g < milli, g, 0).sum(-1)  # [M, T]
    fragment = np.where(satisfied, too_small, total[:, None])
    return (t_freq[None, :] * fragment).sum(-1)


def _sigmoid_x(cur, new):
    """fgd_score.go:124: the value whose floor is the score."""
    return 1.0 / (1.0 + np.exp(-(cur - new) / 1000.0)) * MAX_NODE_SCORE


def _near(x, cur, new):
    """Entries whose floor the float32 program may take on the other side.
    Equal frag scores are exempt: x is then exactly 50 in both precisions
    (the two sides are the same sum of the same integer-valued terms)."""
    return (cur != new) & (np.abs(x - np.rint(x)) < NEAR)


def score_nodes(cpu_left, gpu_left, gpu_type, pod, typical):
    """fgd_score.go:99-148 for M candidate nodes -> (score i64[M], device
    i64[M] or -1, near bool[M]).

    A share-GPU pod (one GPU, under 1,000 milli) is tried on every device
    that fits it and keeps the best, the first on ties (:111-134, a strict
    `>`); any other pod is placed by NodeResource.Sub (:137-148)."""
    cpu, _mem, milli, num, _mask = pod
    cur = frag_scores(cpu_left, gpu_left, gpu_type, typical)
    m = len(cpu_left)
    if num == 1 and milli < MILLI:
        score = np.full(m, -1, np.int64)
        device = np.full(m, -1, np.int64)
        near_dev = np.zeros((m, MAX_GPUS), bool)
        s_dev = np.full((m, MAX_GPUS), -1, np.int64)
        for d in range(MAX_GPUS):
            fits = gpu_left[:, d] >= milli
            hyp = gpu_left.copy()
            hyp[:, d] -= milli
            new = frag_scores(cpu_left - cpu, hyp, gpu_type, typical)
            x = _sigmoid_x(cur, new)
            s = np.floor(x).astype(np.int64)
            s_dev[:, d] = np.where(fits, s, -1)
            near_dev[:, d] = fits & _near(x, cur, new)
            better = fits & (s > score)
            score = np.where(better, s, score)
            device = np.where(better, d, device)
        # a near entry within 1 of the node's best could change its score
        # or its device
        near = (near_dev & (s_dev >= score[:, None] - 1)).any(-1)
        return np.maximum(score, 0), device, near
    # resource.go:454-480 Sub: the pod's GPUs come off the fitting devices
    # with the least free milli, ties by device index (a stable sort)
    hyp = gpu_left.copy()
    if num > 0:
        order = np.argsort(gpu_left, axis=1, kind="stable")
        sorted_left = np.take_along_axis(gpu_left, order, 1)
        fit = sorted_left >= milli
        take = fit & (np.cumsum(fit, 1) <= num)
        np.put_along_axis(hyp, order, sorted_left - take * milli, 1)
    new = frag_scores(cpu_left - cpu, hyp, gpu_type, typical)
    x = _sigmoid_x(cur, new)
    return (np.floor(x).astype(np.int64), np.full(m, -1, np.int64),
            _near(x, cur, new))


def feasible_nodes(cpu_left, mem_left, gpu_left, gpu_cnt, gpu_type, pod):
    """Filter: NodeResourcesFit (CPU, memory) and open_gpu_share.go:81-118:
    a GPU pod needs a GPU node of an allowed model on which AllocateGpuId
    (gpunodeinfo.go:136-204) packs it: each device yields
    floor(left / milli) units, the pod needs `num`."""
    cpu, mem, milli, num, mask = pod
    ok = (cpu_left >= cpu) & (mem_left >= mem)
    if milli * num > 0:
        units = (gpu_left // milli).sum(-1)
        ok &= (gpu_cnt > 0) & _accessible(gpu_type, mask) & (units >= num)
    return ok


def reserve_devices(gpu_left, pod, fgd_device) -> np.ndarray:
    """Reserve on the chosen node -> bool[8]: a share-GPU pod takes the
    device its score chose (allocateGpuIdBasedOnFGDScore,
    fgd_score.go:153-156); any other GPU pod is packed in device-index
    order (gpunodeinfo.go:182-201)."""
    _cpu, _mem, milli, num, _mask = pod
    mask = np.zeros(MAX_GPUS, bool)
    if milli * num == 0:
        return mask
    if num == 1 and milli < MILLI:
        mask[fgd_device] = True
        return mask
    need = num
    for d in range(MAX_GPUS):
        take = min(need, gpu_left[d] // milli)
        mask[d] = take > 0
        need -= take
    return mask


def affinity_class(pod) -> int:
    """pod.go:111-123: share-gpu 0, N whole GPUs N, no GPU -1."""
    _cpu, _mem, milli, num, _mask = pod
    if num == 0:
        return -1
    return 0 if (num == 1 and milli < MILLI) else int(num)


def replay(cluster: dict, pods: dict, typical: dict, rank, weight: int = 1000):
    """Replay `pods` (creations, in order) on the empty `cluster`.

    cluster: cpu_cap, mem_cap, gpu_cnt, gpu_type (model id, -1 none), [N].
    pods: cpu, mem, gpu_milli, gpu_num, gpu_mask (allowed-model bits), [P].
    typical: cpu, gpu_milli, gpu_num, gpu_mask, freq, [T].
    rank: i[N], the lane's tie-break permutation, smaller wins.

    Returns placed_node i32[P] (-1 rejected), dev_mask bool[P, 8],
    ever_failed bool[P], the final cpu_left / mem_left / gpu_left /
    aff_cnt, and what the tolerance needs: `near_entries` (score entries
    within NEAR of an integer, over all events) and `first_undecided`
    (the first event such an entry could decide: a near node within 1 of
    the best total; -1 when there is none)."""
    as_i64 = lambda a: np.asarray(a, np.int64)  # noqa: E731
    cpu_left, mem_left = as_i64(cluster["cpu_cap"]), as_i64(cluster["mem_cap"])
    gpu_cnt, gpu_type = as_i64(cluster["gpu_cnt"]), as_i64(cluster["gpu_type"])
    n = len(cpu_left)
    gpu_left = (np.arange(MAX_GPUS)[None, :] < gpu_cnt[:, None]) * np.int64(MILLI)
    aff_cnt = np.zeros((n, AFFINITY_CLASSES), np.int64)
    rank = as_i64(rank)
    tp = (as_i64(typical["cpu"]), as_i64(typical["gpu_milli"]),
          as_i64(typical["gpu_num"]), as_i64(typical["gpu_mask"]),
          np.asarray(typical["freq"], np.float64))
    fields = [as_i64(pods[f])
              for f in ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask")]
    p = len(fields[0])
    placed = np.full(p, -1, np.int32)
    dev_mask = np.zeros((p, MAX_GPUS), bool)
    near_entries, first_undecided = 0, -1

    for e in range(p):
        pod = tuple(int(f[e]) for f in fields)
        cand = np.flatnonzero(feasible_nodes(
            cpu_left, mem_left, gpu_left, gpu_cnt, gpu_type, pod))
        if cand.size == 0:
            continue  # unschedulable (simulator.go:444-455)
        score, device, near = score_nodes(
            cpu_left[cand], gpu_left[cand], gpu_type[cand], pod, tp)
        total = weight * score
        best = total.max()
        # selectHost: the best total, then the smallest rank
        winners = np.flatnonzero(total == best)
        w = winners[np.argmin(rank[cand][winners])]
        near_entries += int(near.sum())
        if first_undecided < 0 and (near & (total >= best - weight)).any():
            first_undecided = e
        node = int(cand[w])
        mask = reserve_devices(gpu_left[node], pod, int(device[w]))
        # Bind: every field of the node state
        cpu_left[node] -= pod[0]
        mem_left[node] -= pod[1]
        gpu_left[node] -= mask * pod[2]
        cls = affinity_class(pod)
        if cls >= 0:
            aff_cnt[node, cls] += 1
        placed[e], dev_mask[e] = node, mask

    return {
        "placed_node": placed,
        "dev_mask": dev_mask,
        "ever_failed": placed < 0,
        "cpu_left": cpu_left.astype(np.int32),
        "mem_left": mem_left.astype(np.int32),
        "gpu_left": gpu_left.astype(np.int32),
        "aff_cnt": aff_cnt.astype(np.int32),
        "near_entries": near_entries,
        "first_undecided": first_undecided,
    }
