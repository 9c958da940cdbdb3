"""One lane's replay of a creation trace under a PWR+FGD weight row, in
numpy alone: the plain reference behind the configuration `openb-pwrfgd`
(PERF.md section 4), the fork's `-PWR a -FGD b` methods with gpusel
`FGDScore`.

Nothing here imports `tpusim.policies`, `tpusim.ops` or `tpusim.sim`: the
sequential oracle shares the program's score kernels, its energy model and
its normalizers; this file shares nothing. It follows the Go text in its
DIRECT form: PWR's raw score is the node's whole power on the current state
less its whole power on each hypothetical state (plugin/pwr_score.go:150-218
over pkg/type/resource.go:533-563), with none of the program's two-channel
decomposition, tables or deferred commits, and in float64 as Go computes
it. FGD's half (its score, the device it chooses for a share-GPU pod, the
filter, Reserve, the affinity class) is `fgd_numpy`'s, the plain reference
of that policy.

Inputs are data, not code under test: cluster and trace as integer arrays
(the cluster with a CPU model id a node), the typical pods, the lane's
tie-break rank, the lane's weight row, and the energy tables (idle and full
watts by GPU model id; idle watts, full watts and cores a package by CPU
model id: open-gpu-share/utils/const.go:48-121). A copy of this file is the
benchmark's: benchmark/lib/reference_mix.py.

Departures from the Go text, each for a reason (and `fgd_numpy`'s own):

- nodes are scored as arrays over the feasible nodes where Go scores 16 at
  a time; a node's arithmetic is the Go loop's;
- the framework's RunScorePlugins is written out for these two plugins:
  PWR's NormalizeScore is the plugin's own (pwr_score.go:104-139: min-max
  to [0, 100] over the feasible nodes, an all-equal row pinned to 100);
  FGD's score is already in [0, 100] and has no normalizer; the total is
  the weighted sum, and selectHost takes the best total, then the smallest
  `rank` (generic_scheduler.go:187-212);
- a GPU model absent from the energy tables draws 0 W here (Go would
  panic on the missing map entry; the openb cluster names none);
- the raw score is int64(old - new), a truncation toward zero. Every watt
  of the tables is a whole number, so old - new is one too and the
  truncation is exact in any precision; should a table ever hold a
  fraction, an entry within `NEAR` of an integer is flagged as
  `fgd_numpy` flags its own.
"""

from __future__ import annotations

import numpy as np

from tpusim.ref import fgd_numpy as fgd

MILLI = fgd.MILLI
MAX_GPUS = fgd.MAX_GPUS
MAX_NODE_SCORE = fgd.MAX_NODE_SCORE
NEAR = fgd.NEAR
MIN_INT64 = np.iinfo(np.int64).min  # pwr_score.go:158 math.MinInt64


def node_power(cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type,
               energy):
    """resource.go:533-563 GetEnergyConsumptionNode for M node states ->
    (cpu watts f64[M], gpu watts f64[M]).

    GPU (:537-545): a fully idle device draws its model's idle watts, every
    other device of the node its full watts, however little of it is used.
    CPU (:547-559): two vCPUs a physical core; the node's cores fill whole
    packages of the model's core count, and a package draws full watts as
    soon as one of its cores works, idle watts otherwise."""
    idle_gpus = (gpu_left == MILLI).sum(-1)
    has = gpu_type >= 0
    model = np.maximum(gpu_type, 0)
    gpu_w = np.where(has, np.asarray(energy["gpu_idle_w"], np.float64)[model]
                     * idle_gpus
                     + np.asarray(energy["gpu_full_w"], np.float64)[model]
                     * (gpu_cnt - idle_gpus), 0.0)
    real_cores = np.ceil(cpu_cap.astype(np.float64) / MILLI / 2)
    idle_cores = np.floor(cpu_left.astype(np.float64) / MILLI / 2)
    ncores = np.asarray(energy["cpu_ncores"], np.float64)[cpu_type]
    packages = np.ceil(real_cores / ncores)
    active = np.ceil((real_cores - idle_cores) / ncores)
    cpu_w = (np.asarray(energy["cpu_idle_w"], np.float64)[cpu_type]
             * (packages - active)
             + np.asarray(energy["cpu_full_w"], np.float64)[cpu_type] * active)
    return cpu_w, gpu_w


def _trunc(x):
    """int64(x) of Go, and whether the float32 program may truncate it to
    the neighbouring integer (never, with whole-number watts)."""
    near = (x != np.rint(x)) & (np.abs(x - np.rint(x)) < NEAR)
    return np.trunc(x).astype(np.int64), near


def pwr_scores(cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type, pod,
               energy):
    """pwr_score.go:150-218 for M candidate nodes -> (raw i64[M], near
    bool[M]): the node's power now less its power with the pod on it.

    A share-GPU pod (one GPU, under 1,000 milli) is tried on every device
    that fits it and keeps the best score, the first on ties (:150-200, a
    strict `>` from math.MinInt64); any other pod is placed by
    NodeResource.Sub (:204-218), whose devices are `fgd_numpy`'s."""
    cpu, _mem, milli, num, _mask = pod

    def total(c_left, g_left):
        c, g = node_power(c_left, cpu_cap, g_left, gpu_cnt, gpu_type,
                          cpu_type, energy)
        return c + g

    old = total(cpu_left, gpu_left)
    m = len(cpu_left)
    if num == 1 and milli < MILLI:
        best = np.full(m, MIN_INT64, np.int64)
        near = np.zeros(m, bool)
        for d in range(MAX_GPUS):
            fits = gpu_left[:, d] >= milli
            hyp = gpu_left.copy()
            hyp[:, d] -= milli
            s, near_d = _trunc(old - total(cpu_left - cpu, hyp))
            better = fits & (s > best)
            best = np.where(better, s, best)
            near |= fits & near_d
        return best, near
    hyp = gpu_left.copy()
    if num > 0:  # resource.go:454-480 Sub, as fgd_numpy.score_nodes has it
        order = np.argsort(gpu_left, axis=1, kind="stable")
        sorted_left = np.take_along_axis(gpu_left, order, 1)
        fit = sorted_left >= milli
        take = fit & (np.cumsum(fit, 1) <= num)
        np.put_along_axis(hyp, order, sorted_left - take * milli, 1)
    return _trunc(old - total(cpu_left - cpu, hyp))


def pwr_normalize(raw):
    """pwr_score.go:104-139 NormalizeScore over the feasible nodes' raw
    scores: (s - lowest) * 100 / (highest - lowest) in integers; where all
    are equal (one feasible node too) every node gets 100."""
    raw = np.asarray(raw, np.int64)
    lo, hi = raw.min(), raw.max()
    if hi == lo:
        return np.full(raw.shape, MAX_NODE_SCORE, np.int64)
    return (raw - lo) * MAX_NODE_SCORE // (hi - lo)


def score_candidates(state, cand, pod, typical, weights, energy):
    """The two plugins over the feasible nodes `cand` of `state` (cpu_left,
    cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type) -> (total i64[M], FGD's
    device i64[M], fgd_near bool[M], pwr_near bool[M]); `weights` is the
    lane's (PWR, FGD) row."""
    cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type = (
        a[cand] for a in state)
    raw, pwr_near = pwr_scores(cpu_left, cpu_cap, gpu_left, gpu_cnt,
                               gpu_type, cpu_type, pod, energy)
    score, device, fgd_near = fgd.score_nodes(
        cpu_left, gpu_left, gpu_type, pod, typical)
    total = int(weights[0]) * pwr_normalize(raw) + int(weights[1]) * score
    return total, device, fgd_near, pwr_near


def cluster_power(cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type,
                  energy):
    """analysis.go:24-56: (ClusterCPU, ClusterGPU) watts, the nodes summed."""
    c, g = node_power(cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type,
                      cpu_type, energy)
    return float(c.sum()), float(g.sum())


def replay(cluster: dict, pods: dict, typical: dict, rank, weights,
           energy: dict):
    """Replay `pods` (creations, in order) on the empty `cluster` under the
    weight row `weights` = (PWR, FGD).

    cluster: cpu_cap, mem_cap, gpu_cnt, gpu_type (model id, -1 none),
    cpu_type (model id), [N]. pods, typical, rank: as `fgd_numpy.replay`.
    energy: gpu_idle_w, gpu_full_w by GPU model id; cpu_idle_w, cpu_full_w,
    cpu_ncores by CPU model id.

    Returns what `fgd_numpy.replay` returns, and `power_cpu_w`,
    `power_gpu_w` of the final state. `near_entries` counts FGD's and
    PWR's; `first_undecided` is the first event one could decide: an FGD
    entry near an integer within one FGD step of the best total, or any
    PWR entry near one (it moves the extrema, so every node's total)."""
    as_i64 = lambda a: np.asarray(a, np.int64)  # noqa: E731
    cpu_cap, mem_left = as_i64(cluster["cpu_cap"]), as_i64(cluster["mem_cap"])
    cpu_left = cpu_cap.copy()
    gpu_cnt, gpu_type = as_i64(cluster["gpu_cnt"]), as_i64(cluster["gpu_type"])
    cpu_type = as_i64(cluster["cpu_type"])
    n = len(cpu_left)
    gpu_left = (np.arange(MAX_GPUS)[None, :] < gpu_cnt[:, None]) * np.int64(MILLI)
    aff_cnt = np.zeros((n, fgd.AFFINITY_CLASSES), np.int64)
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
        cand = np.flatnonzero(fgd.feasible_nodes(
            cpu_left, mem_left, gpu_left, gpu_cnt, gpu_type, pod))
        if cand.size == 0:
            continue  # unschedulable (simulator.go:444-455)
        total, device, fgd_near, pwr_near = score_candidates(
            (cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type), cand,
            pod, tp, weights, energy)
        best = total.max()
        winners = np.flatnonzero(total == best)
        w = winners[np.argmin(rank[cand][winners])]
        near_entries += int(fgd_near.sum()) + int(pwr_near.sum())
        if first_undecided < 0 and (
                pwr_near.any()
                or (fgd_near & (total >= best - int(weights[1]))).any()):
            first_undecided = e
        node = int(cand[w])
        mask = fgd.reserve_devices(gpu_left[node], pod, int(device[w]))
        cpu_left[node] -= pod[0]
        mem_left[node] -= pod[1]
        gpu_left[node] -= mask * pod[2]
        cls = fgd.affinity_class(pod)
        if cls >= 0:
            aff_cnt[node, cls] += 1
        placed[e], dev_mask[e] = node, mask

    power_cpu_w, power_gpu_w = cluster_power(
        cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type, energy)
    return {
        "placed_node": placed,
        "dev_mask": dev_mask,
        "ever_failed": placed < 0,
        "cpu_left": cpu_left.astype(np.int32),
        "mem_left": mem_left.astype(np.int32),
        "gpu_left": gpu_left.astype(np.int32),
        "aff_cnt": aff_cnt.astype(np.int32),
        "power_cpu_w": power_cpu_w,
        "power_gpu_w": power_gpu_w,
        "near_entries": near_entries,
        "first_undecided": first_undecided,
    }
