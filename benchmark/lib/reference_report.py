"""The per-event report of one cluster state, in numpy alone and from
scratch: the plain reference behind the configuration `openb-load130`
(PERF.md section 4), whose lanes hand back the report series the FGD
artifact's curves against arrived load are cut from.

Nothing here imports `tpusim.policies`, `tpusim.ops` or `tpusim.sim`. The
program rebuilds the series from a replay's telemetry as cumulative deltas
of the touched node's rows, in float32 (`tpusim/sim/metrics.py`); the
sequential oracle's in-scan report shares `ops/frag.py` and `ops/energy.py`
with it. This file shares nothing and accumulates nothing: `report` takes a
cluster STATE the caller holds (a reference's own, e.g. the walk's of
`benchmark/lib/reference_follow_load.py`) and recomputes every number of
the event's `[Report]` / `[Alloc]` / `[Power]` lines over all the nodes, in
float64, following the Go text in its direct form:

- the seven fragmentation amounts: pkg/utils/frag.go:148-188
  NodeGpuShareFragAmount by the class frag.go:460-493 GetNodePodFrag gives
  each (node, typical pod), summed over the nodes (analysis.go:59-121);
- used nodes, used GPUs, used GPU milli, used CPU milli: analysis.go:91-99;
- the arrived counters: simulator.go:406-408, every creation counted
  whether it was placed or rejected;
- the two watts: analysis.go:24-56 over pkg/type/resource.go:533-563, the
  energy model as `mix_numpy.node_power` has it.

A copy of this file is the benchmark's: benchmark/lib/reference_report.py.

Departures from the Go text, each for a reason:

- nodes are evaluated as arrays where Go runs one goroutine a node and
  reduces over a channel (analysis.go:145-170); a node's arithmetic is the
  Go loop's, and a float64 sum over nodes and typical pods runs in numpy's
  order where Go's runs in arrival order (differences near 1e-9 of a milli);
- a node's GPU model and a typical pod's allowed models are an id and a
  bitmask of ids (data/README.md), as in `fgd_numpy`;
- the arrived counters are summed over the requests of the events up to
  the one asked, not carried from event to event: nothing here is a
  cumulative delta, which is the point of the comparison.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib import reference_fgd as fgd
from benchmark.lib import reference_mix as mix

MILLI = fgd.MILLI
# frag.go:17-35, in the order the report prints them
CLASSES = ("q1_lack_both", "q2_lack_gpu", "q3_satisfied", "q4_lack_cpu",
           "xl_satisfied", "xr_lack_cpu", "no_access")
Q1, Q2, Q3, Q4, XL, XR, NA = range(7)
INTEGER_SERIES = ("used_nodes", "used_gpus", "used_gpu_milli",
                  "used_cpu_milli", "arrived_gpu_milli", "arrived_cpu_milli")
FLOAT_SERIES = ("frag_amounts", "power_cpu", "power_gpu")


def frag_classes(cpu_left, gpu_left, gpu_type, typical) -> np.ndarray:
    """frag.go:460-493 GetNodePodFrag for M node states x T typical pods ->
    i64[M, T]. The order of the questions is the Go text's: a pod without
    GPU is XL or XR by CPU alone; a GPU pod whose models exclude the node's
    is no_access; one the node's devices can host is Q3 or Q4 by CPU; any
    other Q2 or Q1 by CPU."""
    t_cpu, t_milli, t_num, t_mask, _freq = typical
    cpu_ok = cpu_left[:, None] >= t_cpu[None, :]
    g = gpu_left[:, None, :]
    milli = t_milli[None, :, None]
    can_host = ((g >= milli) & (milli > 0)).sum(-1) >= t_num[None, :]
    access = fgd._accessible(gpu_type[:, None], t_mask[None, :])
    return np.where(
        (t_milli == 0)[None, :], np.where(cpu_ok, XL, XR),
        np.where(~access, NA,
                 np.where(can_host, np.where(cpu_ok, Q3, Q4),
                          np.where(cpu_ok, Q2, Q1))))


def frag_amounts(cpu_left, gpu_left, gpu_type, typical) -> np.ndarray:
    """frag.go:148-188 NodeGpuShareFragAmount summed over M node states ->
    f64[7]: to a typical pod the node satisfies (Q3) the devices
    individually too small for it are Q2's fragment and the rest of the
    idle milli stays Q3's; to any other pod all the node's idle milli goes
    to that pod's class. Each weighted by the pod's frequency."""
    t_milli, freq = typical[1], typical[4]
    cls = frag_classes(cpu_left, gpu_left, gpu_type, typical)  # [M, T]
    total = gpu_left.sum(-1).astype(np.float64)  # [M]
    g = gpu_left[:, None, :]
    too_small = np.where(g < t_milli[None, :, None], g, 0).sum(-1)  # [M, T]
    q3 = cls == Q3
    whole = freq[None, :] * total[:, None]  # all of a node's idle milli
    small = np.where(q3, freq[None, :] * too_small, 0.0)
    out = np.bincount(cls.ravel(), weights=np.where(q3, 0.0, whole).ravel(),
                      minlength=len(CLASSES))
    out[Q2] += small.sum()
    out[Q3] = (np.where(q3, whole, 0.0) - small).sum()
    return out


def usage(cpu_left, cpu_cap, gpu_left, gpu_cnt) -> tuple:
    """analysis.go:91-99 -> (used nodes, used GPUs, used GPU milli, used CPU
    milli): a node is used once one of its GPUs is not fully idle or any of
    its CPU is taken; every device of a used node counts as a used GPU."""
    idle_gpus = (gpu_left == MILLI).sum(-1)
    used = (idle_gpus < gpu_cnt) | (cpu_left < cpu_cap)
    return (int(used.sum()), int(gpu_cnt[used].sum()),
            int((gpu_cnt[used] * MILLI - gpu_left[used].sum(-1)).sum()),
            int((cpu_cap[used] - cpu_left[used]).sum()))


def arrived(pods: dict, created) -> tuple:
    """simulator.go:406-408 -> (arrived GPU milli, arrived CPU milli) once
    the creations of the pods `created` (indices, any order) have been
    attempted: every one counts, placed or rejected."""
    created = np.asarray(created, np.int64)
    milli = np.asarray(pods["gpu_milli"], np.int64)[created]
    num = np.asarray(pods["gpu_num"], np.int64)[created]
    return (int((milli * num).sum()),
            int(np.asarray(pods["cpu"], np.int64)[created].sum()))


def report(cluster: dict, cpu_left, gpu_left, typical: dict, energy: dict,
           pods: dict, created) -> dict:
    """Every number of one event's report, recomputed over the whole
    cluster from the state (`cpu_left` [N], `gpu_left` [N, 8]) the caller
    holds after that event.

    cluster: cpu_cap, gpu_cnt, gpu_type (model id, -1 none), cpu_type, [N].
    typical: cpu, gpu_milli, gpu_num, gpu_mask, freq, [T]. energy: as
    `mix_numpy.replay` takes it. pods: cpu, gpu_milli, gpu_num [P];
    `created`: the pods whose creation has been attempted so far."""
    as_i64 = lambda a: np.asarray(a, np.int64)  # noqa: E731
    cpu_left, gpu_left = as_i64(cpu_left), as_i64(gpu_left)
    cpu_cap, gpu_cnt = as_i64(cluster["cpu_cap"]), as_i64(cluster["gpu_cnt"])
    gpu_type = as_i64(cluster["gpu_type"])
    cpu_type = as_i64(cluster["cpu_type"])
    tp = tuple(as_i64(typical[f])
               for f in ("cpu", "gpu_milli", "gpu_num", "gpu_mask")) + (
        np.asarray(typical["freq"], np.float64),)
    nodes, gpus, gpu_milli, cpu_milli = usage(cpu_left, cpu_cap, gpu_left,
                                              gpu_cnt)
    arr_gpu, arr_cpu = arrived(pods, created)
    power_cpu, power_gpu = mix.cluster_power(
        cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type, energy)
    return {
        "frag_amounts": frag_amounts(cpu_left, gpu_left, gpu_type, tp),
        "used_nodes": nodes,
        "used_gpus": gpus,
        "used_gpu_milli": gpu_milli,
        "used_cpu_milli": cpu_milli,
        "arrived_gpu_milli": arr_gpu,
        "arrived_cpu_milli": arr_cpu,
        "power_cpu": power_cpu,
        "power_gpu": power_gpu,
    }
