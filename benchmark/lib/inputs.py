"""Clusters and pod streams, made from the repo's openb CSVs and a seed.

The CSVs are those bench.load_trace reads; `synth_cluster` / `synth_pods`
are bench_scale's generators (openb's SKU mix and pod mix, resampled). The
`draw` seed fixes WHICH rows are drawn, the `order` seed only their order,
so every `--seed` of a cell replays the same multiset of work: the shapes
(pod types K, events) and so the compiled programs do not depend on it.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NODE_CSV = os.path.join(REPO, "data/csv/openb_node_list_gpu_node.csv")
POD_CSV = os.path.join(REPO, "data/csv/openb_pod_list_default.csv")


def synth_cluster(num_nodes: int, draw: int):
    from tpusim.io.trace import load_node_csv

    base = load_node_csv(NODE_CSV)
    idx = np.random.default_rng(draw).integers(0, len(base), num_nodes)
    return [
        dataclasses.replace(base[int(j)], name=f"synth-{i:06d}")
        for i, j in enumerate(idx)
    ]


def synth_pods(num_pods: int, draw: int, order: int):
    from tpusim.io.trace import load_pod_csv

    base = load_pod_csv(POD_CSV)
    idx = np.random.default_rng(draw).integers(0, len(base), num_pods)
    idx = np.random.default_rng(order).permutation(idx)
    return [
        dataclasses.replace(base[int(j)], name=f"sp-{i:07d}", creation_time=i)
        for i, j in enumerate(idx)
    ]


def pod_shape(p) -> tuple:
    """What makes two pods one pod type: the resources they ask for."""
    return (p.cpu_milli, p.memory_mib, p.num_gpu, p.gpu_milli, p.gpu_spec)


def build(config: dict, seed: int, depth: int):
    """(nodes, workload pods) of a configuration. `--seed` never changes
    the cluster (it is the deployment); it orders the synthetic stream
    here and, for the openb trace, seeds the program's own tuning and
    shuffle (drivers pass it on as tuning_seed)."""
    cluster, workload = config["cluster"], config["workload"]
    if cluster["source"] == "openb_csv":
        from tpusim.io.trace import load_node_csv

        nodes = load_node_csv(NODE_CSV)[: cluster.get("nodes")]
    elif cluster["source"] == "synth":
        nodes = synth_cluster(int(cluster["nodes"]), int(cluster["draw"]))
    else:
        raise KeyError(f"unknown cluster source {cluster['source']!r}")
    if workload["source"] == "openb_csv":
        from tpusim.io.trace import load_pod_csv

        pods = load_pod_csv(POD_CSV)
    elif workload["source"] == "synth":
        pods = synth_pods(depth, int(workload["draw"]), seed)
    else:
        raise KeyError(f"unknown workload source {workload['source']!r}")
    return nodes, pods
