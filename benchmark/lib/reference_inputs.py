"""What a plain reference replays, read from the CSV files themselves and
made from a lane's seed, in plain Python and numpy alone: the cluster, the
pods' requests and timestamps, and the tie-break rank.

Nothing here imports `tpusim.io` or `tpusim.sim`: the files are read with
the `csv` module, as `reference_typical.py` reads the pod list for the
typical pods, so a fault of the program's loader, of its expansion of the
pods into arrays (`pods_to_specs`) or of its rank table does not pass on
both sides of a comparison. The fields are read as the data's own note
says (data/README.md): a pod without a GPU asks for 0 milli-GPU, one with
GPUs for at most 1,000 each, 1,000 where the field is empty; a GPU type is
a "|"-separated list of model names (`reference_typical.model_mask`);
`model_ids`, the id of every model name, is data to both sides.
"""

from __future__ import annotations

import csv

import numpy as np

from benchmark.lib.reference_typical import MILLI, model_mask

NO_GPU = -1  # the model id of a node without GPUs


def _rows(path: str, count):
    with open(path, newline="") as f:
        for i, row in enumerate(csv.DictReader(f)):
            if count is not None and i >= count:
                return
            yield row


def cluster(path: str, model_ids: dict, nodes: int = None) -> dict:
    """The first `nodes` nodes of a node list (all of them: None) as
    `reference_fgd.replay` takes a cluster: cpu_cap (milli), mem_cap (MiB),
    gpu_cnt, gpu_type (a model id) i64[N]."""
    cols = {"cpu_cap": [], "mem_cap": [], "gpu_cnt": [], "gpu_type": []}
    for row in _rows(path, nodes):
        model = (row.get("model") or "").strip()
        cols["cpu_cap"].append(int(float(row["cpu_milli"])))
        cols["mem_cap"].append(int(float(row["memory_mib"])))
        cols["gpu_cnt"].append(int(float(row["gpu"])))
        cols["gpu_type"].append(
            model_ids[model] if model and model.lower() != "nan" else NO_GPU)
    return {k: np.asarray(v, np.int64) for k, v in cols.items()}


def pods(path: str, model_ids: dict, count: int = None) -> dict:
    """The first `count` pods of a pod list (all of them: None): what each
    asks for, as `reference_fgd.replay` takes pods (cpu, mem, gpu_milli,
    gpu_num, gpu_mask i64[P]), and when it came and went (creation_time,
    deletion_time i64[P]; 0: no deletion)."""
    names = ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask",
             "creation_time", "deletion_time")
    cols = {k: [] for k in names}
    for row in _rows(path, count):
        num = int(float(row["num_gpu"]))
        try:
            milli = int(float(row.get("gpu_milli")))
        except (TypeError, ValueError):
            milli = MILLI
        kind = (row.get("gpu_spec") or "").strip()
        if kind.lower() == "nan" or num == 0:
            kind = ""
        cols["cpu"].append(int(float(row["cpu_milli"])))
        cols["mem"].append(int(float(row.get("memory_mib") or 0)))
        cols["gpu_milli"].append(0 if num == 0 else min(max(milli, 0), MILLI))
        cols["gpu_num"].append(num)
        cols["gpu_mask"].append(model_mask(kind, model_ids))
        for when in ("creation_time", "deletion_time"):
            cols[when].append(int(float(row.get(when) or 0)))
    return {k: np.asarray(v, np.int64) for k, v in cols.items()}


def tiebreak_rank(num_nodes: int, seed: int) -> np.ndarray:
    """rank i64[N] of a lane's seed: the position of node i in a random
    order of the nodes, numpy's `default_rng(seed).permutation`. It stands
    in for the reference scheduler's random 4-digit node-name prefixes and
    its lexicographic selectHost tie-break (simulator.go:584-588): which
    order a seed gives is a convention, and this is the one a lane's seed
    names, written out here and not taken from the program's table."""
    order = np.random.default_rng(seed).permutation(num_nodes)
    rank = np.empty(num_nodes, np.int64)
    rank[order] = np.arange(num_nodes)
    return rank
