"""The typical pods of a pod list, in plain Python and numpy alone: the
target workload that `reference_fgd.replay` scores fragmentation against,
computed from the pod list's CSV file itself.

Nothing here imports `tpusim.sim` (the program's own extraction is
`tpusim/sim/typical.py`), and the CSV is read with the `csv` module, not
with the program's loader. It follows the Go text of the reference
scheduler, pkg/utils/frag.go:285-380 GetTypicalPods:

- every pod counts once (IsInvolvedCpuPods true, GpuResWeight 0) under its
  resource key (MilliCpu, MilliGpu, GpuNumber, GpuType): frag.go:292-310;
- the keys are sorted by count, descending, ties by the key, descending in
  every field, the GPU type as a string (sort.Reverse over Percentage, then
  PodResource.Less, resource.go:18-42);
- keys are taken `step` (10) at a time until those taken hold at least
  `popularity` per cent of the pods, or none is left (frag.go:333-356);
- the frequencies of those taken are renormalised to sum to 1
  (frag.go:358-376).

A pod's milli-GPU is read as the program's data loader documents it
(data/README.md): 0 without a GPU, at most 1,000, 1,000 where the field is
empty. A GPU type is a "|"-separated list of model names; `model_ids`, the
id of every model name, is data to both sides, as in `reference_fgd` (a
node's model is an id, a pod's allowed models a bitmask of ids).
"""

from __future__ import annotations

import csv

import numpy as np

MILLI = 1000


def read_pod_keys(path: str) -> list[tuple]:
    """(cpu_milli, gpu_milli, num_gpu, gpu_type) of every pod of the CSV,
    in file order."""
    keys = []
    with open(path, newline="") as f:
        for row in csv.DictReader(f):
            num = int(float(row["num_gpu"]))
            try:
                milli = int(float(row.get("gpu_milli")))
            except (TypeError, ValueError):
                milli = MILLI
            milli = 0 if num == 0 else min(max(milli, 0), MILLI)
            kind = (row.get("gpu_spec") or "").strip()
            if kind.lower() == "nan" or num == 0:
                kind = ""
            keys.append((int(float(row["cpu_milli"])), milli, num, kind))
    return keys


def model_mask(kind: str, model_ids: dict) -> int:
    """Allowed-model bits of a GPU type string; 0: no constraint."""
    mask = 0
    for name in kind.split("|"):
        if name.strip():
            mask |= 1 << model_ids[name.strip()]
    return mask


def typical_pods(keys: list[tuple], model_ids: dict, popularity: int = 95,
                 step: int = 10) -> dict:
    """The typical pods of the pods `keys` as `reference_fgd.replay` takes
    them: cpu, gpu_milli, gpu_num, gpu_mask i64[T], freq f64[T], most
    popular first."""
    counts: dict = {}
    for key in keys:
        counts[key] = counts.get(key, 0) + 1
    ordered = sorted(counts.items(), key=lambda kv: (kv[1], kv[0]),
                     reverse=True)
    wanted = popularity * len(keys) / 100.0
    taken, held = 0, 0
    while held < wanted and taken < len(ordered):
        for _, count in ordered[taken:taken + step]:
            held += count
        taken = min(taken + step, len(ordered))
    kept = ordered[:taken]
    cols = list(zip(*(key for key, _ in kept))) or [(), (), (), ()]
    return {
        "cpu": np.asarray(cols[0], np.int64),
        "gpu_milli": np.asarray(cols[1], np.int64),
        "gpu_num": np.asarray(cols[2], np.int64),
        "gpu_mask": np.asarray(
            [model_mask(kind, model_ids) for kind in cols[3]], np.int64),
        "freq": np.asarray([count for _, count in kept], np.float64) / held,
    }
