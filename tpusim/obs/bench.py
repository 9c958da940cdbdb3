"""Shared bench harness: the ONE timing protocol + JSON emission the
three bench scripts (bench.py, bench_scale.py, bench_multichip.py) used
to each re-implement.

The protocol (pinned round 5, unchanged here): one cold call (compile +
first run), then `warm_runs` warm calls; the headline wall is the minimum
over the warm samples. All raw samples ship alongside so a reader can
judge the spread. Whether a warm minimum is still the right statistic on
the chip this repo now runs on is ROADMAP S0's to measure.

`device_stamp()` is the one place a bench row or the chip smoke learns
which device it ran on, and the one place a missing chip becomes an
error instead of a quiet CPU number.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Callable, List

# warm replays per measurement — the historical bench.py constant, now
# single-sourced for every bench lane
WARM_RUNS = 6


def device_stamp() -> dict:
    """platform / device_kind / device_count exactly as JAX reports them
    (`jax.devices()[0].platform`, `.device_kind`, `len(jax.devices())`),
    for every bench row and chip_smoke.py's result line. A backend that
    is not `tpu` is an error unless the caller asked for the CPU by
    setting JAX_PLATFORMS=cpu: a measurement path must never fall back
    to the CPU on its own."""
    import jax

    devices = jax.devices()
    stamp = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
    if (stamp["platform"] != "tpu"
            and os.environ.get("JAX_PLATFORMS", "") != "cpu"):
        raise RuntimeError(
            f"JAX came up on {stamp['platform']!r} ({stamp['device_kind']}), "
            "not on a TPU, and JAX_PLATFORMS=cpu was not set: refusing to "
            "report numbers from a backend nobody asked for"
        )
    return stamp


def measure(fn: Callable[[], object], warm_runs: int = WARM_RUNS) -> dict:
    """Cold + warm-minimum measurement of a nullary callable (the callable
    must block on its device work). Returns
    {first_s, samples_s, min_s} — callers rename/round per their row
    schema via `round_row`."""
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    samples: List[float] = []
    for _ in range(max(warm_runs, 1)):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    return {"first_s": first, "samples_s": samples, "min_s": min(samples)}


def measure_cold_warm(fn: Callable[[], object]) -> dict:
    """The two-call variant (multichip lane: every mesh size compiles its
    own program, one warm call is the signal)."""
    m = measure(fn, warm_runs=1)
    return {"cold_s": m["first_s"], "warm_s": m["min_s"]}


def round_row(row: dict, places: int = 3) -> dict:
    """Round the float leaves of a bench row (list leaves element-wise) —
    the shared presentation the BENCH_*.json consumers parse."""
    out = {}
    for k, v in row.items():
        if isinstance(v, float):
            out[k] = round(v, places)
        elif isinstance(v, list) and v and all(
            isinstance(x, float) for x in v
        ):
            out[k] = [round(x, places) for x in v]
        else:
            out[k] = v
    return out


def write_json(path: str, payload: dict, announce: bool = True) -> str:
    """Atomic JSON emission (tmp + rename) with the schema-stable layout
    the committed BENCH_*.json / BENCH_DETAILS.json files carry; prints
    the destination to stderr like every bench script did."""
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)
    if announce:
        print(f"[bench] wrote {path}", file=sys.stderr)
    return path
