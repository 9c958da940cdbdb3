#!/usr/bin/env python
"""One chip measurement, in no cell's path (ISSUE 43): what the readback's
copy costs whole and in pieces, and where the bytes land on the host.

Until PR 47 a sweep's fetch was ONE `np.asarray` of one packed uint8 buffer
(tpusim/sim/fetch.py): 268,947,140 bytes in the openb cell, at 0.70-0.73
GB/s in every cell, where a copy of 8 MB runs at 3.4-4.8 GB/s. ROADMAP S4
asked whether fetching "in pieces" would pay. This script decided it (the
fetch has gone in 8 MB pieces, all in flight, into a kept block since; run it
again to choose the piece size on other hardware), inside a process that has
just run the openb cell's own sweep
(2,560 lanes x 512 events, twice: the second wave's device leaves are kept
and packed by the fetch's own packer), on that very buffer:

  one            np.asarray(packed), as device_fetch did
  k x M MB       the same bytes as k device slices of 8 / 32 / 64 MB,
                 `np.asarray` one after another, or with
                 `copy_to_host_async` issued for all of them first
  -> kept        each of the above copied on into a host buffer this script
                 keeps from repeat to repeat (one more memcpy, and the
                 runtime's own buffers die young: small ones are reused by
                 the allocator, 269 MB ones are mapped afresh)
  fresh | touched  a plain host memcpy of the same bytes into newly
                 allocated memory and into touched memory: what fresh pages
                 cost with no device in it
  kept heap      `one` and the pieces again after mallopt() tells glibc to
                 keep freed blocks in the heap (no mmap, no trim): the
                 runtime's buffer then lands in pages the last repeat
                 touched, which is "a buffer kept from the last wave" as far
                 as a process can hand one to the runtime (np.asarray of a
                 jax.Array takes no destination)

Every variant runs REPEATS times, round-robin, each time on a device buffer
no host copy of which exists yet (the packer and the slicer run again; the
time of neither is in a copy). Beside every time: the minor page faults of
the process during it (`ru_minflt`; 65,661 pages of 4 KiB hold the buffer).
Prints a table, then one JSON line; writes chiprun_out/copy_pieces.json.

    python copy_pieces_on_chip.py            # on a machine with a TPU
    JAX_PLATFORMS=cpu python copy_pieces_on_chip.py --lanes 64 --repeats 2
                                             # a rehearsal: no number of it
                                             # is a device's
"""

from __future__ import annotations

import argparse
import ctypes
import json
import os
import resource
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

LANES, DEPTH = 2560, 512  # benchmark/traffic/fgd-seeds-2560.json
PIECES_MB = (8, 32, 64)
REPEATS = 5
OUT = os.path.join(REPO, "chiprun_out", "copy_pieces.json")
M_TRIM_THRESHOLD, M_TOP_PAD, M_MMAP_THRESHOLD = -1, -2, -3  # <malloc.h>


def openb_wave(lanes: int):
    """The openb cell's Simulator, trace, weights: as its driver makes them
    (benchmark/drivers/wave.py), and a function that runs one wave of
    `lanes` lanes."""
    import numpy as np

    from benchmark.drivers import wave
    from benchmark.lib import inputs
    from tpusim.sim.driver import schedule_pods_sweep

    with open(os.path.join(REPO, "benchmark", "configs", "openb.json")) as f:
        config = json.load(f)
    nodes, pods = inputs.build(config, 43, DEPTH)
    cfg = wave.simulator_config(config["simulator"], 43, profile=False)
    sim = wave.build_simulator(nodes, pods, cfg)
    trace = sim.prepare_pods()[:DEPTH]
    weights = np.tile(np.asarray([w for _, w in cfg.policies], np.int32),
                      (lanes, 1))

    def run(n: int):
        t0 = time.perf_counter()
        out = schedule_pods_sweep(
            sim, trace, weights, wave.lane_seeds(43, n, lanes))
        return out, time.perf_counter() - t0

    return run


def timed(fn):
    """(seconds, minor page faults) of fn()."""
    f0 = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
    t0 = time.perf_counter()
    fn()
    dt = time.perf_counter() - t0
    return dt, resource.getrusage(resource.RUSAGE_SELF).ru_minflt - f0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--lanes", type=int, default=LANES)
    ap.add_argument("--repeats", type=int, default=REPEATS)
    args = ap.parse_args()
    repeats = args.repeats

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpusim.compile_cache import enable_compile_cache
    from tpusim.obs.spans import sweep_log
    from tpusim.sim import driver, fetch

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)

    # ---- a process that has just run the cell's sweep, twice
    run = openb_wave(args.lanes)
    held = {}
    real_fetch = driver.device_fetch

    def keeping(tree, **kw):
        held["tree"] = tree
        return real_fetch(tree, **kw)

    _, warm_s = run(0)
    driver.device_fetch = keeping
    try:
        lanes, wave_s = run(1)
    finally:
        driver.device_fetch = real_fetch
    rec = sweep_log()[-1]
    fetch_span = next(s for s in rec.spans if s.name == "fetch")
    wave = {
        "warm_wave_s": warm_s, "wave_s": wave_s,
        "fetch_bytes": rec.fetch_bytes, "fetch_s": fetch_span.total_s,
        "fetch_copy_s": fetch_span.marks["copied"] - fetch_span.marks["ready"],
        "shared_bytes": fetch_span.meta.get("shared_bytes"),
        "lanes": len(lanes),
    }
    print(f"wave: {wave}", flush=True)

    leaves = [l for l in jax.tree_util.tree_leaves(held["tree"])
              if isinstance(l, jax.Array)]
    sig = tuple((tuple(l.shape), str(l.dtype)) for l in leaves)
    packer = fetch._packer(sig)

    def packed():
        p = jnp.concatenate(packer(leaves))  # the fetch's pieces, whole
        p.block_until_ready()
        return p

    nbytes = int(packed().nbytes)
    assert nbytes == rec.fetch_bytes, (nbytes, rec.fetch_bytes)
    del lanes

    def cuts(mb: int):
        step = mb * 2**20
        return [(a, min(a + step, nbytes)) for a in range(0, nbytes, step)]

    slicers = {mb: jax.jit(lambda p, c=tuple(cuts(mb)): tuple(
        p[a:b] for a, b in c)) for mb in PIECES_MB}

    def pieces(mb: int):
        out = slicers[mb](packed())
        jax.block_until_ready(out)
        return out

    slice_s = {}
    for mb in PIECES_MB:  # compiles, and the device's time to cut
        pieces(mb)
        p = packed()
        t0 = time.perf_counter()
        jax.block_until_ready(slicers[mb](p))
        slice_s[mb] = time.perf_counter() - t0

    kept = np.empty(nbytes, np.uint8)
    kept.fill(1)  # touched
    want = np.asarray(packed()).copy()

    # ---- the variants: name -> (prepare() -> operand, copy(operand) -> host)
    def one(p):
        return np.asarray(p)

    def one_async(p):
        p.copy_to_host_async()
        return np.asarray(p)

    def after_another(ps):
        return [np.asarray(p) for p in ps]

    def all_async(ps):
        for p in ps:
            p.copy_to_host_async()
        return [np.asarray(p) for p in ps]

    def one_into_kept(p):
        np.copyto(kept, np.asarray(p))
        return kept

    def pieces_into_kept(mb, async_first):
        def copy(ps):
            if async_first:
                for p in ps:
                    p.copy_to_host_async()
            # a piece's host array dies before the next is made
            for (a, b), p in zip(cuts(mb), ps):
                np.copyto(kept[a:b], np.asarray(p))
            return kept
        return copy

    def host_fresh(_):
        fresh = np.empty(nbytes, np.uint8)
        np.copyto(fresh, want)
        return fresh

    def host_touched(_):
        np.copyto(kept, want)
        return kept

    variants = {
        "one": (packed, one),
        "one, async first": (packed, one_async),
        "one -> kept": (packed, one_into_kept),
        "host memcpy, fresh": (lambda: None, host_fresh),
        "host memcpy, touched": (lambda: None, host_touched),
    }
    for mb in PIECES_MB:
        k = len(cuts(mb))
        prep = (lambda mb=mb: pieces(mb))
        variants[f"{k} x {mb} MB, one after another"] = (prep, after_another)
        variants[f"{k} x {mb} MB, all async first"] = (prep, all_async)
        variants[f"{k} x {mb} MB, one after another -> kept"] = (
            prep, pieces_into_kept(mb, async_first=False))
        variants[f"{k} x {mb} MB, all async first -> kept"] = (
            prep, pieces_into_kept(mb, async_first=True))

    def check(got):
        flat = got if isinstance(got, np.ndarray) else np.concatenate(got)
        assert flat.nbytes == nbytes and np.array_equal(flat, want)

    def rounds(names, times):
        for r in range(repeats):
            for name in names:
                prepare, copy = variants[name]
                operand = prepare()
                got = []
                dt, faults = timed(lambda: got.append(copy(operand)))
                times.setdefault(name, []).append((dt, faults))
                if r == 0:
                    check(got[0])
                del got, operand

    fresh_heap, kept_heap = {}, {}
    rounds(list(variants), fresh_heap)

    # ---- the same copies once glibc keeps what is freed in its heap
    libc = ctypes.CDLL("libc.so.6")
    ok = [libc.mallopt(M_MMAP_THRESHOLD, 2**30),
          libc.mallopt(M_TRIM_THRESHOLD, 2**31 - 1),
          libc.mallopt(M_TOP_PAD, 2**29)]
    again = ["one", "one, async first"] + [
        n for n in variants if "MB" in n and "kept" not in n]
    rounds(again, kept_heap)

    def row(name, samples):
        secs = [s for s, _ in samples]
        med = statistics.median(secs)
        return {"variant": name, "median_s": med, "min_s": min(secs),
                "max_s": max(secs), "GB_per_s": nbytes / med / 1e9,
                "median_minor_faults": statistics.median(
                    f for _, f in samples),
                "seconds": secs}

    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "bytes": nbytes, "repeats": repeats, "wave": wave,
        "slice_program_s": slice_s, "mallopt_ok": ok,
        "thp": open("/sys/kernel/mm/transparent_hugepage/enabled").read()
        .strip() if os.path.exists(
            "/sys/kernel/mm/transparent_hugepage/enabled") else None,
        "fresh_heap": [row(n, s) for n, s in fresh_heap.items()],
        "kept_heap": [row(n, s) for n, s in kept_heap.items()],
    }
    for title in ("fresh_heap", "kept_heap"):
        print(f"\n{title}: {nbytes:,} bytes, medians of {repeats}")
        print(f"{'variant':<46} {'median s':>9} {'min':>8} {'max':>8} "
              f"{'GB/s':>6} {'faults':>8}")
        for r in result[title]:
            print(f"{r['variant']:<46} {r['median_s']:>9.4f} "
                  f"{r['min_s']:>8.4f} {r['max_s']:>8.4f} "
                  f"{r['GB_per_s']:>6.2f} {r['median_minor_faults']:>8.0f}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": True, "bytes": nbytes, "out": OUT,
                      "device": result["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
