"""Bench regression gate: diff a profiled smoke run against the newest
committed `BENCH_r*.json` baseline (`make bench-gate`).

Every round's driver commits a BENCH_rNN.json capture of `python
bench.py` ({n, cmd, rc, tail, parsed}); until now nothing ever read them
back. The gate closes that loop:

  1. parse the newest committed baseline (highest rNN with rc == 0):
     headline placements/sec from `parsed.value`, plus the
     machine-INDEPENDENT quality numbers from the tail line —
     `events=`, `placed=`, `gpu_alloc=` — and the backend it ran on
     (`parsed.platform`, which bench.py stamps from jax.devices(); a
     capture without it has an unknown backend, never an assumed one);
  2. re-run the same headline measurement (openb default trace, FGD,
     tune 1.3, seed 42) with obs profiling on, emitting the smoke
     profile JSONL/Prometheus files under --out;
  3. fail (exit 1) if a DETERMINISTIC quality number moved — event count
     or placement count off by even one, GPU allocation beyond
     --alloc-tol — or if throughput regressed more than --tol on the
     SAME backend as the baseline. Cross-backend throughput (CPU gate
     vs a TPU-captured baseline) is advisory: printed, never failed on,
     because the two machines measure different hardware.

Placements are backend-independent by the engine-equality contracts
(ENGINES.md; the f32 divergence channel is report-only), so the
quality half of the gate is exact everywhere.

The gate also smoke-checks the decision-provenance surface (ISSUE 4):
a small decision-recording replay writes its decision JSONL under
--out and the digest-verified read-back must round-trip exactly —
`tpusim explain`/`diff` depend on that file format.

And the live-telemetry surface (ISSUE 5): the smoke run's record is
published to an ephemeral MonitorServer and scraped over HTTP — the
scrape must parse as valid Prometheus exposition text and be byte-equal
to the gate_metrics.prom textfile, the same
final-scrape-equals-textfile contract `tpusim apply --listen` promises.

And the config-axis sweep surface (ISSUE 6): a small vmapped weight
sweep must run, reuse ONE compiled executable across weight grids (the
weights-are-operands contract), and its marginal per-config cost is
printed next to the newest committed `bench_scale.py --sweep` capture's
numbers — advisory only, since sweep walls are machine-shaped.

And the replay-service surface (ISSUE 7): a 4-job grid POSTed to an
ephemeral `serve --jobs` instance must come back dedup'd (the duplicate
answered from the digest cache) and batched onto ONE compiled sweep,
with a second weights+tune wave adding zero executables
(jit._cache_size() stable — the zero-recompile contract end-to-end
through the POST path). `--svc-only` runs just this check (the `make
svc-smoke` mode).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_TAIL_EVENTS = re.compile(r"events=(\d+)")
_TAIL_PLACED = re.compile(r"placed=(\d+)")
_TAIL_ALLOC = re.compile(r"gpu_alloc=([0-9.]+)%")


def _iter_captures(repo: str):
    """Yield (path, round_number, data) for every readable committed
    BENCH_rNN.json with rc == 0. Malformed files — unreadable, bad JSON,
    a non-numeric `n` — are skipped, never raised: one torn capture must
    not take the whole gate down."""
    for path in glob.glob(os.path.join(repo, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                data = json.load(f)
            if data.get("rc") != 0:
                continue
            n = int(data.get("n") or m.group(1))
        except (OSError, json.JSONDecodeError, TypeError, ValueError):
            continue
        yield path, n, data


def latest_baseline(repo: str = REPO) -> Optional[dict]:
    """Newest committed BENCH_rNN.json with a clean run, parsed into
    {path, n, throughput, events, placed, gpu_alloc, backend} (quality
    fields None when the tail did not carry them; backend None when the
    capture predates bench.py's device stamp)."""
    best = None
    for path, n, data in _iter_captures(repo):
        if not data.get("parsed"):
            continue
        if best is None or n > best["n"]:
            tail = data.get("tail", "")
            ev = _TAIL_EVENTS.search(tail)
            pl = _TAIL_PLACED.search(tail)
            al = _TAIL_ALLOC.search(tail)
            best = {
                "path": path,
                "n": n,
                "throughput": float(data["parsed"].get("value", 0.0)),
                "events": int(ev.group(1)) if ev else None,
                "placed": int(pl.group(1)) if pl else None,
                "gpu_alloc": float(al.group(1)) if al else None,
                "backend": data["parsed"].get("platform"),
            }
    return best


def latest_sweep(repo: str = REPO) -> Optional[dict]:
    """Newest committed BENCH_rNN.json carrying a `sweep` block (written
    by `bench_scale.py --sweep ... --sweep-out`), parsed into the block
    plus {path, n}. Sweep captures deliberately ship WITHOUT a `parsed`
    key so latest_baseline never mistakes them for the headline
    throughput baseline."""
    best = None
    for path, n, data in _iter_captures(repo):
        if not isinstance(data.get("sweep"), dict):
            continue
        if best is None or n > best["n"]:
            best = {"path": path, "n": n, **data["sweep"]}
    return best


def sweep_advisory(nodes, pods, base: Optional[dict],
                   b: int = 4) -> Tuple[bool, List[str]]:
    """ISSUE 6 satellite: smoke the config-axis sweep surface and print
    an advisory throughput comparison against the newest committed sweep
    capture. Measures a B-config weight sweep over an openb prefix —
    warm wall, marginal per-config cost, and the marginal/standalone
    ratio (the number ENGINES.md Round 11 budgets; ratios travel across
    machines of one backend far better than raw walls). The comparison
    NEVER gates — cross-machine walls aren't comparable — but an
    exception on the sweep path is a FAIL: a broken sweep surface is
    exactly what the gate exists to catch. Also hard-checks the
    one-compile contract: a second sweep with different weights must not
    grow the compiled-executable count."""
    import time

    import numpy as np

    from tpusim.sim.driver import (
        Simulator,
        SimulatorConfig,
        schedule_pods_sweep,
    )

    try:
        import jax

        sim = Simulator(nodes, SimulatorConfig(
            policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
            report_per_event=False, seed=42,
        ))
        sim.set_workload_pods(pods[:200])
        sim.set_typical_pods()
        trace = sim.prepare_pods()

        def run(grid):
            t0 = time.perf_counter()
            lanes = schedule_pods_sweep(sim, trace, grid)
            return lanes, time.perf_counter() - t0

        grid = np.stack(
            [np.asarray([1000 - i], np.int32) for i in range(b)]
        )
        run(grid)  # compile run
        lanes, warm = run(grid)
        grid1 = grid[:1]
        run(grid1)
        _, warm1 = run(grid1)
        # one jaxpr per job family: a different weight grid must reuse
        # the compiled sweep executable, not add one — inspect the
        # wrapper the sweep ACTUALLY dispatched (the small smoke workload
        # may select the sequential path)
        fn = sim._last_sweep_fn
        before = fn._cache_size()
        if before < 1:
            return False, [
                f"[gate] sweep: {sim._last_engine!r} dispatched but its "
                "vmapped executable cache is empty — engine bookkeeping "
                "broken (FAIL)"
            ]
        run(np.stack(
            [np.asarray([500 + i], np.int32) for i in range(b)]
        ))
        if sim._last_sweep_fn is not fn or fn._cache_size() != before:
            return False, [
                "[gate] sweep: weight change RECOMPILED the sweep "
                f"engine ({before} -> {fn._cache_size()} executables) "
                "(FAIL)"
            ]
        marginal = max(warm - warm1, 0.0) / max(b - 1, 1)
    except Exception as err:
        return False, [
            f"[gate] sweep: FAIL ({type(err).__name__}: {err})"
        ]
    msgs = [
        f"[gate] sweep: B={b} x {lanes[0].events} events warm "
        f"{warm:.3f}s, marginal {marginal * 1000:.0f} ms/config, "
        f"placed[0]={lanes[0].placed} — weight change reused the "
        "compiled sweep executable (0 recompiles)"
    ]
    if base is not None and base.get("rows"):
        brow = max(base["rows"], key=lambda r: r.get("b", 0))
        msgs.append(
            f"[gate] sweep baseline {os.path.basename(base['path'])} "
            f"(round {base['n']}, backend {base.get('backend')!r}, "
            f"nodes={base.get('nodes')}, B={brow.get('b')}): "
            f"per_config {brow.get('per_config_s')}s, "
            f"ratio_vs_standalone {brow.get('ratio_vs_standalone')} — "
            "advisory only (different workload shape)"
        )
    else:
        msgs.append(
            "[gate] sweep: no committed sweep capture to compare "
            "(bench_scale.py --sweep 1,4,16 --sweep-out BENCH_rNN.json)"
        )
    return True, msgs


def compare(base: dict, cur: dict, tol: float, alloc_tol: float
            ) -> Tuple[bool, List[str]]:
    """Gate verdict + report lines. `cur` needs {throughput, events,
    placed, gpu_alloc, backend}."""
    ok = True
    msgs = []

    def check(label, b, c, exact=False, tol_abs=None):
        nonlocal ok
        if b is None:
            msgs.append(f"  {label}: baseline missing, current {c} (skip)")
            return
        if exact:
            good = b == c
        else:
            good = abs(c - b) <= tol_abs
        mark = "ok" if good else "REGRESSED"
        msgs.append(f"  {label}: baseline {b} vs current {c} [{mark}]")
        ok = ok and good

    check("events", base["events"], cur["events"], exact=True)
    check("placed pods", base["placed"], cur["placed"], exact=True)
    check("gpu_alloc %", base["gpu_alloc"], cur["gpu_alloc"],
          tol_abs=alloc_tol)
    ratio = (
        cur["throughput"] / base["throughput"] if base["throughput"] else 0.0
    )
    if cur["backend"] == base["backend"]:
        good = ratio >= 1.0 - tol
        mark = "ok" if good else "REGRESSED"
        msgs.append(
            f"  throughput: baseline {base['throughput']:.1f} vs current "
            f"{cur['throughput']:.1f} placements/s "
            f"({100 * ratio:.0f}%, tol -{100 * tol:.0f}%) [{mark}]"
        )
        ok = ok and good
    else:
        msgs.append(
            f"  throughput: {cur['throughput']:.1f} placements/s on "
            f"{cur['backend']!r} (baseline {base['throughput']:.1f} on "
            f"{base['backend']!r} — cross-backend, advisory only)"
        )
    return ok, msgs


def decisions_roundtrip(nodes, pods, out_dir: str) -> Tuple[bool, str]:
    """ISSUE 4 satellite: run a small decision-recording replay (openb
    prefix of the bench trace), write its decision JSONL, read it back
    through the digest-verified loader, and require the rows to
    round-trip exactly. A failure here means the provenance surface the
    explain/diff verbs depend on is broken — gate-worthy, so ANY
    exception on the record/write/read path becomes a FAIL verdict (the
    exit-1-with-messages contract of main()), not a traceback that also
    skips the baseline compare."""
    from tpusim.obs import decisions as obs_decisions
    from tpusim.sim.driver import Simulator, SimulatorConfig

    try:
        sim = Simulator(nodes[:200], SimulatorConfig(
            policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
            report_per_event=False, record_decisions=True, seed=42,
        ))
        sim.set_workload_pods(pods[:120])
        res = sim.run()
        if res.decisions is None:
            return False, "[gate] decisions: no stream recorded (FAIL)"
        names = [p.name for p in res.pods]
        path = os.path.join(out_dir, "gate_decisions.jsonl")
        obs_decisions.write_decisions(
            path, res.decisions, policies=list(sim.cfg.policies),
            meta=sim._telemetry_meta(), pod_names=names,
        )
        header, rows = obs_decisions.read_decisions(path)
    except Exception as err:
        return False, f"[gate] decisions: FAIL ({type(err).__name__}: {err})"
    expect = obs_decisions.decision_rows(res.decisions, names)
    if rows != expect:
        return False, (
            f"[gate] decisions: JSONL round-trip MISMATCH ({path})"
        )
    return True, (
        f"[gate] decisions: JSONL round-trip ok — {path} "
        f"({len(rows)} events, digest {header['digest'][:12]}…)"
    )


def svc_smoke(nodes, pods, out_dir: str, b: int = 4) -> Tuple[bool, List[str]]:
    """ISSUE 7 satellite: boot the queueing replay service (the `serve
    --jobs` machinery) on an ephemeral port, POST a b-job grid over real
    HTTP (weights + tune-factor variants plus one exact duplicate), poll
    to done, and hard-check the service contracts: the duplicate is
    answered from the digest cache (dedup_hits, bit-identical result),
    the fresh jobs ride ONE batch, and a second wave differing only in
    weights+tune adds NO compiled sweep executable — the PR 6
    jit._cache_size() zero-recompile check, now end-to-end through the
    POST path. Any exception on the serve/submit path is a FAIL verdict,
    not a traceback."""
    msgs: List[str] = []
    try:
        import shutil

        from tpusim.svc import TraceRef, start_job_server
        from tpusim.svc.client import _request, submit_and_wait
        from tpusim.svc.jobs import trace_digest

        # a fresh artifact dir per run: stale signed results would turn
        # the batching/dedup checks into no-ops (every job a disk hit)
        art = os.path.join(out_dir, "svc_smoke")
        if os.path.isdir(art):
            shutil.rmtree(art)
        os.makedirs(art)
        sub_nodes, sub_pods = nodes[:200], pods[:120]
        trace = TraceRef(
            "default", sub_nodes, sub_pods,
            trace_digest(sub_nodes, sub_pods),
        )
        srv, service, worker = start_job_server(
            art, {"default": trace}, listen=":0", lane_width=b,
            queue_size=4 * b,
        )
        try:
            fam = [["FGDScore", 1000]]
            docs = [
                {"policies": fam, "weights": [1000], "seed": 42},
                {"policies": fam, "weights": [500], "seed": 43,
                 "tune": 0.5},
                {"policies": fam, "weights": [250], "seed": 42},
                {"policies": fam, "weights": [1000], "seed": 42},  # dup
            ]
            results = submit_and_wait(srv.url, docs, timeout=600)
            _, _, q = _request(srv.url + "/queue")
            if (results[0]["placements_sha256"]
                    != results[3]["placements_sha256"]):
                return False, [
                    "[gate] svc: duplicate job's result diverged (FAIL)"
                ]
            if q.get("dedup_hits", 0) < 1:
                return False, [
                    f"[gate] svc: duplicate submission not dedup'd "
                    f"({q}) (FAIL)"
                ]
            execs = q.get("sweep_executables", -1)
            if execs != 1:
                return False, [
                    f"[gate] svc: expected ONE compiled sweep executable "
                    f"after the first wave, found {execs} (FAIL)"
                ]
            submit_and_wait(
                srv.url,
                [{"policies": fam, "weights": [123], "tune": 0.3,
                  "seed": 5}],
                timeout=600,
            )
            _, _, q2 = _request(srv.url + "/queue")
            if q2.get("sweep_executables") != execs:
                return False, [
                    f"[gate] svc: a weights+tune wave RECOMPILED "
                    f"({execs} -> {q2.get('sweep_executables')} "
                    f"executables) (FAIL)"
                ]
            msgs.append(
                f"[gate] svc: {len(results)} jobs + a weights+tune wave "
                f"via {q2['batches_run']} batches, dedup_hits="
                f"{q2['dedup_hits']}, sweep executables stable at "
                f"{execs} (zero recompiles)"
            )
        finally:
            worker.stop()
            srv.stop()
    except Exception as err:
        return False, [f"[gate] svc: FAIL ({type(err).__name__}: {err})"]
    return True, msgs


# hard admission->result p99 SLO for WARM forks on the gate's tiny
# trace (ISSUE 16): generous against poll jitter, far below a cold
# compile or a silent full replay — either blows straight through it
SERVE_P99_SLO_S = 2.5


def _p99(xs):
    s = sorted(xs)
    return s[min(len(s) - 1, max(0, int(0.99 * len(s) + 0.999999) - 1))]


def serve_latency_smoke(nodes, pods, out_dir: str, b: int = 4,
                        n_pods: int = 2000, k: int = 5
                        ) -> Tuple[bool, List[str]]:
    """ISSUE 16: the interactive what-if serving plane end-to-end over
    real HTTP. Runs a base job (checkpoint ladder + fork index entry),
    then a warmup fork/full pair (compiles the wave's three entries),
    then a timed wave of k warm forks + their k from-event-0 "full"
    twins through ONE POST — more jobs than lanes, so late arrivals
    JOIN the running wave at chunk boundaries. Hard checks:

      - every fork's result is field-identical to its full twin
        (placements sha256, counters, gpu_alloc, frag) — warm-state
        bit-identity through the POST path;
      - every warm fork executed <= tail + one chunk events, and every
        full twin replayed from event 0;
      - the wave executable count is UNCHANGED by the timed wave
        (zero recompiles across joins — jit._cache_size() live);
      - admission->result p99 of the warm forks meets the hard SLO
        AND beats the full-replay p99 by >= 3x (the latency win).
    """
    msgs: List[str] = []
    try:
        import shutil

        from tpusim.svc import TraceRef, start_job_server
        from tpusim.svc.client import (
            _request, fetch_results, submit_and_wait, submit_jobs,
            wait_jobs,
        )
        from tpusim.svc.jobs import trace_digest

        art = os.path.join(out_dir, "serve_latency_smoke")
        if os.path.isdir(art):
            shutil.rmtree(art)
        os.makedirs(art)
        sub_nodes, sub_pods = nodes[:200], pods[:n_pods]
        trace = TraceRef(
            "default", sub_nodes, sub_pods,
            trace_digest(sub_nodes, sub_pods),
        )
        srv, service, worker = start_job_server(
            art, {"default": trace}, listen=":0", lane_width=b,
            queue_size=8 * b,
        )
        try:
            fam = [["FGDScore", 1000]]
            (base_res,) = submit_and_wait(
                srv.url,
                [{"policies": fam, "weights": [1000], "seed": 42,
                  "base": True}],
                timeout=600, poll_s=0.05,
            )
            br = base_res.get("base_run") or {}
            E = int(br.get("events", 0))
            chunk = int(br.get("checkpoint_every", 0))
            if not (E and chunk):
                return False, [
                    f"[gate] serve-latency: base result carries no "
                    f"base_run meta ({sorted(base_res)}) (FAIL)"
                ]
            base_digest = base_res["job"]

            def fork_doc(event, tail, mode="fork"):
                doc = {"fork": {"base": base_digest, "event": int(event),
                                "tail": [[int(a), int(p)]
                                         for a, p in tail]}}
                if mode != "fork":
                    doc["fork"]["mode"] = mode
                return doc

            # warmup pair: compiles the wave's step/scatter/finish
            wtail = [[1, 0], [0, 0]]
            submit_and_wait(
                srv.url,
                [fork_doc(E // 2, wtail),
                 fork_doc(E // 2, wtail, "full")],
                timeout=600, poll_s=0.05,
            )
            _, _, q1 = _request(srv.url + "/queue")
            execs = (q1.get("waves") or {}).get("executables", -1)
            if execs < 0:
                return False, [
                    f"[gate] serve-latency: /queue carries no wave "
                    f"executable census ({sorted(q1)}) (FAIL)"
                ]

            # the timed wave: k warm forks near the end of the base
            # stream + their from-0 twins, one POST, tight poll (a
            # millisecond fork must not be measured through a
            # second-scale poll schedule). Forks FIRST, fulls after:
            # claim order is FIFO, so each class's p99 measures its own
            # replay cost — a fork queued BEHIND a 32-chunk full replay
            # would measure the lane wait, not the warm-state win
            docs, tails = [], []
            for j in range(k):
                tail = [[1, 2 * j], [1, 2 * j + 1], [0, 2 * j]]
                tails.append(tail)
                docs.append(fork_doc(E - 1 - (j % 3) * chunk, tail))
            for j in range(k):
                docs.append(
                    fork_doc(E - 1 - (j % 3) * chunk, tails[j], "full")
                )
            acc = submit_jobs(srv.url, docs, timeout=60)
            ids = [a["id"] for a in acc]
            final = wait_jobs(srv.url, ids, timeout=600, poll_s=0.02)
            results = fetch_results(srv.url, ids)

            fork_lat, full_lat = [], []
            for j in range(k):
                fr, vr = results[j], results[k + j]
                for f in ("placements_sha256", "counters",
                          "gpu_alloc_pct", "frag_gpu_milli", "placed",
                          "failed"):
                    if fr[f] != vr[f]:
                        return False, [
                            f"[gate] serve-latency: fork pair {j} "
                            f"diverged on {f}: {fr[f]!r} != {vr[f]!r} "
                            f"(FAIL)"
                        ]
                fm, vm = fr["fork"], vr["fork"]
                if fm["degrade"] or fm["source_cursor"] <= 0:
                    return False, [
                        f"[gate] serve-latency: fork {j} replayed COLD "
                        f"({fm}) — the warm-state path is broken (FAIL)"
                    ]
                if fm["events_executed"] > 3 + chunk:
                    return False, [
                        f"[gate] serve-latency: fork {j} executed "
                        f"{fm['events_executed']} events > tail(3) + "
                        f"chunk({chunk}) (FAIL)"
                    ]
                if vm["source_cursor"] != 0:
                    return False, [
                        f"[gate] serve-latency: full twin {j} did not "
                        f"replay from event 0 ({vm}) (FAIL)"
                    ]
                fork_lat.append(float(final[j]["latency_s"]))
                full_lat.append(float(final[k + j]["latency_s"]))

            _, _, q2 = _request(srv.url + "/queue")
            w = q2.get("waves") or {}
            if w.get("executables") != execs:
                return False, [
                    f"[gate] serve-latency: the timed wave RECOMPILED "
                    f"({execs} -> {w.get('executables')} wave "
                    f"executables) (FAIL)"
                ]
            if w.get("joins", 0) < 1:
                return False, [
                    f"[gate] serve-latency: {2 * k} jobs over {b} lanes "
                    f"produced no boundary join ({w}) — continuous "
                    f"batching is not engaging (FAIL)"
                ]
            if "fork" not in (q2.get("latency") or {}):
                return False, [
                    f"[gate] serve-latency: /queue latency plane "
                    f"missing fork percentiles ({q2.get('latency')}) "
                    f"(FAIL)"
                ]
            p99f, p99v = _p99(fork_lat), _p99(full_lat)
            if p99f > SERVE_P99_SLO_S:
                return False, [
                    f"[gate] serve-latency: warm-fork p99 {p99f:.3f}s "
                    f"breaks the {SERVE_P99_SLO_S}s SLO (FAIL)"
                ]
            if p99f * 3.0 > p99v:
                return False, [
                    f"[gate] serve-latency: warm-fork p99 {p99f:.3f}s "
                    f"is not >=3x faster than full-replay p99 "
                    f"{p99v:.3f}s (FAIL)"
                ]
            msgs.append(
                f"[gate] serve-latency: base {E} ev (chunk {chunk}), "
                f"{k} warm forks bit-identical to their from-0 twins; "
                f"p99 fork {p99f * 1000:.0f}ms vs full "
                f"{p99v * 1000:.0f}ms ({p99v / max(p99f, 1e-9):.1f}x, "
                f"SLO {SERVE_P99_SLO_S}s), {w['joins']} boundary "
                f"join(s), wave executables stable at {execs} "
                f"(zero recompiles)"
            )
        finally:
            worker.stop()
            srv.stop()
    except Exception as err:
        return False, [
            f"[gate] serve-latency: FAIL ({type(err).__name__}: {err})"
        ]
    return True, msgs


def chaos_smoke(nodes, pods, b: int = 8) -> Tuple[bool, List[str]]:
    """ISSUE 10 satellite: the chaos sweep end-to-end on a tiny trace
    prefix — B fault schedules (varying seed/MTBF/evict cadence) in ONE
    compiled vmapped scan, with three hard checks: exactly one compiled
    chaos executable after the first wave, a second wave with DIFFERENT
    schedules adds none (jit._cache_size() stable — fault schedules are
    operands, never jaxpr), and lane 0's placements + DisruptionMetrics
    reconcile exactly against the standalone single-lane
    run_with_faults path."""
    msgs: List[str] = []
    try:
        import numpy as np

        from tpusim.sim.driver import Simulator, SimulatorConfig
        from tpusim.sim.faults import FaultConfig

        sub_nodes, sub_pods = nodes[:200], pods[:120]

        def mk():
            sim = Simulator(sub_nodes, SimulatorConfig(
                policies=(("FGDScore", 1000),),
                gpu_sel_method="FGDScore", report_per_event=False,
                shuffle_pod=False, seed=42,
            ))
            sim.set_workload_pods(list(sub_pods))
            return sim

        def schedules(seed0):
            # explicit queue capacity: retry-slot blocks scale with it,
            # so pinning it (as a real service config would) keeps every
            # wave's merged stream in one power-of-two shape class
            return [
                FaultConfig(
                    mtbf_events=30 + 7 * i, mttr_events=40,
                    evict_every_events=25 - 3 * i, seed=seed0 + i,
                    backoff_base=4, backoff_cap=32, max_retries=3,
                    queue_capacity=16,
                )
                for i in range(b)
            ]
        w = np.asarray([[1000]] * b, np.int32)

        sim = mk()
        lanes = sim.run_sweep(w, seeds=[42] * b, faults=schedules(100))
        fn = sim._last_sweep_fn
        execs = fn._cache_size()
        if execs != 1:
            return False, [
                f"[gate] chaos: expected ONE compiled chaos executable, "
                f"found {execs} (FAIL)"
            ]
        # lane 0 vs the standalone single-lane fault path: placements
        # and every DisruptionMetrics number must reconcile
        solo = mk()
        res = solo.run_with_faults(fault_cfg=schedules(100)[0])
        if not np.array_equal(res.placed_node, lanes[0].placed_node):
            return False, [
                "[gate] chaos: lane 0 placements diverge from the "
                "standalone run_with_faults path (FAIL)"
            ]
        a, c = solo.last_disruption.as_dict(), lanes[0].disruption.as_dict()
        for k in a:
            same = (abs(a[k] - c[k]) < 1e-6 if isinstance(a[k], float)
                    else a[k] == c[k])
            if not same:
                return False, [
                    f"[gate] chaos: DisruptionMetrics[{k}] diverges "
                    f"(standalone {a[k]} vs lane {c[k]}) (FAIL)"
                ]
        # second wave, different schedules, same Simulator (the service
        # worker keeps per-family sims, so its sticky shape floors
        # apply): zero recompiles — the HARD operand contract
        sim.run_sweep(w, seeds=[42] * b, faults=schedules(900))
        if sim._last_sweep_fn is not fn or fn._cache_size() != execs:
            return False, [
                f"[gate] chaos: a new fault-schedule wave RECOMPILED "
                f"({execs} -> {fn._cache_size()} executables) (FAIL)"
            ]
        dm = lanes[0].disruption
        msgs.append(
            f"[gate] chaos: {b}-lane fault sweep x2 waves on one "
            f"executable (zero recompiles); lane0 reconciles standalone "
            f"(evicted={dm.evicted_pods} resched={dm.rescheduled_pods} "
            f"dead={dm.unscheduled_after_retries})"
        )
    except Exception as err:
        return False, [f"[gate] chaos: FAIL ({type(err).__name__}: {err})"]
    return True, msgs


def _write_fleet_trace(base: str, n_nodes: int = 16,
                       n_pods: int = 40) -> Tuple[str, str]:
    """Write a tiny synthetic node/pod CSV pair (the tune_smoke cluster
    shape) — the fleet smoke hosts a REAL file-backed trace because the
    register handshake hands CSV paths to worker processes."""
    import csv

    import numpy as np

    rng = np.random.default_rng(3)
    nodes_csv = os.path.join(base, "nodes.csv")
    pods_csv = os.path.join(base, "pods.csv")
    with open(nodes_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["sn", "cpu_milli", "memory_mib", "gpu", "model"])
        for i, g in enumerate(rng.choice([0, 2, 4, 8], n_nodes)):
            w.writerow([f"n{i:03d}", 32000, 131072, int(g),
                        "V100M16" if g else ""])
    with open(pods_csv, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["name", "cpu_milli", "memory_mib", "num_gpu",
                    "gpu_milli"])
        for i in range(n_pods):
            gpu = int(rng.choice([0, 1, 2]))
            milli = 1000 if gpu > 1 else int(rng.choice([300, 500, 1000]))
            if gpu == 0:
                milli = 0
            w.writerow([f"p{i:04d}", int(rng.choice([1000, 2000, 4000])),
                        2048, gpu, milli])
    return nodes_csv, pods_csv


def _fleet_jobs() -> list:
    """The smoke's job mix: weight/seed/tune variants plus fault jobs
    with DIFFERENT tunes (the ISSUE 12 chaos x tune lift — they must
    share one compiled scan). engine pinned so both phases and every
    worker resolve the identical jaxpr."""
    # two policies: a meatier jaxpr widens the cold-compile vs
    # cache-hit gap the phase-3 joiner check measures
    fam = [["FGDScore", 1000], ["BestFitScore", 500]]
    fault = {"mtbf_events": 12.0, "mttr_events": 15.0, "seed": 7,
             "backoff_base": 2, "backoff_cap": 16, "max_retries": 2,
             "queue_capacity": 16}
    docs = [
        {"policies": fam, "weights": [1000 + 37 * i, 500 + 13 * i],
         "seed": 40 + i % 3, "tune": [0.0, 0.0, 0.3][i % 3],
         "engine": "sequential"}
        for i in range(8)
    ]
    docs += [
        {"policies": fam, "weights": [900, 450], "seed": 42, "tune": 0.0,
         "engine": "sequential", "fault": dict(fault, seed=11)},
        {"policies": fam, "weights": [1100, 550], "seed": 42,
         "tune": 0.4, "engine": "sequential",
         "fault": dict(fault, seed=13)},
    ]
    return docs


def fleet_chaos_smoke(out_dir: str, n_workers: int = 3
                      ) -> Tuple[bool, List[str]]:
    """ISSUE 12 (`make fleet-chaos-smoke`): the kill-tolerant fleet
    end-to-end. Phase 1 runs every job on a single in-process worker
    with FRESH caches — the byte-identity reference and the cold
    compile wall. Phase 2 boots a coordinator + N worker processes on
    the SAME caches, submits the same jobs over real HTTP, `kill -9`s
    the first worker observed holding leases mid-batch, and hard-checks
    the fleet contracts: (a) 100%% of accepted jobs reach signed
    results BYTE-identical to the single-worker run, (b) the dead
    worker's leases are stolen without operator action (/queue steals +
    lease_expired counters), and (c) a FRESH worker joined after the
    chaos wave serves its first batch well under the phase-1 cold
    compile wall (the shared persistent-compile/table caches). Any
    exception is a FAIL verdict, not a traceback."""
    msgs: List[str] = []
    procs = []
    srv = worker = None
    try:
        import shutil
        import signal as _signal
        import time as _time

        from tpusim.svc import load_trace, start_job_server
        from tpusim.svc.client import _request, submit_jobs, wait_jobs
        from tpusim.svc.fleet import spawn_local_workers, stop_workers

        base = os.path.join(out_dir, "fleet_smoke")
        if os.path.isdir(base):
            shutil.rmtree(base)
        os.makedirs(base)
        nodes_csv, pods_csv = _write_fleet_trace(base)
        tcache = os.path.join(base, "table_cache")
        docs = _fleet_jobs()

        # ---- phase 1: the single-worker reference (cold caches)
        art1 = os.path.join(base, "ref")
        os.makedirs(art1)
        trace = load_trace("default", nodes_csv, pods_csv)
        srv, service, worker = start_job_server(
            art1, {"default": trace}, listen=":0", lane_width=2,
            queue_size=64,
            table_cache_dir=tcache,
        )
        accepted = [service.submit_payload(d) for d in docs]
        digests = [a["digest"] for a in accepted]
        if not service.queue.wait_idle(timeout=300):
            return False, ["[gate] fleet: phase-1 reference run did "
                           "not drain (FAIL)"]
        cold_s = worker.first_dispatch_s
        ref_bytes = {}
        for d in digests:
            from tpusim.svc.jobs import result_path

            with open(result_path(art1, d), "rb") as f:
                ref_bytes[d] = f.read()
        worker.stop()
        srv.stop()
        worker = srv = None

        # ---- phase 2: the fleet, same caches, fresh artifact dir
        art2 = os.path.join(base, "fleet")
        os.makedirs(art2)
        srv, service, _ = start_job_server(
            art2, {"default": trace}, listen=":0", lane_width=2,
            queue_size=64, fleet=True, lease_s=2.0,
            table_cache_dir=tcache,
        )
        # queue the jobs BEFORE the workers join: every worker's first
        # claim then lands mid-compile — the widest kill window
        accepted2 = submit_jobs(srv.url, docs)
        ids2 = [a["id"] for a in accepted2]
        procs = spawn_local_workers(
            srv.url, n_workers, table_cache_dir=tcache,
        )
        killed = ""
        deadline = _time.time() + 240
        while _time.time() < deadline:
            _, _, q = _request(srv.url + "/queue")
            if not killed:
                for wid, row in (q.get("workers") or {}).items():
                    if row.get("leases_held", 0) > 0 and row.get("pid"):
                        os.kill(row["pid"], _signal.SIGKILL)
                        killed = wid
                        msgs.append(
                            f"[gate] fleet: kill -9'd {wid} (pid "
                            f"{row['pid']}) holding "
                            f"{row['leases_held']} lease(s) mid-batch"
                        )
                        break
            if q.get("done", 0) >= len(docs) and killed:
                break
            _time.sleep(0.05)
        if not killed:
            return False, ["[gate] fleet: never observed a worker "
                           "holding leases to kill (FAIL)"]
        final = wait_jobs(srv.url, ids2, timeout=240)
        bad = [d["id"] for d in final if d["status"] != "done"]
        if bad:
            return False, [
                f"[gate] fleet: {len(bad)} job(s) never completed "
                f"after the kill: {bad} (FAIL)"
            ]
        _, _, q = _request(srv.url + "/queue")
        if q.get("steals", 0) < 1 or q.get("lease_expired", 0) < 1:
            return False, [
                f"[gate] fleet: dead worker's leases were NOT stolen "
                f"(steals={q.get('steals')}, "
                f"lease_expired={q.get('lease_expired')}) (FAIL)"
            ]
        # byte-identity of every result file against the single-worker
        # reference — the whole idempotency argument, checked as bytes
        from tpusim.svc.jobs import result_path

        for d in digests:
            with open(result_path(art2, d), "rb") as f:
                got = f.read()
            if got != ref_bytes[d]:
                return False, [
                    f"[gate] fleet: result {d[:12]}… diverges from the "
                    "single-worker reference bytes (FAIL)"
                ]
        msgs.append(
            f"[gate] fleet: {len(docs)} jobs (incl. mixed fault/tune "
            f"lanes) on {n_workers} workers survived a mid-batch "
            f"kill -9 — steals={q['steals']}, "
            f"lease_expired={q['lease_expired']}, every result "
            "byte-identical to the single-worker reference"
        )

        # ---- phase 3: the fresh joiner skips the compile. Drain the
        # original fleet first so the joiner — not a warm survivor —
        # provably serves the next wave
        stop_workers(procs)
        procs = []
        joiner = spawn_local_workers(
            srv.url, 1, table_cache_dir=tcache,
        )
        procs = joiner
        fresh = [
            dict(d, weights=[5000 + 11 * i, 2500 + 7 * i])
            for i, d in enumerate(_fleet_jobs()[:4])
        ]
        acc3 = submit_jobs(srv.url, fresh)
        wait_jobs(srv.url, [a["id"] for a in acc3], timeout=240)
        _, _, q = _request(srv.url + "/queue")
        rows = q.get("workers") or {}
        jrow = next(
            (r for r in rows.values() if r.get("pid") == joiner[0].pid),
            None,
        )
        if jrow is None or not jrow.get("first_dispatch_s"):
            return False, ["[gate] fleet: the fresh joiner never "
                           "served a batch (FAIL)"]
        js = jrow["first_dispatch_s"]
        # the relative margin alone flakes on loaded machines: this
        # trace's cold compile is only ~2 s, and the joiner's wall has
        # an irreducible claim+dispatch overhead floor (~1.3 s of
        # subprocess jax startup noise) that 0.65x can undercut. A
        # BROKEN compile cache still fails — the joiner would pay the
        # full cold wall, well above both bounds.
        if js >= max(0.65 * cold_s, 1.6):
            return False, [
                f"[gate] fleet: fresh joiner's first batch "
                f"({js:.2f}s) did not skip the cold compile "
                f"({cold_s:.2f}s) via the shared caches (FAIL)"
            ]
        msgs.append(
            f"[gate] fleet: fresh joiner's first batch {js:.2f}s vs "
            f"{cold_s:.2f}s cold — the shared compile/table caches "
            "carried the warm state"
        )
    except Exception as err:
        return False, [f"[gate] fleet: FAIL ({type(err).__name__}: {err})"]
    finally:
        try:
            if procs:
                from tpusim.svc.fleet import stop_workers

                stop_workers(procs)
            if worker is not None:
                worker.stop()
            if srv is not None:
                srv.stop()
        except Exception:
            pass
    return True, msgs


def _trace_smoke_jobs() -> list:
    """The flight-recorder smoke's job mix — its OWN policy family
    (PWRScore + DotProductScore). The other fleet smokes measure
    cold-compile walls on THEIR families (fleet: FGD+BestFit, wan:
    FGD+GpuPacking, HA: GpuClustering+BestFit), and sharing a
    bench-gate process must not pre-warm them."""
    fam = [["PWRScore", 800], ["DotProductScore", 300]]
    return [
        {"policies": fam, "weights": [800 + 29 * i, 300 + 17 * i],
         "seed": 60 + i % 3, "tune": [0.0, 0.2, 0.0][i % 3],
         "engine": "sequential"}
        for i in range(6)
    ]


def fleet_trace_smoke(out_dir: str, n_workers: int = 2
                      ) -> Tuple[bool, List[str]]:
    """ISSUE 19 (`make fleet-trace-smoke`): the fleet flight recorder
    end-to-end over real processes and real HTTP. Boots a coordinator +
    supervised worker pair, submits a job wave BEFORE the workers join
    (first claims land mid-compile — the widest kill window), `kill
    -9`s the first worker observed holding leases mid-batch, and
    hard-checks the observability contracts: (a) every job completes
    and its stitched cross-process timeline is gap-free — admission,
    claim, dispatch, upload and verify spans all sharing the ONE trace
    id minted at submit, zero orphan spans anywhere, and the killed
    worker's half-open attempt stitched as ABANDONED rather than lost;
    (b) the `tpusim trace` / `tpusim audit` verbs work against the
    artifact dir (exit 0, Chrome-trace export written, chain verified);
    (c) the hash-chained audit log records the steal AND the
    supervisor's respawn and verifies end-to-end; (d) the aggregated
    coordinator /metrics parses via parse_prometheus_text and carries a
    worker=-labeled series set for every live worker that served a
    batch. Any exception is a FAIL verdict, not a traceback."""
    msgs: List[str] = []
    srv = sup = None
    try:
        import json as _json
        import shutil
        import signal as _signal
        import subprocess
        import time as _time
        import urllib.request

        from tpusim.obs import audit as obs_audit
        from tpusim.obs import trace as obs_trace
        from tpusim.obs.emitters import parse_prometheus_text
        from tpusim.svc import load_trace, start_job_server
        from tpusim.svc.client import _request, submit_jobs, wait_jobs
        from tpusim.svc.fleet import worker_command
        from tpusim.svc.supervisor import Supervisor

        base = os.path.join(out_dir, "fleet_trace_smoke")
        if os.path.isdir(base):
            shutil.rmtree(base)
        os.makedirs(base)
        nodes_csv, pods_csv = _write_fleet_trace(base)
        tcache = os.path.join(base, "table_cache")
        docs = _trace_smoke_jobs()

        art = os.path.join(base, "coord")
        os.makedirs(art)
        trace = load_trace("default", nodes_csv, pods_csv)
        srv, service, _ = start_job_server(
            art, {"default": trace}, listen=":0", lane_width=2,
            queue_size=64, fleet=True, lease_s=2.0,
            table_cache_dir=tcache,
        )

        def spawn(n):
            return subprocess.Popen(worker_command(
                srv.url, table_cache_dir=tcache,
            ))

        # NO on_exit=release_dead here: instant reclaim would requeue
        # the dead worker's jobs before the lease expires, and this
        # smoke exists to witness the STEAL path in the audit chain
        # (the wan smoke covers the release_dead fast path)
        sup = Supervisor(spawn, n_workers, breaker_k=6,
                         breaker_window_s=30.0)
        # the respawn lands in the SAME hash chain as the steal it
        # repairs — the audit log tells the whole story of the kill
        sup.audit = service.audit
        service.fleet.supervisor = sup

        accepted = submit_jobs(srv.url, docs)
        ids = [a["id"] for a in accepted]
        digests = [a["digest"] for a in accepted]
        sup.start()

        killed_wid, killed_pid = "", 0
        deadline = _time.time() + 240
        while _time.time() < deadline:
            sup.poll()
            _, _, q = _request(srv.url + "/queue")
            if not killed_wid:
                for wid, row in (q.get("workers") or {}).items():
                    if row.get("leases_held", 0) > 0 and row.get("pid"):
                        os.kill(row["pid"], _signal.SIGKILL)
                        killed_wid, killed_pid = wid, row["pid"]
                        msgs.append(
                            f"[gate] trace: kill -9'd {wid} (pid "
                            f"{killed_pid}) holding "
                            f"{row['leases_held']} lease(s) mid-batch"
                        )
                        break
            if q.get("done", 0) >= len(docs) and killed_wid:
                break
            _time.sleep(0.05)
        if not killed_wid:
            return False, ["[gate] trace: never observed a worker "
                           "holding leases to kill (FAIL)"]
        final = None
        deadline = _time.time() + 240
        while _time.time() < deadline:
            sup.poll()  # keep reaping/respawning while jobs finish
            try:
                final = wait_jobs(srv.url, ids, timeout=2.0)
                break
            except Exception:
                continue
        if final is None:
            return False, ["[gate] trace: jobs did not finish after "
                           "the kill (FAIL)"]
        bad = [d["id"] for d in final if d["status"] != "done"]
        if bad:
            return False, [
                f"[gate] trace: {len(bad)} job(s) never completed "
                f"after the kill: {bad} (FAIL)"
            ]
        sup.poll()  # reap the killed child: its pid must read as DEAD
        # (not zombie) for stitch() to classify its corpse as abandoned

        # ---- (a) the stitched cross-process timelines
        spans, problems = obs_trace.stitch(art)
        if problems:
            return False, [
                f"[gate] trace: span files damaged: {problems} (FAIL)"
            ]
        orphans = [s for s in spans if s["status"] == "orphan"]
        if orphans:
            return False, [
                f"[gate] trace: {len(orphans)} orphan span(s) — "
                "end-without-begin should be impossible (FAIL)"
            ]
        abandoned = [s for s in spans if s["status"] == "abandoned"]
        if not abandoned:
            return False, [
                "[gate] trace: the killed worker left NO abandoned "
                "span — the stolen attempt vanished from the "
                "timeline (FAIL)"
            ]
        want = {obs_trace.SPAN_ADMIT, obs_trace.SPAN_QUEUE_WAIT,
                obs_trace.SPAN_CLAIM, obs_trace.SPAN_DISPATCH,
                obs_trace.SPAN_UPLOAD, obs_trace.SPAN_VERIFY}
        for d in digests:
            mine = [s for s in spans if s["job"] == d]
            names = {s["name"] for s in mine if s["status"] == "ok"}
            missing = want - names
            if missing:
                return False, [
                    f"[gate] trace: job {d[:12]}… timeline has gaps — "
                    f"missing {sorted(missing)} (FAIL)"
                ]
            tids = {s["trace"] for s in mine} - {""}
            if len(tids) != 1:
                return False, [
                    f"[gate] trace: job {d[:12]}… spans carry "
                    f"{len(tids)} trace ids (want exactly the one "
                    "minted at submit) (FAIL)"
                ]
        n_procs = len({s["proc"] for s in spans})
        msgs.append(
            f"[gate] trace: {len(spans)} spans across {n_procs} "
            f"processes — every job's timeline complete, "
            f"{len(abandoned)} abandoned attempt(s) from the kill, "
            "zero orphans"
        )

        # ---- (b) the CLI verbs against the same artifact dir
        stolen = next((d for d in final if d.get("stolen")), None)
        probe = (stolen or final[0])["digest"]
        chrome_out = os.path.join(base, "trace.json")
        r = subprocess.run(
            [sys.executable, "-m", "tpusim", "trace", probe,
             "-d", art, "--out", chrome_out],
            capture_output=True, text=True, timeout=120,
        )
        if r.returncode != 0 or not os.path.isfile(chrome_out):
            return False, [
                f"[gate] trace: `tpusim trace` failed (rc={r.returncode}"
                f", stderr={r.stderr.strip()[-200:]}) (FAIL)"
            ]
        with open(chrome_out) as f:
            if not _json.load(f).get("traceEvents"):
                return False, ["[gate] trace: Chrome-trace export is "
                               "empty (FAIL)"]
        r = subprocess.run(
            [sys.executable, "-m", "tpusim", "audit", "-d", art,
             "--verify"],
            capture_output=True, text=True, timeout=120,
        )
        if r.returncode != 0:
            return False, [
                f"[gate] trace: `tpusim audit --verify` failed "
                f"(rc={r.returncode}, stderr="
                f"{r.stderr.strip()[-200:]}) (FAIL)"
            ]
        msgs.append(
            f"[gate] trace: `tpusim trace {probe[:12]}…` stitched the "
            f"{'stolen ' if stolen else ''}job and `tpusim audit "
            "--verify` passed over the live chain"
        )

        # ---- (c) the audit chain records the whole incident
        n_audit = obs_audit.verify(art)
        kinds = {r["kind"] for r in obs_audit.tail(art, n=0)}
        for needed in ("steal", "respawn"):
            if needed not in kinds:
                return False, [
                    f"[gate] trace: audit chain ({n_audit} records, "
                    f"kinds={sorted(kinds)}) never recorded the "
                    f"{needed!r} (FAIL)"
                ]
        msgs.append(
            f"[gate] trace: audit chain intact — {n_audit} records "
            f"covering {sorted(kinds)}"
        )

        # ---- (d) the aggregated /metrics
        with urllib.request.urlopen(srv.url + "/metrics",
                                    timeout=30) as resp:
            metrics_text = resp.read().decode()
        series = parse_prometheus_text(metrics_text)
        if ("tpusim_fleet_workers_live", ()) not in series:
            return False, ["[gate] trace: merged /metrics lacks the "
                           "fleet gauges (FAIL)"]
        by_worker = {
            dict(labels).get("worker")
            for (_, labels) in series
            if dict(labels).get("worker")
        }
        _, _, q = _request(srv.url + "/queue")
        served = [
            wid for wid, row in (q.get("workers") or {}).items()
            if row.get("batches", 0) > 0 and row.get("pid") != killed_pid
        ]
        missing_w = [w for w in served if w not in by_worker]
        if not by_worker or missing_w:
            return False, [
                f"[gate] trace: merged /metrics missing worker series "
                f"for {missing_w or 'every worker'} "
                f"(have {sorted(by_worker)}) (FAIL)"
            ]
        msgs.append(
            f"[gate] trace: /metrics aggregates {len(by_worker)} live "
            f"worker(s) under worker= labels "
            f"({len(series)} series parse clean)"
        )
    except Exception as err:
        return False, [f"[gate] trace: FAIL ({type(err).__name__}: "
                       f"{err})"]
    finally:
        try:
            if sup is not None:
                sup.stop()
            if srv is not None:
                srv.stop()
        except Exception:
            pass
    return True, msgs


def _ha_jobs() -> list:
    """The HA smoke's job mix: weight/seed/tune variants plus one fault
    job (capability-routed — every spawned worker declares fault-lane
    support). The policy family deliberately differs from _fleet_jobs()
    and _wan_jobs(): those smokes measure cold-compile walls on THEIR
    families, and sharing a process (bench-gate) must not pre-warm
    them."""
    fam = [["GpuClusteringScore", 900], ["BestFitScore", 450]]
    docs = [
        {"policies": fam, "weights": [900 + 31 * i, 450 + 11 * i],
         "seed": 50 + i % 2, "tune": [0.0, 0.0, 0.25][i % 3],
         "engine": "sequential"}
        for i in range(6)
    ]
    docs.append(
        {"policies": fam, "weights": [1000, 500], "seed": 52, "tune": 0.0,
         "engine": "sequential",
         "fault": {"mtbf_events": 12.0, "mttr_events": 15.0, "seed": 9,
                   "backoff_base": 2, "backoff_cap": 16, "max_retries": 2,
                   "queue_capacity": 16}}
    )
    return docs


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def fleet_ha_smoke(out_dir: str) -> Tuple[bool, List[str]]:
    """ISSUE 17 (`make fleet-ha-smoke`): coordinator failover end to
    end, over real processes and real HTTP. Phase 1 runs the job mix on
    a single in-process coordinator — the byte-identity reference.
    Phase 2 boots a token-armed leader + standby CLI pair sharing one
    artifact dir, joins two workers against BOTH urls, submits the same
    jobs through the failover client, `kill -9`s the LEADER while
    leases are held mid-batch, and hard-checks the HA contracts:
    (a) the standby promotes (role/epoch on /healthz) and 100%% of jobs
    complete with per-file byte-identity vs the reference, (b) a
    stale-epoch op and missing/forged tokens are rejected (409 / 401 on
    every mutating endpoint), (c) the resurrected old leader fences
    itself to standby against the live lease, and (d) token material
    never appears in /queue. Any exception is a FAIL verdict."""
    msgs: List[str] = []
    procs: list = []
    coords: list = []
    srv = worker = None
    try:
        import shutil
        import signal as _signal
        import subprocess
        import threading
        import time as _time

        from tpusim.svc import load_trace, start_job_server
        from tpusim.svc.auth import bearer_headers
        from tpusim.svc.client import _request, submit_and_wait
        from tpusim.svc.fleet import stop_workers
        from tpusim.svc.jobs import result_path

        base = os.path.join(out_dir, "fleet_ha_smoke")
        if os.path.isdir(base):
            shutil.rmtree(base)
        os.makedirs(base)
        nodes_csv, pods_csv = _write_fleet_trace(base)
        tcache = os.path.join(base, "table_cache")
        docs = _ha_jobs()

        # ---- phase 1: the single-coordinator reference
        art1 = os.path.join(base, "ref")
        os.makedirs(art1)
        trace = load_trace("default", nodes_csv, pods_csv)
        srv, service, worker = start_job_server(
            art1, {"default": trace}, listen=":0", lane_width=2,
            queue_size=64,
            table_cache_dir=tcache,
        )
        accepted = [service.submit_payload(d) for d in docs]
        digests = [a["digest"] for a in accepted]
        if not service.queue.wait_idle(timeout=300):
            return False, ["[gate] fleet-ha: phase-1 reference run did "
                           "not drain (FAIL)"]
        ref_bytes = {}
        for d in digests:
            with open(result_path(art1, d), "rb") as f:
                ref_bytes[d] = f.read()
        worker.stop()
        srv.stop()
        worker = srv = None

        # ---- phase 2: leader + standby CLI pair, token-armed
        token = "ha-smoke-" + os.urandom(12).hex()
        token_file = os.path.join(base, "token.txt")
        with open(token_file, "w") as f:
            f.write(token + "\n")
        art2 = os.path.join(base, "fleet")
        os.makedirs(art2)
        p1, p2 = _free_port(), _free_port()
        u1, u2 = f"http://127.0.0.1:{p1}", f"http://127.0.0.1:{p2}"
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            TPUSIM_COORD_LEASE_S="1.5", TPUSIM_COORD_SKEW_S="0.5",
        )

        def _coord_cmd(port: int, standby: bool = False) -> list:
            cmd = [
                sys.executable, "-m", "tpusim", "serve", art2, "--jobs",
                "--nodes", nodes_csv, "--pods", pods_csv, "--fleet",
                "--listen", f"127.0.0.1:{port}", "--poll", "0.3",
                "--lane-width", "2", "--lease-s", "2.0",
                "--token-file", token_file,
                "--table-cache-dir", tcache,
            ]
            if standby:
                cmd.append("--standby")
            return cmd

        def _spawn_coord(port: int, tag: str, standby: bool = False):
            log = open(os.path.join(base, f"coord_{tag}.log"), "ab")
            proc = subprocess.Popen(
                _coord_cmd(port, standby), env=env,
                stdout=log, stderr=log,
            )
            coords.append(proc)
            return proc

        def _wait_role(url: str, want: str, timeout_s: float) -> dict:
            end = _time.time() + timeout_s
            last = "?"
            while _time.time() < end:
                try:
                    _, _, h = _request(url + "/healthz", timeout=5)
                    last = h.get("role", "?")
                    if last == want:
                        return h
                except OSError:
                    pass
                _time.sleep(0.1)
            raise RuntimeError(
                f"{url} never reached role {want!r} (last: {last!r})"
            )

        leader = _spawn_coord(p1, "leader")
        _wait_role(u1, "leader", 60)
        _spawn_coord(p2, "standby", standby=True)
        _wait_role(u2, "standby", 60)

        wcmd = [
            sys.executable, "-m", "tpusim", "worker",
            "--join", f"{u1},{u2}", "--token-file", token_file,
            "--table-cache-dir", tcache,
        ]
        for i in range(2):
            log = open(os.path.join(base, f"worker_{i}.log"), "ab")
            procs.append(
                subprocess.Popen(wcmd, env=env, stdout=log, stderr=log)
            )

        # submit through the failover client against BOTH urls; it must
        # ride out the leader's death mid-wait
        box: dict = {}

        def _submit():
            try:
                box["results"] = submit_and_wait(
                    f"{u1},{u2}", docs, timeout=300, token=token
                )
            except Exception as err:  # surfaced below as a FAIL
                box["err"] = err

        th = threading.Thread(target=_submit, daemon=True)
        th.start()

        # kill -9 the LEADER once a worker provably holds leases
        deadline = _time.time() + 120
        held = False
        while _time.time() < deadline and not held:
            try:
                _, _, q = _request(u1 + "/queue", timeout=5)
            except OSError:
                break  # leader already gone?
            for row in (q.get("workers") or {}).values():
                if row.get("leases_held", 0) > 0:
                    held = True
                    break
            _time.sleep(0.05)
        if not held:
            return False, ["[gate] fleet-ha: never observed a worker "
                           "holding leases before the kill (FAIL)"]
        os.kill(leader.pid, _signal.SIGKILL)
        msgs.append(
            f"[gate] fleet-ha: kill -9'd the LEADER (pid {leader.pid}) "
            "with leases held mid-batch"
        )

        h = _wait_role(u2, "leader", 30)
        epoch = int(h.get("epoch", 0))
        if epoch < 2:
            return False, [
                f"[gate] fleet-ha: standby promoted WITHOUT bumping the "
                f"epoch (epoch={epoch}) (FAIL)"
            ]
        msgs.append(
            f"[gate] fleet-ha: standby took over as leader at epoch "
            f"{epoch}"
        )

        # fencing probe: an op stamped with the dead leader's epoch
        auth = bearer_headers(token)
        code, _, doc = _request(
            u2 + "/workers/claim",
            json.dumps({"worker": "ghost", "epoch": 1}).encode(),
            headers=auth,
        )
        if code != 409 or not doc.get("stale_epoch"):
            return False, [
                f"[gate] fleet-ha: stale-epoch claim answered {code} "
                f"{doc} instead of 409 stale_epoch (FAIL)"
            ]
        # auth probes: every mutating endpoint, tokenless AND forged
        mutating = [
            ("/jobs", b"{}"), ("/workers/register", b"{}"),
            ("/workers/claim", b"{}"), ("/workers/renew", b"{}"),
            ("/workers/complete", b"{}"), ("/leases", b"{}"),
            ("/results/deadbeef", b"x"),
        ]
        for path, body in mutating:
            for hdrs in (None, {"Authorization": "Bearer forged"}):
                code, _, _doc = _request(u2 + path, body, headers=hdrs)
                if code != 401:
                    return False, [
                        f"[gate] fleet-ha: POST {path} with "
                        f"{'no' if hdrs is None else 'a forged'} token "
                        f"answered {code}, want 401 (FAIL)"
                    ]
        msgs.append(
            "[gate] fleet-ha: stale-epoch op fenced (409) and all "
            f"{len(mutating)} mutating endpoints reject missing/forged "
            "tokens (401)"
        )

        th.join(300)
        if "err" in box:
            return False, [
                f"[gate] fleet-ha: submit flow failed across the "
                f"failover ({type(box['err']).__name__}: {box['err']}) "
                "(FAIL)"
            ]
        results = box.get("results") or []
        if len(results) != len(docs):
            return False, [
                f"[gate] fleet-ha: {len(results)}/{len(docs)} jobs "
                "completed after the failover (FAIL)"
            ]

        # the resurrected old leader must fence itself to standby
        res = _spawn_coord(p1, "resurrected")
        _wait_role(u1, "standby", 30)
        msgs.append(
            f"[gate] fleet-ha: resurrected old leader (pid {res.pid}) "
            "fenced itself to standby against the live epoch-"
            f"{epoch} lease"
        )

        # byte-identity vs the single-coordinator reference
        for d in digests:
            with open(result_path(art2, d), "rb") as f:
                got = f.read()
            if got != ref_bytes[d]:
                return False, [
                    f"[gate] fleet-ha: result {d[:12]}… diverges from "
                    "the single-coordinator reference bytes (FAIL)"
                ]
        # token redaction: /queue must describe auth without material
        _, _, q = _request(u2 + "/queue", timeout=5)
        blob = json.dumps(q)
        if token in blob:
            return False, ["[gate] fleet-ha: token material LEAKED "
                           "into /queue (FAIL)"]
        if not str(q.get("auth", "")).startswith("enabled"):
            return False, [
                f"[gate] fleet-ha: /queue auth field says "
                f"{q.get('auth')!r}, want 'enabled (...)' (FAIL)"
            ]
        msgs.append(
            f"[gate] fleet-ha: {len(docs)} jobs (incl. a fault lane) "
            "survived a leader kill -9 — every result byte-identical "
            "to the single-coordinator reference; auth described, "
            "never leaked"
        )
    except Exception as err:
        return False, [
            f"[gate] fleet-ha: FAIL ({type(err).__name__}: {err})"
        ]
    finally:
        try:
            if procs:
                from tpusim.svc.fleet import stop_workers

                stop_workers(procs)
            for c in coords:
                if c.poll() is None:
                    try:
                        c.kill()
                    except OSError:
                        pass
            if worker is not None:
                worker.stop()
            if srv is not None:
                srv.stop()
        except Exception:
            pass
    return True, msgs


def slo_smoke(out_dir: str) -> Tuple[bool, List[str]]:
    """ISSUE 20 (`make slo-smoke`): the SLO plane end to end, over real
    HTTP. Three phases:

    (a) alert lifecycle — a coordinator armed with a tight --slo-file
        fork-p99 burn rule serves a base run, then a COLD fork wave (the
        deliberately induced latency regression: every completion eats
        the compile wall) fires the burn-rate page. While firing:
        /healthz degrades with the alert named, `tpusim top --once`
        shows the PAGE, /metrics carries the native latency summary,
        /query serves the event series, /events pages by cursor, and
        the kind=alert record sits in a VERIFYING audit chain. Then
        warm forks (recovery) displace the burn windows and the alert
        RESOLVES — with traffic still flowing, not by going silent.
    (b) breaker trip — a fleet-mode coordinator with the DEFAULT rules
        and a supervisor forced into a crash loop: the circuit breaker
        opens and the built-in breaker-open page fires off the sampled
        gauge, recorded in the chain.
    (c) takeover continuity — a leader + standby CLI pair sharing one
        artifact dir; jobs run, the leader is kill -9'd, the standby
        promotes at a bumped epoch and ADOPTS the signed tsdb snapshot:
        /query on the new leader must serve pre-kill history with no
        gap at the splice (newest adopted point within snapshot cadence
        of the kill) plus fresh post-promotion points.
    """
    msgs: List[str] = []
    procs: list = []
    coords: list = []
    srv = worker = srv_b = sup = None
    saved_env = {k: os.environ.get(k)
                 for k in ("TPUSIM_TSDB_STEP_S", "TPUSIM_TSDB_SNAPSHOT_S")}
    try:
        import shutil
        import signal as _signal
        import subprocess
        import time as _time
        import urllib.request

        from tpusim.obs import audit as obs_audit
        from tpusim.svc import load_trace, start_job_server
        from tpusim.svc.client import _request, submit_and_wait
        from tpusim.svc.supervisor import Supervisor

        # tight sampling so the smoke's windows have real resolution
        os.environ["TPUSIM_TSDB_STEP_S"] = "0.25"
        os.environ["TPUSIM_TSDB_SNAPSHOT_S"] = "0.5"

        base = os.path.join(out_dir, "slo_smoke")
        if os.path.isdir(base):
            shutil.rmtree(base)
        os.makedirs(base)
        nodes_csv, pods_csv = _write_fleet_trace(base)
        tcache = os.path.join(base, "table_cache")
        trace = load_trace("default", nodes_csv, pods_csv)
        fam = [["FGDScore", 700]]

        # the smoke's SLO file: the fork-p99 rule reshaped to smoke
        # scale. objective 1.0s sits far above a warm fork (~ms) and
        # far below a cold compile (seconds); the 30s fast window keeps
        # the page up long enough to probe every surface, and budget
        # 0.25 x burn 2 = a 0.5 breach fraction, so the alert resolves
        # once warm completions OUTNUMBER the cold ones — recovery
        # under live traffic, not silence
        slo_file = os.path.join(base, "slo.json")
        with open(slo_file, "w") as f:
            json.dump({"defaults": False, "rules": [{
                "name": "fork-p99-burn", "type": "burn_rate",
                "severity": "page",
                "metric": "tpusim_queue_latency_event_seconds",
                "label": {"kind": "fork"},
                "objective": 1.0, "op": ">", "budget": 0.25,
                "windows": [{"window_s": 30.0, "burn": 2.0},
                            {"window_s": 60.0, "burn": 1.0}],
                "clear_for_s": 1.0,
            }]}, f)

        # ---- phase (a): fire -> probe every surface -> resolve
        art1 = os.path.join(base, "local")
        os.makedirs(art1)
        srv, service, worker = start_job_server(
            art1, {"default": trace}, listen=":0", lane_width=2,
            queue_size=64,
            table_cache_dir=tcache, slo_file=slo_file,
        )
        (base_res,) = submit_and_wait(
            srv.url,
            [{"policies": fam, "weights": [700], "seed": 61,
              "base": True}],
            timeout=600, poll_s=0.05,
        )
        br = base_res.get("base_run") or {}
        E = int(br.get("events", 0))
        if not E:
            return False, [f"[gate] slo: base result carries no "
                           f"base_run meta ({sorted(base_res)}) (FAIL)"]
        bd = base_res["job"]

        def fork_doc(tail):
            return {"fork": {"base": bd, "event": E - 1,
                             "tail": [[int(a), int(p)]
                                      for a, p in tail]}}

        # the induced regression: the FIRST fork wave compiles the
        # fork-path executables cold — every completion in it pays the
        # compile wall, well past the 1s objective
        t0 = _time.time()
        submit_and_wait(
            srv.url, [fork_doc([[1, 0], [0, 0]]),
                      fork_doc([[1, 1], [0, 1]])],
            timeout=600, poll_s=0.05,
        )
        cold_s = _time.time() - t0
        if cold_s <= 1.0:
            return False, [
                f"[gate] slo: the cold fork wave finished in "
                f"{cold_s:.2f}s — too fast to breach the 1s objective, "
                "the regression never happened (FAIL)"
            ]

        deadline = _time.time() + 30
        fire = None
        while _time.time() < deadline and fire is None:
            _, _, a = _request(srv.url + "/alerts", timeout=5)
            for fd in a.get("firing") or []:
                if fd.get("alert") == "fork-p99-burn":
                    fire = fd
            if fire is None:
                _time.sleep(0.1)
        if fire is None:
            return False, [
                f"[gate] slo: cold fork wave ({cold_s:.1f}s "
                "completions) never fired fork-p99-burn (FAIL)"
            ]
        msgs.append(
            f"[gate] slo: induced fork regression ({cold_s:.1f}s cold "
            f"wave vs 1s objective) fired fork-p99-burn "
            f"(burn fraction {fire.get('value')})"
        )

        # while firing: /healthz flips, top shows the PAGE, /metrics
        # carries the native summary, /query serves the series
        code, _, h = _request(srv.url + "/healthz", timeout=5)
        if code != 503 or "fork-p99-burn" not in (
                h.get("alerts_page") or []):
            return False, [
                f"[gate] slo: /healthz did not degrade on the page "
                f"burn (HTTP {code}, body={h}) (FAIL)"
            ]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        top = subprocess.run(
            [sys.executable, "-m", "tpusim", "top", srv.url, "--once",
             "--width", "100"],
            capture_output=True, text=True, timeout=120, env=env,
        )
        if (top.returncode != 0 or "fork-p99-burn" not in top.stdout
                or "PAGE" not in top.stdout):
            return False, [
                f"[gate] slo: `tpusim top --once` does not show the "
                f"firing page (rc={top.returncode}):\n{top.stdout}"
                f"{top.stderr} (FAIL)"
            ]
        with urllib.request.urlopen(srv.url + "/metrics",
                                    timeout=5) as resp:
            mtext = resp.read().decode()
        if ("# TYPE tpusim_queue_latency_seconds summary" not in mtext
                or 'tpusim_queue_latency_seconds{kind="fork",'
                   'quantile="0.99"}' not in mtext):
            return False, [
                "[gate] slo: /metrics lacks the native per-kind "
                "latency summary series (FAIL)"
            ]
        _, _, qd = _request(
            srv.url + "/query?name=tpusim_queue_latency_event_seconds"
            "&label=kind%3Dfork&since=-120", timeout=5,
        )
        ev_pts = [p for s in qd.get("series") or []
                  for p in s["points"]]
        if not ev_pts:
            return False, ["[gate] slo: /query serves no fork event-"
                           "latency history (FAIL)"]

        # /events cursor pagination (live): page 1 record, then resume
        # from the cursor — no overlap, no skips
        _, _, ev1 = _request(srv.url + "/events?limit=1", timeout=5)
        cur = int(ev1.get("next_after", 0))
        if len(ev1.get("events") or []) != 1 or cur < 1:
            return False, [f"[gate] slo: /events?limit=1 answered "
                           f"{ev1} (FAIL)"]
        _, _, ev2 = _request(
            srv.url + f"/events?after={cur}&limit=500", timeout=5)
        seqs = [e.get("seq", 0) for e in ev2.get("events") or []]
        if any(s <= cur for s in seqs):
            return False, [
                f"[gate] slo: cursor page re-served seqs <= {cur}: "
                f"{seqs} (FAIL)"
            ]

        # recovery: warm forks (compile cached now, ~ms each) displace
        # the burn windows until the fraction drops and the page clears
        deadline = _time.time() + 90
        resolved = False
        j = 0
        while _time.time() < deadline and not resolved:
            submit_and_wait(
                srv.url,
                [fork_doc([[1, j % 40], [0, (j * 7 + 1) % 40]])],
                timeout=600, poll_s=0.05,
            )
            j += 1
            _, _, a = _request(srv.url + "/alerts", timeout=5)
            resolved = not (a.get("firing") or [])
            if not resolved:
                _time.sleep(0.3)
        if not resolved:
            return False, [
                f"[gate] slo: fork-p99-burn never resolved after "
                f"{j} warm recovery forks (FAIL)"
            ]
        code, _, h = _request(srv.url + "/healthz", timeout=5)
        if code != 200:
            return False, [f"[gate] slo: /healthz still {code} after "
                           "the alert resolved (FAIL)"]

        # the firing AND the resolution are records in a chain that
        # still verifies
        n_chain = obs_audit.verify(art1)
        alert_recs = obs_audit.tail(art1, n=0, kind="alert")
        states = [(r.get("alert"), r.get("state")) for r in alert_recs]
        if (("fork-p99-burn", "firing") not in states
                or ("fork-p99-burn", "resolved") not in states):
            return False, [
                f"[gate] slo: audit chain lacks the firing/resolved "
                f"alert records (got {states}) (FAIL)"
            ]
        msgs.append(
            f"[gate] slo: page visible on /healthz(503) + `tpusim top` "
            f"+ /metrics summary + /query; resolved after {j} warm "
            f"fork(s) under live traffic; firing+resolved records in a "
            f"verifying {n_chain}-record audit chain"
        )
        worker.stop()
        srv.stop()
        worker = srv = None

        # ---- phase (b): forced crash loop -> breaker-open page
        art_b = os.path.join(base, "breaker")
        os.makedirs(art_b)
        srv_b, service_b, _ = start_job_server(
            art_b, {"default": trace}, listen=":0", lane_width=2,
            queue_size=16, fleet=True, lease_s=2.0,
        )
        sup = Supervisor(
            lambda n: subprocess.Popen(
                [sys.executable, "-c", "raise SystemExit(3)"]),
            1, breaker_k=3, breaker_window_s=20.0,
            on_exit=service_b.fleet.release_dead,
        )
        sup.healthy_after_s = 3600.0  # every exit counts as a crash
        service_b.fleet.supervisor = sup
        sup.start()
        deadline = _time.time() + 60
        while _time.time() < deadline and not sup.breaker.open:
            sup.poll()
            _time.sleep(0.05)
        if not sup.breaker.open:
            return False, ["[gate] slo: forced crash loop never "
                           "tripped the breaker (FAIL)"]
        deadline = _time.time() + 20
        fired_b = False
        while _time.time() < deadline and not fired_b:
            _, _, a = _request(srv_b.url + "/alerts", timeout=5)
            fired_b = any(fd.get("alert") == "breaker-open"
                          for fd in a.get("firing") or [])
            if not fired_b:
                _time.sleep(0.1)
        if not fired_b:
            return False, [
                "[gate] slo: the open breaker never fired the default "
                "breaker-open page off the sampled gauge (FAIL)"
            ]
        obs_audit.verify(art_b)
        brecs = obs_audit.tail(art_b, n=0, kind="alert")
        if not any(r.get("alert") == "breaker-open"
                   and r.get("state") == "firing" for r in brecs):
            return False, ["[gate] slo: breaker-open firing record "
                           "missing from the audit chain (FAIL)"]
        msgs.append(
            "[gate] slo: crash-loop breaker trip fired the built-in "
            "breaker-open page, chained in audit"
        )
        sup.stop()
        sup = None
        srv_b.stop()
        srv_b = None

        # ---- phase (c): history survives an epoch-fenced takeover
        token = "slo-smoke-" + os.urandom(8).hex()
        token_file = os.path.join(base, "token.txt")
        with open(token_file, "w") as f:
            f.write(token + "\n")
        art2 = os.path.join(base, "fleet")
        os.makedirs(art2)
        p1, p2 = _free_port(), _free_port()
        u1, u2 = f"http://127.0.0.1:{p1}", f"http://127.0.0.1:{p2}"
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            TPUSIM_COORD_LEASE_S="1.5", TPUSIM_COORD_SKEW_S="0.5",
            TPUSIM_TSDB_STEP_S="0.25", TPUSIM_TSDB_SNAPSHOT_S="0.5",
        )

        def _coord_cmd(port: int, standby: bool = False) -> list:
            cmd = [
                sys.executable, "-m", "tpusim", "serve", art2, "--jobs",
                "--nodes", nodes_csv, "--pods", pods_csv, "--fleet",
                "--listen", f"127.0.0.1:{port}", "--poll", "0.3",
                "--lane-width", "2", "--lease-s", "2.0",
                "--token-file", token_file,
                "--table-cache-dir", tcache,
            ]
            if standby:
                cmd.append("--standby")
            return cmd

        def _spawn_coord(port: int, tag: str, standby: bool = False):
            log = open(os.path.join(base, f"coord_{tag}.log"), "ab")
            proc = subprocess.Popen(
                _coord_cmd(port, standby), env=env,
                stdout=log, stderr=log,
            )
            coords.append(proc)
            return proc

        def _wait_role(url: str, want: str, timeout_s: float) -> dict:
            end = _time.time() + timeout_s
            last = "?"
            while _time.time() < end:
                try:
                    _, _, hh = _request(url + "/healthz", timeout=5)
                    last = hh.get("role", "?")
                    if last == want:
                        return hh
                except OSError:
                    pass
                _time.sleep(0.1)
            raise RuntimeError(
                f"{url} never reached role {want!r} (last: {last!r})"
            )

        leader = _spawn_coord(p1, "leader")
        _wait_role(u1, "leader", 60)
        _spawn_coord(p2, "standby", standby=True)
        _wait_role(u2, "standby", 60)
        wcmd = [
            sys.executable, "-m", "tpusim", "worker",
            "--join", f"{u1},{u2}", "--token-file", token_file,
            "--table-cache-dir", tcache,
        ]
        wlog = open(os.path.join(base, "worker_0.log"), "ab")
        procs.append(
            subprocess.Popen(wcmd, env=env, stdout=wlog, stderr=wlog))

        docs = [{"policies": fam, "weights": [700 + 13 * i], "seed": 61,
                 "engine": "sequential"} for i in range(4)]
        results = submit_and_wait(f"{u1},{u2}", docs, timeout=300,
                                  token=token)
        if len(results) != len(docs):
            return False, [f"[gate] slo: {len(results)}/{len(docs)} "
                           "jobs completed on the HA pair (FAIL)"]
        _time.sleep(1.5)  # >= two snapshot cadences: history on disk

        _, _, pre = _request(
            u1 + "/query?name=tpusim_queue_done_total&since=-120",
            timeout=5)
        if not any(s["points"] for s in pre.get("series") or []):
            return False, ["[gate] slo: leader served no done_total "
                           "history before the kill (FAIL)"]
        t_kill = _time.time()
        os.kill(leader.pid, _signal.SIGKILL)
        h = _wait_role(u2, "leader", 30)
        epoch = int(h.get("epoch", 0))
        if epoch < 2:
            return False, [f"[gate] slo: standby promoted without "
                           f"bumping the epoch ({epoch}) (FAIL)"]
        _time.sleep(2.0)  # let the adopted history gain fresh points

        _, _, post = _request(
            u2 + "/query?name=tpusim_queue_done_total&since=-180",
            timeout=5)
        pts = sorted((t, v) for s in post.get("series") or []
                     for t, v in s["points"])
        pre_side = [t for t, _ in pts if t <= t_kill]
        post_side = [t for t, _ in pts if t > t_kill]
        if not pre_side or not post_side:
            return False, [
                f"[gate] slo: promoted standby's /query did not splice "
                f"history ({len(pre_side)} pre-kill / {len(post_side)} "
                "post-promotion points) (FAIL)"
            ]
        gap = t_kill - max(pre_side)
        if gap > 3.0:
            return False, [
                f"[gate] slo: {gap:.1f}s of history lost at the splice "
                "(snapshot cadence is 0.5s) (FAIL)"
            ]
        ts = [t for t, _ in pts]
        if ts != sorted(ts) or len(set(ts)) != len(ts):
            return False, ["[gate] slo: spliced series timestamps are "
                           "not strictly increasing (FAIL)"]
        _, _, a2 = _request(u2 + "/alerts", timeout=5)
        if not a2.get("rules"):
            return False, ["[gate] slo: promoted standby serves no "
                           "alert rules (FAIL)"]
        n2 = obs_audit.verify(art2)
        msgs.append(
            f"[gate] slo: kill -9 takeover at epoch {epoch} adopted "
            f"{len(pre_side)} pre-kill points with {gap:.2f}s gap at "
            f"the splice (cadence 0.5s) + {len(post_side)} fresh "
            f"points; alert engine live on the new leader; shared "
            f"audit chain verifies ({n2} records)"
        )
    except Exception as err:
        return False, [f"[gate] slo: FAIL ({type(err).__name__}: {err})"]
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            if procs:
                from tpusim.svc.fleet import stop_workers

                stop_workers(procs)
            for c in coords:
                if c.poll() is None:
                    try:
                        c.kill()
                    except OSError:
                        pass
            if sup is not None:
                sup.stop()
            if worker is not None:
                worker.stop()
            if srv is not None:
                srv.stop()
            if srv_b is not None:
                srv_b.stop()
        except Exception:
            pass
    return True, msgs


class FlakyShim:
    """The WAN fault injector of `make fleet-wan-smoke` (ISSUE 13): a
    MonitorServer extension app inserted BEFORE the real fleet app that
    drops (503 + Retry-After: 0) or delays a seeded ~20% of
    transfer-plane and fleet-protocol requests — the workers' shared
    backoff schedule must absorb all of it."""

    PATHS = ("/traces/", "/results/", "/leases", "/workers/")

    def __init__(self, rate: float = 0.2, seed: int = 20817,
                 delay_s: float = 0.05):
        import random

        self.rng = random.Random(seed)
        self.rate = float(rate)
        self.delay_s = float(delay_s)
        self.seen = self.dropped = self.delayed = 0

    def handle(self, method, path, body, headers=None):
        import time as _time

        if not any(path.startswith(p) for p in self.PATHS):
            return None
        self.seen += 1
        r = self.rng.random()
        if r < self.rate:
            self.dropped += 1
            return (503, "application/json",
                    b'{"error": "injected WAN fault (FlakyShim)"}\n',
                    {"Retry-After": "0"})
        if r < 2 * self.rate:
            self.delayed += 1
            _time.sleep(self.delay_s)
        return None  # fall through to the real app


def _wan_jobs() -> list:
    """The WAN smoke's job mix: weight/seed/tune variants on the
    'default' trace plus two jobs on a SECOND hosted trace (the
    ISSUE 13 multi-trace hosting check — batching stays per-(trace,
    family)). The policy family deliberately differs from
    _fleet_jobs(): fleet_chaos_smoke measures a COLD compile wall on
    ITS family, and when both smokes share one process (bench-gate,
    resume-smoke) this smoke must not pre-warm that jaxpr."""
    fam = [["FGDScore", 1000], ["GpuPackingScore", 400]]
    docs = [
        {"policies": fam, "weights": [1000 + 41 * i, 500 + 17 * i],
         "seed": 40 + i % 2, "tune": [0.0, 0.0, 0.3][i % 3],
         "engine": "sequential"}
        for i in range(6)
    ]
    docs += [
        {"trace": "alt", "policies": fam, "weights": [900 + 50 * i, 450],
         "seed": 42, "engine": "sequential"}
        for i in range(2)
    ]
    return docs


def fleet_wan_smoke(out_dir: str, n_workers: int = 2
                    ) -> Tuple[bool, List[str]]:
    """ISSUE 13 (`make fleet-wan-smoke`): the wide-area fleet
    end-to-end, with NO shared filesystem between coordinator and
    workers. Phase 1 runs every job on a single in-process worker — the
    byte-identity reference. Phase 2 boots a coordinator hosting TWO
    traces behind a FlakyShim (drops/delays ~20% of transfer requests)
    and a Supervisor spawning N REMOTE-mode workers with fully isolated
    per-worker dirs (own trace cache, artifact scratch, compile/table
    caches), `kill -9`s a remote worker observed holding leases
    mid-batch, and hard-checks: (a) 100%% of jobs reach signed results
    BYTE-identical to the reference, (b) the supervisor respawned the
    killed child (respawn counter >= 1 in /queue), (c) workers report
    mode=remote with live transfer counters and the shim really
    injected faults, (d) a torn upload probe is rejected with nothing
    written. Phase 3 forces a crash loop (spawn_fn that exits
    immediately) and checks the circuit breaker opens — /healthz
    degrades loudly and /queue says why — instead of spinning."""
    import shutil
    import signal as _signal
    import subprocess
    import time as _time

    msgs: List[str] = []
    srv = worker = sup = None
    try:
        from tpusim.svc import load_trace, start_job_server
        from tpusim.svc.client import _request, submit_jobs, wait_jobs
        from tpusim.svc.fleet import _post_bytes, worker_command
        from tpusim.svc.jobs import result_path
        from tpusim.svc.supervisor import Supervisor

        base = os.path.join(out_dir, "fleet_wan")
        if os.path.isdir(base):
            shutil.rmtree(base)
        os.makedirs(base)
        t_dir = os.path.join(base, "traces_default")
        a_dir = os.path.join(base, "traces_alt")
        os.makedirs(t_dir)
        os.makedirs(a_dir)
        nodes_csv, pods_csv = _write_fleet_trace(t_dir)
        alt_nodes, alt_pods = _write_fleet_trace(a_dir, n_nodes=12,
                                                 n_pods=24)
        docs = _wan_jobs()

        # ---- phase 1: single-worker reference
        art1 = os.path.join(base, "ref")
        os.makedirs(art1)
        trace = load_trace("default", nodes_csv, pods_csv)
        alt = load_trace("alt", alt_nodes, alt_pods)
        srv, service, worker = start_job_server(
            art1, {"default": trace, "alt": alt}, listen=":0",
            lane_width=2, queue_size=64,
        )
        accepted = [service.submit_payload(d) for d in docs]
        digests = [a["digest"] for a in accepted]
        if not service.queue.wait_idle(timeout=300):
            return False, ["[gate] wan: phase-1 reference run did not "
                           "drain (FAIL)"]
        ref_bytes = {}
        for d in digests:
            with open(result_path(art1, d), "rb") as f:
                ref_bytes[d] = f.read()
        worker.stop()
        srv.stop()
        worker = srv = None

        # ---- phase 2: remote fleet behind the flaky shim
        art2 = os.path.join(base, "coord")
        os.makedirs(art2)
        srv, service, _ = start_job_server(
            art2, {"default": trace, "alt": alt}, listen=":0",
            lane_width=2, queue_size=64, fleet=True, lease_s=2.0,
        )
        shim = FlakyShim()
        srv._apps.insert(0, shim)

        def spawn_remote(n):
            wdir = os.path.join(base, f"wk{n}")
            return subprocess.Popen(worker_command(
                srv.url, mode="remote", cache_dir=wdir,
                table_cache_dir=os.path.join(wdir, "tables"),
            ))

        sup = Supervisor(
            spawn_remote, n_workers,
            breaker_k=4, breaker_window_s=20.0,
            on_exit=service.fleet.release_dead,
        )
        service.fleet.supervisor = sup

        accepted2 = submit_jobs(srv.url, docs)
        ids2 = [a["id"] for a in accepted2]
        sup.start()
        killed = ""
        deadline = _time.time() + 240
        while _time.time() < deadline:
            sup.poll()
            _, _, q = _request(srv.url + "/queue")
            if not killed:
                for wid, row in (q.get("workers") or {}).items():
                    if (row.get("leases_held", 0) > 0 and row.get("pid")
                            and row.get("mode") == "remote"):
                        os.kill(row["pid"], _signal.SIGKILL)
                        killed = wid
                        msgs.append(
                            f"[gate] wan: kill -9'd remote worker "
                            f"{wid} (pid {row['pid']}) holding "
                            f"{row['leases_held']} lease(s) mid-batch"
                        )
                        break
            if q.get("done", 0) >= len(docs) and killed:
                break
            _time.sleep(0.05)
        if not killed:
            return False, ["[gate] wan: never observed a remote worker "
                           "holding leases to kill (FAIL)"]
        deadline = _time.time() + 240
        final = None
        while _time.time() < deadline:
            sup.poll()  # keep supervising while the jobs finish
            try:
                final = wait_jobs(srv.url, ids2, timeout=2.0)
                break
            except Exception:
                continue
        if final is None:
            return False, ["[gate] wan: jobs did not finish after the "
                           "kill (FAIL)"]
        bad = [d["id"] for d in final if d["status"] != "done"]
        if bad:
            return False, [
                f"[gate] wan: {len(bad)} job(s) never completed after "
                f"the kill: {bad} (FAIL)"
            ]
        # 100% completion: every result byte-identical to the
        # single-worker reference, ACROSS the lossy transfer plane
        for d in digests:
            with open(result_path(art2, d), "rb") as f:
                if f.read() != ref_bytes[d]:
                    return False, [
                        f"[gate] wan: result {d[:12]}… diverges from "
                        "the single-worker reference bytes (FAIL)"
                    ]
        _, _, q = _request(srv.url + "/queue")
        supq = q.get("supervisor") or {}
        if supq.get("respawns", 0) < 1:
            return False, [
                f"[gate] wan: the killed worker was NOT respawned "
                f"(supervisor={supq}) (FAIL)"
            ]
        if q.get("steals", 0) < 1:
            return False, [
                f"[gate] wan: the dead worker's jobs were not "
                f"reclaimed (steals={q.get('steals')}) (FAIL)"
            ]
        rows = q.get("workers") or {}
        remote_rows = [r for r in rows.values()
                       if r.get("mode") == "remote"]
        if not remote_rows or not any(
            (r.get("transfers") or {}).get("uploads", 0) > 0
            for r in remote_rows
        ):
            return False, [
                "[gate] wan: no remote-mode worker reported upload "
                f"transfer counters (rows={rows}) (FAIL)"
            ]
        tr = q.get("transfer") or {}
        if shim.dropped < 1:
            return False, ["[gate] wan: the flaky shim never dropped a "
                           "request — the chaos was a no-op (FAIL)"]
        if tr.get("uploads_ok", 0) < len(digests):
            return False, [
                f"[gate] wan: only {tr.get('uploads_ok')} of "
                f"{len(digests)} results arrived via upload (FAIL)"
            ]
        # torn upload probe: truncated bytes must be rejected with the
        # landed file untouched
        probe = digests[0]
        code, _, _ = _post_bytes(
            srv.url, f"/results/{probe}", ref_bytes[probe][:-25],
            max_attempts=20,
        )
        with open(result_path(art2, probe), "rb") as f:
            intact = f.read() == ref_bytes[probe]
        if code != 400 or not intact:
            return False, [
                f"[gate] wan: torn upload probe not rejected cleanly "
                f"(HTTP {code}, intact={intact}) (FAIL)"
            ]
        msgs.append(
            f"[gate] wan: {len(docs)} jobs over 2 hosted traces on "
            f"{n_workers} REMOTE workers (no shared fs) survived "
            f"{shim.dropped} dropped + {shim.delayed} delayed "
            f"transfers and a mid-batch kill -9 — respawns="
            f"{supq.get('respawns')}, steals={q['steals']}, "
            f"uploads_ok={tr['uploads_ok']}, every result "
            "byte-identical to the single-worker reference"
        )

        # ---- phase 3: forced crash loop -> the breaker, not a spin
        sup.stop()
        sup.spawn_fn = lambda n: subprocess.Popen(
            [sys.executable, "-c", "raise SystemExit(3)"]
        )
        sup.healthy_after_s = 3600.0  # every exit counts as a crash
        sup.start()
        deadline = _time.time() + 60
        while _time.time() < deadline:
            sup.poll()
            if sup.breaker.open:
                break
            _time.sleep(0.05)
        if not sup.breaker.open:
            return False, ["[gate] wan: forced crash loop never "
                           "tripped the circuit breaker (FAIL)"]
        _, _, q = _request(srv.url + "/queue")
        br = (q.get("supervisor") or {}).get("breaker") or {}
        if br.get("state") != "open" or "crash loop" not in str(
            br.get("reason")
        ):
            return False, [
                f"[gate] wan: /queue does not say WHY respawning "
                f"stopped (breaker={br}) (FAIL)"
            ]
        code, _, h = _request(srv.url + "/healthz")
        if code != 503 or h.get("supervisor_breaker") != "open":
            return False, [
                f"[gate] wan: /healthz did not degrade on the open "
                f"breaker (HTTP {code}, body={h}) (FAIL)"
            ]
        msgs.append(
            f"[gate] wan: forced crash loop tripped the breaker after "
            f"{sup.counters['respawns']} respawns — /healthz 503, "
            "/queue names the reason, no spinning"
        )
    except Exception as err:
        return False, [f"[gate] wan: FAIL ({type(err).__name__}: {err})"]
    finally:
        try:
            if sup is not None:
                sup.stop()
            if worker is not None:
                worker.stop()
            if srv is not None:
                srv.stop()
        except Exception:
            pass
    return True, msgs


def latest_multichip(repo: str = REPO) -> Optional[dict]:
    """Newest committed MULTICHIP_r*.json carrying a `scale` block (the
    ISSUE 11 scale-lane capture written by `bench_multichip.py
    --scale-lane --json-out`), parsed into the block plus {path, n}.
    Older rounds' dryrun captures (n_devices/tail schema) are skipped."""
    best = None
    for path in glob.glob(os.path.join(repo, "MULTICHIP_r*.json")):
        m = re.search(r"MULTICHIP_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                data = json.load(f)
            if data.get("rc") != 0 or not isinstance(
                data.get("scale"), dict
            ):
                continue
            n = int(data.get("n") or m.group(1))
        except (OSError, json.JSONDecodeError, TypeError, ValueError):
            continue
        if best is None or n > best["n"]:
            best = {"path": path, "n": n, **data["scale"]}
    return best


def multichip_advisory(base: Optional[dict]) -> Tuple[bool, List[str]]:
    """ISSUE 11 satellite: advisory comparison of the newest committed
    scale-lane capture, like the BENCH_r*.json baselines — never gates
    on walls (cross-machine), but prints the pipelined-vs-unpipelined
    speedups and the aggregate row so a missing/torn capture or a
    pipelined row that stopped beating the unpipelined body is visible
    in every `make bench-gate` run. FAILs only on a capture whose rows
    report placement divergence (equal=false) — that is a correctness
    bit, not a wall."""
    if base is None:
        return True, [
            "[gate] multichip: no committed scale-lane capture "
            "(bench_multichip.py --scale-lane --json-out "
            "MULTICHIP_rNN.json)"
        ]
    msgs = []
    ok = True
    for r in base.get("rows", []):
        if not r.get("equal", True):
            ok = False
            msgs.append(
                f"[gate] multichip: row nloc={r.get('nloc')} recorded "
                "pipelined/unpipelined placement DIVERGENCE (FAIL)"
            )
            continue
        msgs.append(
            f"[gate] multichip baseline "
            f"{os.path.basename(base['path'])} (round {base['n']}): "
            f"nloc={r.get('nloc')} "
            f"{r.get('us_per_event_pipelined')} us/ev pipelined vs "
            f"{r.get('us_per_event_unpipelined')} unpipelined "
            f"(x{r.get('speedup')}) — advisory"
        )
    agg = base.get("aggregate")
    if agg:
        line = (
            f"[gate] multichip aggregate: {agg.get('nodes')} nodes on "
            f"{agg.get('devices')} devices, "
            f"{agg.get('us_per_event')} us/ev (donated chunked stream)"
        )
        if agg.get("fault"):
            line += (
                f"; chaos {agg['fault'].get('us_per_event')} us/ev over "
                f"{agg['fault'].get('merged_events')} merged events"
            )
        msgs.append(line)
    return ok, msgs


def mesh_chaos_smoke(n_dev: int = 2) -> Tuple[bool, List[str]]:
    """ISSUE 11 satellite (`make mesh-chaos-smoke`): the pipelined shard
    engine end-to-end on a small forced-virtual mesh — (a) a FAULTED
    mesh replay must reproduce the single-device fault lane's placements
    and DisruptionMetrics (the pending registers carry fault kinds too),
    with the frag-delta degrade loud (warning + obs counter, not silent
    zeros); (b) a chunked replay with DONATION armed must hold ONE
    compiled executable across equal-size chunks
    (run_chunk_donated._cache_size), actually consume its input carries
    (donated buffers deleted), keep the live-buffer census stable across
    chunks (nothing re-materialized), and finish bit-identical to the
    one-shot replay. Skips (PASS) when fewer than `n_dev` devices are
    visible — `make mesh-chaos-smoke` forces a virtual CPU mesh."""
    msgs: List[str] = []
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        if len(jax.devices()) < n_dev:
            return True, [
                f"[gate] mesh-chaos: skipped — {len(jax.devices())} "
                f"device(s) visible, needs {n_dev} (run `make "
                "mesh-chaos-smoke` for the forced-virtual-mesh form)"
            ]
        from tpusim.io.trace import NodeRow, PodRow
        from tpusim.sim.driver import Simulator, SimulatorConfig
        from tpusim.sim.faults import FaultConfig

        rng = np.random.default_rng(7)
        nodes = [
            NodeRow(f"n{i:02d}", 32000, 131072, int(g),
                    "V100M16" if g else "")
            for i, g in enumerate(rng.choice([0, 2, 4, 8], 10))
        ]
        pods = [
            PodRow(f"p{i:03d}", int(rng.choice([1000, 2000])), 2048,
                   int(rng.choice([0, 1])), 500)
            for i in range(36)
        ]
        fcfg = FaultConfig(
            mtbf_events=9, mttr_events=8, evict_every_events=7, seed=5,
            backoff_base=2, backoff_cap=8, max_retries=2,
            queue_capacity=8,
        )

        def mk(mesh):
            sim = Simulator(nodes, SimulatorConfig(
                policies=(("FGDScore", 1000),), gpu_sel_method="FGDScore",
                report_per_event=False, seed=42, mesh=mesh,
            ))
            sim.set_workload_pods(list(pods))
            return sim

        # (a) faulted mesh replay reconciles the single-device lane
        solo = mk(0)
        ra = solo.run_with_faults(fault_cfg=fcfg)
        mesh_sim = mk(n_dev)
        rb = mesh_sim.run_with_faults(fault_cfg=fcfg)
        if not mesh_sim._last_engine.startswith("shard_map"):
            return False, [
                f"[gate] mesh-chaos: fault replay ran on "
                f"{mesh_sim._last_engine!r}, not the shard engine (FAIL)"
            ]
        if not np.array_equal(ra.placed_node, rb.placed_node):
            return False, [
                "[gate] mesh-chaos: faulted mesh placements diverge "
                "from the single-device fault lane (FAIL)"
            ]
        a = solo.last_disruption.as_dict()
        b = mesh_sim.last_disruption.as_dict()
        for k in a:
            if k.startswith("post_recovery"):
                continue
            if a[k] != b[k]:
                return False, [
                    f"[gate] mesh-chaos: DisruptionMetrics[{k}] "
                    f"diverges ({a[k]} vs {b[k]}) (FAIL)"
                ]
        # the degrade must be LOUD when recovers were scheduled
        had_recover = solo.last_disruption.node_recoveries > 0
        degraded_loudly = any(
            "[Degrade] mesh fault replay" in l for l in mesh_sim.log.lines
        ) and mesh_sim.obs.counts.get("degrade_mesh_frag", 0) > 0
        if had_recover and not degraded_loudly:
            return False, [
                "[gate] mesh-chaos: frag-delta capture dropped "
                "SILENTLY (no [Degrade] line / obs counter) (FAIL)"
            ]

        # (b) donated chunked replay: one executable, buffers consumed,
        # census stable, bit-identical finish
        from tpusim.io.trace import pods_to_specs
        from tpusim.parallel import make_mesh, pad_nodes, shard_state
        from tpusim.parallel.shard_engine import (
            make_shardmap_table_replay,
        )
        from tpusim.policies import make_policy
        from tpusim.sim.table_engine import build_pod_types

        sim = mk(0)
        sim.set_typical_pods()
        specs = pods_to_specs(pods, sim.node_index)
        e = len(pods)
        ev_kind = jnp.zeros(e, jnp.int32)
        ev_pod = jnp.arange(e, dtype=jnp.int32)
        types = build_pod_types(specs)
        key = jax.random.PRNGKey(3)
        mesh = make_mesh(n_dev)
        state, rank = pad_nodes(sim.init_state, sim.rank, n_dev)
        state = shard_state(state, mesh)
        policies = [(make_policy("FGDScore"), 1000)]
        replay = make_shardmap_table_replay(
            policies, mesh, gpu_sel="FGDScore"
        )
        ref = replay(state, specs, types, ev_kind, ev_pod, sim.typical,
                     key, rank)
        chunk = e // 4
        carry = replay.init_carry(state, specs, types, sim.typical, key,
                                  rank)
        census = []
        steady = None
        for i in range(4):
            prev_leaves = jax.tree.leaves(carry)
            carry, _ys = replay.run_chunk_donated(
                carry, specs, types,
                ev_kind[i * chunk:(i + 1) * chunk],
                ev_pod[i * chunk:(i + 1) * chunk], sim.typical, rank,
            )
            jax.block_until_ready(jax.tree.leaves(carry))
            if i > 0 and not all(
                getattr(l, "is_deleted", lambda: True)()
                for l in prev_leaves
            ):
                return False, [
                    "[gate] mesh-chaos: donated input carry still "
                    "alive after the chunk dispatch — donation not "
                    "armed (FAIL)"
                ]
            census.append(len(jax.live_arrays()))
            if i == 1:
                # chunk 0 consumes the init-shaped carry (its own
                # executable); chunk 1 compiles the steady-state entry
                # every later chunk MUST reuse
                steady = replay.run_chunk_donated._cache_size()
        execs = replay.run_chunk_donated._cache_size()
        if execs != steady or execs > 2:
            return False, [
                f"[gate] mesh-chaos: donated chunk executables grew "
                f"past steady state ({steady} -> {execs}) — equal-size "
                "chunks recompiled (FAIL)"
            ]
        if len(set(census[1:])) != 1:
            return False, [
                f"[gate] mesh-chaos: live-buffer census drifted across "
                f"chunks {census} — donated buffers re-materialized "
                "(FAIL)"
            ]
        st, placed, masks, failed = replay.finish(carry)
        if not (
            np.array_equal(np.asarray(placed), np.asarray(ref.placed_node))
            and np.array_equal(np.asarray(masks), np.asarray(ref.dev_mask))
        ):
            return False, [
                "[gate] mesh-chaos: donated chunked replay diverges "
                "from the one-shot replay (FAIL)"
            ]
        dm = mesh_sim.last_disruption
        msgs.append(
            f"[gate] mesh-chaos: faulted {n_dev}-device replay "
            f"reconciles single-device (evicted={dm.evicted_pods} "
            f"resched={dm.rescheduled_pods}); donated chunked replay "
            f"held {execs} executable(s) at steady state, census stable "
            f"at {census[-1]} buffers, finish bit-identical"
        )
    except Exception as err:
        return False, [
            f"[gate] mesh-chaos: FAIL ({type(err).__name__}: {err})"
        ]
    return True, msgs


def tune_smoke(out_dir: str, generations: int = 3) -> Tuple[bool, List[str]]:
    """ISSUE 9 satellite (`make tune-smoke`): run the learned-scoring
    loop on a tiny synthetic trace for a few generations on the LOCAL
    backend and hard-check the lane's contracts — (a) zero recompiles
    after generation 1 (every generation's population rides ONE compiled
    sweep executable; jit._cache_size() via the backend's tracked
    wrapper), (b) the digest-signed tuning log reads back (signature
    verifies, one record per generation, optimizer state present), and
    (c) a resume of the finished log under the same flags is a no-op
    that reproduces the file byte-identically. Any exception is a FAIL
    verdict, not a traceback."""
    msgs: List[str] = []
    try:
        import numpy as np

        from tpusim.io.trace import NodeRow, PodRow
        from tpusim.learn import (
            LocalRollout,
            TuneConfig,
            make_family_sim,
            read_log,
            run_tune,
        )

        rng = np.random.default_rng(11)
        nodes = [
            NodeRow(f"n{i:03d}", 32000, 131072, int(g),
                    "V100M16" if g else "")
            for i, g in enumerate(rng.choice([0, 2, 4, 8], 16))
        ]
        pods = []
        for i in range(48):
            gpu = int(rng.choice([0, 1, 2]))
            milli = 1000 if gpu > 1 else int(rng.choice([300, 500, 1000]))
            if gpu == 0:
                milli = 0
            pods.append(PodRow(
                f"p{i:04d}", int(rng.choice([1000, 2000, 4000])), 2048,
                gpu, milli,
            ))
        policies = [("FGDScore", 1000), ("BestFitScore", 500)]
        cfg = TuneConfig(algo="es", generations=generations, popsize=4,
                         sigma=300.0, lr=400.0, seed=3)
        log_path = os.path.join(out_dir, "tune_smoke_log.jsonl")
        if os.path.isfile(log_path):
            os.unlink(log_path)

        sim = make_family_sim(nodes, pods, policies)
        backend = LocalRollout(sim, width=cfg.popsize)
        result = run_tune(backend, policies, cfg, log_path)

        execs = backend.executables()
        if execs != 1:
            return False, [
                f"[gate] tune: expected ONE compiled sweep executable "
                f"across {generations} generations, found {execs} (FAIL)"
            ]
        header, records = read_log(log_path)  # signature verifies here
        if len(records) != generations or any(
            "state" not in r for r in records
        ):
            return False, [
                f"[gate] tune: log carries {len(records)} records for "
                f"{generations} generations (FAIL)"
            ]
        with open(log_path, "rb") as f:
            before = f.read()
        resumed = run_tune(backend, policies, cfg, log_path, resume=True)
        with open(log_path, "rb") as f:
            after = f.read()
        if before != after:
            return False, [
                "[gate] tune: resume of a finished log rewrote it "
                "differently (FAIL)"
            ]
        if resumed.best_weights != result.best_weights:
            return False, [
                "[gate] tune: resume diverged from the original best "
                "(FAIL)"
            ]
        msgs.append(
            f"[gate] tune: {generations} generations x {cfg.popsize} "
            f"candidates on one compiled sweep (zero recompiles), log "
            f"signed + resume byte-identical — best "
            f"{','.join(str(w) for w in result.best_weights)} at "
            f"{result.best_objective:+.4f}"
        )
    except Exception as err:
        return False, [f"[gate] tune: FAIL ({type(err).__name__}: {err})"]
    return True, msgs


def pallas_hbm_smoke(out_dir: str) -> Tuple[bool, List[str]]:
    """ISSUE 15 (`make pallas-hbm-smoke`): the HBM-residency fused
    Pallas engine above the old VMEM ceiling — (a) a synthetic
    N = 8192 / K = 151 trace replayed by a forced pallas engine in
    interpreter mode must NOT degrade: the two-tier residency select
    routes the HBM kernel ("pallas (hbm)") and the placements/devices
    reconcile the blocked table engine BIT-exactly; (b) the residency
    auto-select is pinned at both tiers (old-ceiling shapes -> vmem,
    above-ceiling -> hbm, genuinely impossible -> degrade None) and the
    documented HBM ceiling clears 256k nodes at K = 151; (c) the run
    record carries the residency and the kernel's exact in-kernel DMA
    counters, with every started DMA waited. Any exception is a FAIL
    verdict, not a traceback."""
    msgs: List[str] = []
    try:
        import numpy as np

        from tpusim.io.trace import NodeRow, PodRow
        from tpusim.sim import pallas_engine
        from tpusim.sim.driver import Simulator, SimulatorConfig
        from tpusim.sim.typical import TypicalPodsConfig

        # (b) the two-tier footprint math, pinned first (no compiles)
        sel = pallas_engine.select_residency
        if sel(512, 151, 1, 2048, 4096) != "vmem":
            return False, ["[pallas-hbm] FAIL: old-ceiling shape did not "
                           "auto-select the VMEM tier"]
        if sel(8192, 151, 1, 2048, 4096) != "hbm":
            return False, ["[pallas-hbm] FAIL: above-ceiling shape did "
                           "not auto-select the HBM tier"]
        if sel(10**6, 151, 1, 2048, 4096) is not None:
            return False, ["[pallas-hbm] FAIL: an impossible shape did "
                           "not degrade"]
        ceiling = pallas_engine.hbm_ceiling_nodes(151, 1, 1)
        if ceiling < 256 * 1024:
            return False, [f"[pallas-hbm] FAIL: HBM ceiling {ceiling} < "
                           "256k at K = 151"]
        msgs.append(f"[pallas-hbm] residency select pinned at both tiers; "
                    f"HBM ceiling {ceiling} nodes at K=151")

        # (a) N = 8192, K = 151, above the old ceiling, interpreter mode
        rng = np.random.default_rng(7)
        nodes = [
            NodeRow(
                f"n{i:05d}", int(rng.choice([32000, 64000, 96000])),
                131072, int(g),
                ["2080", "T4", "V100M16"][i % 3] if g else "",
            )
            for i, g in enumerate(rng.choice([0, 2, 4, 8], 8192))
        ]
        kinds = rng.integers(0, 3, 151)
        pods = [
            PodRow(
                f"p{i:04d}", 1000 + 100 * i, 2048,
                (0 if kinds[i] == 0 else 1 if kinds[i] == 1
                 else int(rng.choice([1, 2]))),
                (0 if kinds[i] == 0
                 else int(rng.choice([250, 500])) if kinds[i] == 1
                 else 1000),
            )
            for i in range(151)
        ]

        def run(engine):
            sim = Simulator(nodes, SimulatorConfig(
                policies=(("FGDScore", 1000),),
                gpu_sel_method="FGDScore", seed=42,
                report_per_event=False, engine=engine,
                typical_pods=TypicalPodsConfig(
                    pod_popularity_threshold=95),
            ))
            sim.set_workload_pods(pods)
            return sim, sim.run()

        s_h, r_h = run("pallas")
        if s_h._last_engine != "pallas (hbm)":
            return False, msgs + [
                f"[pallas-hbm] FAIL: N=8192 dispatched "
                f"{s_h._last_engine!r}, not the HBM-residency kernel"]
        if any("[Degrade]" in l for l in s_h.log.lines):
            return False, msgs + [
                "[pallas-hbm] FAIL: the N=8192 run printed [Degrade]"]
        s_t, r_t = run("table")
        if not np.array_equal(r_t.placed_node, r_h.placed_node) or \
                not np.array_equal(r_t.dev_mask, r_h.dev_mask):
            return False, msgs + [
                "[pallas-hbm] FAIL: HBM-kernel placements diverge from "
                "the blocked table engine"]
        msgs.append("[pallas-hbm] N=8192 K=151 replay: pallas (hbm), no "
                    "degrade, bit-identical to the table engine "
                    f"({int((r_h.placed_node >= 0).sum())} placed)")

        # (c) residency + exact DMA counters in the run record
        det = s_h.run_telemetry().to_record()["deterministic"]
        if det.get("pallas_residency") != "hbm":
            return False, msgs + [
                "[pallas-hbm] FAIL: run record lacks "
                "pallas_residency=hbm"]
        waits = det["counts"].get("pallas_dma_waits", 0)
        starts = det["counts"].get("pallas_dma_starts", -1)
        if waits <= 0 or waits != starts:
            return False, msgs + [
                f"[pallas-hbm] FAIL: DMA counters absent or leaking "
                f"(waits={waits}, starts={starts})"]
        msgs.append(f"[pallas-hbm] run record: residency=hbm, "
                    f"dma_waits={waits} == dma_starts, "
                    f"rebuilds={det['counts'].get('pallas_hbm_rebuilds')}")
        return True, msgs
    except Exception as err:  # the gate reports, never tracebacks
        import traceback

        return False, msgs + [
            f"[pallas-hbm] FAIL: {type(err).__name__}: {err}",
            traceback.format_exc(limit=3),
        ]


def policy_smoke(out_dir: str) -> Tuple[bool, List[str]]:
    """ISSUE 14 satellite (`make policy-smoke`): the learned-policy lane
    end-to-end on a tiny synthetic trace — (a) tiny-trace imitation
    round-trip: record an FGD teacher's decisions, teacher-force the
    dataset builder through the log (feasible counts cross-checked),
    train + export, and require the i32 theta's teacher-forced
    agreement to clear the smoke bar; (b) learned-vs-built-in engine
    bit-identity: the exported theta replays identically on the
    sequential, flat, and blocked engines — plus the shard_map engine
    whenever >= 2 devices are visible (the `--policy-only` mode forces a
    2-device virtual CPU mesh, the mesh-chaos pattern); (c) ES policy
    search over theta adds ZERO compiled sweep executables after its
    first generation (hard jit._cache_size() check via the backend's
    tracked wrapper); (d) the signed artifact round-trips and a torn/
    edited copy is rejected loudly; (e) a service-side policy preset
    answers a submit job with the exact placements of the artifact run
    locally. Any exception is a FAIL verdict, not a traceback."""
    msgs: List[str] = []
    try:
        import json as _json

        import jax
        import numpy as np

        from tpusim.io.trace import NodeRow, PodRow
        from tpusim.learn import (
            ImitateConfig,
            LocalRollout,
            TeacherReplay,
            TuneConfig,
            load_policy_artifact,
            load_teacher_log,
            make_family_sim,
            policies_from_artifact,
            run_tune,
            save_policy_artifact,
        )
        from tpusim.learn.dataset import imitate_with_mining
        from tpusim.learn.policy import learned_policies
        from tpusim.obs import decisions as obs_dec
        from tpusim.sim.driver import Simulator, SimulatorConfig

        rng = np.random.default_rng(11)
        nodes = [
            NodeRow(f"n{i:03d}", 32000, 131072, int(g),
                    "V100M16" if g else "")
            for i, g in enumerate(rng.choice([0, 2, 4, 8], 16))
        ]
        pods = []
        for i in range(48):
            gpu = int(rng.choice([0, 1, 2]))
            milli = 1000 if gpu > 1 else int(rng.choice([300, 500, 1000]))
            if gpu == 0:
                milli = 0
            pods.append(PodRow(
                f"p{i:04d}", int(rng.choice([1000, 2000, 4000])), 2048,
                gpu, milli,
            ))

        def sim_for(policies, **kw):
            kw.setdefault("gpu_sel_method", "best")
            kw.setdefault("seed", 42)
            kw.setdefault("report_per_event", False)
            s = Simulator(nodes, SimulatorConfig(
                policies=tuple(policies), **kw))
            s.set_workload_pods(list(pods))
            return s

        # (a) imitation round-trip off a recorded FGD teacher
        teacher = sim_for(
            (("FGDScore", 1000),), gpu_sel_method="FGDScore",
            record_decisions=True,
        )
        tres = teacher.run()
        log_path = os.path.join(out_dir, "policy_smoke_teacher.jsonl")
        obs_dec.write_decisions(
            log_path, tres.decisions, policies=[("FGDScore", 1000)],
            meta=teacher._telemetry_meta(),
            pod_names=[p.name for p in tres.pods],
        )
        header, rows = load_teacher_log(log_path)
        replay = TeacherReplay(nodes, teacher.prepare_pods(), header, rows)
        cut = len(rows) - len(rows) // 5
        _, theta, _hist = imitate_with_mining(
            replay, ImitateConfig(steps=600, lr=0.3, l2=1e-6),
            end_event=cut, rounds=4,
        )
        rep = replay.agreement(theta)
        if rep["agreement"] < 0.7:
            return False, [
                f"[gate] policy: imitation agreement "
                f"{100 * rep['agreement']:.1f}% below the 70% smoke bar "
                f"(theta {theta}) (FAIL)"
            ]

        # (d) signed artifact round-trip + torn rejection
        art = os.path.join(out_dir, "policy_smoke_artifact.json")
        save_policy_artifact(art, theta, meta={"source": "policy-smoke"})
        feats, theta2, _ = load_policy_artifact(art)
        if list(theta2) != [int(t) for t in theta]:
            return False, ["[gate] policy: artifact round-trip drifted "
                           "(FAIL)"]
        with open(art) as f:
            lines = f.read().splitlines()
        doc = _json.loads(lines[1])
        doc["theta"][0] = int(doc["theta"][0]) + 1
        torn = os.path.join(out_dir, "policy_smoke_torn.json")
        with open(torn, "w") as f:
            f.write(lines[0] + "\n")
            f.write(_json.dumps(doc, sort_keys=True,
                                separators=(",", ":")) + "\n")
        try:
            load_policy_artifact(torn)
            return False, ["[gate] policy: a TORN artifact loaded "
                           "cleanly (FAIL)"]
        except ValueError:
            pass

        # (b) engine bit-identity of the exported theta
        pol = policies_from_artifact(art)
        engines = [
            ("sequential", dict(engine="sequential")),
            ("flat", dict(engine="table", block_size=-1)),
            ("blocked", dict(engine="table", block_size=4)),
        ]
        if len(jax.devices()) >= 2:
            engines.append(("shard", dict(engine="auto", mesh=2)))
        ref = None
        for label, kw in engines:
            r = sim_for(pol, **kw).run()
            if ref is None:
                ref = (label, r)
                continue
            if not (np.array_equal(np.asarray(ref[1].placed_node),
                                   np.asarray(r.placed_node))
                    and np.array_equal(np.asarray(ref[1].dev_mask),
                                       np.asarray(r.dev_mask))):
                return False, [
                    f"[gate] policy: {label} diverged from {ref[0]} "
                    "replaying the learned artifact (FAIL)"
                ]
        placed = int((np.asarray(ref[1].placed_node) >= 0).sum())

        # (c) one-executable ES generation: a second tuning run over the
        # same family must add ZERO compiled sweep executables (counts
        # read relative — the wrapper is process-global)
        fam = learned_policies(theta2)
        backend = LocalRollout(make_family_sim(nodes, pods, fam), width=4)
        cfg = TuneConfig(algo="es", generations=2, popsize=4,
                         sigma=300.0, lr=400.0, seed=3,
                         w_lo=-4000, w_hi=4000)
        run_tune(backend, fam, cfg,
                 os.path.join(out_dir, "policy_smoke_tune.jsonl"))
        before = backend.executables()
        if before < 1:
            return False, ["[gate] policy: ES backend tracked no "
                           "compiled sweep executable (FAIL)"]
        os.unlink(os.path.join(out_dir, "policy_smoke_tune.jsonl"))
        run_tune(backend, fam,
                 TuneConfig(algo="es", generations=2, popsize=4,
                            sigma=300.0, lr=400.0, seed=4,
                            w_lo=-4000, w_hi=4000),
                 os.path.join(out_dir, "policy_smoke_tune.jsonl"))
        if backend.executables() != before:
            return False, [
                f"[gate] policy: a second ES run grew the compiled "
                f"sweep executables ({before} -> "
                f"{backend.executables()}) (FAIL)"
            ]

        # (e) a served preset answers exactly like the local artifact
        from tpusim.svc import jobs as svc_jobs
        from tpusim.svc.api import JobService
        from tpusim.svc.batcher import JobQueue
        from tpusim.svc.worker import TraceRef, Worker

        trace = TraceRef("default", nodes, pods,
                         svc_jobs.trace_digest(nodes, pods))
        art_dir = os.path.join(out_dir, "policy_smoke_svc")
        os.makedirs(art_dir, exist_ok=True)
        queue = JobQueue(maxsize=8, lane_width=4)
        worker = Worker(queue, {"default": trace}, art_dir)
        service = JobService(
            queue, worker, {"default": trace}, art_dir,
            policy_presets={"smoke": pol},
        )
        resp = service.handle(
            "POST", "/jobs",
            _json.dumps({"policy_preset": "smoke", "seed": 42}).encode(),
        )
        if resp[0] not in (200, 202):
            return False, [f"[gate] policy: preset POST answered "
                           f"{resp[0]} (FAIL)"]
        job_id = _json.loads(resp[2].decode())["id"]
        while True:
            batch = queue.next_batch(timeout=0)
            if not batch:
                break
            worker.run_batch(batch)
        code, _, body = service.handle(
            "GET", f"/jobs/{job_id}/result", b"")[:3]
        got = _json.loads(body.decode())
        local = sim_for(pol).run()
        if code != 200 or not np.array_equal(
            np.asarray(got["placed_node"]), np.asarray(local.placed_node)
        ):
            return False, [
                "[gate] policy: the served preset's placements differ "
                "from the local artifact run (FAIL)"
            ]

        msgs.append(
            f"[gate] policy: imitation {rep['matches']}/"
            f"{rep['creates']} agreement, artifact signed + torn copy "
            f"rejected, {len(engines)}-engine bit-identity "
            f"({placed} placements), ES zero-recompile held at "
            f"{before} executable(s), served preset == local run"
        )
    except Exception as err:
        return False, [
            f"[gate] policy: FAIL ({type(err).__name__}: {err})"
        ]
    return True, msgs


def metrics_scrape_check(record: dict, prom_path: str) -> Tuple[bool, str]:
    """ISSUE 5 satellite: publish the smoke record to an ephemeral
    MonitorServer, scrape /metrics over real HTTP, and require (a) the
    scrape to parse as exposition-format text (parse_prometheus_text —
    the strict checks a textfile collector applies) and (b) the scrape
    to be byte-equal to the emitted textfile. Any exception on the
    serve/scrape path is a FAIL verdict, not a traceback."""
    import urllib.request

    from tpusim.obs.emitters import parse_prometheus_text
    from tpusim.obs.server import MonitorServer

    try:
        srv = MonitorServer(":0").start()
        try:
            srv.publish_record(record)
            with urllib.request.urlopen(srv.url + "/metrics",
                                        timeout=10) as resp:
                scrape = resp.read().decode()
        finally:
            srv.stop()
        parsed = parse_prometheus_text(scrape)
        with open(prom_path) as f:
            disk = f.read()
    except Exception as err:
        return False, f"[gate] scrape: FAIL ({type(err).__name__}: {err})"
    if scrape != disk:
        return False, (
            f"[gate] scrape: /metrics differs from {prom_path} (FAIL)"
        )
    return True, (
        f"[gate] scrape: /metrics parses ({len(parsed)} series) and is "
        f"byte-equal to {os.path.basename(prom_path)}"
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--tol", type=float, default=0.5,
        help="same-backend throughput regression tolerance as a fraction "
        "(default 0.5 — the run-to-run spread on the chip is not "
        "measured yet, ROADMAP S0)",
    )
    ap.add_argument(
        "--alloc-tol", type=float, default=0.05,
        help="absolute GPU-allocation-percent tolerance (default 0.05 — "
        "one rounding ulp of the 2-decimal bench print)",
    )
    ap.add_argument(
        "--warm-runs", type=int, default=2,
        help="warm replays for the smoke throughput sample (full bench "
        "uses 6; 2 keeps the gate fast — quality numbers need only one)",
    )
    ap.add_argument(
        "--out", default=os.path.join(REPO, ".tpusim_obs"),
        help="smoke-profile output dir (JSONL + Prometheus textfile)",
    )
    ap.add_argument(
        "--svc-only", action="store_true",
        help="run only the replay-service smoke (ISSUE 7) — the "
        "`make svc-smoke` mode",
    )
    ap.add_argument(
        "--serve-latency-only", action="store_true",
        help="run only the interactive what-if serving smoke (ISSUE 16: "
        "real-HTTP base run + warm fork wave with boundary joins, fork "
        "vs from-0 bit-identity, zero recompiles, hard admission->"
        "result p99 SLO) — the `make serve-latency-smoke` mode",
    )
    ap.add_argument(
        "--tune-only", action="store_true",
        help="run only the learned-scoring smoke (ISSUE 9) — the "
        "`make tune-smoke` mode",
    )
    ap.add_argument(
        "--chaos-only", action="store_true",
        help="run only the chaos-sweep smoke (ISSUE 10) — the "
        "`make chaos-smoke` mode",
    )
    ap.add_argument(
        "--mesh-chaos-only", action="store_true",
        help="run only the mesh-chaos smoke (ISSUE 11: pipelined shard "
        "fault replay + donated chunked replay on a forced virtual "
        "mesh) — the `make mesh-chaos-smoke` mode",
    )
    ap.add_argument(
        "--fleet-chaos-only", action="store_true",
        help="run only the fleet-chaos smoke (ISSUE 12: 3 worker "
        "processes, random kill -9 mid-batch, byte-identity vs a "
        "single-worker run, orphan stealing, warm-joiner compile "
        "skip) — the `make fleet-chaos-smoke` mode",
    )
    ap.add_argument(
        "--fleet-ha-only", action="store_true",
        help="run only the coordinator-HA smoke (ISSUE 17: token-armed "
        "leader + standby pair over real HTTP, kill -9 the leader "
        "mid-batch, standby adopts at a bumped epoch, workers re-join, "
        "100%% completion byte-identical to a single-coordinator "
        "reference, stale-epoch 409, forged-token 401s, resurrected "
        "leader fenced) — the `make fleet-ha-smoke` mode",
    )
    ap.add_argument(
        "--fleet-trace-only", action="store_true",
        help="run only the fleet flight-recorder smoke (ISSUE 19: "
        "real-HTTP fleet + supervised workers, kill -9 of a "
        "lease-holder mid-batch, gap-free stitched cross-process "
        "timeline for every job with zero orphan spans, the stolen "
        "attempt stitched as abandoned, hash-chained audit log "
        "verifying end-to-end with the steal + respawn recorded, "
        "aggregated /metrics with per-live-worker labeled series) — "
        "the `make fleet-trace-smoke` mode",
    )
    ap.add_argument(
        "--fleet-wan-only", action="store_true",
        help="run only the fleet-wan smoke (ISSUE 13: remote-mode "
        "workers with NO shared filesystem behind a flaky HTTP shim, "
        "kill -9 + supervisor respawn, byte-identity vs a "
        "single-worker run, forced crash loop tripping the circuit "
        "breaker) — the `make fleet-wan-smoke` mode",
    )
    ap.add_argument(
        "--slo-only", action="store_true",
        help="run only the SLO-plane smoke (ISSUE 20: real-HTTP fleet, "
        "induced fork-latency regression fires a burn-rate page "
        "visible on /alerts + /healthz + `tpusim top`, chained in a "
        "verifying audit log, resolving under live recovery traffic; "
        "crash-loop breaker trip fires the built-in page; /query "
        "history survives a kill -9 takeover with no gap at the "
        "splice) — the `make slo-smoke` mode",
    )
    ap.add_argument(
        "--pallas-hbm-only", action="store_true",
        help="run only the HBM-residency pallas smoke (ISSUE 15: "
        "N=8192/K=151 interpreter replay above the old VMEM ceiling "
        "reconciled bit-exactly against the table engine, two-tier "
        "residency auto-select pinned, DMA-wait counters in the run "
        "record) — the `make pallas-hbm-smoke` mode",
    )
    ap.add_argument(
        "--policy-only", action="store_true",
        help="run only the learned-policy smoke (ISSUE 14: tiny-trace "
        "imitation round-trip, learned-vs-built-in engine bit-identity "
        "on a forced 2-device virtual mesh, one-executable ES "
        "generation, signed-artifact round-trip + torn rejection, "
        "served preset == local run) — the `make policy-smoke` mode",
    )
    args = ap.parse_args(argv)

    if args.pallas_hbm_only:
        os.makedirs(args.out, exist_ok=True)
        ok, msgs = pallas_hbm_smoke(args.out)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1

    if args.policy_only:
        # a 2-device virtual CPU mesh BEFORE jax initializes so the
        # bit-identity leg covers the shard_map engine too (the Makefile
        # target pins JAX_PLATFORMS=cpu)
        from tpusim.virtual_mesh import virtual_cpu_devices

        virtual_cpu_devices(2)
        os.makedirs(args.out, exist_ok=True)
        ok, msgs = policy_smoke(args.out)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1

    if args.slo_only:
        ok, msgs = slo_smoke(args.out)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1

    if args.fleet_ha_only:
        ok, msgs = fleet_ha_smoke(args.out)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1

    if args.fleet_trace_only:
        ok, msgs = fleet_trace_smoke(args.out)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1

    if args.fleet_wan_only:
        ok, msgs = fleet_wan_smoke(args.out)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1

    if args.fleet_chaos_only:
        ok, msgs = fleet_chaos_smoke(args.out)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1

    if args.mesh_chaos_only:
        # a CPU smoke by design (the Makefile target pins
        # JAX_PLATFORMS=cpu, like chaos-smoke): a 2-device virtual CPU
        # mesh BEFORE jax initializes
        from tpusim.virtual_mesh import virtual_cpu_devices

        virtual_cpu_devices(2)
        ok, msgs = mesh_chaos_smoke()
        adv_ok, adv = multichip_advisory(latest_multichip())
        msgs += adv
        ok = ok and adv_ok
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1

    if args.tune_only:
        ok, msgs = tune_smoke(args.out)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1

    base = latest_baseline()
    sys.path.insert(0, REPO)
    import bench

    import jax

    nodes, pods = bench.load_trace()

    if args.svc_only:
        ok, msgs = svc_smoke(nodes, pods, args.out)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1
    if args.serve_latency_only:
        ok, msgs = serve_latency_smoke(nodes, pods, args.out)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1
    if args.chaos_only:
        ok, msgs = chaos_smoke(nodes, pods)
        print("\n".join(msgs))
        print(f"[gate] {'PASS' if ok else 'FAIL'}")
        return 0 if ok else 1
    row = bench.measure_policy(
        nodes, pods,
        *next(r for r in bench.POLICY_ROWS if r[0] == "FGD"),
        warm_runs=args.warm_runs, profile=True,
    )
    telemetry = row.pop("_telemetry", None)
    cur = {
        "throughput": row["placements_per_sec"],
        "events": row["events"],
        "placed": row["placements"],
        "gpu_alloc": row["gpu_alloc_pct"],
        "backend": jax.default_backend(),
    }

    scrape_ok, scrape_msg = True, ""
    if telemetry is not None:
        from tpusim.obs import emitters

        prom_path = os.path.join(args.out, "gate_metrics.prom")
        record = emitters.build_record(
            telemetry, meta={"gate": "bench-gate", "row": row}
        )
        paths = emitters.emit_record(
            record, telemetry.spans,
            jsonl=os.path.join(args.out, "gate_profile.jsonl"),
            metrics=prom_path,
        )
        print(f"[gate] smoke profile: {', '.join(paths)}")
        # live-telemetry smoke: a /metrics scrape of the same record must
        # parse and match the textfile byte-for-byte (ISSUE 5 satellite)
        scrape_ok, scrape_msg = metrics_scrape_check(record, prom_path)
        print(scrape_msg)

    # decision-provenance smoke: the JSONL the explain/diff verbs consume
    # must round-trip (ISSUE 4 satellite) — checked regardless of
    # whether a throughput baseline exists
    dec_ok, dec_msg = decisions_roundtrip(nodes, pods, args.out)
    print(dec_msg)
    # config-axis sweep smoke + advisory throughput comparison (ISSUE 6
    # satellite): the one-compile contract gates, the walls never do
    swp_ok, swp_msgs = sweep_advisory(nodes, pods, latest_sweep())
    print("\n".join(swp_msgs))
    # replay-service smoke (ISSUE 7 satellite): POST path end-to-end —
    # dedup via the digest cache, one batch per wave, zero recompiles
    # across a weights+tune wave
    svc_ok, svc_msgs = svc_smoke(nodes, pods, args.out)
    print("\n".join(svc_msgs))
    # interactive what-if serving smoke (ISSUE 16): warm-state fork wave
    # over real HTTP — bit-identity vs from-0 twins, boundary joins with
    # zero recompiles, hard admission->result p99 SLO
    serve_ok, serve_msgs = serve_latency_smoke(nodes, pods, args.out)
    print("\n".join(serve_msgs))
    # learned-scoring smoke (ISSUE 9 satellite): the tuning loop on one
    # compiled sweep — zero recompiles, signed resumable log
    tune_ok, tune_msgs = tune_smoke(args.out)
    print("\n".join(tune_msgs))
    # chaos-sweep smoke (ISSUE 10 satellite): B-lane fault sweep — hard
    # zero-recompile check + standalone disruption reconciliation
    chaos_ok, chaos_msgs = chaos_smoke(nodes, pods)
    print("\n".join(chaos_msgs))
    # learned-policy smoke (ISSUE 14): imitation round-trip, engine
    # bit-identity of a signed artifact, ES zero-recompile, preset
    pol_ok, pol_msgs = policy_smoke(args.out)
    print("\n".join(pol_msgs))
    # HBM-residency pallas smoke (ISSUE 15): above-the-old-ceiling
    # interpreter replay vs the table engine, residency select, DMA
    # counters
    hbm_ok, hbm_msgs = pallas_hbm_smoke(args.out)
    print("\n".join(hbm_msgs))
    # mesh-chaos smoke (ISSUE 11 satellite): pipelined shard fault
    # replay + donated chunked replay — skips (PASS) on single-device
    # hosts; `make mesh-chaos-smoke` runs the forced-virtual-mesh form
    mesh_ok, mesh_msgs = mesh_chaos_smoke()
    print("\n".join(mesh_msgs))
    # fleet-chaos smoke (ISSUE 12): worker processes + kill -9 mid-batch
    # — byte-identity vs single-worker, orphan stealing, warm joiner
    fleet_ok, fleet_msgs = fleet_chaos_smoke(args.out)
    print("\n".join(fleet_msgs))
    # fleet-wan smoke (ISSUE 13): no-shared-fs remote workers under a
    # flaky transfer plane + supervisor respawn + the circuit breaker
    wan_ok, wan_msgs = fleet_wan_smoke(args.out)
    print("\n".join(wan_msgs))
    # fleet-trace smoke (ISSUE 19): the flight recorder — stitched
    # cross-process timelines across a kill -9 + steal, hash-chained
    # audit log, aggregated per-worker /metrics
    trace_ok, trace_msgs = fleet_trace_smoke(args.out)
    print("\n".join(trace_msgs))
    # fleet-ha smoke (ISSUE 17): leader + standby pair, kill -9 the
    # leader mid-batch — epoch-fenced takeover, auth probes,
    # byte-identity vs a single-coordinator reference
    ha_ok, ha_msgs = fleet_ha_smoke(args.out)
    print("\n".join(ha_msgs))
    # SLO-plane smoke (ISSUE 20): burn-rate page fires on an induced
    # fork regression, resolves under recovery traffic, breaker trip
    # pages, /query history survives a kill -9 takeover
    slo_ok, slo_msgs = slo_smoke(args.out)
    print("\n".join(slo_msgs))
    # scale-lane advisory (ISSUE 11 satellite): newest committed
    # MULTICHIP_r*.json, like the BENCH_r*.json baselines
    mc_ok, mc_msgs = multichip_advisory(latest_multichip())
    print("\n".join(mc_msgs))
    smoke_ok = (dec_ok and scrape_ok and swp_ok and svc_ok and serve_ok
                and tune_ok and chaos_ok and pol_ok and hbm_ok
                and mesh_ok and fleet_ok and wan_ok and trace_ok
                and ha_ok and slo_ok and mc_ok)

    if base is None:
        print("[gate] no committed BENCH_r*.json baseline found — smoke "
              "profile recorded, nothing to diff "
              f"({'PASS' if smoke_ok else 'FAIL'})")
        return 0 if smoke_ok else 1

    ok, msgs = compare(base, cur, args.tol, args.alloc_tol)
    ok = ok and smoke_ok
    print(f"[gate] baseline {os.path.basename(base['path'])} "
          f"(round {base['n']}, backend {base['backend']!r}):")
    print("\n".join(msgs))
    print(f"[gate] {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
