#!/usr/bin/env python
"""Synthetic scale stress: 100k-node cluster / 1M-pod stream under FGD
(BASELINE.json config 5 — "Synthetic 100k-node / 1M-pod stress").

The openb cluster (1523 nodes) is tiled out to --nodes heterogeneous nodes
(same SKU mix) and a --pods creation stream is sampled from the openb
typical-pod distribution. --engine picks the replay engine (the fused
Pallas engine's VMEM-resident tables bound its N; the table engine scales
to 100k nodes — measured table in ENGINES.md); for the node-axis sharded
multi-device path see tpusim.parallel and tests/test_parallel.py.

    python bench_scale.py                     # 100k nodes, 1M pods, 1 chip
    python bench_scale.py --nodes 10000 --pods 100000
"""

from __future__ import annotations

import argparse
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def synth_cluster(num_nodes: int, seed: int = 0):
    import numpy as np

    from tpusim.io.trace import load_node_csv

    base = load_node_csv(os.path.join(REPO, "data/csv/openb_node_list_gpu_node.csv"))
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(base), num_nodes)
    rows = []
    for i, j in enumerate(idx):
        b = base[int(j)]
        rows.append(
            type(b)(
                name=f"synth-{i:06d}",
                cpu_milli=b.cpu_milli,
                memory_mib=b.memory_mib,
                gpu=b.gpu,
                model=b.model,
                cpu_model=b.cpu_model,
            )
        )
    return rows


def synth_pods(num_pods: int, seed: int = 1):
    import numpy as np

    from tpusim.io.trace import load_pod_csv

    base = load_pod_csv(os.path.join(REPO, "data/csv/openb_pod_list_default.csv"))
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(base), num_pods)
    rows = []
    for i, j in enumerate(idx):
        b = base[int(j)]
        rows.append(
            type(b)(
                name=f"sp-{i:07d}",
                cpu_milli=b.cpu_milli,
                memory_mib=b.memory_mib,
                num_gpu=b.num_gpu,
                gpu_milli=b.gpu_milli,
                gpu_spec=b.gpu_spec,
            )
        )
    return rows


def run_sweep_bench(args, sim, cache_dir):
    """`--sweep B[,B...]` (ISSUE 6): measure the config-axis sweep — one
    row per batch size B with the cold wall (first dispatch, incl. the
    ONE scan compile the whole weight grid shares), the warm wall, and
    the marginal per-config cost against a standalone warm replay of the
    same workload. The weight rows are distinct (base - i per config) so
    every lane is a real what-if, yet all of them run one jaxpr — the
    one-compile-per-job-family contract `replay.engine` carries."""
    import jax
    import numpy as np

    from tpusim.io.trace import build_events, pods_to_specs
    from tpusim.obs import bench as obs_bench
    from tpusim.sim.driver import schedule_pods_sweep

    bs = sorted({int(x) for x in str(args.sweep).split(",") if x.strip()})
    if not bs or min(bs) < 1:
        raise SystemExit(f"--sweep wants positive batch sizes, got {args.sweep!r}")

    trace = sim.prepare_pods()
    specs = pods_to_specs(trace)
    ev_kind, ev_pod = build_events(trace)
    events = len(ev_kind)
    cfg = sim.cfg
    base_w = np.asarray([w for _, w in cfg.policies], np.int32)

    # standalone warm baseline: the regular single-config replay the
    # marginal per-config cost is judged against (same protocol as
    # bench.py: one compile run, then a warm minimum)
    import jax.numpy as jnp

    ev_kind_d, ev_pod_d = jnp.asarray(ev_kind), jnp.asarray(ev_pod)
    key = jax.random.PRNGKey(cfg.seed)

    def standalone():
        # same bucket as schedule_pods_sweep's default so both sides pad
        # the event stream identically — the per-config ratio compares
        # equal replay lengths
        res = sim.run_events(
            sim.init_state, specs, ev_kind_d, ev_pod_d, key, bucket=512
        )
        jax.block_until_ready(res.state)

    m0 = obs_bench.measure(standalone, warm_runs=2)
    standalone_warm = m0["min_s"]
    print(
        f"[sweep] standalone nodes={args.nodes} pods={args.pods} "
        f"events={events} engine={sim._last_engine} "
        f"warm={standalone_warm:.3f}s (first incl. compile "
        f"{m0['first_s']:.1f}s)"
    )

    rows = []
    for b in bs:
        # distinct rows: every lane is a genuine what-if configuration
        grid = np.stack([base_w - i for i in range(b)]).astype(np.int32)
        box = {}

        def run_b(grid=grid, box=box):
            box["lanes"] = schedule_pods_sweep(sim, trace, grid)

        m = obs_bench.measure(run_b, warm_runs=2)
        per_cfg = m["min_s"] / b
        ratio = per_cfg / standalone_warm if standalone_warm else 0.0
        row = obs_bench.round_row({
            "b": b,
            "events": events,
            "engine": sim._last_engine,
            "cold_s": m["first_s"],
            "warm_s": m["min_s"],
            "per_config_s": per_cfg,
            "ratio_vs_standalone": round(ratio, 3),
            "placed_lane0": box["lanes"][0].placed,
        })
        rows.append(row)
        print(
            f"[sweep] B={b} cold={row['cold_s']:.1f}s "
            f"warm={row['warm_s']:.3f}s per_config={row['per_config_s']:.3f}s "
            f"ratio_vs_standalone={row['ratio_vs_standalone']:.3f} "
            f"engine={row['engine']}"
        )

    if args.sweep_out:
        payload = {
            # BENCH_rNN.json-shape capture WITHOUT a `parsed` key: the
            # gate must never mistake sweep rows for the headline
            # throughput baseline — it reads the `sweep` block instead
            "cmd": "python bench_scale.py --sweep "
            + ",".join(str(b) for b in bs)
            + f" --nodes {args.nodes} --pods {args.pods}",
            "rc": 0,
            "sweep": {
                "nodes": args.nodes,
                "pods": args.pods,
                "events": events,
                "policies": [name for name, _ in cfg.policies],
                "backend": jax.default_backend(),
                "compile_cache": bool(cache_dir),
                "standalone_warm_s": round(standalone_warm, 3),
                "standalone_cold_s": round(m0["first_s"], 3),
                "rows": rows,
            },
        }
        obs_bench.write_json(args.sweep_out, payload)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=100_000)
    ap.add_argument("--pods", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument(
        "--engine", type=str, default="auto",
        help="replay engine (auto | sequential | table | pallas): the "
        "N-scaling comparison in ENGINES.md runs table vs pallas at "
        "several --nodes values",
    )
    ap.add_argument(
        "--block-size", type=int, default=0,
        help="table-engine select layout (SimulatorConfig.block_size): "
        "0 = auto (blocked incremental reductions at large N), > 0 "
        "forces that block size, -1 forces the flat O(N) select — the "
        "blocked-vs-flat rows in ENGINES.md compare 0 against -1",
    )
    ap.add_argument(
        "--unswitched", action="store_true",
        help="flat-path select layout A/B (ENGINES.md Round 18): run "
        "the unconditional-select form instead of the event switch "
        "(SimulatorConfig.unswitched_select); bit-identical, throughput "
        "differs per backend",
    )
    ap.add_argument(
        "--pallas-residency", default="auto", metavar="auto|vmem|hbm",
        help="fused-Pallas table residency (SimulatorConfig."
        "table_residency, ENGINES.md Round 19): where the [K, N] score "
        "tables live — 'vmem' is the all-resident kernel (ceiling "
        "N <= 4096 at K = 151), 'hbm' the HBM-resident-table kernel "
        "with per-event double-buffered DMA (ceiling >= 256k), 'auto' "
        "the two-tier footprint select; bit-identical either way",
    )
    ap.add_argument(
        "--pallas-ceiling", action="store_true",
        help="print the two-tier Pallas residency ceiling sweep instead "
        "of running: for each tier the max N whose footprint fits the "
        "TPUSIM_PALLAS_VMEM_BYTES budget at this run's K/policy shape "
        "(the ENGINES.md Round 19 capture), then exit",
    )
    ap.add_argument(
        "--chunk",
        type=int,
        default=200_000,
        help="events per device dispatch (a single multi-minute XLA "
        "execution can exceed the TPU transport's per-call limits; state "
        "carries across chunks, which is exact for this creation-only "
        "stream — mixed create/delete streams must replay in one call)",
    )
    # observability (tpusim.obs; README "Profiling & telemetry")
    ap.add_argument(
        "--profile", default="", metavar="PATH",
        help="profile the run (phase spans with compile/execute split, "
        "exact scan counters) and append the JSONL run record here",
    )
    ap.add_argument(
        "--metrics-out", default="", metavar="PATH",
        help="write a Prometheus textfile snapshot of the run telemetry",
    )
    ap.add_argument(
        "--trace-out", default="", metavar="PATH",
        help="write a Chrome-trace timeline of the phase spans",
    )
    ap.add_argument(
        "--table-cache", default="", metavar="DIR",
        help="content-keyed init_tables cache dir: repeat runs skip the "
        "~27 s N=100k table build bit-identically "
        "(SimulatorConfig.table_cache_dir)",
    )
    ap.add_argument(
        "--heartbeat", type=int, default=0, metavar="EVENTS",
        help="in-scan progress line (events/s, ETA) every N events — "
        "long scans are no longer silent (0 = off)",
    )
    ap.add_argument(
        "--series-every", type=int, default=0, metavar="EVENTS",
        help="sample the in-scan cluster time-series plane every N "
        "processed events (0 = off); lands in the --profile JSONL and "
        "as --trace-out counter tracks (README \"Live monitoring\")",
    )
    ap.add_argument(
        "--listen", default="", metavar="[HOST]:PORT",
        help="serve /metrics, /healthz, /progress over HTTP for the "
        "run's lifetime (tpusim.obs.server; bare :PORT binds loopback)",
    )
    # config-axis sweep bench (ISSUE 6; ENGINES.md "Round 11"): replace
    # the scale run with the vmapped weight-sweep measurement
    ap.add_argument(
        "--sweep", default="", metavar="B[,B...]",
        help="measure the config-axis sweep instead of the scale run: "
        "for each batch size B, one row with cold (incl. compile) and "
        "warm wall of a B-config vmapped weight sweep plus the marginal "
        "per-config cost against a standalone warm replay "
        "(e.g. --sweep 1,4,16)",
    )
    ap.add_argument(
        "--sweep-out", default="", metavar="PATH",
        help="write the sweep rows as a BENCH_rNN.json-style capture "
        "(a `sweep` block; `make bench-gate` reads the newest committed "
        "one for its advisory sweep comparison)",
    )
    args = ap.parse_args()
    if args.chunk <= 0:
        ap.error("--chunk must be positive")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpusim.compile_cache import enable_compile_cache
    from tpusim.constants import MILLI
    from tpusim.io.trace import build_events, pods_to_specs
    from tpusim.sim.driver import Simulator, SimulatorConfig
    from tpusim.sim.typical import TypicalPodsConfig

    cache_dir = enable_compile_cache()
    print(f"[obs] compile cache at {cache_dir}", file=sys.stderr)

    if args.unswitched and args.block_size >= 0:
        # unswitched_select only alters the FLAT scan body; under the
        # auto/blocked layouts the knob is inert and the A/B would read
        # as a bogus "layout is neutral"
        ap.error("--unswitched measures the flat select layout: pass "
                 "--block-size -1")
    nodes = synth_cluster(args.nodes, args.seed)
    pods = synth_pods(args.pods, args.seed + 1)

    if args.pallas_ceiling:
        # the ceiling-sweep capture (ISSUE 15): pure footprint math at
        # this run's K/policy shape — no replay, no device
        from tpusim.io.trace import pods_to_specs as _pts
        from tpusim.sim import pallas_engine as _pe
        from tpusim.sim.table_engine import build_pod_types as _bpt

        _types = _bpt(_pts(pods))
        _k = int(_types.share.cpu.shape[0]) + int(_types.whole.cpu.shape[0])
        budget = _pe.vmem_budget()
        print(f"[pallas-ceiling] budget {budget} bytes, K={_k}, "
              f"num_pol=1, P={args.pods}, E={args.pods}")
        for n_probe in (2048, 4096, 8192, 65536, 262144, 1048576):
            tier = _pe.select_residency(n_probe, _k, 1, args.pods,
                                        args.pods)
            print(f"[pallas-ceiling] N={n_probe:>8}: "
                  f"{tier or 'degrade (blocked table engine)'}")
        print(f"[pallas-ceiling] HBM-tier max N at this shape: "
              f"{_pe.hbm_ceiling_nodes(_k, 1, 1, args.pods, args.pods)}")
        print(f"[pallas-ceiling] reference (K=151, small workload): "
              f"{_pe.hbm_ceiling_nodes(151, 1, 1)}")
        return
    profiling = bool(args.profile or args.metrics_out or args.trace_out)
    cfg = SimulatorConfig(
        policies=(("FGDScore", 1000),),
        gpu_sel_method="FGDScore",
        seed=args.seed,
        report_per_event=False,
        engine=args.engine,
        block_size=args.block_size,
        unswitched_select=args.unswitched,
        profile=profiling,
        heartbeat_every=args.heartbeat,
        series_every=args.series_every,
        table_cache_dir=args.table_cache,
        table_residency=args.pallas_residency,
        typical_pods=TypicalPodsConfig(pod_popularity_threshold=95),
    )
    sim = Simulator(nodes, cfg)
    sim.set_workload_pods(pods)
    sim.set_typical_pods()

    if args.sweep:
        run_sweep_bench(args, sim, cache_dir)
        return

    specs = pods_to_specs(pods)
    ev_kind, ev_pod = build_events(pods)
    ev_kind, ev_pod = jnp.asarray(ev_kind), jnp.asarray(ev_pod)
    key = jax.random.PRNGKey(args.seed)

    from tpusim.sim.table_engine import build_pod_types, resolve_block_size

    types = build_pod_types(specs)  # hoisted: identical for every chunk
    k_types = int(types.share.cpu.shape[0]) + int(types.whole.cpu.shape[0])
    # the block size the table engine will resolve for this shape (0 = flat)
    eff_block = resolve_block_size(args.block_size, args.nodes, k_types)

    from tpusim.obs import bench as obs_bench

    # live monitoring endpoint (--listen): up before the first dispatch
    # so a scraper watches the whole run, /progress fed by the heartbeat
    monitor = None
    if args.listen:
        from tpusim.obs.server import MonitorServer

        monitor = MonitorServer(args.listen).start()
        monitor.attach_heartbeat()
        monitor.publish_progress(phase="starting", nodes=args.nodes,
                                 pods=args.pods)
        print(f"[obs] monitoring at {monitor.url} "
              "(/metrics /healthz /progress)", file=sys.stderr)

    box = {}

    def run_chunked():
        state = sim.init_state
        failed_chunks = []
        ser_logs = []
        for lo in range(0, int(ev_kind.shape[0]), args.chunk):
            hi = min(lo + args.chunk, int(ev_kind.shape[0]))
            res = sim.run_events(
                state, specs, ev_kind[lo:hi], ev_pod[lo:hi], key,
                bucket=args.chunk, types=types,
            )
            state = res.state
            # keep the reduction on device; pull once after the run
            failed_chunks.append(res.ever_failed.sum())
            if res.series is not None:
                # each chunk's scan restarts its stride clock at 0 —
                # rebase onto the run-global event position like the
                # driver's fault loop does
                from tpusim.obs.series import log_from_stacked

                ser_logs.append(log_from_stacked(res.series, base_pos=lo))
        jax.block_until_ready(state)
        box["out"] = (
            state, int(sum(int(np.asarray(f)) for f in failed_chunks))
        )
        box["series"] = ser_logs  # last run's logs (cold run overwritten)

    # shared cold + warm protocol (tpusim.obs.bench): one compile run,
    # one warm run — the historical bench_scale shape
    m = obs_bench.measure(run_chunked, warm_runs=1)
    final_state, failed = box["out"]
    first, wall = m["first_s"], m["min_s"]

    placed = int(args.pods - failed)
    s = jax.tree.map(np.asarray, final_state)
    slot = np.arange(s.gpu_left.shape[1])[None, :] < s.gpu_cnt[:, None]
    alloc = 100.0 * np.where(slot, MILLI - s.gpu_left, 0).sum() / (
        s.gpu_cnt.sum() * MILLI
    )
    print(
        f"[scale] nodes={args.nodes} pods={args.pods} "
        f"engine={sim._last_engine} block={eff_block or 'flat'} "
        f"wall={wall:.1f}s "
        f"(first incl. compile {first:.1f}s) placed={placed} "
        f"throughput={placed / wall:.0f} placements/s "
        f"us_per_event={1e6 * wall / args.pods:.1f} gpu_alloc={alloc:.2f}%"
        + (f" table_cache={sim.obs.table_cache}" if args.table_cache else "")
    )

    series_block = None
    if args.series_every and box.get("series"):
        from tpusim.obs.series import concat_series, series_to_record

        series_block = series_to_record(
            concat_series(box["series"]), args.series_every,
            [name for name, _ in cfg.policies],
        )

    if profiling or monitor is not None:
        from tpusim.obs import emitters, note_compile_cache

        note_compile_cache(sim.obs, enabled=True, cache_dir=cache_dir)
        telemetry = sim.run_telemetry()
        record = emitters.build_record(
            telemetry,
            meta={"bench": "bench_scale", "nodes": args.nodes,
                  "pods": args.pods, "block": eff_block},
            series=series_block,
        )
        counter_series = None
        if args.trace_out:
            counter_series = sim.event_counter_series()
            if series_block is not None:
                from tpusim.obs.series import series_from_record, series_tracks

                counter_series.update(
                    series_tracks(series_from_record(series_block))
                )
        for p in emitters.emit_record(
            record, telemetry.spans,
            jsonl=args.profile,
            metrics=args.metrics_out,
            trace=args.trace_out,
            counter_series=counter_series,
        ):
            print(f"[obs] wrote {p}", file=sys.stderr)
        if monitor is not None:
            monitor.publish_record(record)
            monitor.publish_progress(phase="done", events_done=args.pods,
                                     events_total=args.pods)


if __name__ == "__main__":
    main()
