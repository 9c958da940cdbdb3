#!/usr/bin/env python
"""Multi-chip scale proof: the 100k-node synthetic stress through the
node-axis-sharded table engine on a virtual CPU mesh (1/2/4/8 devices),
asserting placement equality against the single-device replay and
recording per-event wall + compile/table-init cost per mesh size.

One physical host serves every virtual device, so wall-clock SPEEDUP is
not observable here — what this measures is that the sharded program (a)
stays placement-identical at scale, (b) keeps per-event cost flat as the
mesh grows (the per-event column refresh is local to the owning chip; only
the selectHost argmax all-reduce crosses the mesh), and (c) does not
serialize the [K, N] table init. Real-ICI scaling follows the same program
with real devices (ref scale-out being replaced: the vendored scheduler's
16-way parallelize over nodes, generic_scheduler.go:473-560, and the
harness's xargs --max-procs process fleet).

    python bench_multichip.py                       # 100k nodes, 8k events
    python bench_multichip.py --nodes 20000 --events 2048 --devices 1 2 4

The 1M-node lane (ISSUE 11): `--scale-lane` measures the
software-pipelined shard commit against the unpipelined body at
nloc ∈ {10k, 100k, 250k} per device, then streams a 1M-node aggregate
replay through the chunked run_chunk surface with buffer donation armed
(events generated chunk-by-chunk, never materialized as one array), and
writes the machine-readable capture `--json-out MULTICHIP_r06.json` the
bench gate advisory-compares. `--fault` additionally runs the aggregate
as a chaos bench (the PR 10 fault lane through the shard engine's
pipelined registers).

    python bench_multichip.py --scale-lane --json-out MULTICHIP_r06.json
    python bench_multichip.py --scale-lane --nodes 1000000 --fault
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def _force_virtual_devices(max_dev: int):
    """Pre-jax-init virtual CPU mesh. This bench is the CPU virtual-mesh
    protocol by design, so it is the caller that asks for the CPU; the
    shared tpusim.virtual_mesh bootstrap then widens the platform."""
    from tpusim.virtual_mesh import virtual_cpu_devices

    os.environ["JAX_PLATFORMS"] = "cpu"
    virtual_cpu_devices(max(max_dev, 2))


def synth_pods_pooled(num_events: int, seed: int, pool: int):
    """synth_pods drawing from only the first `pool` rows of the openb
    pod list: caps the distinct-type count K so the 250k/1M table init
    stays CPU-tractable (the per-event loop cost under test is
    K-independent in the select and O(K) in the refresh either way)."""
    import numpy as np

    from tpusim.io.trace import load_pod_csv

    base = load_pod_csv(
        os.path.join(REPO, "data/csv/openb_pod_list_default.csv")
    )[:pool]
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(base), num_events)
    return [
        type(base[0])(
            name=f"sp-{i:07d}",
            cpu_milli=base[int(j)].cpu_milli,
            memory_mib=base[int(j)].memory_mib,
            num_gpu=base[int(j)].num_gpu,
            gpu_milli=base[int(j)].gpu_milli,
            gpu_spec=base[int(j)].gpu_spec,
        )
        for i, j in enumerate(idx)
    ]


def scale_lane(args):
    """The 1M-node lane: pipelined-vs-unpipelined us/event at
    nloc ∈ {10k, 100k, 250k} on a 1-device mesh, then the N-node
    aggregate (nloc = N / --agg-devices per device) streamed through
    run_chunk with donation armed. Placement equality pipelined vs
    unpipelined is asserted on every row."""
    _force_virtual_devices(max(args.agg_devices, 1))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench_scale import synth_cluster
    from tpusim.io.trace import build_events, pods_to_specs, tiebreak_rank
    from tpusim.parallel import make_mesh, pad_nodes, shard_state
    from tpusim.parallel.shard_engine import make_shardmap_table_replay
    from tpusim.policies import make_policy
    from tpusim.sim.driver import Simulator, SimulatorConfig
    from tpusim.sim.table_engine import build_pod_types, pad_pod_types
    from tpusim.sim.typical import TypicalPodsConfig

    policies = [(make_policy("FGDScore"), 1000)]
    cfg = SimulatorConfig(
        policies=(("FGDScore", 1000),),
        gpu_sel_method="FGDScore",
        seed=args.seed,
        report_per_event=False,
        table_residency=args.pallas_residency,
        typical_pods=TypicalPodsConfig(pod_popularity_threshold=95),
    )
    pods = synth_pods_pooled(args.events, args.seed + 1, args.pod_pool)
    specs = pods_to_specs(pods)
    ev_kind_np, ev_pod_np = build_events(pods)
    ev_kind = jnp.asarray(ev_kind_np)
    ev_pod = jnp.asarray(ev_pod_np)
    types = pad_pod_types(build_pod_types(specs))
    key = jax.random.PRNGKey(args.seed)

    def row_inputs(n_nodes, n_dev):
        nodes = synth_cluster(n_nodes, args.seed)
        sim = Simulator(nodes, cfg)
        sim.set_workload_pods(pods)
        sim.set_typical_pods()
        mesh = make_mesh(n_dev)
        base_rank = jnp.asarray(tiebreak_rank(n_nodes, cfg.seed))
        state, rank = pad_nodes(sim.init_state, base_rank, n_dev)
        state = shard_state(state, mesh)
        return sim, mesh, state, rank

    def measure_scan(replay, sim, state, rank, chunk=0, warm_runs=3):
        """(cold_s, warm_s, placed) of the post-init event scan through
        the DONATED chunk entry — the production shape of the 1M lane
        (ENGINES.md Round 15): without donation every run_chunk call
        pays a defensive whole-carry copy at the jit boundary that
        drowns the per-event signal. The first pass pays the compile;
        warm is the min over `warm_runs` passes (each re-inits, since
        donation consumes the carry; init sits outside the timer)."""
        e = int(ev_kind.shape[0])
        step = chunk or e

        def one_pass():
            carry = replay.init_carry(
                state, specs, types, sim.typical, key, rank
            )
            jax.block_until_ready(jax.tree.leaves(carry))
            t0 = time.perf_counter()
            for a in range(0, e, step):
                carry, _ys = replay.run_chunk_donated(
                    carry, specs, types, ev_kind[a:a + step],
                    ev_pod[a:a + step], sim.typical, rank,
                )
            out = replay.finish(carry)
            jax.block_until_ready(jax.tree.leaves(out))
            return time.perf_counter() - t0, out

        cold, _ = one_pass()
        samples = [one_pass() for _ in range(warm_runs)]
        warm, out = min(samples, key=lambda s: s[0])
        return cold, warm, np.asarray(out[1])

    rows = []
    for nloc in args.nloc:
        sim, mesh, state, rank = row_inputs(nloc, 1)
        res = {"nloc": nloc, "devices": 1, "events": args.events}
        placed = {}
        for pipelined in (True, False):
            replay = make_shardmap_table_replay(
                policies, mesh, gpu_sel="FGDScore", pipelined=pipelined
            )
            cold, warm, pl = measure_scan(replay, sim, state, rank)
            tag = "pipelined" if pipelined else "unpipelined"
            res[f"cold_s_{tag}"] = round(cold, 2)
            res[f"warm_s_{tag}"] = round(warm, 3)
            res[f"us_per_event_{tag}"] = round(1e6 * warm / args.events, 1)
            placed[pipelined] = pl
        res["equal"] = bool(np.array_equal(placed[True], placed[False]))
        res["placed"] = int((placed[True] >= 0).sum())
        res["speedup"] = round(
            res["us_per_event_unpipelined"]
            / max(res["us_per_event_pipelined"], 1e-9), 2,
        )
        rows.append(res)
        print(json.dumps(res), flush=True)
        assert res["equal"], f"pipelined != unpipelined at nloc={nloc}"

    # ---- the aggregate: nodes sharded over the mesh, events STREAMED
    # through the donated chunk entry (generated per chunk, one
    # executable across chunks, the input carry's buffers reused)
    agg = None
    if args.nodes:
        n_dev = args.agg_devices
        sim, mesh, state, rank = row_inputs(args.nodes, n_dev)
        replay = make_shardmap_table_replay(
            policies, mesh, gpu_sel="FGDScore", pipelined=True
        )
        cold, warm, pl = measure_scan(
            replay, sim, state, rank, chunk=args.chunk
        )
        agg = {
            "nodes": args.nodes, "devices": n_dev,
            "nloc": args.nodes // n_dev, "events": args.events,
            "chunk": args.chunk, "donated": True,
            "cold_s": round(cold, 2), "warm_s": round(warm, 3),
            "us_per_event": round(1e6 * warm / args.events, 1),
            "placed": int((pl >= 0).sum()),
        }
        if args.fault:
            # chaos variant: the PR 10 fault lane through the pipelined
            # shard registers at aggregate scale
            from tpusim.sim import fault_lane
            from tpusim.sim.faults import (
                FaultConfig,
                generate_fault_schedule,
            )

            fcfg = FaultConfig(
                mtbf_events=max(args.events // 8, 1),
                mttr_events=max(args.events // 8, 1),
                evict_every_events=max(args.events // 16, 1),
                seed=args.seed, backoff_base=4, backoff_cap=32,
                max_retries=2, queue_capacity=16,
            )
            faults = generate_fault_schedule(
                args.nodes, args.events, fcfg
            )
            plan = fault_lane.compile_fault_plan(
                ev_kind_np, ev_pod_np, faults, fcfg, args.nodes,
                args.events,
            )
            n_pad = state.num_nodes
            ops = fault_lane.FaultOps(
                pos=jnp.asarray(plan.pos), arg=jnp.asarray(plan.arg),
                aux=jnp.asarray(plan.aux), draws=jnp.asarray(plan.draws),
                params=jnp.asarray(plan.params),
                gcnt=jnp.pad(
                    jnp.asarray(sim.init_state.gpu_cnt),
                    (0, n_pad - sim.init_state.num_nodes),
                ),
            )
            fc0 = fault_lane.init_fault_carry(
                args.events, n_pad, plan.capacity
            )
            frep = make_shardmap_table_replay(
                policies, mesh, gpu_sel="FGDScore", faults=True
            )
            ftypes = build_pod_types(specs)  # hoisted out of the timer
            fkind, fidx = jnp.asarray(plan.kind), jnp.asarray(plan.idx)

            def fault_pass():
                t0 = time.perf_counter()
                out = frep(
                    state, specs, ftypes, fkind, fidx,
                    sim.typical, key, rank, fault_ops=ops,
                    fault_carry0=fc0,
                )
                jax.block_until_ready(out.placed_node)
                return time.perf_counter() - t0, out

            fcold, _ = fault_pass()
            fwarm, fout = fault_pass()
            e_m = int(plan.kind.shape[0])
            dm, _, attempts = fault_lane.assemble_disruption(
                plan, fout.fault_ys, fout.fault_carry,
                np.asarray(sim.init_state.gpu_cnt), frag_delta=False,
            )
            agg["fault"] = {
                "merged_events": e_m,
                "cold_s": round(fcold, 2), "warm_s": round(fwarm, 3),
                "us_per_event": round(1e6 * fwarm / e_m, 1),
                "evicted": dm.evicted_pods,
                "rescheduled": dm.rescheduled_pods,
                "dead": dm.unscheduled_after_retries,
                "retries_run": attempts,
            }
        print(json.dumps(agg), flush=True)

    capture = {
        "n": args.round, "rc": 0, "kind": "scale-lane",
        "scale": {
            "backend": jax.default_backend(),
            "devices_virtual": True,
            "events": args.events,
            "pod_pool": args.pod_pool,
            "rows": rows,
            "aggregate": agg,
        },
    }
    if args.json_out:
        with open(os.path.join(REPO, args.json_out), "w") as f:
            json.dump(capture, f, indent=1)
            f.write("\n")
        print(f"[multichip] wrote {args.json_out}")
    return capture


def main():
    ap = argparse.ArgumentParser()
    # default resolves per mode below: 100k for the classic mesh table,
    # 1M for --scale-lane (so the documented one-liner really runs the
    # 1M aggregate instead of silently overwriting the committed capture
    # with a 100k one)
    ap.add_argument("--nodes", type=int, default=None)
    ap.add_argument("--events", type=int, default=8192)
    ap.add_argument("--devices", type=int, nargs="*", default=[1, 2, 4, 8])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--out", default="MULTICHIP.md")
    ap.add_argument(
        "--engine", choices=["shardmap", "partitioner"], default="shardmap",
        help="shardmap = explicit-collective engine (parallel.shard_engine, "
        "flat us/event); partitioner = XLA-SPMD-partitioned table engine "
        "(parallel.sharding, the round-2 baseline)",
    )
    ap.add_argument(
        "--scale-lane", action="store_true",
        help="the 1M-node lane (ISSUE 11): pipelined-vs-unpipelined "
        "us/event rows at --nloc per device + the --nodes aggregate "
        "streamed through donated chunks; writes --json-out",
    )
    ap.add_argument(
        "--nloc", type=int, nargs="*", default=[10_000, 100_000, 250_000],
        help="scale-lane per-device node counts (1-device mesh rows)",
    )
    ap.add_argument(
        "--agg-devices", type=int, default=4,
        help="scale-lane aggregate mesh width (nloc = --nodes / this)",
    )
    ap.add_argument(
        "--pod-pool", type=int, default=32,
        help="scale-lane distinct-pod-type cap (openb rows sampled)",
    )
    ap.add_argument(
        "--chunk", type=int, default=512,
        help="scale-lane aggregate chunk length (events per donated "
        "run_chunk dispatch)",
    )
    ap.add_argument(
        "--fault", action="store_true",
        help="scale-lane: also run the aggregate as a chaos bench "
        "(fault-lane merged stream through the shard engine)",
    )
    ap.add_argument(
        "--pallas-residency", default="auto", metavar="auto|vmem|hbm",
        help="fused-Pallas table residency for any single-device "
        "reference dispatch this bench makes (SimulatorConfig."
        "table_residency, ENGINES.md Round 19); the shard rows "
        "themselves run the shard_map engine and ignore it — the knob "
        "exists so mixed captures stay comparable with bench_scale's",
    )
    ap.add_argument(
        "--json-out", default="",
        help="scale-lane capture path (e.g. MULTICHIP_r06.json)",
    )
    ap.add_argument(
        "--round", type=int, default=6,
        help="capture round number recorded in --json-out",
    )
    args = ap.parse_args()
    if args.nodes is None:
        args.nodes = 1_000_000 if args.scale_lane else 100_000
    if args.scale_lane:
        scale_lane(args)
        return
    max_dev = max(args.devices)

    # virtual CPU mesh must be configured before jax initializes (also
    # overrides a stale pre-set device count)
    _force_virtual_devices(max_dev)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench_scale import synth_cluster, synth_pods
    from tpusim.io.trace import build_events, pods_to_specs, tiebreak_rank
    from tpusim.parallel import (
        make_mesh,
        make_sharded_table_replay,
        pad_nodes,
        shard_state,
    )
    from tpusim.policies import make_policy
    from tpusim.sim.driver import Simulator, SimulatorConfig
    from tpusim.sim.table_engine import build_pod_types, pad_pod_types
    from tpusim.sim.typical import TypicalPodsConfig

    assert len(jax.devices()) >= max_dev, (
        f"need {max_dev} devices, have {len(jax.devices())}"
    )

    nodes = synth_cluster(args.nodes, args.seed)
    pods = synth_pods(args.events, args.seed + 1)
    cfg = SimulatorConfig(
        policies=(("FGDScore", 1000),),
        gpu_sel_method="FGDScore",
        seed=args.seed,
        report_per_event=False,
        table_residency=args.pallas_residency,
        typical_pods=TypicalPodsConfig(pod_popularity_threshold=95),
    )
    sim = Simulator(nodes, cfg)
    sim.set_workload_pods(pods)
    sim.set_typical_pods()

    specs = pods_to_specs(pods)
    ev_kind, ev_pod = build_events(pods)
    ev_kind, ev_pod = jnp.asarray(ev_kind), jnp.asarray(ev_pod)
    types = pad_pod_types(build_pod_types(specs))
    key = jax.random.PRNGKey(args.seed)
    base_rank = jnp.asarray(tiebreak_rank(len(nodes), cfg.seed))
    policies = [(make_policy("FGDScore"), 1000)]

    rows = []
    ref_placed = None
    for n_dev in args.devices:
        mesh = make_mesh(n_dev)
        state, rank = pad_nodes(sim.init_state, base_rank, n_dev)
        state = shard_state(state, mesh)
        if args.engine == "shardmap":
            from tpusim.parallel.shard_engine import make_shardmap_table_replay

            replay = make_shardmap_table_replay(
                policies, mesh, gpu_sel="FGDScore"
            )
        else:
            replay = make_sharded_table_replay(
                policies, mesh, gpu_sel="FGDScore"
            )

        from tpusim.obs import bench as obs_bench

        box = {}

        def run():
            box["out"] = replay(
                state, specs, types, ev_kind, ev_pod, sim.typical, key, rank
            )
            jax.block_until_ready(box["out"].state)

        # shared cold/warm protocol (tpusim.obs.bench): every mesh size
        # compiles its own program, one warm call is the signal
        m = obs_bench.measure_cold_warm(run)
        out, cold, warm = box["out"], m["cold_s"], m["warm_s"]

        placed = np.asarray(out.placed_node)
        n_placed = int((placed >= 0).sum())
        if ref_placed is None:
            ref_mesh = n_dev  # first (smallest) mesh size is the reference
            ref_placed = placed
            equal = True
        else:
            equal = bool(np.array_equal(placed, ref_placed))
        rows.append(
            {
                "devices": n_dev,
                "nodes": args.nodes,
                "events": args.events,
                "placed": n_placed,
                "cold_s": round(cold, 2),
                "warm_s": round(warm, 2),
                "us_per_event": round(1e6 * warm / args.events, 1),
                f"equal_vs_{ref_mesh}dev": equal,
            }
        )
        print(json.dumps(rows[-1]), flush=True)
        assert equal, (
            f"placements diverged: {n_dev}-device vs {ref_mesh}-device mesh"
        )

    engine_desc = (
        "explicit-collective shard_map engine (tpusim.parallel.shard_engine: "
        "local Filter/Score/refresh, 3-scalar selectHost collectives, "
        "owner-local bind)"
        if args.engine == "shardmap"
        else "XLA-SPMD-partitioned table engine (tpusim.parallel.sharding)"
    )
    with open(os.path.join(REPO, args.out), "w") as f:
        f.write(
            "# MULTICHIP — node-axis-sharded table engine at scale\n\n"
            "Generated by `python bench_multichip.py` "
            f"(nodes={args.nodes}, events={args.events}, FGD, "
            f"{engine_desc}, virtual CPU "
            "mesh — one physical host backs all virtual devices, so this "
            "table proves placement equality + flat per-event cost under "
            "sharding, not wall-clock speedup; see bench_multichip.py "
            "docstring).\n\n"
            f"| devices | cold (compile+init) s | warm replay s | us/event | "
            f"placements equal vs {ref_mesh}-device |\n|---|---|---|---|---|\n"
        )
        for r in rows:
            f.write(
                f"| {r['devices']} | {r['cold_s']} | {r['warm_s']} | "
                f"{r['us_per_event']} | {r[f'equal_vs_{ref_mesh}dev']} |\n"
            )
        f.write(
            f"\nplaced = {rows[0]['placed']} / {args.events} on every mesh "
            "size (bit-identical placements and device masks).\n"
        )
        if args.engine == "shardmap":
            r1 = next(
                (r["us_per_event"] for r in rows if r["devices"] == 1), None
            )
            r8 = next(
                (r["us_per_event"] for r in rows if r["devices"] == 8), None
            )
            f.write(
                "\n## Why the curve is flat now\n\n"
                "Round 2's sharded engine re-jitted the table engine with "
                "node-axis in_shardings and let XLA's SPMD partitioner place "
                "the communication; the per-event dynamic gathers/scatters "
                "at the winning node became whole-array movement and "
                "us/event grew 3.5x from 1 to 8 devices (2750.9 -> 9730.9 "
                "at these exact settings). The shard_map engine "
                "(tpusim/parallel/shard_engine.py) writes the collectives "
                "by hand — local Filter/Score/table-refresh, a 3-scalar "
                "selectHost reduction (pmax best score, pmin winner rank, "
                "psum winner node id), owner-local bind with one 8-lane "
                "psum; per-event metrics never touch the loop (the shared "
                "post-pass, tpusim.sim.metrics, reconstructs the report "
                "series from the replicated telemetry) — so the per-event "
                "collective payload is independent of cluster and mesh size"
                + (
                    f" (this run: {r8} us/event at 8 devices vs {r1} at 1, "
                    f"ratio {r8 / r1:.2f})"
                    if r8 and r1
                    else ""
                )
                + ". Run-to-run variance on the shared host is ~20-50%; "
                "the signal is the ratio staying ~1, not the absolute "
                "numbers.\n"
            )
            f.write(
                "\n## Blocked local selectHost (round 6)\n\n"
                "`make_shardmap_table_replay(..., block_size=...)` (driven "
                "by `SimulatorConfig.block_size`, default auto) layers the "
                "blocked table engine's incremental reductions onto each "
                "shard for configs whose policies all use "
                "`normalize: \"none\"` (FGD, DotProd, Packing, Clustering "
                "— including this file's FGD lane): each device keeps "
                "per-(type, block-of-B) summaries of (max total, min "
                "tie-break rank, winner node), refreshed only at the "
                "touched node's block, so the per-event selectHost input "
                "on each device shrinks from nloc node rows to nloc/B "
                "block maxima before the device contributes its scalar to "
                "the collective. The cross-device payload itself was "
                "already N-independent (3 scalars + one 8-lane psum) and "
                "is unchanged; what shrinks is the local reduction feeding "
                "it — the dominant per-event cost at nloc = N/D >= ~10k. "
                "Placements stay bit-identical (the block summaries feed "
                "the same lexicographic (max score, min rank) combine — "
                "sim.step.block_reduce/packed_argmax, shared with the "
                "single-device blocked engine). Normalized policies "
                "(minmax/pwr) keep the flat local path: their per-event "
                "global-extrema pmin/pmax collectives need the full local "
                "rows anyway.\n"
            )
            f.write(
                "\n## Product path (round 5)\n\n"
                "Sharding is a config knob, not a bench-only engine: "
                "`customConfig.mesh: N` in the Simon CR, "
                "`SimulatorConfig.mesh`, or `experiments/run.py --mesh N` "
                "route every replay through this engine on an N-device "
                "mesh (under JAX_PLATFORMS=cpu the mesh is N virtual CPU "
                "devices via tpusim.virtual_mesh). Verified end to "
                "end: a full sweep-protocol cell (openb default x FGD x "
                "tune 1.3, per-event reports) run with --mesh 8 writes "
                "ALL analysis CSV families byte-identical to the "
                "single-device run on the same backend "
                "(tests/test_mesh_product.py pins the same on the tiny "
                "trace + the Simon-CR knob). Cross-backend runs (virtual "
                "CPU mesh vs real TPU) differ only in the documented f32 "
                "last-ulp report channel; placements are identical "
                "everywhere.\n"
            )
    print(f"[multichip] wrote {args.out}")


if __name__ == "__main__":
    main()
