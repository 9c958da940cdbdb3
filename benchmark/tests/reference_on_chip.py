"""The plain numpy reference (benchmark/lib/reference_fgd.py: no kernel,
table or engine of the program) against one lane of a full-width wave of a
cell, on the chip, over ALL the events (not run by the benchmark's own
runs, nor by pytest: start it by hand through the chip tool):
`python benchmark/tests/reference_on_chip.py --workload openb.fgd-seeds
--seeds 11 12`.

For each seed it runs one wave of the cell's lanes through
`schedule_pods_sweep`, draws a lane from the seed and holds it to the
reference. The tolerance is the reference's own: every integer exact
(placements, device masks, failure flags, every field of the node state
the reference computes); a score may differ by 1 only within
`reference_fgd.NEAR` of an integer, such entries are counted and printed,
and a lane is held placement for placement up to the first event one of
them could decide.
"""

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402

STATE_FIELDS = ("cpu_left", "mem_left", "gpu_left", "aff_cnt")


def reference_inputs(sim, trace, lane_seed):
    """The reference's inputs as plain arrays: capacities, requests,
    typical pods and the lane's tie-break rank are data to both sides."""
    from tpusim.io.trace import pods_to_specs, tiebreak_rank

    specs = pods_to_specs(trace, sim.node_index, device=False)
    if (np.asarray(specs.pinned) >= 0).any():
        raise ValueError("the reference replays traces without nodeSelector")
    cluster = {k: np.asarray(getattr(sim.init_state, k))
               for k in ("cpu_cap", "mem_cap", "gpu_cnt", "gpu_type")}
    pods = {k: np.asarray(getattr(specs, k))
            for k in ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask")}
    typical = {k: np.asarray(getattr(sim.typical, k))
               for k in ("cpu", "gpu_milli", "gpu_num", "gpu_mask", "freq")}
    return cluster, pods, typical, tiebreak_rank(len(sim.nodes), lane_seed)


def lane_against_reference(lane, ref) -> dict:
    """Entries of the lane that differ from the reference, field by
    field, up to the first event a near-integer score could decide."""
    stop = ref["first_undecided"]
    upto = len(ref["placed_node"]) if stop < 0 else stop
    differing = {
        "placed_node": int((np.asarray(lane.placed_node)[:upto]
                            != ref["placed_node"][:upto]).sum()),
        "dev_mask": int((np.asarray(lane.dev_mask)[:upto]
                         != ref["dev_mask"][:upto]).sum()),
    }
    if stop < 0:
        differing["ever_failed"] = int(
            (np.asarray(lane.ever_failed) != ref["ever_failed"]).sum())
        for f in STATE_FIELDS:
            differing[f"state.{f}"] = int(
                (np.asarray(getattr(lane.state, f)) != ref[f]).sum())
    return {"events_held": upto, "near_entries": ref["near_entries"],
            "first_undecided": stop, "differing": differing}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = bench_run.by_name(bench["workloads"], args.workload, "workload")
    entry = bench_run.by_name(bench["configs"], cell["config"], "config")
    stamp = bench_run.check_device(int(cell["chips"]), args.rehearse)

    from benchmark.drivers import wave
    from benchmark.lib import inputs, reference_fgd
    from tpusim.compile_cache import enable_compile_cache
    from tpusim.sim import driver

    enable_compile_cache()
    config = wave.sized(bench_run.load_json(os.path.join(REPO, entry["file"])),
                        args.rehearse)
    traffic = wave.sized(bench_run.load_json(os.path.join(
        BENCH, "traffic", f"{cell['traffic']}.json")), args.rehearse)
    lanes, depth = int(traffic["lanes"]), int(traffic["depth_events"])
    rows, ok = [], True
    for seed in args.seeds:
        nodes, pods = inputs.build(config, seed, depth)
        cfg = wave.simulator_config(config["simulator"], seed, profile=False)
        sim = wave.build_simulator(nodes, pods, cfg)
        trace = sim.prepare_pods()[:depth]
        weights = np.tile(np.asarray([w for _, w in cfg.policies], np.int32),
                          (lanes, 1))
        seeds = wave.lane_seeds(seed, 1, lanes)
        out = driver.schedule_pods_sweep(sim, trace, weights, seeds)
        pick = int(np.random.default_rng(seed).integers(lanes))
        ref = reference_fgd.replay(
            *reference_inputs(sim, trace, seeds[pick]),
            weight=int(weights[pick][0]))
        row = {"seed": seed, "lanes": len(out), "lane": pick,
               "lane_seed": seeds[pick], "events": len(trace),
               "nodes": len(nodes),
               **lane_against_reference(out[pick], ref)}
        ok &= (len(out) == lanes and not any(row["differing"].values())
               and row["events_held"] >= len(trace) // 2)
        rows.append(row)
        print(f"[reference] {row}", flush=True)
    print(json.dumps({"ok": bool(ok), "workload": args.workload,
                      "device": stamp, "near": reference_fgd.NEAR,
                      "runs": rows}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
