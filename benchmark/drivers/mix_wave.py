"""The `mix_wave` driver: the fork's PWR+FGD methods as ONE wide sweep, back
to back.

A lane is one (weight row, tuning seed, tie-break seed): it replays the
first `depth_events` events of the configuration's pod list, tuned and
shuffled by its tuning seed (the program's own `prepare_pods`), scored
under ITS weight row: `weight * PWR's normalized score + weight * FGD's
score`, devices picked by FGDScore. The rows are operands of one compiled
program, so one wave is one call of `schedule_pods_sweep(lead, None,
weights[B, 2], seeds[B], lane_pods=[trace of lane i])`, timed from the call
to the returned [SweepLane]; the lanes of one shuffle hand over the SAME
trace object. Traces are made once at set-up and are the same in every
wave and for every `--seed`; only the tie-break seeds are fresh. The order
of the process is `drivers/wave.py`'s, which `lib/sweep_log.py` reads the
log's tail by: one warm wave, the window's waves, and in a traced run one
more wave, with no other sweep in between.

A program whose SweepLane carries no `power_cpu_w` (the parent of the PR
that brought this cell) cannot give what the deployment's users read: the
driver says so before any device work.

After the window, and in no metric: every lane of every wave is held to the
in-scan counter identities and carries the weight row it was given, and the
window may not compile (as in `wave.py`); THREE lanes of the last wave, one
a weight row, drawn from `--seed`, are replayed whole on the sequential
oracle under their (row, shuffle, seed) and compared bit for bit, and their
two watts with the energy model over the oracle's final state; and one of
the three, drawn from `--seed`, is walked beside the plain numpy reference
(`lib/reference_mix.py`, `lib/reference_follow_mix.py`) over ALL its events:
every integer exact, a raw score may differ by 1 only within
`reference_fgd.NEAR`; such entries are counted and printed.
"""

from __future__ import annotations

import dataclasses
import os
import statistics
import time

import numpy as np

from benchmark.drivers import family_wave, wave
from benchmark.lib import (
    compare,
    device,
    inputs,
    reference_fgd,
    reference_follow_mix,
    reference_typical,
    roofline,
    trace_reduce,
)


def lane_grid(rows: int, shuffles: int, per_shuffle: int) -> list:
    """lane -> (weight row, shuffle), row-major: lane = (row * shuffles +
    shuffle) * per_shuffle + k."""
    return [(r, s) for r in range(rows) for s in range(shuffles)
            for _ in range(per_shuffle)]


def energy_tables(model: dict) -> dict:
    """The configuration's energy rows as arrays by model id, for the
    reference: the GPU model ids are data (data/README.md), the CSV names
    no CPU model, so CPU id 0 holds the default row."""
    from tpusim import constants

    idle = np.zeros(constants.MAX_GPU_MODELS)
    full = np.zeros(constants.MAX_GPU_MODELS)
    for name, (i_w, f_w) in model["gpu_idle_full_w"].items():
        idle[constants.GPU_MODEL_IDS[name]] = i_w
        full[constants.GPU_MODEL_IDS[name]] = f_w
    cpu = model["cpu_default"]
    return {"gpu_idle_w": idle, "gpu_full_w": full,
            "cpu_idle_w": np.asarray([cpu["idle_w"]], float),
            "cpu_full_w": np.asarray([cpu["full_w"]], float),
            "cpu_ncores": np.asarray([cpu["cores"]], float)}


def reference_walk(lead, trace, lane, weights, pod_csv, popularity, energy):
    """One lane walked beside the plain reference over all its events
    (lib/reference_follow_mix.py): cluster, requests, the lane's tie-break
    rank and weight row and the energy tables are data to both sides; the
    typical pods come from the CSV through reference_typical alone."""
    from tpusim import constants
    from tpusim.io.trace import pods_to_specs, tiebreak_rank

    specs = pods_to_specs(trace, lead.node_index, device=False)
    if (np.asarray(specs.pinned) >= 0).any():
        raise ValueError("the reference replays traces without nodeSelector")
    cluster = {k: np.asarray(getattr(lead.init_state, k))
               for k in ("cpu_cap", "mem_cap", "gpu_cnt", "gpu_type",
                         "cpu_type")}
    pods = {k: np.asarray(getattr(specs, k))
            for k in ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask")}
    typical = reference_typical.typical_pods(
        reference_typical.read_pod_keys(pod_csv), constants.GPU_MODEL_IDS,
        popularity=popularity)
    return reference_follow_mix.walk(
        cluster, pods, typical, tiebreak_rank(len(lead.nodes), lane.seed),
        lane, [int(w) for w in weights], energy)


def oracle_watts(state) -> tuple:
    """The energy model over a final state, summed over the nodes: what a
    lane's power_cpu_w / power_gpu_w have to equal. Whole watts, and 6,212
    GPUs x 400 W stay under 2^24, so the f32 sums are exact in any order
    and the comparison is `==`."""
    from tpusim.sim.engine import power_rows

    return tuple(float(np.asarray(rows).sum()) for rows in power_rows(state))


def run(ctx) -> dict:
    from tpusim.compile_cache import enable_compile_cache
    from tpusim.io.trace import load_node_csv, load_pod_csv
    from tpusim.sim import driver

    if "power_cpu_w" not in {
            f.name for f in dataclasses.fields(driver.SweepLane)}:
        raise RuntimeError(
            "this program's SweepLane carries no power_cpu_w: a sweep hands "
            "back placements, GPU allocation and frag a lane and no watts, "
            "which is what this deployment's users read")

    say = ctx.say
    traffic = wave.sized(ctx.traffic, ctx.rehearse)
    config = wave.sized(ctx.config, ctx.rehearse)
    workload, sim_cfg = config["workload"], config["simulator"]
    rows = [[int(w) for w in row] for row in sim_cfg["weight_rows"]]
    tuning_seeds = [int(s) for s in workload["tuning_seeds"]]
    per_shuffle, depth = (int(traffic["seeds_per_shuffle"]),
                          int(traffic["depth_events"]))
    lane_of = lane_grid(len(rows), len(tuning_seeds), per_shuffle)
    lanes = len(lane_of)
    if not ctx.rehearse and lanes != int(traffic["lanes"]):
        raise ValueError(f"{len(rows)} weight rows x {len(tuning_seeds)} "
                         f"shuffles x {per_shuffle} seeds are {lanes} lanes, "
                         f"the traffic file says {traffic['lanes']}")

    cache_dir = enable_compile_cache()
    compiles = wave.CompileCounter()
    t_mark = time.perf_counter()

    nodes = load_node_csv(inputs.NODE_CSV)[: config["cluster"].get("nodes")]
    pod_csv = os.path.join(inputs.REPO, workload["pod_csv"])
    pod_list = load_pod_csv(pod_csv)
    t_inputs, t_mark = time.perf_counter() - t_mark, time.perf_counter()
    cfg = wave.simulator_config(sim_cfg, tuning_seeds[0], profile=ctx.trace)
    lead = wave.build_simulator(nodes, pod_list, cfg)
    traces = [lead.prepare_pods(tuning_seed=s)[:depth] for s in tuning_seeds]
    events = len(traces[0])
    if any(len(t) != events for t in traces) or (
            events != depth and not ctx.rehearse):
        raise ValueError(f"the traces do not all hold {depth} events")
    lane_pods = [traces[s] for _, s in lane_of]
    weights = np.asarray([rows[r] for r, _ in lane_of], np.int32)
    n_pol = len(cfg.policies)
    t_sim, t_mark = time.perf_counter() - t_mark, time.perf_counter()

    def one_wave(index: int):
        seeds = wave.lane_seeds(ctx.seed, index, lanes)
        first_span = len(lead.obs.spans)
        t0 = time.perf_counter()
        out = driver.schedule_pods_sweep(
            lead, None, weights, seeds, lane_pods=lane_pods)
        t1 = time.perf_counter()
        return {"seeds": seeds, "t0": t0, "t1": t1,
                "wall_s": t1 - t0, "spans": lead.obs.spans[first_span:],
                "lanes": out}

    def lane_gap(w) -> int:
        """Worst counter identity over the wave's lanes; a lane missing,
        out of the order its (weights, seed) were given in, or carrying
        another weight row than it was given counts too."""
        worst = abs(len(w["lanes"]) - lanes) + sum(
            1 for lane, seed, row in zip(w["lanes"], w["seeds"], weights)
            if lane.seed != seed
            or not np.array_equal(np.asarray(lane.weights), row))
        for lane in w["lanes"]:
            worst = max([worst] + [d for _, d in
                                   compare.counter_differences(lane, events)])
        return worst

    warm = wave.warm_up(one_wave, traffic)
    setup_s = time.perf_counter() - ctx.t_start
    say(f"set-up {setup_s:.3f} s: inputs {t_inputs:.3f}, simulator and "
        f"{len(traces)} traces {t_sim:.3f}, warm waves {warm}; "
        f"{len(nodes)} nodes, {events} events, {lanes} lanes = {len(rows)} "
        f"weight rows {rows} x {len(tuning_seeds)} shuffles x {per_shuffle} "
        f"seeds, typical pods {int(lead.typical.cpu.shape[0])}, engine "
        f"{lead._last_engine}; cache {cache_dir}")

    # ---- the window
    waves, lane_gaps = [], []
    compiles.armed = True
    window_t0 = time.perf_counter()
    while True:
        w = one_wave(len(waves) + 1)
        lane_gaps.append(lane_gap(w))
        if waves:
            waves[-1].pop("lanes")  # keep the last wave's lanes only
        waves.append(w)
        if time.perf_counter() - window_t0 >= ctx.seconds:
            break
    compiles.armed = False
    window_s = time.perf_counter() - window_t0
    memory = device.memory_peaks()

    # ---- one more wave under the profiler, outside the window
    traced = None
    if ctx.trace:
        raw, tw = wave.traced_wave(one_wave, len(waves) + 1)
        tw.pop("lanes")
        phases = wave.wave_phases(tw["spans"], tw["t0"], tw["t1"],
                                  lead.obs.epoch)
        traced = trace_reduce.reduce_wave(raw, phases)
        del raw
        traced["wall_s"] = tw["wall_s"]
        waited = sum(e - s for name, s, e in phases if name == "scan")
        if not ctx.rehearse and traced["scan_device_s"] < 0.9 * waited - 0.2:
            raise RuntimeError(
                f"the device trace is cut short: its longest program ran "
                f"{traced['scan_device_s']:.3f} s, the host waited "
                f"{waited:.3f} s on the scan")

    # ---- correctness, outside every metric
    checks = [("lanes in order, each with its weight row, and counter "
               "identities, worst of any wave", max(lane_gaps), 0),
              ("compiles inside the window", compiles.compiles, 0)]
    last = waves[-1]
    rng = np.random.default_rng(ctx.seed)
    per_row = len(tuning_seeds) * per_shuffle
    picks = [r * per_row + int(rng.integers(per_row))
             for r in range(len(rows))]
    held_to_reference = picks[int(rng.integers(len(picks)))]
    t_oracle = time.perf_counter()
    for i in picks:
        r, s = lane_of[i]
        lane = last["lanes"][i]
        who = (f"lane {i} (row {rows[r]}, shuffle {tuning_seeds[s]}, seed "
               f"{last['seeds'][i]}) vs the sequential oracle")
        want = wave.oracle_lane(nodes, pod_list, sim_cfg, tuning_seeds[s],
                                lane_pods[i], weights[i], last["seeds"][i])
        for what, differing in compare.lane_differences(lane, want):
            checks.append((f"{who}: {what}", differing, 0))
        watts = oracle_watts(want.state)
        checks.append((f"{who}: power_cpu_w, power_gpu_w "
                       f"{lane.power_cpu_w, lane.power_gpu_w} vs {watts}",
                       sum(got != exp for got, exp in zip(
                           (lane.power_cpu_w, lane.power_gpu_w), watts)), 0))
    t_oracle, t_ref = time.perf_counter() - t_oracle, time.perf_counter()
    r, s = lane_of[held_to_reference]
    ref = reference_walk(
        lead, lane_pods[held_to_reference], last["lanes"][held_to_reference],
        weights[held_to_reference], pod_csv,
        int(sim_cfg["pod_popularity_threshold"]),
        energy_tables(config["energy_model"]))
    who = (f"lane {held_to_reference} (row {rows[r]}, shuffle "
           f"{tuning_seeds[s]}) vs the numpy reference")
    checks.append((f"{who}: events not held", events - ref["events_held"], 0))
    for what, differing in ref["differing"].items():
        checks.append((f"{who}: {what}", differing, 0))
    t_ref = time.perf_counter() - t_ref
    watts_by_row = [
        [statistics.median(getattr(lane, f)
                           for lane in last["lanes"][k * per_row:
                                                     (k + 1) * per_row])
         for f in ("power_cpu_w", "power_gpu_w", "frag_gpu_milli")]
        for k in range(len(rows))]
    last.pop("lanes")
    for what, got, limit in checks:
        say(f"check: {what}: {got} (limit {limit})")
    say(f"reference: near entries {ref['near_entries']} of FGD's scores "
        f"(within {reference_fgd.NEAR} of an integer) and "
        f"{ref['pwr_near_entries']} of PWR's, events at which the lane's "
        f"choice was another one they admit {ref['admitted']}, events held "
        f"{ref['events_held']}; took {t_ref:.3f} s for one lane; the "
        f"oracle took {t_oracle:.3f} s for {len(picks)} lanes; window "
        f"{window_s:.3f} s, {len(waves)} waves; programs traced again in "
        f"the window and loaded from the persistent cache: "
        f"{compiles.cache_loads}")
    say(f"last wave, median (power_cpu_w, power_gpu_w, frag_gpu_milli) a "
        f"weight row: { {str(row): w for row, w in zip(rows, watts_by_row)} }")
    shape = {"nodes": len(nodes),
             "pod_types": family_wave.table_pod_types(traces),
             "policies": n_pol, "lanes": lanes, "events": events}
    carried = lanes * roofline.carry_bytes_per_lane(
        shape["nodes"], shape["pod_types"], n_pol, events, events)
    caches = [sp.meta.get("cache") for w in waves for sp in w["spans"]
              if sp.name == "init_tables"]
    say(f"init_tables in the window's {len(waves)} waves, by cache: "
        f"{ {c: caches.count(c) for c in sorted(set(caches))} }")
    say(f"device memory peaks {memory}; carried by the scan, from shapes "
        f"(K = {shape['pod_types']}, {n_pol} policies): {carried} bytes "
        f"over {lanes} lanes")

    walls = [w["wall_s"] for w in waves]
    say(f"wave walls {[round(x, 3) for x in walls]}")
    return {
        "correct": all(got <= limit for _, got, limit in checks),
        "attempted": len(waves),
        "failed": sum(1 for g in lane_gaps if g),
        "memory_peak_bytes": device.memory_peak_bytes(memory),
        "end_to_end": {
            "lane_events_per_s": events * lanes * len(waves) / sum(walls),
            "wave_s": statistics.median(walls),
            "setup_s": setup_s,
        },
        "waves": [wave.wave_account(w) for w in waves],
        **wave.window_account(walls, warm, t_inputs, t_sim, setup_s),
        "checks": checks,
        "spans_blocked": bool(ctx.trace),
        "shape": shape,
        "traced": traced,
    }
