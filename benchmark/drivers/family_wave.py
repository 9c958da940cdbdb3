"""The `family_wave` driver: the FGD artifact's experiment matrix as ONE
wide sweep, back to back.

A lane is one (trace family, tuning seed, tie-break seed): it replays the
first `depth_events` events of its family's pod list, tuned and shuffled
by its tuning seed (the program's own `prepare_pods`), and is scored and
frag-reported against ITS family's typical pods. One wave is one call of
`schedule_pods_sweep(lead, None, weights[B, n_pol], seeds[B],
lane_pods=[trace of lane i], lane_typical=[typical pods of lane i])`,
timed from the call to the returned [SweepLane]. The traces (families x
tuning seeds of them) and the typical pods (one set a family) are made
once at set-up and are the same objects in every wave and for every
`--seed`; only the tie-break seeds are fresh. The order of the process is
`drivers/wave.py`'s, which `lib/sweep_log.py` reads the log's tail by: one
warm wave, the window's waves, and in a traced run one more wave, with no
other sweep in between.

After the window, and in no metric: every lane of every wave is held to
the in-scan counter identities and the window may not compile (as in
`wave.py`); FIVE lanes of the last wave, one a family, drawn from
`--seed`, are replayed whole on the sequential oracle of a Simulator built
from THAT family's pod list and compared bit for bit; and one of the
five, drawn from `--seed`, is held to the plain numpy reference
(`lib/reference_fgd.py`'s scoring, its typical pods by
`lib/reference_typical.py`) over ALL its events under
`tests/reference_on_chip.py`'s rule, applied event by event
(`lib/reference_follow.py`): every integer exact, a score may differ by 1
only within `reference_fgd.NEAR`; such entries are counted and printed, and
so are the events at which they let the lane choose otherwise.
"""

from __future__ import annotations

import inspect
import os
import statistics
import time

import numpy as np

from benchmark.drivers import wave
from benchmark.lib import (
    compare,
    device,
    inputs,
    reference_fgd,
    reference_follow,
    reference_typical,
    roofline,
    trace_reduce,
)

TYPE_BUCKET = 16  # the program pads each group of pod types to this


def table_pod_types(traces) -> int:
    """K of the score tables the wave carries, counted here and not read
    from the program: the distinct pod types over ALL the traces (one union
    type set serves every lane; a type is the resources asked for, the
    allowed GPU models as the set they name, so "A|B" and "B|A|B" are one),
    the share-GPU group (one GPU, under 1,000 milli) and the rest each on
    the program's 16-type bucket."""
    from tpusim.constants import gpu_spec_to_mask

    types = {(p.cpu_milli, p.memory_mib, p.num_gpu, p.gpu_milli,
              gpu_spec_to_mask(p.gpu_spec))
             for trace in traces for p in trace}
    share = sum(1 for t in types if t[2] == 1 and 0 < t[3] < 1000)
    up = lambda k: -(-k // TYPE_BUCKET) * TYPE_BUCKET  # noqa: E731
    return up(share) + up(len(types) - share)


def reference_walk(lead, trace, lane, weight, pod_csv, popularity):
    """One lane held to the plain reference over all its events
    (lib/reference_follow.py): cluster, requests and the lane's tie-break
    rank are data to both sides; the typical pods come from the family's
    CSV through reference_typical alone."""
    from tpusim import constants
    from tpusim.io.trace import pods_to_specs, tiebreak_rank

    specs = pods_to_specs(trace, lead.node_index, device=False)
    if (np.asarray(specs.pinned) >= 0).any():
        raise ValueError("the reference replays traces without nodeSelector")
    cluster = {k: np.asarray(getattr(lead.init_state, k))
               for k in ("cpu_cap", "mem_cap", "gpu_cnt", "gpu_type")}
    pods = {k: np.asarray(getattr(specs, k))
            for k in ("cpu", "mem", "gpu_milli", "gpu_num", "gpu_mask")}
    typical = reference_typical.typical_pods(
        reference_typical.read_pod_keys(pod_csv), constants.GPU_MODEL_IDS,
        popularity=popularity)
    return reference_follow.walk(
        cluster, pods, typical, tiebreak_rank(len(lead.nodes), lane.seed),
        lane, weight=weight)


def run(ctx) -> dict:
    from tpusim.compile_cache import enable_compile_cache
    from tpusim.io.trace import load_node_csv, load_pod_csv
    from tpusim.sim import driver

    if "lane_typical" not in inspect.signature(
            driver.schedule_pods_sweep).parameters:
        raise RuntimeError(
            "this program's schedule_pods_sweep scores every lane against "
            "ONE typical-pod set (it takes no lane_typical): it cannot run "
            "lanes of different trace families in one sweep")

    say = ctx.say
    traffic = wave.sized(ctx.traffic, ctx.rehearse)
    config = wave.sized(ctx.config, ctx.rehearse)
    workload = config["workload"]
    families = list(workload["families"])
    tuning_seeds = [int(s) for s in workload["tuning_seeds"]]
    per_shuffle, depth = (int(traffic["seeds_per_shuffle"]),
                          int(traffic["depth_events"]))
    lanes = len(families) * len(tuning_seeds) * per_shuffle
    if not ctx.rehearse and lanes != int(traffic["lanes"]):
        raise ValueError(f"{len(families)} families x {len(tuning_seeds)} "
                         f"shuffles x {per_shuffle} seeds are {lanes} lanes, "
                         f"the traffic file says {traffic['lanes']}")

    cache_dir = enable_compile_cache()
    compiles = wave.CompileCounter()
    t_mark = time.perf_counter()

    nodes = load_node_csv(inputs.NODE_CSV)[: config["cluster"].get("nodes")]
    csvs = [os.path.join(inputs.REPO, workload["pod_csv"].format(family=f))
            for f in families]
    pod_lists = [load_pod_csv(path) for path in csvs]
    t_inputs, t_mark = time.perf_counter() - t_mark, time.perf_counter()
    # one Simulator a family (its pod list makes its typical pods and its
    # traces); the first leads the sweep: they share cluster and policies
    cfg = wave.simulator_config(config["simulator"], tuning_seeds[0],
                                profile=ctx.trace)
    sims = [wave.build_simulator(nodes, pods, cfg) for pods in pod_lists]
    lead = sims[0]
    traces = [[sim.prepare_pods(tuning_seed=s)[:depth] for s in tuning_seeds]
              for sim in sims]
    events = len(traces[0][0])
    if any(len(t) != events for per in traces for t in per) or (
            events != depth and not ctx.rehearse):
        raise ValueError(f"the traces do not all hold {depth} events")
    # lane -> (family, shuffle), family-major
    lane_of = [(f, s) for f in range(len(families))
               for s in range(len(tuning_seeds)) for _ in range(per_shuffle)]
    lane_pods = [traces[f][s] for f, s in lane_of]
    lane_typical = [sims[f].typical for f, _ in lane_of]
    n_pol = len(cfg.policies)
    weights = np.tile(np.asarray([w for _, w in cfg.policies], np.int32),
                      (lanes, 1))
    t_sim, t_mark = time.perf_counter() - t_mark, time.perf_counter()

    def one_wave(index: int):
        seeds = wave.lane_seeds(ctx.seed, index, lanes)
        first_span = len(lead.obs.spans)
        t0 = time.perf_counter()
        out = driver.schedule_pods_sweep(
            lead, None, weights, seeds, lane_pods=lane_pods,
            lane_typical=lane_typical)
        t1 = time.perf_counter()
        return {"seeds": seeds, "t0": t0, "t1": t1,
                "wall_s": t1 - t0, "spans": lead.obs.spans[first_span:],
                "lanes": out}

    def counter_gap(w) -> int:
        """Worst counter identity over the wave's lanes; a lane missing or
        out of the order its (weights, seed) were given in counts too."""
        worst = abs(len(w["lanes"]) - lanes) + sum(
            1 for lane, seed in zip(w["lanes"], w["seeds"]) if lane.seed != seed)
        for lane in w["lanes"]:
            worst = max([worst] + [d for _, d in
                                   compare.counter_differences(lane, events)])
        return worst

    warm = wave.warm_up(one_wave, traffic)
    setup_s = time.perf_counter() - ctx.t_start
    say(f"set-up {setup_s:.3f} s: inputs {t_inputs:.3f}, {len(sims)} "
        f"simulators and {len(sims) * len(tuning_seeds)} traces {t_sim:.3f}, "
        f"warm waves {warm}; {len(nodes)} nodes, {events} events, "
        f"{lanes} lanes of {families}, typical pods "
        f"{[int(s.typical.cpu.shape[0]) for s in sims]}, engine "
        f"{lead._last_engine}; cache {cache_dir}")

    # ---- the window
    waves, counter_gaps = [], []
    compiles.armed = True
    window_t0 = time.perf_counter()
    while True:
        w = one_wave(len(waves) + 1)
        counter_gaps.append(counter_gap(w))
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
    checks = [("lanes in order and counter identities, worst of any wave",
               max(counter_gaps), 0),
              ("compiles inside the window", compiles.compiles, 0)]
    last = waves[-1]
    rng = np.random.default_rng(ctx.seed)
    per_family = len(tuning_seeds) * per_shuffle
    picks = [f * per_family + int(rng.integers(per_family))
             for f in range(len(families))]
    held_to_reference = picks[int(rng.integers(len(picks)))]
    t_oracle = time.perf_counter()
    for i in picks:
        f, s = lane_of[i]
        want = wave.oracle_lane(nodes, pod_lists[f], config["simulator"],
                                tuning_seeds[s], lane_pods[i], weights[i],
                                last["seeds"][i])
        for what, differing in compare.lane_differences(last["lanes"][i], want):
            checks.append((f"lane {i} ({families[f]}, shuffle "
                           f"{tuning_seeds[s]}, seed {last['seeds'][i]}) vs "
                           f"its family's sequential oracle: {what}",
                           differing, 0))
    t_oracle, t_ref = time.perf_counter() - t_oracle, time.perf_counter()
    f, s = lane_of[held_to_reference]
    ref = reference_walk(
        lead, lane_pods[held_to_reference], last["lanes"][held_to_reference],
        int(weights[held_to_reference][0]), csvs[f],
        int(config["simulator"]["pod_popularity_threshold"]))
    who = (f"lane {held_to_reference} ({families[f]}, shuffle "
           f"{tuning_seeds[s]}) vs the numpy reference")
    checks.append((f"{who}: events not held", events - ref["events_held"], 0))
    for what, differing in ref["differing"].items():
        checks.append((f"{who}: {what}", differing, 0))
    t_ref = time.perf_counter() - t_ref
    last.pop("lanes")
    for what, got, limit in checks:
        say(f"check: {what}: {got} (limit {limit})")
    say(f"reference: near entries {ref['near_entries']} (within "
        f"{reference_fgd.NEAR} of an integer), events at which the lane's "
        f"choice was another one they admit {ref['admitted']}, events held "
        f"{ref['events_held']}; took {t_ref:.3f} s for one lane; the "
        f"oracle took {t_oracle:.3f} s for {len(picks)} lanes; window "
        f"{window_s:.3f} s, {len(waves)} waves; programs traced again in "
        f"the window and loaded from the persistent cache: "
        f"{compiles.cache_loads}")
    shape = {"nodes": len(nodes),
             "pod_types": table_pod_types(t for per in traces for t in per),
             "policies": n_pol, "lanes": lanes, "events": events}
    carried = lanes * roofline.carry_bytes_per_lane(
        shape["nodes"], shape["pod_types"], n_pol, events, events)
    caches = [sp.meta.get("cache") for w in waves for sp in w["spans"]
              if sp.name == "init_tables"]
    say(f"init_tables in the window's {len(waves)} waves, by cache: "
        f"{ {c: caches.count(c) for c in sorted(set(caches))} }")
    say(f"device memory peaks {memory}; carried by the scan, from shapes "
        f"(K = {shape['pod_types']}): {carried} bytes over {lanes} lanes")

    walls = [w["wall_s"] for w in waves]
    return {
        "correct": all(got <= limit for _, got, limit in checks),
        "attempted": len(waves),
        "failed": sum(1 for g in counter_gaps if g),
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
