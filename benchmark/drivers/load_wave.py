"""The `load_wave` driver: the FGD artifact's protocol at its own depth, as
ONE wide sweep, back to back.

A lane is one (tuning seed, tie-break seed): it replays EVERY event of the
default pod list tuned to 130 % of the cluster's GPU capacity and shuffled
by its tuning seed (the program's own `prepare_pods`) on the empty cluster,
with the per-event report ON, so it hands back its `EventMetrics` series.
The traces differ in length (10,763-10,893 creates), so every count here
is a lane's OWN: the window's events are the real events of its lanes,
never the bucket. One wave is one call of `schedule_pods_sweep(lead, None,
weights[B, 1], seeds[B], lane_pods=[trace of lane i])`, timed from the call
to the returned [SweepLane]. The traces (one a tuning seed) are made once
at set-up, held to the reference's own shuffle and tuning of the CSV's rows
(`lib/reference_follow_load.tuned_order`), and are the same objects in
every wave and for every `--seed`; only the tie-break seeds are fresh. The
order of the process is `drivers/wave.py`'s, which `lib/sweep_log.py` reads
the log's tail by.

After the window, and in no metric: every lane of every wave is held to the
in-scan counter identities with its own event count and carries series of
its own length, and the window may not compile; ONE lane of the last wave,
drawn from `--seed`, (i) equals the sequential oracle's whole replay of its
(shuffle, seed) with the report on: placements, masks, flags, every
NodeState field and every integer series bit for bit, the float series
within `FLOAT_LIMITS`; (ii) is walked beside the plain numpy reference over
ALL its events (`lib/reference_follow_load.py`): the chosen node passes
the reference's Filter, the devices are ones its Reserve admits, every
rejected create has no feasible node, the final state equals, and
`scored_creates` of its creates, drawn from `--seed` by arrived-load
decile, are held to the full FGD scoring rule; (iii) its series are held
to `lib/reference_report.py`, which recomputes the report from the walk's
own state, at every event where the arrived GPU load reaches a new whole
per cent of capacity and at the last: integers `==`, floats within
`FLOAT_LIMITS`. What the reference replays it reads itself
(`lib/reference_inputs.py`: cluster and requests from the CSV files, the
tie-break rank from the lane's seed; the typical pods by
`lib/reference_typical.py`; the trace's order by `tuned_order`).
"""

from __future__ import annotations

import csv
import re
import statistics
import time

import numpy as np

from benchmark.drivers import family_wave, mix_wave, wave
from benchmark.lib import (
    compare,
    device,
    inputs,
    reference_fgd,
    reference_follow_load,
    reference_inputs,
    reference_report,
    reference_typical,
    roofline,
    roofline_report,
    trace_reduce,
)

# The float series against a float64 recompute from scratch, as absolute
# limits. The program's series are float32 cumulative sums of per-event row
# deltas over a lane's 10,8xx events (tpusim/sim/metrics.py). The watts of
# the energy tables are whole numbers and the cluster draws under 2^24 of
# them, so the power series are exact in float32 in any order: limit 0. A
# frag amount is a sum over typical pods of freq x whole milli, up to the
# cluster's 6,212,000 idle milli, where one float32 step is 0.5 milli; the
# running sum rounds once an event, so its error grows like the square root
# of the events: PERF.md section 6 (PR 41) has the readings, 16 milli is the
# limit. A bfloat16 series is off by thousands of milli at the first loaded
# event, and a dropped delta of a whole-GPU create by 1,000 x a class's
# frequency share, in every later event.
FLOAT_LIMITS = {"frag_amounts": 16.0, "power_cpu": 0.0, "power_gpu": 0.0}
TUNED = re.compile(r"-tuned-\d+$")  # the suffix tune_pods gives a clone


def lane_grid(shuffles: int, per_shuffle: int) -> list:
    """lane -> shuffle, shuffle-major: lane = shuffle * per_shuffle + k."""
    return [s for s in range(shuffles) for _ in range(per_shuffle)]


def trace_rows(trace, names) -> list:
    """The pod list's row of every pod of a tuned trace, by name (a clone
    is its original's row)."""
    row_of = {name: i for i, name in enumerate(names)}
    return [row_of[TUNED.sub("", p.name)] for p in trace]


def series_differences(got, want, limits=FLOAT_LIMITS) -> list:
    """[(what, got, limit)] between two EventMetrics over the same events:
    entries that differ for the integer series (limit 0), the largest
    absolute difference for the float series. A missing series or another
    length counts every entry."""
    out = []
    exact_names = reference_report.INTEGER_SERIES
    for name in exact_names + reference_report.FLOAT_SERIES:
        x = None if got is None else np.asarray(getattr(got, name))
        y = np.asarray(getattr(want, name))
        exact = name in exact_names
        if x is None or x.shape != y.shape:
            out.append((name, int(y.size), 0))
        elif exact:
            out.append((name, int((x != y).sum()), 0))
        else:
            gap = np.abs(x.astype(np.float64) - y.astype(np.float64))
            out.append((name, float(gap.max(initial=0.0)), limits[name]))
    return out


def report_differences(metrics, at: dict, limits=FLOAT_LIMITS) -> list:
    """[(what, got, limit)] between a lane's series and the reference's
    reports `at` {event: reference_report.report(...)}: integer series by
    the events that differ, float series by the largest absolute gap."""
    events = sorted(at)
    out = []
    for name in reference_report.INTEGER_SERIES:
        got = np.asarray(getattr(metrics, name))[events]
        want = np.asarray([at[e][name] for e in events], np.int64)
        out.append((name, int((got != want).sum()), 0))
    for name in reference_report.FLOAT_SERIES:
        got = np.asarray(getattr(metrics, name), np.float64)[events]
        want = np.asarray([at[e][name] for e in events], np.float64)
        out.append((name, float(np.abs(got - want).max(initial=0.0)),
                    limits[name]))
    return out


def scored_events(gpu_milli, gpu_num, placed, capacity_milli: int, count: int,
                  rng) -> list:
    """`count` of the creates a lane PLACED (all of them where it placed
    fewer; a rejected create has no node to score), drawn evenly from the
    deciles of the arrived GPU load they arrive at, a tenth from the empty
    cluster, a tenth from the full one; a decile that holds too few (the
    last ones, where most creates are rejected) is made up from the rest."""
    arrived = np.cumsum(np.asarray(gpu_milli, np.int64)
                        * np.asarray(gpu_num, np.int64))
    placed = np.flatnonzero(placed)
    if count >= len(placed):
        return placed.tolist()
    decile = np.minimum((10 * arrived[placed]) // max(int(arrived[-1]), 1), 9)
    picked = np.concatenate([
        rng.choice(pool, size=min(len(pool), count // 10), replace=False)
        for pool in (placed[decile == d] for d in range(10))])
    rest = np.setdiff1d(placed, picked)
    more = rng.choice(rest, size=count - len(picked), replace=False)
    return sorted(picked.tolist() + more.tolist())


def pod_names() -> list:
    """The pod list's names, in the CSV's row order."""
    with open(inputs.POD_CSV, newline="") as f:
        return [row["name"] for row in csv.DictReader(f)]


def reference_side(config: dict, num_nodes: int):
    """What the plain reference replays, read by itself: the cluster (with
    the default CPU model's id), the pod list's requests and names, the
    typical pods and the energy tables."""
    from tpusim import constants

    ids = constants.GPU_MODEL_IDS
    cluster = reference_inputs.cluster(inputs.NODE_CSV, ids, num_nodes)
    cluster["cpu_type"] = np.zeros(len(cluster["cpu_cap"]), np.int64)
    requests = reference_inputs.pods(inputs.POD_CSV, ids)
    typical = reference_typical.typical_pods(
        reference_typical.read_pod_keys(inputs.POD_CSV), ids,
        popularity=int(config["simulator"]["pod_popularity_threshold"]))
    return (cluster, requests, pod_names(), typical,
            mix_wave.energy_tables(config["energy_model"]))


def oracle_lane(nodes, pods, sim_cfg, tuning_seed, trace, weights, lane_seed):
    """`wave.oracle_lane` with the per-event report on: the lane's
    (weights, seed) replayed standalone on the sequential oracle over its
    whole trace, its series beside its placements."""
    import jax
    import jax.numpy as jnp

    from tpusim.io.trace import build_events, pods_to_specs

    names = [name for name, _ in sim_cfg["policies"]]
    cfg = wave.simulator_config(
        sim_cfg, tuning_seed, profile=False, engine="sequential",
        seed=int(lane_seed), report_per_event=True,
        policies=tuple(zip(names, (int(w) for w in weights))))
    sim = wave.build_simulator(nodes, pods, cfg)
    ev_kind, ev_pod = build_events(trace)
    out = sim.run_events(
        sim.init_state, pods_to_specs(trace, sim.node_index),
        jnp.asarray(ev_kind), jnp.asarray(ev_pod),
        jax.random.PRNGKey(cfg.seed), bucket=512)
    if "sequential" not in str(sim._last_engine):
        raise RuntimeError(f"the oracle ran on {sim._last_engine!r}")
    jax.block_until_ready((out.state, out.metrics))
    return out


def hold_lane(ref, rows, lane, seed: int, weight: int, scored: int, rng):
    """One lane held to the plain reference: the walk over all its events
    and the reports at the load's crossings. `ref` is `reference_side`'s,
    `rows` the pod list's row of every event of the lane's trace. Returns
    (the walk's result, [(what, got, limit)] of the series, the
    crossings)."""
    cluster, requests, _names, typical, energy = ref
    pods = {k: v[rows] for k, v in requests.items()}
    capacity = int(cluster["gpu_cnt"].sum()) * reference_fgd.MILLI
    crossings = reference_follow_load.load_crossings(
        pods["gpu_milli"], pods["gpu_num"], capacity)
    walked = reference_follow_load.walk(
        cluster, pods, typical,
        reference_inputs.tiebreak_rank(len(cluster["cpu_cap"]), seed), lane,
        weight, scored_events(pods["gpu_milli"], pods["gpu_num"],
                              np.asarray(lane.placed_node) >= 0, capacity,
                              scored, rng), crossings)
    at = {e: reference_report.report(
        cluster, *walked["states"][e], typical, energy, pods,
        np.arange(e + 1)) for e in crossings if e in walked["states"]}
    series = ([("events reported", len(crossings) - len(at), 0)]
              + (report_differences(lane.metrics, at) if at else []))
    return walked, series, crossings


def run(ctx) -> dict:
    from tpusim.compile_cache import enable_compile_cache
    from tpusim.io.trace import load_node_csv, load_pod_csv
    from tpusim.sim import driver

    say = ctx.say
    traffic = wave.sized(ctx.traffic, ctx.rehearse)
    config = wave.sized(ctx.config, ctx.rehearse)
    workload, sim_cfg = config["workload"], config["simulator"]
    if not sim_cfg.get("report_per_event"):
        raise ValueError("a load wave replays a configuration with "
                         "report_per_event")
    tuning_seeds = [int(s) for s in workload["tuning_seeds"]]
    per_shuffle = int(traffic["seeds_per_shuffle"])
    lane_of = lane_grid(len(tuning_seeds), per_shuffle)
    lanes = len(lane_of)
    if not ctx.rehearse and lanes != int(traffic["lanes"]):
        raise ValueError(f"{len(tuning_seeds)} shuffles x {per_shuffle} seeds "
                         f"are {lanes} lanes, the traffic file says "
                         f"{traffic['lanes']}")

    cache_dir = enable_compile_cache()
    compiles = wave.CompileCounter()
    t_mark = time.perf_counter()

    nodes = load_node_csv(inputs.NODE_CSV)[: config["cluster"].get("nodes")]
    pods = load_pod_csv(inputs.POD_CSV)
    ref = reference_side(config, len(nodes))
    t_inputs, t_mark = time.perf_counter() - t_mark, time.perf_counter()
    cfg = wave.simulator_config(sim_cfg, tuning_seeds[0], profile=ctx.trace,
                                report_per_event=True)
    lead = wave.build_simulator(nodes, pods, cfg)
    traces = [lead.prepare_pods(tuning_seed=s) for s in tuning_seeds]
    # every trace WHOLE, and the one the reference's own shuffle and tuning
    # of the CSV's rows gives
    rows = [trace_rows(t, ref[2]) for t in traces]
    capacity = int(ref[0]["gpu_cnt"].sum()) * reference_fgd.MILLI
    for s, got in zip(tuning_seeds, rows):
        want = reference_follow_load.tuned_order(
            ref[2], ref[1]["gpu_milli"], ref[1]["gpu_num"], capacity,
            float(sim_cfg["tuning_ratio"]), s)
        if got != want:
            raise ValueError(
                f"the trace of tuning seed {s} ({len(got)} events) is not "
                f"the reference's shuffle and tuning of the pod list "
                f"({len(want)} events)")
    events_of = [len(t) for t in traces]
    stated = traffic.get("events_by_shuffle")
    if not ctx.rehearse and events_of != stated:
        raise ValueError(f"the traces hold {events_of} events, the traffic "
                         f"file says {stated}: a lane replays every event")
    lane_events = [events_of[s] for s in lane_of]
    wave_events = sum(lane_events)
    lane_pods = [traces[s] for s in lane_of]
    n_pol = len(cfg.policies)
    weights = np.tile(np.asarray([w for _, w in cfg.policies], np.int32),
                      (lanes, 1))
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

    def counter_gap(w) -> int:
        """Worst counter identity over the wave's lanes, each against its
        OWN trace's events; a lane missing, out of the order its (weights,
        seed) were given in, or without series of its own length counts
        too."""
        worst = abs(len(w["lanes"]) - lanes) + sum(
            lane.seed != seed for lane, seed in zip(w["lanes"], w["seeds"]))
        for lane, events in zip(w["lanes"], lane_events):
            series = lane.metrics is not None and all(
                len(a) == events for a in lane.metrics)
            worst = max([worst, 0 if series else events] + [
                d for _, d in compare.counter_differences(lane, events)])
        return worst

    def rejected(w) -> int:
        return sum(int(lane.counters[2]) for lane in w["lanes"])

    warm = wave.warm_up(one_wave, traffic)
    setup_s = time.perf_counter() - ctx.t_start
    say(f"set-up {setup_s:.3f} s: inputs and the reference's {t_inputs:.3f}, "
        f"simulator and {len(traces)} traces {t_sim:.3f}, warm waves "
        f"{warm}; {len(nodes)} nodes, events by shuffle {events_of} "
        f"({wave_events} real lane-events a wave), {lanes} lanes, typical "
        f"pods {int(lead.typical.cpu.shape[0])}, engine {lead._last_engine}; "
        f"cache {cache_dir}")

    # ---- the window
    waves, counter_gaps = [], []
    compiles.armed = True
    window_t0 = time.perf_counter()
    while True:
        w = one_wave(len(waves) + 1)
        counter_gaps.append(counter_gap(w))
        w["rejected"] = rejected(w)
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
        traced["report_device_s"] = roofline_report.report_device_seconds(raw)
        del raw
        traced["wall_s"] = tw["wall_s"]
        waited = sum(e - s for name, s, e in phases if name == "scan")
        if not ctx.rehearse and traced["scan_device_s"] < 0.9 * waited - 0.2:
            raise RuntimeError(
                f"the device trace is cut short: its longest program ran "
                f"{traced['scan_device_s']:.3f} s, the host waited "
                f"{waited:.3f} s on the scan (the profiler's buffer holds "
                f"about 6 M device events and drops the rest)")

    # ---- correctness, outside every metric
    checks = [("lanes in order, counter identities with each lane's own "
               "events and series of its own length, worst of any wave",
               max(counter_gaps), 0),
              ("compiles inside the window", compiles.compiles, 0)]
    last = waves[-1]
    rng = np.random.default_rng(ctx.seed)
    i = int(rng.integers(lanes))
    lane, s = last["lanes"][i], lane_of[i]
    who = f"lane {i} (shuffle {tuning_seeds[s]}, seed {last['seeds'][i]})"
    t_oracle = time.perf_counter()
    want = oracle_lane(nodes, pods, sim_cfg, tuning_seeds[s], traces[s],
                       weights[i], last["seeds"][i])
    for what, differing in compare.lane_differences(lane, want):
        checks.append((f"{who} vs sequential oracle: {what}", differing, 0))
    want_series = type(want.metrics)(
        *(np.asarray(a)[:events_of[s]] for a in want.metrics))
    for what, got, limit in series_differences(lane.metrics, want_series):
        checks.append((f"{who} vs sequential oracle, series {what}", got,
                       limit))
    t_oracle, t_ref = time.perf_counter() - t_oracle, time.perf_counter()
    walked, series, crossings = hold_lane(
        ref, rows[s], lane, last["seeds"][i], int(weights[i][0]),
        int(traffic["scored_creates"]), rng)
    vs = f"{who} vs the numpy reference"
    checks.append((f"{vs}: events not held",
                   events_of[s] - walked["events_held"], 0))
    for what, differing in walked["differing"].items():
        checks.append((f"{vs}: {what}", differing, 0))
    for what, got, limit in series:
        checks.append((f"{vs}, report at {len(crossings)} events: {what}",
                       got, limit))
    t_ref = time.perf_counter() - t_ref
    alloc, lane_rejected = lane.gpu_alloc_pct, int(lane.counters[2])
    last.pop("lanes")
    for what, got, limit in checks:
        say(f"check: {what}: {got} (limit {limit})")
    say(f"reference: creates rejected {walked['rejected']} of "
        f"{events_of[s]} (no feasible node in the reference's state; the "
        f"lane counts {lane_rejected}), creates held to the scoring rule "
        f"{walked['scored']}, near entries {walked['near_entries']} (within "
        f"{reference_fgd.NEAR} of an integer), events at which the lane's "
        f"choice was another one they admit {walked['admitted']}, events "
        f"held {walked['events_held']}; the lane's final GPU allocation "
        f"{alloc:.3f} %; took {t_ref:.3f} s for one lane; the oracle took "
        f"{t_oracle:.3f} s; window {window_s:.3f} s, {len(waves)} waves; "
        f"programs traced again in the window and loaded from the "
        f"persistent cache: {compiles.cache_loads}")
    shape = {"nodes": len(nodes),
             "pod_types": family_wave.table_pod_types(traces),
             "policies": n_pol, "lanes": lanes,
             # the MEAN real events a lane: the readers that multiply lanes
             # by events get the real count
             "events": wave_events / lanes}
    carried = lanes * roofline.carry_bytes_per_lane(
        shape["nodes"], shape["pod_types"], n_pol, max(events_of),
        max(events_of))
    caches = [sp.meta.get("cache") for w in waves for sp in w["spans"]
              if sp.name == "init_tables"]
    say(f"init_tables in the window's {len(waves)} waves, by cache: "
        f"{ {c: caches.count(c) for c in sorted(set(caches), key=str)} }")
    say(f"device memory peaks {memory}; carried by the scan, from shapes "
        f"(K = {shape['pod_types']}): {carried} bytes over {lanes} lanes")

    walls = [w["wall_s"] for w in waves]
    share = statistics.median(w["rejected"] for w in waves) / wave_events
    say(f"wave walls {[round(x, 3) for x in walls]}; creates rejected a wave "
        f"{[w['rejected'] for w in waves]} of {wave_events} real "
        f"lane-events: share {share:.4f}")
    return {
        "correct": all(got <= limit for _, got, limit in checks),
        "attempted": len(waves),
        "failed": sum(1 for g in counter_gaps if g),
        "memory_peak_bytes": device.memory_peak_bytes(memory),
        "end_to_end": {
            "lane_events_per_s": wave_events * len(waves) / sum(walls),
            "wave_s": statistics.median(walls),
            "setup_s": setup_s,
        },
        "waves": [dict(wave.wave_account(w), rejected=w["rejected"])
                  for w in waves],
        **wave.window_account(walls, warm, t_inputs, t_sim, setup_s),
        "checks": checks,
        "spans_blocked": bool(ctx.trace),
        "shape": shape,
        "real_events": wave_events,
        "lane_events": lane_events,
        "final_gpu_alloc_pct": alloc,
        "traced": traced,
    }
