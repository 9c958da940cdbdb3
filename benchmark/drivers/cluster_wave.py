"""The `cluster_wave` driver: the FGD artifact's headline protocol under one
of its BASELINE rows, GpuClustering with `best` devices, as ONE wide sweep,
back to back.

The traffic is `load_wave`'s (ten tuned, shuffled traces x tie-break seeds,
every lane ALL the creates of its trace on the empty cluster, the per-event
report on; `load_wave` has the words), and so is the order of the process.
What differs is the program the sweep compiles: GpuClustering's kernel READS
`NodeState.aff_cnt` at every event, so the flat body's commit keeps its add
into that leaf inside the event loop (`SweepRecord.affinity_deferred` 0),
where every FGD cell's program makes the leaf once a chunk. `load_wave` holds
its lane to FGD's scoring rule, so this kind has a `correct` of its own.

After the window, and in no metric: every lane of every wave is held to the
in-scan counter identities with its own event count and carries series of
its own length, the window may not compile, and every sweep record reads
what the traffic file's `record_must_read` and `record_must_read_too` say
(the second key holds what came later: an accepted tier-1 test pins the
first one's entries; fields a program does not have, as the parent of the
PR that brought them, are passed over); ONE lane of the last wave, drawn
from `--seed`, (i) equals the sequential oracle's whole replay of its (shuffle, seed) with the report on: placements, masks,
flags, every NodeState field and every integer series bit for bit, the
float series within `load_wave.FLOAT_LIMITS`; (ii) equals the plain numpy
reference's whole replay (`lib/reference_clustering.py`: its own Filter,
score, selectHost, `best` devices and affinity counts; integer throughout,
so ALL events are scored and every limit is 0): the lane's node is the
reference's choice at every create, the devices are the reference's, a
create is rejected exactly where the reference finds no feasible node, and
the final state equals, `aff_cnt` included; (iii) its series are held to
`lib/reference_report.py`, which recomputes the report from the reference's
own state, at every event where the arrived GPU load reaches a new whole
per cent of capacity and at the last. What the reference replays it reads
itself (`load_wave.reference_side`, `reference_follow_load.tuned_order`).
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmark.drivers import family_wave, load_wave, wave
from benchmark.lib import (
    compare,
    device,
    inputs,
    reference_clustering,
    reference_follow_load,
    reference_inputs,
    reference_report,
    roofline,
    trace_reduce,
)


def hold_lane(ref, rows, lane, seed: int, weight: int):
    """One lane held to the plain reference: its whole replay, and the
    reports at the load's crossings recomputed from the reference's own
    state. `ref` is `load_wave.reference_side`'s, `rows` the pod list's row
    of every event of the lane's trace. Returns (the reference's replay,
    {field: entries that differ}, the first event that differs or -1,
    [(what, got, limit)] of the series, the crossings)."""
    cluster, requests, _names, typical, energy = ref
    pods = {k: v[rows] for k, v in requests.items()}
    capacity = int(cluster["gpu_cnt"].sum()) * reference_clustering.MILLI
    crossings = reference_follow_load.load_crossings(
        pods["gpu_milli"], pods["gpu_num"], capacity)
    want = reference_clustering.replay(
        cluster, pods,
        reference_inputs.tiebreak_rank(len(cluster["cpu_cap"]), seed),
        weight, keep=crossings)
    differing, first = reference_clustering.lane_differences(lane, want)
    at = {e: reference_report.report(
        cluster, *want["states"][e], typical, energy, pods, np.arange(e + 1))
        for e in crossings}
    return (want, differing, first,
            load_wave.report_differences(lane.metrics, at), crossings)


def record_gaps(records, must_read: dict, say) -> list:
    """[(what, got, limit)]: for each field the traffic file pins, how many
    of the sweep records read another value. A field the program's record
    does not have is passed over, and said."""
    out = []
    for field, want in sorted(must_read.items()):
        if not all(hasattr(rec, field) for rec in records):
            say(f"the sweep record has no {field!r}: not held to {want}")
            continue
        read = sorted({getattr(rec, field) for rec in records})
        out.append((f"sweep records whose {field} is not {want} (read "
                    f"{read})",
                    sum(getattr(rec, field) != want for rec in records), 0))
    return out


def run(ctx) -> dict:
    from tpusim.compile_cache import enable_compile_cache
    from tpusim.io.trace import load_node_csv, load_pod_csv
    from tpusim.sim import driver

    say = ctx.say
    traffic = wave.sized(ctx.traffic, ctx.rehearse)
    config = wave.sized(ctx.config, ctx.rehearse)
    workload, sim_cfg = config["workload"], config["simulator"]
    if not sim_cfg.get("report_per_event"):
        raise ValueError("a cluster wave replays a configuration with "
                         "report_per_event")
    tuning_seeds = [int(s) for s in workload["tuning_seeds"]]
    per_shuffle = int(traffic["seeds_per_shuffle"])
    lane_of = load_wave.lane_grid(len(tuning_seeds), per_shuffle)
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
    ref = load_wave.reference_side(config, len(nodes))
    t_inputs, t_mark = time.perf_counter() - t_mark, time.perf_counter()
    cfg = wave.simulator_config(sim_cfg, tuning_seeds[0], profile=ctx.trace,
                                report_per_event=True)
    lead = wave.build_simulator(nodes, pods, cfg)
    traces = [lead.prepare_pods(tuning_seed=s) for s in tuning_seeds]
    # every trace WHOLE, and the one the reference's own shuffle and tuning
    # of the CSV's rows gives
    rows = [load_wave.trace_rows(t, ref[2]) for t in traces]
    capacity = int(ref[0]["gpu_cnt"].sum()) * reference_clustering.MILLI
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
                "record": lead.obs.sweeps[-1], "lanes": out}

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

    warm = wave.warm_up(one_wave, traffic)
    setup_s = time.perf_counter() - ctx.t_start
    say(f"set-up {setup_s:.3f} s: inputs and the reference's {t_inputs:.3f}, "
        f"simulator and {len(traces)} traces {t_sim:.3f}, warm waves "
        f"{warm}; {len(nodes)} nodes, events by shuffle {events_of} "
        f"({wave_events} real lane-events a wave), {lanes} lanes, policies "
        f"{list(cfg.policies)}, devices by {cfg.gpu_sel_method!r}, engine "
        f"{lead._last_engine}; cache {cache_dir}")

    # ---- the window
    waves, counter_gaps = [], []
    compiles.armed = True
    window_t0 = time.perf_counter()
    while True:
        w = one_wave(len(waves) + 1)
        counter_gaps.append(counter_gap(w))
        w["rejected"] = sum(int(lane.counters[2]) for lane in w["lanes"])
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
                f"{waited:.3f} s on the scan (the profiler's buffer holds "
                f"about 6 M device events and drops the rest)")

    # ---- correctness, outside every metric
    checks = [("lanes in order, counter identities with each lane's own "
               "events and series of its own length, worst of any wave",
               max(counter_gaps), 0),
              ("compiles inside the window", compiles.compiles, 0)]
    checks += record_gaps([w["record"] for w in waves],
                          {**traffic.get("record_must_read", {}),
                           **traffic.get("record_must_read_too", {})}, say)
    last = waves[-1]
    rng = np.random.default_rng(ctx.seed)
    i = int(rng.integers(lanes))
    lane, s = last["lanes"][i], lane_of[i]
    who = f"lane {i} (shuffle {tuning_seeds[s]}, seed {last['seeds'][i]})"
    t_oracle = time.perf_counter()
    want = load_wave.oracle_lane(nodes, pods, sim_cfg, tuning_seeds[s],
                                 traces[s], weights[i], last["seeds"][i])
    for what, differing in compare.lane_differences(lane, want):
        checks.append((f"{who} vs sequential oracle: {what}", differing, 0))
    want_series = type(want.metrics)(
        *(np.asarray(a)[:events_of[s]] for a in want.metrics))
    for what, got, limit in load_wave.series_differences(
            lane.metrics, want_series):
        checks.append((f"{who} vs sequential oracle, series {what}", got,
                       limit))
    t_oracle, t_ref = time.perf_counter() - t_oracle, time.perf_counter()
    replayed, differing, first, series, crossings = hold_lane(
        ref, rows[s], lane, last["seeds"][i], int(weights[i][0]))
    vs = f"{who} vs the numpy reference, all {events_of[s]} events scored"
    for what, count in differing.items():
        checks.append((f"{vs}: {what}", count, 0))
    for what, got, limit in series:
        checks.append((f"{vs}, report at {len(crossings)} events: {what}",
                       got, limit))
    t_ref = time.perf_counter() - t_ref
    alloc, lane_rejected = lane.gpu_alloc_pct, int(lane.counters[2])
    last.pop("lanes")
    for what, got, limit in checks:
        say(f"check: {what}: {got} (limit {limit})")
    by_class = replayed["aff_cnt"].sum(0).tolist()
    say(f"reference: creates rejected {int(replayed['ever_failed'].sum())} of "
        f"{events_of[s]} (no feasible node in the reference's state; the "
        f"lane counts {lane_rejected}), first event that differs {first} "
        f"(-1: none), pods placed by affinity class (share, 1..8 GPUs) "
        f"{by_class}; the lane's final GPU allocation {alloc:.3f} %; took "
        f"{t_ref:.3f} s for one lane; the oracle took {t_oracle:.3f} s; "
        f"window {window_s:.3f} s, {len(waves)} waves; programs traced again "
        f"in the window and loaded from the persistent cache: "
        f"{compiles.cache_loads}")
    shape = {"nodes": len(nodes),
             "pod_types": family_wave.table_pod_types(traces),
             "policies": n_pol, "lanes": lanes,
             # the MEAN real events a lane: the readers that multiply lanes
             # by events get the real count
             "events": wave_events / lanes}
    carried = lanes * roofline.carry_bytes_per_lane(
        shape["nodes"], shape["pod_types"], n_pol, max(events_of),
        max(events_of))
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
