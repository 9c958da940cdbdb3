"""The `clock_wave` driver: the recorded trace replayed by its OWN clock,
as one wide seed sweep, back to back.

Every lane replays the first `depth_events` events of the time-sorted
stream of `openb_pod_list_default.csv` (each pod a creation event and a
deletion event, stable-sorted by timestamp: simulator.go:672-717) on the
empty cluster; a lane is one tie-break seed. The window of the stream is
made here from the CSV rows: the pods created in it (the CSV's first rows),
those still alive at the cut handed over without a deletion time. It
stands only if three expansions agree: the program's `build_events` of the
window, the first `depth_events` events of its `build_events` of the whole
list, and the plain reference's own expansion of the timestamps
(`lib/reference_clock.event_stream`); and if it holds the `delete_events`
deletions the traffic file states. One wave is one call of
`schedule_pods_sweep(sim, window, weights[B, n_pol], seeds[B])` on a
Simulator with `use_timestamps`, timed from the call to the returned
[SweepLane]; the order of the process is `drivers/wave.py`'s, which
`lib/sweep_log.py` reads the log's tail by.

After the window, and in no metric: every lane of every wave is held to
the in-scan counter identities, with the deletes of the stream among them
(`delete_gap`), and the window may not compile (as in `wave.py`); one lane
of the last wave, drawn from `--seed`, is replayed whole on the sequential
oracle and compared bit for bit, and the same lane is walked beside the
plain numpy reference WITH deletions over all its events
(`lib/reference_follow_clock.py`): the lane's own record of its events
where the program's SweepLane carries one (`event_node`, `event_dev`),
else the oracle's record of the same (weights, seed), which the walk ties
to the lane's final arrays either way. What the reference replays it reads
itself (`lib/reference_inputs.py`: cluster, requests and timestamps from
the CSV files, the tie-break rank from the lane's seed; the typical pods by
`lib/reference_typical.py`): of the program it takes the lane alone.
"""

from __future__ import annotations

import dataclasses
import statistics
import time

import numpy as np

from benchmark.drivers import family_wave, wave
from benchmark.lib import (
    compare,
    device,
    inputs,
    reference_clock,
    reference_fgd,
    reference_follow_clock,
    reference_inputs,
    reference_typical,
    roofline,
    trace_reduce,
)


def stream_window(pods, depth: int, deletes: int, times=None):
    """(the window's pods, (kind, pod) of its events): the first `depth`
    events of the time-sorted stream of `pods`, as pods the program can be
    handed. Raises unless the program's expansion of the window, its
    expansion of the whole list and the reference's own agree, and the
    window holds `deletes` deletions. `times`: the (creation, deletion)
    times the reference expands, where it read them itself; the pods' own
    otherwise."""
    from tpusim.io.trace import build_events

    whole_kind, whole_pod = (a[:depth] for a in build_events(pods, True))
    created = whole_pod[whole_kind == reference_clock.EV_CREATE]
    gone = set(whole_pod[whole_kind == reference_clock.EV_DELETE].tolist())
    if not np.array_equal(created, np.arange(len(created))):
        raise ValueError("the window's creations are not the list's first "
                         f"{len(created)} rows in order")
    window = [p if i in gone else dataclasses.replace(p, deletion_time=0)
              for i, p in enumerate(pods[:len(created)])]
    kind, pod = build_events(window, True)
    if times is None:
        times = ([p.creation_time for p in pods],
                 [p.deletion_time for p in pods])
    ref_kind, ref_pod = (
        a[:depth] for a in reference_clock.event_stream(*times))
    found = int((np.asarray(kind) == reference_clock.EV_DELETE).sum())
    same = all(np.array_equal(a, b) for a, b in (
        (kind, whole_kind), (pod, whole_pod),
        (kind, ref_kind), (pod, ref_pod)))
    if len(kind) != depth or found != deletes or not same:
        raise ValueError(
            f"the window holds {len(kind)} events of which {found} are "
            f"deletions (the traffic file: {depth} / {deletes}); equal to "
            f"the whole stream's first {depth} and to the reference's: "
            f"{same}")
    return window, (np.asarray(kind), np.asarray(pod))


def delete_gap(lane, deleted) -> int:
    """The stream's deletes (`deleted`: the pod of each deletion event)
    against a lane's in-scan counters, as one absolute gap: the deletes
    counted are the stream's, and the pods placed at the end are the binds
    less the deletions of pods that WERE placed (a deletion of a rejected
    pod gives nothing back; `ever_failed` names them)."""
    _creates, binds, _fails, deletes, _skips = (
        int(v) for v in lane.counters[:5])
    never_placed = int(np.asarray(lane.ever_failed)[deleted].sum())
    placed = int((np.asarray(lane.placed_node) >= 0).sum())
    return (abs(deletes - len(deleted))
            + abs(binds - (deletes - never_placed) - placed))


def oracle_lane(nodes, pods, sim_cfg, seed, window, weights, lane_seed):
    """`wave.oracle_lane` for a Simulator with `use_timestamps`: the lane's
    (weights, seed) replayed standalone on the sequential oracle over the
    window's whole stream."""
    import jax
    import jax.numpy as jnp

    from tpusim.io.trace import build_events, pods_to_specs

    names = [name for name, _ in sim_cfg["policies"]]
    cfg = wave.simulator_config(
        sim_cfg, seed, profile=False, engine="sequential", seed=int(lane_seed),
        use_timestamps=True,
        policies=tuple(zip(names, (int(w) for w in weights))))
    sim = wave.build_simulator(nodes, pods, cfg)
    ev_kind, ev_pod = build_events(window, True)
    out = sim.run_events(
        sim.init_state, pods_to_specs(window, sim.node_index),
        jnp.asarray(ev_kind), jnp.asarray(ev_pod),
        jax.random.PRNGKey(cfg.seed), bucket=512)
    if "sequential" not in str(sim._last_engine):
        raise RuntimeError(f"the oracle ran on {sim._last_engine!r}")
    jax.block_until_ready(out.state)
    return out


def reference_walk(num_nodes, requests, events, lane, record, weight,
                   popularity):
    """One lane walked beside the plain reference with deletions over all
    its events. The reference's side is read from the CSV files and made
    from the lane's seed (`reference_inputs`, `reference_typical`):
    `requests` are the pod list's rows as it read them; the program gives
    the lane and its record."""
    from tpusim import constants

    ids = constants.GPU_MODEL_IDS
    typical = reference_typical.typical_pods(
        reference_typical.read_pod_keys(inputs.POD_CSV), ids,
        popularity=popularity)
    return reference_follow_clock.walk(
        reference_inputs.cluster(inputs.NODE_CSV, ids, num_nodes), requests,
        events, typical, reference_inputs.tiebreak_rank(num_nodes, lane.seed),
        lane, record, weight=weight)


def run(ctx) -> dict:
    from tpusim import constants
    from tpusim.compile_cache import enable_compile_cache
    from tpusim.sim import driver

    say = ctx.say
    traffic = wave.sized(ctx.traffic, ctx.rehearse)
    config = wave.sized(ctx.config, ctx.rehearse)
    sim_cfg = config["simulator"]
    lanes, depth = int(traffic["lanes"]), int(traffic["depth_events"])
    if not sim_cfg.get("use_timestamps"):
        raise ValueError("a clock wave replays a configuration with "
                         "use_timestamps")

    cache_dir = enable_compile_cache()
    compiles = wave.CompileCounter()
    t_mark = time.perf_counter()

    nodes, pods = inputs.build(config, ctx.seed, depth)
    n_deletes = int(traffic["delete_events"])
    requests = reference_inputs.pods(inputs.POD_CSV, constants.GPU_MODEL_IDS)
    window, events = stream_window(
        pods, depth, n_deletes,
        times=(requests["creation_time"], requests["deletion_time"]))
    requests = {k: v[:len(window)] for k, v in requests.items()}
    n_events = len(events[0])
    deleted = events[1][events[0] == reference_clock.EV_DELETE]
    t_inputs, t_mark = time.perf_counter() - t_mark, time.perf_counter()
    cfg = wave.simulator_config(sim_cfg, ctx.seed, profile=ctx.trace,
                                use_timestamps=True)
    sim = wave.build_simulator(nodes, pods, cfg)
    n_pol = len(cfg.policies)
    weights = np.tile(np.asarray([w for _, w in cfg.policies], np.int32),
                      (lanes, 1))
    t_sim, t_mark = time.perf_counter() - t_mark, time.perf_counter()

    def one_wave(index: int):
        seeds = wave.lane_seeds(ctx.seed, index, lanes)
        first_span = len(sim.obs.spans)
        t0 = time.perf_counter()
        out = driver.schedule_pods_sweep(sim, window, weights, seeds)
        t1 = time.perf_counter()
        return {"seeds": seeds, "t0": t0, "t1": t1,
                "wall_s": t1 - t0, "spans": sim.obs.spans[first_span:],
                "lanes": out}

    def counter_gap(w) -> int:
        """Worst counter identity over the wave's lanes, the stream's
        deletes among them; a lane missing or out of the order its
        (weights, seed) were given in counts too."""
        worst = abs(len(w["lanes"]) - lanes) + sum(
            1 for lane, seed in zip(w["lanes"], w["seeds"]) if lane.seed != seed)
        for lane in w["lanes"]:
            worst = max(
                [worst, delete_gap(lane, deleted)]
                + [d for _, d in compare.counter_differences(lane, n_events)])
        return worst

    warm = wave.warm_up(one_wave, traffic)
    setup_s = time.perf_counter() - ctx.t_start
    say(f"set-up {setup_s:.3f} s: inputs and the stream's window "
        f"{t_inputs:.3f}, simulator {t_sim:.3f}, warm waves {warm}; "
        f"{len(nodes)} nodes, {n_events} events = {n_events - n_deletes} "
        f"creations + {n_deletes} deletions over {len(window)} pods, {lanes} "
        f"lanes, engine {sim._last_engine}; cache {cache_dir}")

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
                                  sim.obs.epoch)
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
    checks = [("lanes in order and counter identities (the stream's "
               f"{n_deletes} deletes among them), worst of any wave",
               max(counter_gaps), 0),
              ("compiles inside the window", compiles.compiles, 0)]
    last = waves[-1]
    i = int(np.random.default_rng(ctx.seed).integers(lanes))
    lane = last["lanes"][i]
    t_oracle = time.perf_counter()
    want = oracle_lane(nodes, pods, sim_cfg, ctx.seed, window, weights[i],
                       last["seeds"][i])
    for what, differing in compare.lane_differences(lane, want):
        checks.append((f"lane {i} (seed {last['seeds'][i]}) vs sequential "
                       f"oracle: {what}", differing, 0))
    t_oracle, t_ref = time.perf_counter() - t_oracle, time.perf_counter()
    own = getattr(lane, "event_node", None) is not None
    record = ((lane.event_node, lane.event_dev) if own else
              (np.asarray(want.event_node)[:n_events],
               np.asarray(want.event_dev)[:n_events]))
    ref = reference_walk(
        len(nodes), requests, events, lane, record, int(weights[i][0]),
        int(sim_cfg["pod_popularity_threshold"]))
    whose = "its own" if own else "the oracle's"
    who = (f"lane {i}, {whose} record of its events, vs the numpy reference "
           f"with deletions")
    checks.append((f"{who}: events not held", n_events - ref["events_held"],
                   0))
    for what, differing in ref["differing"].items():
        checks.append((f"{who}: {what}", differing, 0))
    t_ref = time.perf_counter() - t_ref
    last.pop("lanes")
    for what, got, limit in checks:
        say(f"check: {what}: {got} (limit {limit})")
    say(f"reference: deletions held {ref['deletes_held']} of {n_deletes}, "
        f"near entries {ref['near_entries']} (within {reference_fgd.NEAR} "
        f"of an integer), events at which the lane's choice was another one "
        f"they admit {ref['admitted']}, events held {ref['events_held']}; "
        f"took {t_ref:.3f} s for one lane; the oracle took {t_oracle:.3f} s; "
        f"window {window_s:.3f} s, {len(waves)} waves; programs traced again "
        f"in the window and loaded from the persistent cache: "
        f"{compiles.cache_loads}")
    shape = {"nodes": len(nodes),
             "pod_types": family_wave.table_pod_types([window]),
             "policies": n_pol, "lanes": lanes, "events": n_events}
    carried = lanes * roofline.carry_bytes_per_lane(
        shape["nodes"], shape["pod_types"], n_pol, n_events, n_events)
    caches = [sp.meta.get("cache") for w in waves for sp in w["spans"]
              if sp.name == "init_tables"]
    say(f"init_tables in the window's {len(waves)} waves, by cache: "
        f"{ {c: caches.count(c) for c in sorted(set(caches), key=str)} }")
    say(f"device memory peaks {memory}; carried by the scan, from shapes "
        f"(K = {shape['pod_types']}): {carried} bytes over {lanes} lanes")

    walls = [w["wall_s"] for w in waves]
    say(f"wave walls {[round(x, 3) for x in walls]}")
    return {
        "correct": all(got <= limit for _, got, limit in checks),
        "attempted": len(waves),
        "failed": sum(1 for g in counter_gaps if g),
        "memory_peak_bytes": device.memory_peak_bytes(memory),
        "end_to_end": {
            "lane_events_per_s": n_events * lanes * len(waves) / sum(walls),
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
