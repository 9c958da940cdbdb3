"""The `wave` driver: wide vmapped sweeps, back to back.

One wave is one call of the product's `schedule_pods_sweep(sim, trace,
weights[B, n_pol], seeds[B])`, timed from the call to the returned
[SweepLane]: host prep, table build, the vmapped scan, the one packed
fetch and the per-lane slicing, which is what a caller of
`Simulator.run_sweep` waits for. Set-up builds the cluster and the trace
from the config and `--seed` and runs the traffic file's `warm_waves` (default
1) of the exact shapes, each while the one before it is still held, as the
window holds its last wave (`warm_up`); the window then runs waves with
fresh lane seeds until `--seconds` is up and lets the wave in flight
finish. After the window, and in no metric,
`check_lanes` lanes of the last wave are replayed standalone on the
sequential oracle and compared bit for bit; every lane of every wave is
held to the in-scan counter identities, and the window may not compile.
"""

from __future__ import annotations

import statistics
import tempfile
import time

import numpy as np

from benchmark.lib import compare, device, inputs, roofline, trace_reduce

LANE_SEED_MOD = 2**31 - 1  # jax.random.PRNGKey keeps 32 bits of a seed
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


STALL = 1.5  # a wave over this many median walls of its window is stalled


def lane_seeds(seed: int, wave: int, lanes: int) -> list[int]:
    """`seed*10^6 + wave*lanes + i`, folded into 31 bits: distinct within a
    wave and from wave to wave, and a pure function of `--seed`. The warm
    waves are 0, -1, ...; the window's 1, 2, ..."""
    base = seed * 10**6 + wave * lanes
    return [(base + i) % LANE_SEED_MOD for i in range(lanes)]


def sized(block: dict, rehearse: bool) -> dict:
    """The block's sizes, with its `tiny` entries on top in a rehearsal."""
    out = {k: v for k, v in block.items() if k != "tiny"}
    if rehearse:
        out.update(block.get("tiny", {}))
    return out


def simulator_config(sim_cfg: dict, tuning_seed: int, profile: bool, **over):
    from tpusim.sim.driver import SimulatorConfig
    from tpusim.sim.typical import TypicalPodsConfig

    fields = dict(
        policies=tuple((name, int(w)) for name, w in sim_cfg["policies"]),
        gpu_sel_method=sim_cfg["gpu_sel_method"],
        dim_ext_method=sim_cfg["dim_ext_method"],
        norm_method=sim_cfg["norm_method"],
        tuning_ratio=float(sim_cfg["tuning_ratio"]),
        tuning_seed=tuning_seed,
        seed=tuning_seed,
        shuffle_pod=bool(sim_cfg["shuffle_pod"]),
        report_per_event=False,
        engine=sim_cfg["engine"],
        profile=profile,
        typical_pods=TypicalPodsConfig(
            pod_popularity_threshold=int(sim_cfg["pod_popularity_threshold"])),
    )
    fields.update(over)
    return SimulatorConfig(**fields)


def build_simulator(nodes, pods, cfg):
    from tpusim.sim.driver import Simulator

    sim = Simulator(nodes, cfg)
    sim.set_workload_pods(pods)
    sim.set_typical_pods()
    return sim


def pod_type_count(trace) -> int:
    """Distinct resource shapes among the trace's pods: the K of the score
    tables, counted here and not read from the program."""
    return len({inputs.pod_shape(p) for p in trace})


class CompileCounter:
    """Programs that reached the backend while `armed`, counted through
    jax.monitoring: `requests` (each costs a trace and a lowering) and the
    `cache_loads` among them that the persistent cache served. The rest
    were compiled."""

    def __init__(self):
        import jax.monitoring as mon

        self.armed = False
        self.requests = 0
        self.cache_loads = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, _secs, **_kw):
        if self.armed and event == COMPILE_EVENT:
            self.requests += 1

    def _on_event(self, event, **_kw):
        if self.armed and event == CACHE_HIT_EVENT:
            self.cache_loads += 1

    @property
    def compiles(self) -> int:
        return self.requests - self.cache_loads


def wave_phases(spans, t0: float, t1: float, epoch: float) -> list:
    """Host phases of one wave as (name, start, end) relative to its
    start, from the program's spans (tpusim/obs/spans.py): what precedes
    the first span is host prep, what follows the fetch is lane slicing."""
    phases, at = [], 0.0
    for sp in spans:
        s = epoch + sp.start_s - t0
        e = s + sp.dispatch_s + sp.block_s
        between = ("host prep" if not phases else
                   f"after {phases[-1][0]}")
        if s > at:
            phases.append((between, at, s))
        phases.append((sp.name, s, e))
        at = e
    phases.append(("lane slicing" if phases else "host prep", at, t1 - t0))
    return phases


def span_seconds(spans, name: str, part: str) -> float:
    return sum(getattr(sp, part) for sp in spans if sp.name == name)


def warm_up(one_wave, traffic: dict) -> list[float]:
    """The traffic file's `warm_waves` (default 1) before the window; their
    walls. The first loads or compiles every program of the window. Each
    later one runs while the wave before it is still held, as a window's
    wave runs while the last one's lanes are kept: it finds the readback's
    landing block taken and pays for the second block's fresh pages (0.4 s
    at 2,560 lanes, PERF.md section 5), which a window behind ONE warm wave
    pays in its second wave; and what a compile in the first leaves behind
    in the process is out of the window. All of it is `setup_s`."""
    walls, held = [], None
    for k in range(int(traffic.get("warm_waves", 1))):
        held = one_wave(-k)  # the wave before stays alive until this returns
        walls.append(held["wall_s"])
    del held
    return walls


def stalled_waves(walls) -> int:
    """Waves of the window over STALL x its median wall. They are counted,
    printed and left IN every end-to-end metric: their lanes are correct."""
    mid = statistics.median(walls)
    return sum(1 for x in walls if x > STALL * mid)


POSTPASS_SPANS = ("frag_postpass", "event_metrics")


def wave_account(w: dict) -> dict:
    """What the layer metrics keep of one window wave: its wall and, from
    the program's spans, the scan's block, the whole fetch, the table
    build and the post-passes (frag amounts and watts; the per-event report
    where it is on)."""
    spans = w["spans"]

    def whole(*names):
        return sum(span_seconds(spans, n, "dispatch_s")
                   + span_seconds(spans, n, "block_s") for n in names)

    return {"wall_s": w["wall_s"],
            "scan_block_s": span_seconds(spans, "scan", "block_s"),
            "fetch_s": whole("fetch"),
            "table_build_s": whole("init_tables"),
            "postpass_s": whole(*POSTPASS_SPANS)}


def window_account(walls, warm_walls, t_inputs: float, t_sim: float,
                   setup_s: float) -> dict:
    """What every wave driver returns beside its metrics for the line's
    `window`: the stalled waves, the warm waves, and `setup_s` in the three
    parts the driver times with the rest (imports, the device, the compile
    cache's placing) before them."""
    return {"stalled_waves": stalled_waves(walls),
            "warm_waves": len(warm_walls),
            "setup_parts": {
                "inputs_s": t_inputs, "simulator_s": t_sim,
                "warm_waves_s": list(warm_walls),
                "before_s": setup_s - t_inputs - t_sim - sum(warm_walls)}}


def run(ctx) -> dict:
    from tpusim.compile_cache import enable_compile_cache
    from tpusim.sim import driver

    say = ctx.say
    traffic = sized(ctx.traffic, ctx.rehearse)
    config = sized(ctx.config, ctx.rehearse)
    lanes, depth = int(traffic["lanes"]), int(traffic["depth_events"])
    check_lanes = int(traffic["check_lanes"])

    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    t_mark = time.perf_counter()

    nodes, pods = inputs.build(config, ctx.seed, depth)
    t_inputs, t_mark = time.perf_counter() - t_mark, time.perf_counter()
    cfg = simulator_config(config["simulator"], ctx.seed, profile=ctx.trace)
    sim = build_simulator(nodes, pods, cfg)
    trace = sim.prepare_pods()[:depth]
    events = len(trace)
    if events != depth and not ctx.rehearse:
        raise ValueError(f"the trace holds {events} events, the traffic "
                         f"file asks for {depth}")
    n_pol = len(cfg.policies)
    weights = np.tile(np.asarray([w for _, w in cfg.policies], np.int32),
                      (lanes, 1))
    t_sim, t_mark = time.perf_counter() - t_mark, time.perf_counter()

    def wave(index: int):
        seeds = lane_seeds(ctx.seed, index, lanes)
        first_span = len(sim.obs.spans)
        t0 = time.perf_counter()
        out = driver.schedule_pods_sweep(sim, trace, weights, seeds)
        t1 = time.perf_counter()
        return {"seeds": seeds, "t0": t0, "t1": t1,
                "wall_s": t1 - t0, "spans": sim.obs.spans[first_span:],
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

    warm = warm_up(wave, traffic)
    setup_s = time.perf_counter() - ctx.t_start
    say(f"set-up {setup_s:.3f} s: inputs {t_inputs:.3f}, simulator and trace "
        f"{t_sim:.3f}, warm waves {warm}; {len(nodes)} nodes, {events} "
        f"events, {lanes} lanes, engine {sim._last_engine}; cache {cache_dir}")

    # ---- the window
    waves, counter_gaps = [], []
    compiles.armed = True
    window_t0 = time.perf_counter()
    while True:
        w = wave(len(waves) + 1)
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
        raw, tw = traced_wave(wave, len(waves) + 1)
        tw.pop("lanes")
        phases = wave_phases(tw["spans"], tw["t0"], tw["t1"], sim.obs.epoch)
        traced = trace_reduce.reduce_wave(raw, phases)
        del raw
        traced["wall_s"] = tw["wall_s"]
        # the host's spans say how long the device ran the scan; a trace
        # that shows much less lost its tail
        waited = sum(e - s for name, s, e in phases if name == "scan")
        if not ctx.rehearse and traced["scan_device_s"] < 0.9 * waited - 0.2:
            raise RuntimeError(
                f"the device trace is cut short: its longest program ran "
                f"{traced['scan_device_s']:.3f} s, the host waited "
                f"{waited:.3f} s on the scan (the profiler's buffer holds "
                f"about 6 M device events and drops the rest)")

    # ---- correctness, outside every metric
    checks = [("lanes in order and counter identities, worst of any wave",
               max(counter_gaps), 0),
              ("compiles inside the window", compiles.compiles, 0)]
    last = waves[-1]
    pick = np.random.default_rng(ctx.seed).choice(
        lanes, size=min(check_lanes, lanes), replace=False)
    t_oracle = time.perf_counter()
    for i in sorted(int(x) for x in pick):
        lane = last["lanes"][i]
        want = oracle_lane(nodes, pods, config["simulator"], ctx.seed, trace,
                           weights[i], last["seeds"][i])
        for what, differing in compare.lane_differences(lane, want):
            checks.append((f"lane {i} (seed {last['seeds'][i]}) vs sequential "
                           f"oracle: {what}", differing, 0))
    t_oracle = time.perf_counter() - t_oracle
    last.pop("lanes")
    for what, got, limit in checks:
        say(f"check: {what}: {got} (limit {limit})")
    say(f"oracle took {t_oracle:.3f} s for {len(pick)} lanes; window "
        f"{window_s:.3f} s, {len(waves)} waves; programs traced again in "
        f"the window and loaded from the persistent cache: "
        f"{compiles.cache_loads}")
    shape = {"nodes": len(nodes), "pod_types": pod_type_count(trace),
             "policies": n_pol, "lanes": lanes, "events": events}
    carried = lanes * roofline.carry_bytes_per_lane(
        shape["nodes"], shape["pod_types"], n_pol, len(pods), events)
    say(f"device memory peaks {memory}; carried by the scan, from shapes: "
        f"{carried} bytes over {lanes} lanes")

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
        "waves": [wave_account(w) for w in waves],
        **window_account(walls, warm, t_inputs, t_sim, setup_s),
        "checks": checks,
        "spans_blocked": bool(ctx.trace),
        "shape": shape,
        "traced": traced,
    }


def traced_wave(wave, index: int):
    """Run wave `index` under jax.profiler and return (the trace as
    trace_reduce.read_xplane gives it, the wave)."""
    import jax

    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        jax.profiler.start_trace(tdir)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WAVE_ANNOTATION):
                tw = wave(index)
        finally:
            jax.profiler.stop_trace()
        return trace_reduce.read_xplane(trace_reduce.find_xplane(tdir)), tw


def oracle_lane(nodes, pods, sim_cfg, seed, trace, weights, lane_seed):
    """One lane's (weights, seed) replayed standalone on the sequential
    oracle (tpusim/sim/engine.py), over the whole trace."""
    import jax
    import jax.numpy as jnp

    from tpusim.io.trace import build_events, pods_to_specs

    names = [name for name, _ in sim_cfg["policies"]]
    cfg = simulator_config(
        sim_cfg, seed, profile=False, engine="sequential", seed=int(lane_seed),
        policies=tuple(zip(names, (int(w) for w in weights))))
    sim = build_simulator(nodes, pods, cfg)
    specs = pods_to_specs(trace, sim.node_index)
    ev_kind, ev_pod = build_events(trace)
    out = sim.run_events(sim.init_state, specs, jnp.asarray(ev_kind),
                         jnp.asarray(ev_pod), jax.random.PRNGKey(cfg.seed),
                         bucket=512)
    if "sequential" not in str(sim._last_engine):
        raise RuntimeError(f"the oracle ran on {sim._last_engine!r}")
    jax.block_until_ready(out.state)
    return out
