"""Config-axis sweeps + weight-operand engines (ISSUE 6).

The per-policy weight vector is a traced i32[num_pol] operand
(sim.step.resolve_weights) threaded through all four engines, and
driver.schedule_pods_sweep vmaps one compiled replay over a [B, num_pol]
weight matrix plus per-config seeds. These tests pin:

  1. cross-engine bit-identity under a NON-static weight operand —
     sequential / flat table / blocked table / shard_map all agree for
     every weight vector of a grid, including RandomScore's key split
     and minmax/pwr normalize mixes (the blocked summaries bt/br/bn are
     built in-scan FROM the operand, so this is the blocked-summary
     drift check under traced weights);
  2. sweep lanes == standalone runs with those weights baked into the
     config, per engine path (table, sequential) and per-lane seed;
  3. one jaxpr per job family: a weight change reuses the compiled
     engine (replayers differing only in weights share `replay.engine`,
     and a second sweep over a different grid adds no executable);
  4. the digest vocabulary: weights are a RUN input (the run digest
     moves when they move, so a checkpointed carry — whose blocked
     summaries embed the weights — can never be resumed under different
     weights) but NOT a table-cache input (one build serves every
     weight vector of the family);
  5. the openb acceptance (slow, `make resume-smoke` / `make
     sweep-smoke`): a B=16 sweep over the openb prefix runs under
     exactly one scan span with zero recompiles on a weight change,
     each sampled lane bit-identical to its standalone baked-weight
     run, and a bounded marginal per-config cost (strict 1/5 on
     accelerator backends; on CPU vmap only strips per-op dispatch
     overhead, so the honest bound is "cheaper than a standalone warm
     replay" — ENGINES.md Round 11 quantifies both).
"""

import io
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.fixtures import random_cluster, random_pods
from tests.test_table_engine import _events_with_deletes
from tpusim.io.trace import NodeRow, PodRow
from tpusim.policies import make_policy
from tpusim.sim.driver import (
    Simulator,
    SimulatorConfig,
    SweepLane,
    format_sweep_table,
    schedule_pods_sweep,
    tiebreak_rank,
)
from tpusim.sim.engine import make_replay
from tpusim.sim.step import resolve_weights
from tpusim.sim.table_engine import build_pod_types, make_table_replay
from tpusim.sim.typical import TypicalPodsConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# resolve_weights + input validation (no compiles)
# ---------------------------------------------------------------------------


def test_resolve_weights():
    policies = [(make_policy("FGDScore"), 1000),
                (make_policy("BestFitScore"), 500)]
    np.testing.assert_array_equal(
        np.asarray(resolve_weights(policies)), [1000, 500]
    )
    np.testing.assert_array_equal(
        np.asarray(resolve_weights(policies, [7, 8])), [7, 8]
    )
    assert resolve_weights(policies, [7, 8]).dtype == jnp.int32
    with pytest.raises(ValueError, match="does not match"):
        resolve_weights(policies, [1, 2, 3])


def _mk_cluster(rng, n=16):
    return [
        NodeRow(f"n{i:03d}", 32000, 131072, int(g), "V100M16" if g else "")
        for i, g in enumerate(rng.choice([0, 2, 4, 8], n))
    ]


def _mk_pods(rng, n=40):
    out = []
    for i in range(n):
        gpu = int(rng.choice([0, 1, 2]))
        milli = 1000 if gpu > 1 else int(rng.choice([0, 300, 500, 1000]))
        if gpu == 0:
            milli = 0
        out.append(
            PodRow(f"p{i:04d}", int(rng.choice([1000, 2000, 4000])), 2048,
                   gpu, milli)
        )
    return out


def _cfg(seed, policies=(("FGDScore", 1000),), gpu_sel="FGDScore", **kw):
    base = dict(
        policies=policies,
        gpu_sel_method=gpu_sel,
        seed=seed,
        report_per_event=False,
        typical_pods=TypicalPodsConfig(pod_popularity_threshold=95),
    )
    base.update(kw)
    return SimulatorConfig(**base)


def test_sweep_input_validation():
    rng = np.random.default_rng(3)
    nodes, pods = _mk_cluster(rng), _mk_pods(rng, 10)

    sim = Simulator(nodes, _cfg(42))
    sim.set_workload_pods(pods)
    with pytest.raises(ValueError, match=r"\[B, 1\] matrix"):
        sim.run_sweep([1000, 500])  # 1-D, not [B, P]
    with pytest.raises(ValueError, match=r"\[B, 1\] matrix"):
        sim.run_sweep([[1000, 500]])  # wrong policy count
    with pytest.raises(ValueError, match="at least one config"):
        sim.run_sweep(np.zeros((0, 1), np.int32))
    with pytest.raises(ValueError, match="seeds has 3"):
        sim.run_sweep([[1000], [900]], seeds=[1, 2, 3])

    sim = Simulator(nodes, _cfg(42, record_decisions=True))
    sim.set_workload_pods(pods)
    with pytest.raises(ValueError, match="decisions"):
        sim.run_sweep([[1000]])

    sim = Simulator(nodes, _cfg(42, series_every=4))
    sim.set_workload_pods(pods)
    with pytest.raises(ValueError, match="series"):
        sim.run_sweep([[1000]])


def test_digest_weight_vocabulary(tmp_path):
    """Weights are a RUN input (digest moves with them — checkpoint
    resume across a weight change is impossible) but NOT a table-build
    input (one cached table set serves every weight vector)."""
    from tpusim.io.trace import build_events, pods_to_specs

    rng = np.random.default_rng(4)
    nodes, pods = _mk_cluster(rng), _mk_pods(rng, 12)

    def digests(weights):
        sim = Simulator(
            nodes, _cfg(42, policies=(("FGDScore", weights),))
        )
        sim.set_workload_pods(pods)
        sim.set_typical_pods()
        trace = sim.prepare_pods()
        specs = pods_to_specs(trace, sim.node_index)
        ev_kind, ev_pod = build_events(trace)
        types = build_pod_types(specs)
        run = sim._run_digest(
            sim.init_state, specs, np.asarray(ev_kind), np.asarray(ev_pod),
            np.asarray(jax.random.PRNGKey(42)), np.asarray(sim.rank),
        )
        tbl = sim._tables_digest(sim.init_state, types)
        return run, tbl

    run_a, tbl_a = digests(1000)
    run_a2, tbl_a2 = digests(1000)
    run_b, tbl_b = digests(999)
    assert run_a == run_a2 and tbl_a == tbl_a2  # deterministic
    assert run_a != run_b  # weights joined the run-input vocabulary
    assert tbl_a == tbl_b  # ...but never the (weight-independent) build


def test_format_sweep_table():
    lane = SweepLane(
        weights=np.asarray([1000, 500], np.int32), seed=42,
        placed_node=np.asarray([0, 1, -1]), dev_mask=np.zeros((3, 8), bool),
        ever_failed=np.asarray([False, False, True]), counters=None,
        metrics=None, state=None, events=5, placed=2, failed=1,
        gpu_alloc_pct=12.5, frag_gpu_milli=321.0,
    )
    text = format_sweep_table([lane], [("FGDScore", 1000),
                                       ("BestFitScore", 500)])
    assert "weights(FGDScore,BestFitScore)" in text
    assert "1000,500" in text and "12.50" in text and "321" in text


# ---------------------------------------------------------------------------
# sweep lanes == standalone baked-weight runs (tier-1: one table family)
# ---------------------------------------------------------------------------


def _assert_lane_matches(lane, res, telemetry=None):
    from tpusim.obs.counters import INVARIANT_FIELDS, COUNTER_FIELDS

    np.testing.assert_array_equal(lane.placed_node, np.asarray(res.placed_node))
    np.testing.assert_array_equal(lane.dev_mask, np.asarray(res.dev_mask))
    assert lane.failed == len(res.unscheduled_pods)
    for a, b in zip(jax.tree.leaves(lane.state), jax.tree.leaves(res.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if telemetry is not None and telemetry.counters is not None:
        # engine-invariant counter vocabulary, both sides pad-corrected
        got = dict(zip(COUNTER_FIELDS, (int(c) for c in lane.counters)))
        assert all(
            got[f] == telemetry.counters[f] for f in INVARIANT_FIELDS
        ), (got, telemetry.counters)


@pytest.mark.slow  # three standalone oracles + the vmapped sweep
# (~10 s of compiles) — ISSUE 19 tier-1 buy-back, resume-smoke runs it
def test_sweep_matches_standalone_table():
    """Each lane of a table-engine config-axis sweep must equal the
    standalone run with that weight row baked into the config — same
    placements, device masks, final state, counters — including a
    zero-weight row and duplicated rows."""
    rng = np.random.default_rng(5)
    nodes, pods = _mk_cluster(rng), _mk_pods(rng)
    base = (("FGDScore", 1000), ("BestFitScore", 500))
    grid = [[1000, 500], [100, 2000], [0, 1000], [1000, 500]]

    # one standalone oracle per DISTINCT row (row 3 duplicates row 0 —
    # its lane is pinned against lane 0 below, so a fourth standalone
    # run would add wall without coverage; tier-1 trim, ISSUE 11)
    singles = []
    for w in grid[:3]:
        pol = (("FGDScore", w[0]), ("BestFitScore", w[1]))
        sim = Simulator(nodes, _cfg(42, pol))
        sim.set_workload_pods(pods)
        res = sim.run()
        singles.append((res, res.telemetry))
    singles.append(singles[0])

    # heartbeat_every set: the sweep must strip the in-scan heartbeat
    # (its cond has no batched form) and replay on the heartbeat-free
    # build of the same family — trajectories unchanged
    sim = Simulator(nodes, _cfg(42, base, heartbeat_every=10_000))
    sim.set_workload_pods(pods)
    lanes = sim.run_sweep(grid)
    assert len(lanes) == len(grid)
    assert "vmap sweep" in sim._last_engine
    for lane, (res, tel) in zip(lanes, singles):
        _assert_lane_matches(lane, res, tel)
    # duplicated rows give bit-identical lanes
    np.testing.assert_array_equal(lanes[0].placed_node, lanes[3].placed_node)

    # one jaxpr per family: replayers differing only in weights share one
    # underlying engine (the machinery the standalone runs above used)
    engines = {
        id(make_table_replay(
            [(make_policy("FGDScore"), wrow[0]),
             (make_policy("BestFitScore"), wrow[1])],
            gpu_sel="FGDScore",
        ).engine)
        for wrow in grid
    }
    assert len(engines) == 1


@pytest.mark.slow  # a full CLI sweep replay (~4 s) — ISSUE 19 tier-1
# buy-back, resume-smoke runs it
def test_apply_sweep_weights_cli(tmp_path):
    """`tpusim apply --sweep-weights weights.json` — the CLI face: loads
    a {"weights": ..., "seeds": ...} grid, replays it as one sweep, and
    prints the per-config summary table."""
    import json

    from tpusim.apply import Applier, ApplyOptions

    wfile = tmp_path / "weights.json"
    wfile.write_text(json.dumps(
        {"weights": [[1000], [500], [1]], "seeds": [42, 42, 42]}
    ))
    out = io.StringIO()
    applier = Applier(ApplyOptions(
        simon_config=os.path.join(REPO, "example/test-cluster-config.yaml"),
        default_scheduler_config=os.path.join(
            REPO, "example/test-scheduler-config.yaml"
        ),
        base_dir=REPO,
        sweep_weights=str(wfile),
    ))
    result = applier.run(out=out)
    text = out.getvalue()
    assert result is None  # sweep mode returns no single-run result
    assert "[Sweep] 3 configs" in text
    assert "weights(FGDScore)" in text
    # one row per config with its weight vector
    for w in ("1000", "500", "1"):
        assert any(
            line.split()[1] == w for line in text.splitlines()
            if line.strip() and line.split()[0].isdigit()
        ), (w, text)

    # the CLI main threads the flag through to ApplyOptions (regression:
    # a declared-but-unthreaded argparse flag would silently no-op into
    # a full standalone run)
    from tpusim.cli import main

    # `apply` is an entry point and places the process-wide compile cache
    # (tpusim.compile_cache); in-process, put back what the test found
    cache_knobs = ("jax_compilation_cache_dir",
                   "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in cache_knobs}
    try:
        rc = main([
            "apply",
            "-f", os.path.join(REPO, "example/test-cluster-config.yaml"),
            "-s", os.path.join(REPO, "example/test-scheduler-config.yaml"),
            "--base-dir", REPO,
            "--sweep-weights", str(wfile),
        ])
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
    assert rc == 0

    # a bare list-of-rows payload parses too, and an empty one is loud
    bare = tmp_path / "bare.json"
    bare.write_text("[]")
    applier = Applier(ApplyOptions(
        simon_config=os.path.join(REPO, "example/test-cluster-config.yaml"),
        default_scheduler_config=os.path.join(
            REPO, "example/test-scheduler-config.yaml"
        ),
        base_dir=REPO,
        sweep_weights=str(bare),
    ))
    with pytest.raises(ValueError, match="no weight rows"):
        applier.run(out=io.StringIO())


# ---------------------------------------------------------------------------
# cross-engine bit-identity under a non-static weight operand (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize(
    "mix,gpu_sel",
    [
        ([("FGDScore", 1000), ("BestFitScore", 500)], "FGDScore"),
        ([("PWRScore", 800), ("DotProductScore", 300)], "PWRScore"),
        ([("RandomScore", 1000)], "random"),
    ],
    ids=["fgd+bestfit", "pwr+dotprod", "random"],
)
def test_weight_operand_cross_engine(mix, gpu_sel):
    """sequential == flat table == blocked table (== shard_map where the
    config allows) for EVERY weight vector of a grid passed as a traced
    operand. The blocked lane is the weight-operand blocked-summary
    drift check: bt/br/bn are built in-scan from the operand, and the
    minmax/pwr stored-extrema rebuild path must stay exact under it."""
    from tpusim.parallel import make_mesh, pad_nodes, shard_state
    from tpusim.parallel.shard_engine import make_shardmap_table_replay

    rng = np.random.default_rng(11)
    state, tp = random_cluster(rng, num_nodes=21)
    pods = random_pods(rng, num_pods=48)
    ev_kind, ev_pod = _events_with_deletes(48, rng)
    types = build_pod_types(pods)
    policies = [(make_policy(n), w) for n, w in mix]
    key = jax.random.PRNGKey(7)
    rank = jnp.asarray(tiebreak_rank(21, seed=3))

    seq = make_replay(policies, gpu_sel=gpu_sel, report=False)
    flat = make_table_replay(policies, gpu_sel=gpu_sel)
    blocked = make_table_replay(policies, gpu_sel=gpu_sel, block_size=8)
    shard = None
    if gpu_sel != "random" and len(jax.devices()) >= 8:
        mesh = make_mesh(8)
        pstate, prank = pad_nodes(state, rank, 8)
        pstate = shard_state(pstate, mesh)
        shard = make_shardmap_table_replay(policies, mesh, gpu_sel=gpu_sel)

    grid = [[w for _, w in mix],  # the static row: operand == baked
            [1 for _ in mix],
            [3777 * (i + 1) for i in range(len(mix))]]
    for w in grid:
        r_seq = seq(state, pods, ev_kind, ev_pod, tp, key, rank, weights=w)
        r_flat = flat(
            state, pods, types, ev_kind, ev_pod, tp, key, rank, weights=w
        )
        r_blk = blocked(
            state, pods, types, ev_kind, ev_pod, tp, key, rank, weights=w
        )
        for r in (r_flat, r_blk):
            np.testing.assert_array_equal(
                np.asarray(r_seq.placed_node), np.asarray(r.placed_node)
            )
            np.testing.assert_array_equal(
                np.asarray(r_seq.dev_mask), np.asarray(r.dev_mask)
            )
            for a, b in zip(jax.tree.leaves(r_seq.state),
                            jax.tree.leaves(r.state)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if shard is not None:
            r_sh = shard(
                pstate, pods, types, ev_kind, ev_pod, tp, key, prank,
                weights=w,
            )
            np.testing.assert_array_equal(
                np.asarray(r_seq.placed_node), np.asarray(r_sh.placed_node)
            )
            np.testing.assert_array_equal(
                np.asarray(r_seq.dev_mask), np.asarray(r_sh.dev_mask)
            )
            n = state.num_nodes
            for a, b in zip(jax.tree.leaves(r_seq.state),
                            jax.tree.leaves(r_sh.state)):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)[:n]
                )


@pytest.mark.slow
def test_sweep_sequential_and_seeds():
    """The forced-sequential sweep path, plus per-lane SEEDS: a lane's
    seed drives its PRNG key and tie-break rank exactly like cfg.seed
    does standalone (shuffle off so all lanes share one workload)."""
    rng = np.random.default_rng(6)
    nodes, pods = _mk_cluster(rng), _mk_pods(rng, 24)
    grid = [[1000], [250]]
    seeds = [41, 43]

    singles = []
    for w, s in zip(grid, seeds):
        sim = Simulator(nodes, _cfg(
            s, policies=(("RandomScore", w[0]),), gpu_sel="random",
            engine="sequential", shuffle_pod=False,
        ))
        sim.set_workload_pods(pods)
        singles.append(sim.run())

    sim = Simulator(nodes, _cfg(
        42, policies=(("RandomScore", 1000),), gpu_sel="random",
        engine="sequential", shuffle_pod=False,
    ))
    sim.set_workload_pods(pods)
    lanes = sim.run_sweep(grid, seeds=seeds)
    assert "sequential" in sim._last_engine
    for lane, res in zip(lanes, singles):
        _assert_lane_matches(lane, res)


# ---------------------------------------------------------------------------
# openb acceptance: one compile, lane identity, bounded marginal (slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_openb_sweep_acceptance():
    """ISSUE 6 acceptance: a B=16 weight sweep over the openb prefix —
    ONE scan span (asserted via obs spans), a different weight grid
    reuses the compiled executable (zero recompiles), sampled lanes
    bit-identical to standalone baked-weight runs, and the marginal
    per-config cost bounded: ≤ 1/5 of a standalone warm replay on
    accelerator backends; on CPU (where vmap can only strip the per-op
    dispatch overhead — ENGINES.md Round 11) it must still beat the
    standalone warm replay outright."""
    from tpusim.io.trace import (
        build_events,
        load_node_csv,
        load_pod_csv,
        pods_to_specs,
    )
    nodes = load_node_csv(
        os.path.join(REPO, "data/csv/openb_node_list_gpu_node.csv")
    )
    pods = load_pod_csv(
        os.path.join(REPO, "data/csv/openb_pod_list_default.csv")
    )[:400]
    b = 16
    # a 2-policy mix: relative weights genuinely reshape placements (a
    # single positive weight only scales the argmax)
    base = (("FGDScore", 1000), ("BestFitScore", 500))
    grid = np.stack(
        [np.asarray([1000 - 37 * i, 100 + 60 * i], np.int32)
         for i in range(b)]
    )

    sim = Simulator(nodes, _cfg(42, base))
    sim.set_workload_pods(pods)
    lanes = sim.run_sweep(grid)
    assert len(lanes) == b

    # exactly one scan dispatch for all 16 configs
    scans = [s for s in sim.obs.spans if s.name == "scan"]
    assert len(scans) == 1, [s.name for s in sim.obs.spans]

    # a different weight grid must NOT add a compiled executable
    fn = sim._last_sweep_fn
    before = fn._cache_size()
    grid2 = np.stack(
        [np.asarray([500 + 11 * i, 900 - 23 * i], np.int32)
         for i in range(b)]
    )
    sim.run_sweep(grid2)
    assert sim._last_sweep_fn is fn and fn._cache_size() == before

    # sampled lanes are bit-identical to standalone baked-weight runs
    for i in (0, 7, 15):
        single = Simulator(nodes, _cfg(42, policies=(
            ("FGDScore", int(grid[i, 0])),
            ("BestFitScore", int(grid[i, 1])),
        )))
        single.set_workload_pods(pods)
        res = single.run()
        _assert_lane_matches(lanes[i], res, res.telemetry)

    # distinct weight rows genuinely diverge somewhere across the grid
    assert any(
        not np.array_equal(lanes[0].placed_node, ln.placed_node)
        for ln in lanes[1:]
    )

    # marginal per-config cost: warm B=16 vs warm B=1 slope against a
    # standalone warm replay
    trace = sim.prepare_pods()
    specs = pods_to_specs(trace)
    ev_kind, ev_pod = build_events(trace)
    ev_kind, ev_pod = jnp.asarray(ev_kind), jnp.asarray(ev_pod)
    key = jax.random.PRNGKey(42)

    def standalone():
        # bucket matches the sweep's default so both sides replay the
        # same padded event count
        res = sim.run_events(
            sim.init_state, specs, ev_kind, ev_pod, key, bucket=512
        )
        jax.block_until_ready(res.state)

    def warm(fn_, reps=3):
        fn_()
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn_()
            walls.append(time.perf_counter() - t0)
        return min(walls)

    sw = warm(standalone)
    w16 = warm(lambda: schedule_pods_sweep(sim, trace, grid))
    w1 = warm(lambda: schedule_pods_sweep(sim, trace, grid[:1]))
    marginal = max(w16 - w1, 0.0) / (b - 1)
    bound = 0.2 if jax.default_backend() != "cpu" else 1.0
    assert marginal <= bound * sw, (marginal, sw, jax.default_backend())
    # and the whole 16-config batch beats 16 standalone warm replays
    assert w16 < b * sw, (w16, sw)


@pytest.mark.slow  # tier-1 trim, ISSUE 16: rides resume-smoke
def test_sweep_multi_stream_donation(monkeypatch):
    """ISSUE 15 satellite: the multi-trace sweep's per-lane event-stream
    buffer is DONATED when nothing reads it after dispatch (the
    sweep/service lane runs report_per_event=False), finishing the PR 11
    donation story for the batched surfaces. Pins: (1) the donating
    wrapper is the one the dispatch resolves for report-off configs and
    carries the ev_pod argnum; (2) two waves of different tuned traces
    produce bit-identical lanes to fresh standalone runs AND add zero
    executables (the zero-recompile bookkeeping is donation-invariant —
    the (engine, in_axes, donated operands) cache key keeps one wrapper
    per family); (3) a report-ON config keeps the non-donating wrapper
    (the metrics postpass re-reads the streams)."""
    from tests.sweep_program import capture_sweep
    from tpusim.sim.driver import _sweep_engine

    rng = np.random.default_rng(29)
    nodes, pods = _mk_cluster(rng), _mk_pods(rng, 24)
    # engine="table" pins the table-form wrapper (the service lane's
    # path) regardless of the events-per-type heuristic
    sim = Simulator(nodes, _cfg(42, engine="table"))
    sim.set_workload_pods(pods)
    grid = np.asarray([[1000], [1000]], np.int32)

    # the one factory reads the wrapper off the operands: a per-lane
    # sweep's, stopped before it runs
    sim.set_typical_pods()
    lane_pods = [sim.prepare_pods(tuning_ratio=t) for t in (0.0, 0.3)]
    resolved, shapes, _ = capture_sweep(
        sim, None, grid, None, lane_pods=lane_pods)
    engine = sim._table_fn.engine.replay
    fn_don = _sweep_engine(engine, shapes)
    fn_plain = _sweep_engine(engine, shapes, keep_streams=True)
    assert fn_don is not fn_plain  # distinct wrappers, one cache each
    assert resolved is fn_don  # report off: the sweep asks for the donor
    # counts are read RELATIVE to this point — the wrappers are
    # process-global, so sibling tests may have compiled other shapes
    # into either one (the test_svc.py discipline)
    don0 = fn_don._cache_size()
    plain0 = fn_plain._cache_size()

    lanes1 = sim.run_sweep(grid, tunes=[0.0, 0.3])
    before = fn_don._cache_size()
    assert before == don0 + 1  # report-off dispatch resolved the donor
    assert fn_plain._cache_size() == plain0  # ...never the other
    lanes2 = sim.run_sweep(grid, tunes=[0.0, 0.3])
    assert fn_don._cache_size() == before  # second wave: zero recompiles
    for l1, l2 in zip(lanes1, lanes2):
        assert np.array_equal(l1.placed_node, l2.placed_node)

    # lane 0 (tune 0.0) == the plain standalone run
    single = Simulator(nodes, _cfg(42, engine="table"))
    single.set_workload_pods(pods)
    res = single.run()
    assert np.array_equal(
        lanes1[0].placed_node, res.placed_node[:len(lanes1[0].placed_node)]
    )

    # report-on config: the metrics postpass reads the streams after
    # dispatch, so the dispatch must resolve the NON-donating twin
    sim_r = Simulator(nodes, _cfg(42, engine="table", report_per_event=True))
    sim_r.set_workload_pods(pods)
    plain_before = fn_plain._cache_size()
    don_before = fn_don._cache_size()
    sim_r.run_sweep(grid, tunes=[0.0, 0.3])
    assert fn_plain._cache_size() == plain_before + 1
    assert fn_don._cache_size() == don_before
