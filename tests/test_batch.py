"""A seed group (driver.run_batch: per-sim prep and reporting around ONE
schedule_pods_sweep, a trace and a seed a lane) must give each seed exactly
what a standalone run gives: same placements, device
masks, final state, unscheduled lists, and reference-format log content
(metric float rows may differ in last-ulp reduce order, which the log's
fixed-precision formatting absorbs)."""

import numpy as np
import pytest

from tpusim.io.trace import NodeRow, PodRow
from tpusim.sim.driver import Simulator, SimulatorConfig, run_batch
from tpusim.sim.typical import TypicalPodsConfig


def _mk_cluster(rng):
    return [
        NodeRow(
            f"n{i:03d}", 32000, 131072, int(g), "V100M16" if g else ""
        )
        for i, g in enumerate(rng.choice([0, 2, 4, 8], 16))
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


def _cfg(seed, policies=(("FGDScore", 1000),), gpu_sel="FGDScore",
         report=True, shuffle=True):
    return SimulatorConfig(
        policies=policies,
        gpu_sel_method=gpu_sel,
        shuffle_pod=shuffle,
        tuning_ratio=1.2,
        tuning_seed=seed,
        seed=seed,
        report_per_event=report,
        typical_pods=TypicalPodsConfig(pod_popularity_threshold=95),
    )


@pytest.mark.parametrize(
    "policies,gpu_sel",
    [
        ((("FGDScore", 1000),), "FGDScore"),
        # tier-1 trim, ISSUE 16: these two ride resume-smoke
        pytest.param((("BestFitScore", 1000),), "best",
                     marks=pytest.mark.slow),
        pytest.param((("RandomScore", 1000),), "random",  # sequential path
                     marks=pytest.mark.slow),
    ],
    ids=["fgd", "bestfit", "random"],
)
def test_batch_matches_single_runs(policies, gpu_sel):
    rng = np.random.default_rng(5)
    nodes = _mk_cluster(rng)
    pods = _mk_pods(rng)
    seeds = [42, 43, 44]

    singles = []
    for s in seeds:
        sim = Simulator(nodes, _cfg(s, policies, gpu_sel))
        sim.set_workload_pods(pods)
        sim.run()
        sim.finish()
        singles.append((sim.last_result, sim.log.dump()))

    batch_sims = []
    for s in seeds:
        sim = Simulator(nodes, _cfg(s, policies, gpu_sel))
        sim.set_workload_pods(pods)
        batch_sims.append(sim)
    results = run_batch(batch_sims)
    for sim in batch_sims:
        sim.finish()

    for (single, slog), sim, res in zip(singles, batch_sims, results):
        assert np.array_equal(single.placed_node, res.placed_node)
        assert np.array_equal(single.dev_mask, res.dev_mask)
        for a, b in zip(single.state, res.state):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert len(single.unscheduled_pods) == len(res.unscheduled_pods)
        assert [u.pod.name for u in single.unscheduled_pods] == [
            u.pod.name for u in res.unscheduled_pods
        ]
        assert np.array_equal(single.creation_rank, res.creation_rank)
        # the reference-format logs must match line-for-line: fixed-precision
        # formatting absorbs last-ulp float differences from vmapped reduces
        assert slog == sim.log.dump()


def test_batch_rejects_mixed_configs():
    rng = np.random.default_rng(9)
    nodes = _mk_cluster(rng)
    pods = _mk_pods(rng, 12)
    a = Simulator(nodes, _cfg(42))
    b = Simulator(
        nodes, _cfg(43, policies=(("BestFitScore", 1000),), gpu_sel="best")
    )
    a.set_workload_pods(pods)
    b.set_workload_pods(pods)
    with pytest.raises(ValueError, match="same-config"):
        run_batch([a, b])


def test_batch_no_report_mode():
    rng = np.random.default_rng(11)
    nodes = _mk_cluster(rng)
    pods = _mk_pods(rng, 30)
    seeds = [7, 8]
    singles = []
    for s in seeds:
        sim = Simulator(nodes, _cfg(s, report=False))
        sim.set_workload_pods(pods)
        sim.run()
        singles.append(sim.last_result)
    sims = []
    for s in seeds:
        sim = Simulator(nodes, _cfg(s, report=False))
        sim.set_workload_pods(pods)
        sims.append(sim)
    results = run_batch(sims)
    for single, res in zip(singles, results):
        assert np.array_equal(single.placed_node, res.placed_node)


def _group(seeds, pods_n=30):
    rng = np.random.default_rng(13)
    nodes = _mk_cluster(rng)
    pods = _mk_pods(rng, pods_n)
    sims = []
    for s in seeds:
        sim = Simulator(nodes, _cfg(s, report=False))
        sim.set_workload_pods(pods)
        sims.append(sim)
    return sims


def test_a_seed_group_is_one_sweep():
    """The group runs on the sweep every cell measures: one call, so one
    SweepRecord, a lane and a trace a member; and what a member keeps is
    its own, not a view of the sweep's buffer or of a leaf the lanes
    share."""
    from tpusim.obs import sweep_log

    sims = _group([42, 43, 44])
    seen = {r.id for r in sweep_log()}
    results = run_batch(sims)
    new = [r for r in sweep_log() if r.id not in seen]
    assert len(new) == 1
    assert new[0].lanes == 3 and new[0].traces == 3
    # the sweep's own line stays out of every member's log, the lead's too
    for sim in sims:
        engine_lines = [l for l in sim.log.lines if "[Engine]" in l]
        assert len(engine_lines) == 1 and "replay of" in engine_lines[0]
    leaves = [
        a for r in results for a in (*r.state, r.placed_node, r.dev_mask)
    ]
    assert all(a.flags.owndata and a.flags.writeable for a in leaves)
    assert len({id(a) for a in leaves}) == len(leaves)


def test_batch_refuses_a_member_that_records():
    """ANY member that records decisions or the in-scan series is refused,
    not the lead alone: the sweep replays on the lead's engine and would
    hand the member None for its stream."""
    import dataclasses

    for knob in ({"record_decisions": True}, {"series_every": 4}):
        sims = _group([42, 43])
        sims[1].cfg = dataclasses.replace(sims[1].cfg, **knob)
        logged = list(sims[0].log.lines)
        with pytest.raises(ValueError, match="decisions|series"):
            run_batch(sims)
        assert sims[0].log.lines == logged  # refused before any prep
