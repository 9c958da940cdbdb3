"""The fork's PWR+FGD mixes as lanes of one sweep (ISSUE 34): a weight row
a lane, a second raw-score table, PWR's per-event NormalizeScore and the
weighted total in the grouped flat body; every sweep hands back the watts
of each lane's final cluster; and `tpusim/ref/mix_numpy.py`, the plain
numpy reference of such a lane (no kernel, op, normalizer or engine of the
program), alone and against the sweep."""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import wave
from benchmark.lib import compare, inputs
from tests import sweep_program
from tests.test_reference_fgd import held_to_the_reference, reference_inputs
from tests.test_sweep_compile import _fault_specs
from tpusim import constants
from tpusim.io.trace import load_node_csv, load_pod_csv
from tpusim.obs.spans import sweep_log
from tpusim.ref import mix_numpy
from tpusim.sim.driver import format_sweep_table, schedule_pods_sweep
from tpusim.sim.engine import power_rows

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIM = {
    "policies": [["PWRScore", 500], ["FGDScore", 500]],
    "gpu_sel_method": "FGDScore", "dim_ext_method": "share",
    "norm_method": "max", "tuning_ratio": 1.3, "shuffle_pod": True,
    "pod_popularity_threshold": 95, "engine": "table",
}
ROWS = ([500, 500], [100, 900], [50, 950])  # the fork's methods 08, 11, 12
SHUFFLES, PER_SHUFFLE, NODES, DEPTH = (42, 43), 11, 96, 64
# open-gpu-share/utils/const.go:48-121 as the program's constants hold it:
# data to the reference
ENERGY = {"gpu_idle_w": constants.GPU_IDLE_W, "gpu_full_w": constants.GPU_FULL_W,
          "cpu_idle_w": constants.CPU_IDLE_W, "cpu_full_w": constants.CPU_FULL_W,
          "cpu_ncores": constants.CPU_NCORES}


def reference_watts(state):
    """The reference's energy model over a (host) NodeState, summed."""
    return mix_numpy.cluster_power(*(
        np.asarray(getattr(state, f), np.int64) for f in (
            "cpu_left", "cpu_cap", "gpu_left", "gpu_cnt", "gpu_type",
            "cpu_type")), ENERGY)


def assert_watts(lanes):
    """Every lane's two watts equal the program's energy model and the
    reference's over the lane's own final state: exactly, the tables hold
    whole watts and the sums stay under 2^24."""
    for lane in lanes:
        got = (lane.power_cpu_w, lane.power_gpu_w)
        rows = power_rows(jax.tree.map(jnp.asarray, lane.state))
        assert got == tuple(float(np.asarray(r).sum()) for r in rows)
        assert got == reference_watts(lane.state)
        assert got[0] > 0 and got[1] > 0


class MixWave:
    """3 weight rows x 2 shuffles x 11 seeds = 66 lanes of 64 events on a
    96-node cut of openb: from 64 lanes the grouped flat body."""

    def __init__(self):
        self.nodes = load_node_csv(inputs.NODE_CSV)[:NODES]
        self.pods = load_pod_csv(inputs.POD_CSV)
        cfg = wave.simulator_config(SIM, SHUFFLES[0], profile=False)
        self.sim = wave.build_simulator(self.nodes, self.pods, cfg)
        self.traces = [self.sim.prepare_pods(tuning_seed=s)[:DEPTH]
                       for s in SHUFFLES]
        self.lane_of = [(r, s) for r in range(len(ROWS))
                        for s in range(len(SHUFFLES))
                        for _ in range(PER_SHUFFLE)]
        self.weights = np.asarray([ROWS[r] for r, _ in self.lane_of], np.int32)
        self.lane_pods = [self.traces[s] for _, s in self.lane_of]
        self.seeds = [100 + i for i in range(len(self.lane_of))]
        self.lanes = schedule_pods_sweep(
            self.sim, None, self.weights, self.seeds, lane_pods=self.lane_pods)
        self.rec = sweep_log()[-1]


@pytest.fixture(scope="module")
def mix():
    return MixWave()


def test_the_mix_wave_runs_the_grouped_body_under_three_weight_rows(mix):
    rec = mix.rec
    assert (rec.lanes, rec.events, rec.traces) == (66, DEPTH, 2)
    assert (rec.weight_rows, rec.normalized_policies) == (3, 1)
    # 31 dense sites where ISSUE 34 counted 32: neither PWR nor FGD reads
    # aff_cnt, so the commit's add into it left the event loop (ISSUE 42)
    assert (rec.table_pass_events, rec.dense_accesses) == (16, 31)
    assert rec.affinity_deferred == rec.to_dict()["affinity_deferred"] == 1
    assert rec.to_dict()["weight_rows"] == 3
    assert rec.to_dict()["normalized_policies"] == 1
    # PWR has no whole_split: it tries Sub once a whole-branch pod TYPE, so
    # a column holds K_whole hypotheticals though FGD's go by request
    assert rec.sub_requests == rec.to_dict()["sub_requests"] > 8
    assert rec.sub_requests % 16 == 0  # the padded whole group
    for lane, row in zip(mix.lanes, mix.weights):
        np.testing.assert_array_equal(lane.weights, row)
    # the rows matter: lanes of one (shuffle, seed offset) differ by row
    assert any((a.placed_node != b.placed_node).any() for a, b in
               zip(mix.lanes[:22], mix.lanes[22:44]))


def test_every_mix_lane_equals_the_sequential_oracle_under_its_row(mix):
    for i, (lane, (r, s)) in enumerate(zip(mix.lanes, mix.lane_of)):
        want = wave.oracle_lane(mix.nodes, mix.pods, SIM, SHUFFLES[s],
                                mix.lane_pods[i], mix.weights[i], mix.seeds[i])
        assert not [d for d in compare.lane_differences(lane, want) if d[1]], (
            i, ROWS[r])
        assert not any(d for _, d in compare.counter_differences(lane, DEPTH))
    # and not the next row's: the oracle under another row is another replay
    other = wave.oracle_lane(mix.nodes, mix.pods, SIM, SHUFFLES[0],
                             mix.lane_pods[0], mix.weights[22], mix.seeds[0])
    assert any(d for _, d in compare.lane_differences(mix.lanes[0], other))


@pytest.mark.parametrize("row", range(len(ROWS)))
def test_a_lane_of_each_weight_row_equals_the_numpy_reference(mix, row):
    i = row * len(SHUFFLES) * PER_SHUFFLE + 3 * row + 1
    assert mix.lane_of[i][0] == row
    cluster, pods, typical, rank = reference_inputs(
        mix.sim, mix.lane_pods[i], mix.seeds[i])
    cluster["cpu_type"] = np.asarray(mix.sim.init_state.cpu_type)
    ref = mix_numpy.replay(cluster, pods, typical, rank, ROWS[row], ENERGY)
    print(f"row {ROWS[row]}: {ref['near_entries']} raw scores within "
          f"{mix_numpy.NEAR} of an integer, first event one could decide: "
          f"{ref['first_undecided']}")
    lane = mix.lanes[i]
    upto = held_to_the_reference(lane, ref, f"lane {i}, row {ROWS[row]}")
    assert upto >= DEPTH // 2
    if ref["first_undecided"] < 0:
        assert (lane.power_cpu_w, lane.power_gpu_w) == (
            ref["power_cpu_w"], ref["power_gpu_w"])


def test_the_grouped_sweeps_watts_are_the_energy_model_over_the_final_state(
        mix):
    assert_watts(mix.lanes)
    text = format_sweep_table(mix.lanes[:2], SIM["policies"])
    assert "power_w" in text
    assert f"{mix.lanes[0].power_cpu_w + mix.lanes[0].power_gpu_w:.0f}" in text


FGD = dict(SIM, policies=[["FGDScore", 1000]])


def _plain_sweep():
    """Three seed lanes of one shared trace under FGD: the plain body."""
    nodes = load_node_csv(inputs.NODE_CSV)[:NODES]
    sim = wave.build_simulator(
        nodes, load_pod_csv(inputs.POD_CSV),
        wave.simulator_config(FGD, 42, profile=False))
    trace = sim.prepare_pods()[:DEPTH]
    return schedule_pods_sweep(sim, trace, [[1000]] * 3, [7, 8, 9])


def _fault_sweep():
    sim, trace, cfg = sweep_program.cell_simulator(96, DEPTH, config="openb")
    return schedule_pods_sweep(
        sim, trace, sweep_program.cell_weights(cfg, 4), [1, 2, 3, 4],
        fault_specs=_fault_specs(4))


def _blocked_sweep():
    sim, trace, cfg = sweep_program.cell_simulator(8192, 16)
    return schedule_pods_sweep(
        sim, trace, sweep_program.cell_weights(cfg, 2), [5, 6])


@pytest.mark.parametrize("sweep, blocked", [
    (_plain_sweep, False), (_fault_sweep, False), (_blocked_sweep, True)],
    ids=["plain", "fault plans", "blocked"])
def test_every_sweeps_lanes_carry_their_final_watts(sweep, blocked):
    lanes = sweep()
    rec = sweep_log()[-1]
    assert (rec.table_pass_events == 0) == blocked
    # an FGD seed sweep: one weight row, no normalizer in the scan, Sub's
    # hypotheticals by request
    assert (rec.weight_rows, rec.normalized_policies) == (1, 0)
    assert rec.sub_requests == 8
    assert_watts(lanes)
    if any(lane.disruption is not None for lane in lanes):
        assert any(lane.disruption.evicted_pods for lane in lanes)


# ------------------------------------------------------ the reference alone


def test_pwr_normalize_pins_equal_rows_to_100_and_scales_the_rest():
    """pwr_score.go:104-139: integer min-max over the feasible nodes; an
    all-equal row, and so a single feasible node, reads 100 (the
    framework's default min-max would read 0)."""
    assert mix_numpy.pwr_normalize([-60, -60, -60]).tolist() == [100] * 3
    assert mix_numpy.pwr_normalize([-225]).tolist() == [100]
    assert mix_numpy.pwr_normalize([0]).tolist() == [100]
    # lo -105, hi 0: (s + 105) * 100 // 105
    assert mix_numpy.pwr_normalize([-105, 0, -60, -1]).tolist() == [
        0, 100, 42, 99]


def test_pwr_raw_scores_of_hand_worked_nodes_of_two_gpu_models():
    """Node A: two idle T4 (10 W idle, 70 W full), 32 vCPU = 16 cores = one
    package of the default CPU row (15 / 120 W), all idle: 15 + 20 = 35 W.
    Node B: one V100M16 (30 / 300 W) with 400 milli left, 64 vCPU = 32
    cores = two packages, 20 vCPU left: 10 idle cores, 22 working, both
    packages active: 240 + 300 = 540 W."""
    cpu_cap = np.asarray([32000, 64000])
    cpu_left = np.asarray([32000, 20000])
    gpu_left = np.asarray([[1000, 1000, 0, 0, 0, 0, 0, 0],
                           [400, 0, 0, 0, 0, 0, 0, 0]])
    gpu_cnt = np.asarray([2, 1])
    gpu_type = np.asarray([constants.GPU_MODEL_IDS["T4"],
                           constants.GPU_MODEL_IDS["V100M16"]])
    cpu_type = np.asarray([0, 0])
    node = (cpu_left, cpu_cap, gpu_left, gpu_cnt, gpu_type, cpu_type)
    cpu_w, gpu_w = mix_numpy.node_power(*node, ENERGY)
    assert (cpu_w.tolist(), gpu_w.tolist()) == ([15, 240], [20, 300])
    # a share pod of 300 milli and 4 vCPU. A: two cores start working, the
    # package goes to 120 W, one T4 to 70 W: 35 - 200 = -165 on either
    # device. B: 16 vCPU left, 24 cores working, still two packages, the
    # GPU was busy already: 0
    raw, near = mix_numpy.pwr_scores(*node, (4000, 1024, 300, 1, 0), ENERGY)
    assert raw.tolist() == [-165, 0] and not near.any()
    # 500 milli fits no device of B: Go's math.MinInt64 stays
    raw, _ = mix_numpy.pwr_scores(*node, (4000, 1024, 500, 1, 0), ENERGY)
    assert raw.tolist() == [-165, mix_numpy.MIN_INT64]
    # two whole GPUs and 8 vCPU by Sub. A: both T4 busy, one package:
    # 35 - (120 + 140) = -225. B (not feasible; Sub takes what fits,
    # nothing): 14 vCPU... 12,000 milli left, 6 idle cores, 26 working,
    # two packages: 0
    raw, _ = mix_numpy.pwr_scores(*node, (8000, 1024, 1000, 2, 0), ENERGY)
    assert raw.tolist() == [-225, 0]
    # a CPU-only pod of 20 vCPU on B frees nothing and fills nothing new
    raw, _ = mix_numpy.pwr_scores(*node, (20000, 1024, 0, 0, 0), ENERGY)
    assert raw.tolist() == [-105, 0]
    # the weighted total: PWR normalized over the two, FGD's score as is
    tp = tuple(np.asarray(a) for a in (
        [4000], [300], [1], [0])) + (np.asarray([1.0]),)
    total, device, _, _ = mix_numpy.score_candidates(
        node, np.asarray([0, 1]), (4000, 1024, 300, 1, 0), tp, (100, 900),
        ENERGY)
    fgd_score, fgd_device, _ = mix_numpy.fgd.score_nodes(
        cpu_left, gpu_left, gpu_type, (4000, 1024, 300, 1, 0), tp)
    assert total.tolist() == (100 * np.asarray([0, 100])
                              + 900 * fgd_score).tolist()
    assert device.tolist() == fgd_device.tolist()


def test_the_program_s_energy_model_counts_whole_cores_under_jit():
    """What the reference found: `ceil(cap / 1000 / 2)` in f32 compiles to
    `cap * 0.0005f`, 48.000004 for 96,000 milli, so a 96-vCPU node (587 of
    openb's 1,213) counted 49 cores and a fourth package under jit: 165 W
    empty where the reference, and the eager program, have 45."""
    from tpusim.ops.energy import cpu_power_watts

    cap = jnp.asarray([96000, 96000, 104000, 82000, 8000], jnp.int32)
    left = jnp.asarray([96000, 63000, 104000, 1000, 0], jnp.int32)
    typ = jnp.zeros(5, jnp.int32)
    want, _ = mix_numpy.node_power(
        np.asarray(left), np.asarray(cap), np.zeros((5, 8), np.int64),
        np.zeros(5, np.int64), np.full(5, -1), np.zeros(5, np.int64), ENERGY)
    assert want.tolist() == [45, 255, 60, 360, 120]
    for fn in (jax.vmap(cpu_power_watts), jax.jit(jax.vmap(cpu_power_watts))):
        assert np.asarray(fn(left, cap, typ)).tolist() == want.tolist()


def test_the_mix_reference_imports_nothing_of_the_program():
    """numpy and the FGD reference only; and the benchmark's copy is this
    file but for the line that names ITS copy of the FGD reference."""
    path = os.path.join(REPO, "tpusim", "ref", "mix_numpy.py")
    with open(path) as f:
        text = f.read()
    imported = set()
    for node in ast.walk(ast.parse(text)):
        if isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.update(f"{node.module}.{a.name}" for a in node.names)
    assert imported == {"__future__.annotations", "numpy",
                        "tpusim.ref.fgd_numpy"}, imported
    with open(os.path.join(REPO, "benchmark", "lib",
                           "reference_mix.py")) as f:
        copy = f.read()
    assert copy == text.replace("from tpusim.ref import fgd_numpy as fgd",
                                "from benchmark.lib import reference_fgd as fgd")
