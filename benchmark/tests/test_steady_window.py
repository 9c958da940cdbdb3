"""CPU checks of what PR 48 put under the window: warm-up that ends warm
(`drivers/wave.warm_up`), the count of stalled waves, the waves' accounts
behind `host_s`, the first warm wave's record behind
`warm_wave_dispatch_s`, a roofline that reads the stream's deletions, and
the identity tests of every cell held against a BENCHMARK.json that a
later PR has appended to.

Run: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import copy
import importlib
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import run as bench_run  # noqa: E402  (benchmark/run.py)
from benchmark.drivers import wave  # noqa: E402
from benchmark.lib import roofline, sweep_log  # noqa: E402
from tpusim.obs import spans  # noqa: E402

CELL_TESTS = ["test_openb_cell", "test_family_cell", "test_mix_cell",
              "test_clock_cell", "test_load_cell"]
SHORT_MIXES = ["fgd-seeds-40", "fgd-seeds-2560", "family-seeds-600",
               "mix-seeds-1200", "clock-seeds"]
LONG_MIXES = ["report-seeds-320", "cluster-report-seeds"]


# ------------------------------------------------------------ the warm-up
class Lanes:
    pass


class Waves:
    """A fake `one_wave`: records the index asked for and which earlier
    waves' lanes were still alive when it was called."""

    def __init__(self):
        self.calls, self.alive = [], []
        self._lanes = {}

    def __call__(self, index):
        import weakref

        self.alive.append(sorted(
            i for i, ref in self._lanes.items() if ref() is not None))
        self.calls.append(index)
        lanes = Lanes()
        self._lanes[index] = weakref.ref(lanes)
        return {"wall_s": 1.0 + 0.1 * len(self.calls), "lanes": lanes}


@pytest.mark.parametrize("traffic, indices, alive", [
    ({}, [0], [[]]),
    ({"warm_waves": 1}, [0], [[]]),
    # the second runs while the first is held, as a window's wave does
    ({"warm_waves": 2}, [0, -1], [[], [0]]),
    ({"warm_waves": 3}, [0, -1, -2], [[], [0], [-1]]),
])
def test_each_warm_wave_runs_while_the_one_before_is_held(
        traffic, indices, alive):
    one_wave = Waves()
    walls = wave.warm_up(one_wave, traffic)
    assert one_wave.calls == indices and one_wave.alive == alive
    assert walls == pytest.approx([1.1, 1.2, 1.3][:len(indices)])
    # and nothing of the warm-up is held when the window opens
    assert one_wave(1) and one_wave.alive[-1] == []


@pytest.mark.parametrize("mix", SHORT_MIXES + LONG_MIXES)
def test_the_short_cells_warm_twice_and_the_long_ones_once(mix):
    traffic = bench_run.load_json(os.path.join(BENCH, "traffic", f"{mix}.json"))
    assert traffic.get("warm_waves", 1) == (2 if mix in SHORT_MIXES else 1)
    if mix in SHORT_MIXES:
        assert "setup_s holds one wave more" in traffic["warm_waves_why"]
        # a rehearsal keeps one: tier-1 tests read the log behind one
        assert traffic["tiny"]["warm_waves"] == 1


def test_warm_lane_seeds_are_not_the_windows():
    lanes = 2560
    seen = [set(wave.lane_seeds(2480000001, k, lanes)) for k in (-1, 0, 1, 2)]
    assert all(len(s) == lanes for s in seen)
    assert not set.intersection(*seen) and len(set.union(*seen)) == 4 * lanes
    # the window's seeds are what they were behind one warm wave
    assert wave.lane_seeds(7, 1, 3) == [7000003, 7000004, 7000005]


# ------------------------------------------------- stalled waves, accounts
@pytest.mark.parametrize("walls, stalled", [
    ([1.0, 1.0, 1.0], 0),
    ([1.0, 1.49, 1.0, 1.0], 0),
    ([1.0, 1.51, 1.0, 1.0], 1),
    ([1.39, 6.25, 1.40, 3.61, 1.38, 1.39], 2),
    ([2.0], 0),
])
def test_a_stalled_wave_is_over_one_and_a_half_medians(walls, stalled):
    assert wave.stalled_waves(walls) == stalled
    metric = bench_run.load_module("layer_metrics", "stalled_waves")
    assert metric.read({"waves": [{"wall_s": w} for w in walls]}) == stalled


def test_a_run_without_waves_has_no_stalled_waves_to_read():
    metric = bench_run.load_module("layer_metrics", "stalled_waves")
    assert metric.read({}) is None and metric.read({"waves": []}) is None


def _span(name, dispatch_s, block_s):
    return types.SimpleNamespace(name=name, dispatch_s=dispatch_s,
                                 block_s=block_s)


def _wave(wall_s=3.0, report=False):
    sp = [_span("specs", 0.05, 0.0), _span("lane_keys", 0.01, 0.0),
          _span("init_tables", 0.02, 0.1), _span("scan", 0.03, 1.5),
          _span("frag_postpass", 0.1, 0.06), _span("fetch", 0.4, 0.0),
          _span("slice_lanes", 0.04, 0.0)]
    if report:
        sp.insert(5, _span("event_metrics", 0.02, 0.9))
    return {"wall_s": wall_s, "spans": sp}


@pytest.mark.parametrize("report, postpass", [(False, 0.16), (True, 1.08)])
def test_host_s_is_less_the_table_build_and_the_post_passes(report, postpass):
    account = wave.wave_account(_wave(report=report))
    assert account == pytest.approx({
        "wall_s": 3.0, "scan_block_s": 1.5, "fetch_s": 0.4,
        "table_build_s": 0.12, "postpass_s": postpass})
    host_s = bench_run.load_module("layer_metrics", "host_s")
    run = {"spans_blocked": True, "waves": [account] * 3}
    assert host_s.read(run) == pytest.approx(3.0 - 1.5 - 0.4 - 0.12 - postpass)
    # a driver kind whose waves carry the three older numbers alone
    old = {k: account[k] for k in ("wall_s", "scan_block_s", "fetch_s")}
    assert host_s.read(dict(run, waves=[old])) == pytest.approx(1.1)
    assert host_s.read(dict(run, spans_blocked=False)) is None


def test_setup_parts_sum_to_setup_s():
    got = wave.window_account([1.0, 1.6, 1.0], [9.0, 1.4], 2.0, 3.5, 22.0)
    assert got == {"stalled_waves": 1, "warm_waves": 2, "setup_parts": {
        "inputs_s": 2.0, "simulator_s": 3.5, "warm_waves_s": [9.0, 1.4],
        "before_s": 6.1}}


# ---------------------------------------- the records behind two warm waves
def _record(i, wall_s):
    unit = 0.001 * (i + 1)
    names = ["specs", "lane_keys", "lane_ranks", "init_tables", "scan",
             "frag_postpass", "fetch", "slice_lanes"]
    return spans.SweepRecord(
        id=100 + i, start_s=10.0 * i, blocked=True, lanes=3, events=64,
        engine="table (3-config vmap sweep)", wall_s=wall_s,
        spans=[spans.Span(n, 0.0, unit * (k + 1), unit * (k + 1), sweep=100 + i)
               for k, n in enumerate(names)])


@pytest.mark.parametrize("warm_waves", [1, 2, 3])
def test_the_first_warm_wave_and_the_window_are_found_behind_any_warm_up(
        monkeypatch, warm_waves):
    walls = [1.0, 1.1, 1.05]
    log = [_record(0, 1.0)]  # a foreign sweep
    log += [_record(1 + k, 9.0 - k) for k in range(warm_waves)]
    log += [_record(1 + warm_waves + i, 0.995 * w) for i, w in enumerate(walls)]
    log.append(_record(1 + warm_waves + len(walls), 1.2))  # the traced wave
    monkeypatch.setattr(spans, "sweep_log", lambda: list(log))
    run = {"spans_blocked": True, "waves": [{"wall_s": w} for w in walls],
           "warm_waves": warm_waves}
    warm, window = sweep_log.records(run)
    assert warm.id == 101
    assert [r.id for r in window] == [101 + warm_waves + i for i in range(3)]
    # the first warm wave's dispatch halves, less `fetch` and `slice_lanes`
    dispatch = bench_run.load_module("layer_metrics", "warm_wave_dispatch_s")
    assert dispatch.read(run) == pytest.approx(0.002 * (1 + 2 + 3 + 4 + 5 + 6))
    # a run that said one warm wave fewer than it ran would find the same
    # window behind a LATER warm wave, which loaded nothing
    if warm_waves > 1:
        later, same = sweep_log.records(dict(run, warm_waves=warm_waves - 1))
        assert later.id == 102 and same == window


# ----------------------------------------------- a roofline of the stream
def test_a_deletion_reads_no_node_row():
    create = roofline.scan_bytes_per_lane_event(1213, 64, 1)
    assert create == 1213 * 5 + 64 * 9 == 6641
    assert roofline.stream_bytes_per_lane_event(1213, 64, 1, 0.0) == create
    assert roofline.stream_bytes_per_lane_event(1213, 64, 1, 1.0) == 64 * 9
    clock = roofline.stream_bytes_per_lane_event(1213, 64, 1, 238 / 512)
    assert clock == pytest.approx(6641 - 0.46484375 * 6065)
    assert clock / create == pytest.approx(0.5755, abs=1e-4)
    # two policies: the row of each score table and of the feasibility one
    assert (roofline.stream_bytes_per_lane_event(1213, 144, 2, 0.5)
            == 1213 * 9 * 0.5 + 144 * 13)


@pytest.mark.parametrize("deletes, factor", [
    (2560 * 238, 1 - 0.46484375 * 6065 / 6641), (0, 1.0), (None, 1.0)])
def test_scan_roofline_takes_the_streams_bytes(monkeypatch, deletes, factor):
    """The clock cell's share falls to 0.58 of the create-only form's; a
    cell without deletions, and a program without the counter, read as
    they did."""
    fields = {} if deletes is None else {"delete_events": deletes}
    rec = types.SimpleNamespace(lanes=2560, events=512, **fields)
    monkeypatch.setattr(sweep_log, "records", lambda run: (rec, [rec, rec]))
    run = {"traced": {"scan_device_s": 1.2}, "device_kind": "TPU v5 lite",
           "rehearsal": False,
           "shape": {"nodes": 1213, "pod_types": 64, "policies": 1,
                     "lanes": 2560, "events": 512}}
    got = bench_run.load_module("layer_metrics", "scan_roofline").read(run)
    create_only = 100.0 * (6641 * 2560 * 512 / 819e9) / 1.2
    assert got == pytest.approx(factor * create_only)
    assert got < 105.0


# -------------------------------------------- what says lower means lower
@pytest.mark.parametrize("name", ["dense_access_sites",
                                  "load_dense_access_sites"])
def test_a_count_a_faster_program_lowers_says_lower(name):
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    assert bench_run.by_name(bench["per_layer"], name, "metric")[
        "better"] == "lower"


# ----------------------------------------- a later PR appends: none breaks
def appended(bench: dict) -> dict:
    """BENCHMARK.json as a later PR leaves it: a configuration, a cell and
    a per-layer metric more, each at the end of its list."""
    out = copy.deepcopy(bench)
    out["configs"].append({
        "name": "later", "source": "a later PR's deployment",
        "file": "benchmark/configs/later.json", "reduced": [],
        "why": "appended by test_steady_window.py"})
    out["workloads"].append({
        "name": "later.fgd-seeds", "config": "later",
        "traffic": "later-seeds", "chips": 1,
        "why": "appended by test_steady_window.py"})
    out["per_layer"].append({
        "name": "later_step_us_per_lane_event", "unit": "us",
        "better": "lower", "source": "program_span",
        "layer": out["per_layer"][1]["layer"], "moves": "lane_events_per_s",
        "workloads": ["later.fgd-seeds"]})
    return out


@pytest.mark.parametrize("module", CELL_TESTS)
def test_an_appended_cell_breaks_no_cells_identity_test(monkeypatch, module):
    """Every `test_*_cell.py` finds its cell, configuration and metrics by
    name: run its identity test against the appended file."""
    cell_test = importlib.import_module(module)
    assert cell_test.bench_run is bench_run
    load_json = bench_run.load_json

    def load(path):
        got = load_json(path)
        if os.path.basename(path) == "BENCHMARK.json":
            got = appended(got)
            assert got["workloads"][-1]["name"] == "later.fgd-seeds"
        return got

    monkeypatch.setattr(bench_run, "load_json", load)
    cell_test.test_the_cell_is_the_one_the_issue_names()


def test_every_cell_test_file_is_held_to_the_appended_file():
    there = sorted(f[:-3] for f in os.listdir(HERE)
                   if f.startswith("test_") and f.endswith("_cell.py"))
    assert there == sorted(CELL_TESTS)


def test_a_rehearsal_with_two_warm_waves_reads_its_records(monkeypatch, capsys):
    """The cells' own `warm_waves` 2 through a whole traced run (the tiny
    sizes keep 1, which tier-1 tests lean on): the line says 2, set-up
    holds both walls, and the readers find the window behind them."""
    load_json = bench_run.load_json

    def load(path):
        got = load_json(path)
        if os.path.basename(path) == "fgd-seeds-2560.json":
            del got["tiny"]["warm_waves"]
        return got

    monkeypatch.setattr(bench_run, "load_json", load)
    for _ in range(3):
        assert bench_run.main([
            "--workload", "openb.fgd-seeds", "--seed", "3000000048",
            "--seconds", "0.5", "--trace", "1", "--rehearse"]) == 0
        got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        # a preempted tiny wave puts a wall outside sweep_log's 1 %
        if "table_reuse_share" in got["metrics"]:
            break
    assert got["correct"] is True and got["failed"] == 0
    assert got["window"]["warm_waves"] == 2
    assert len(got["window"]["setup_parts"]["warm_waves_s"]) == 2
    assert got["metrics"]["table_reuse_share"]["value"] == 1.0
    assert got["metrics"]["stalled_waves"]["unit"] == "waves"
    from tpusim.obs.spans import sweep_log as program_log

    tail = program_log()[-(got["attempted"] + 3):]
    # the first warm wave built the tables, the second already reused them
    assert [rec.tables_reused for rec in tail] == [0] + [1] * (len(tail) - 1)
