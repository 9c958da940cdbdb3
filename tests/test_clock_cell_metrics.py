"""The clock cell (`openb-clock.fgd-seeds`, ISSUE 38) as the harness runs it,
at rehearsal sizes: `delete_share` and `clock_step_us_per_lane_event` off
the sweep record, the window's waves reading the resident tables although
every wave ends on another state than it began, and the planted control (a
reference that never releases) reading not correct. Here and not only under
benchmark/tests: the tier-1 lane runs it."""

import json
import os
import sys
import types

import pytest

from benchmark.lib import sweep_log
from tests.test_table_reuse_metric import (  # noqa: F401  (fixtures)
    bench_run,
    compile_cache_put_back,
)
from tpusim.obs import sweep_log as program_log

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "openb-clock.fgd-seeds"
NEW = ["clock_step_us_per_lane_event", "delete_share"]
# readers the control cell (`openb.fgd-seeds`) has, read in this cell under
# names of its own: accepted tests pin their lists to the control
SHARED = ["table_reuse_share", "table_pass_events", "sub_requests",
          "host_lead_s", "host_tail_s", "fetch_copy_s", "fetch_bytes"]


def test_the_two_metrics_stand_as_entered_and_list_the_clock_cell(bench_run):
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW[0])
    assert names[at - 1:at + 2] == ["sub_requests"] + NEW
    step = next(m for m in bench["per_layer"] if m["name"] == "scan_s")
    assert bench["per_layer"][at] == {
        "name": NEW[0], "unit": "us", "better": "lower",
        "source": "program_span", "layer": step["layer"],
        "moves": "lane_events_per_s", "workloads": [CELL]}
    assert bench["per_layer"][at + 1] == {
        "name": NEW[1], "unit": "share", "better": "higher",
        "source": "program_counter", "layer": step["layer"],
        "moves": "lane_events_per_s", "workloads": [CELL]}
    for name in NEW:
        assert hasattr(bench_run.load_module("layer_metrics", name), "read")


@pytest.mark.parametrize("name", SHARED)
def test_a_reader_of_the_control_reads_the_clock_cell_under_its_own_name(
        bench_run, name):
    """The entry is the control's but for the name and the cell, after the
    PR's two, and the reader is the control's own function."""
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    by_name = dict(zip(names, bench["per_layer"]))
    at = names.index("delete_share") + 1
    assert names[at:at + len(SHARED)] == [f"clock_{n}" for n in SHARED]
    assert by_name[f"clock_{name}"] == dict(
        by_name[name], name=f"clock_{name}", workloads=[CELL])
    reader = bench_run.load_module("layer_metrics", f"clock_{name}").read
    assert reader.__module__ == f"benchmark.layer_metrics.{name}"


def _record(**fields):
    return types.SimpleNamespace(lanes=2560, events=512, spans=[], **fields)


@pytest.mark.parametrize("window, want", [
    ([_record(delete_events=2560 * 238)] * 3, 0.46484375),
    # a cell that stops sending deletes reads 0
    ([_record(delete_events=0)] * 2, 0.0),
    # the parent's record shape, in every wave or in one: nothing, no raise
    ([_record(), _record()], None),
    ([_record(delete_events=2560 * 238), _record()], None),
    (None, None),
], ids=["the clock cell", "no deletes", "the parent", "one without", "no log"])
def test_delete_share_reads_the_record_or_nothing(
        bench_run, monkeypatch, window, want):
    metric = bench_run.load_module("layer_metrics", "delete_share")
    warm = _record(delete_events=7)
    monkeypatch.setattr(
        sweep_log, "records",
        lambda run: None if window is None else (warm, window))
    assert metric.read({}) == want


def _rehearse(bench_run, capsys, trace):
    assert bench_run.main([
        "--workload", CELL, "--seed", "3000000038", "--seconds", "0.5",
        "--trace", str(trace), "--rehearse"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_the_tiny_clock_cells_traced_line_reads_the_stream(
        bench_run, capsys, compile_cache_put_back):
    for _ in range(3):
        got = _rehearse(bench_run, capsys, trace=1)
        assert got["correct"] is True and got["failed"] == 0
        # as in test_table_reuse_metric: a preempted tiny wave reads nothing
        if "delete_share" in got["metrics"]:
            break
    assert got["metrics"]["delete_share"] == {"value": 18 / 64, "unit": "share"}
    assert got["metrics"]["clock_step_us_per_lane_event"]["value"] > 0
    assert {f"clock_{n}" for n in SHARED} <= set(got["metrics"])
    assert got["metrics"]["clock_table_reuse_share"]["value"] == 1.0
    # the warm wave built the tables; every later wave read them from the
    # device, though each wave's 18 deletions and 46 creations left the
    # lanes on another state than the initial one the build read
    tail = program_log()[-(got["attempted"] + 2):]
    assert [rec.tables_reused for rec in tail] == [0] + [1] * (len(tail) - 1)
    assert {(rec.lanes, rec.events, rec.delete_events) for rec in tail} == {
        (3, 64, 3 * 18)}


def test_a_reference_that_never_releases_reads_not_correct(
        bench_run, capsys, compile_cache_put_back):
    sys.path.insert(0, os.path.join(REPO, "benchmark", "tests"))
    try:
        import clock_control
    finally:
        sys.path.pop(0)
    undo = clock_control.hand_the_reference_a_stream_without_deletions()
    try:
        got = _rehearse(bench_run, capsys, trace=0)
    finally:
        undo()
    assert got["correct"] is False
    assert set(got["metrics"]) == {"lane_events_per_s", "wave_s", "setup_s"}
    assert _rehearse(bench_run, capsys, trace=0)["correct"] is True
