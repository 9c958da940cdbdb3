"""`sub_requests` (benchmark/layer_metrics, ISSUE 37) as the harness reads
it: the Sub hypotheticals one column computation evaluates a lane, off the
sweep record; the tiny openb cell's traced line reads 8 (FGD takes its
whole-branch pod types by request: the type set's distinct
(gpu_milli, gpu_num) on their bucket), a record without the counter (the
parent's) reads as nothing. Here and not under benchmark/tests: the tier-1
lane runs it, and the benchmark gains the one reader only."""

import json
import os
import types

import pytest

from benchmark.lib import sweep_log
from tests.test_table_reuse_metric import (  # noqa: F401  (fixtures)
    bench_run,
    compile_cache_put_back,
)
from tpusim.obs import sweep_log as program_log
from tpusim.obs.spans import SweepRecord

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL, METRIC = "openb.fgd-seeds", "sub_requests"


def test_the_metric_stands_as_entered_and_lists_openb_alone(bench_run):
    """Appended after PR 36's six; the accepted benchmark tests pin the
    other three cells' lines exactly (PERF.md section 7), so it lists
    `openb.fgd-seeds` alone."""
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(METRIC)
    assert names[at - 1] == "host_tail_s"
    sets = next(m for m in bench["per_layer"] if m["name"] == "typical_sets")
    assert bench["per_layer"][at] == {
        "name": METRIC, "unit": "requests", "better": "lower",
        "source": "program_counter", "layer": sets["layer"],
        "moves": "lane_events_per_s", "workloads": [CELL]}
    assert hasattr(bench_run.load_module("layer_metrics", METRIC), "read")


def _record(**fields):
    return types.SimpleNamespace(lanes=3, events=64, spans=[], **fields)


@pytest.mark.parametrize("window, want", [
    ([_record(sub_requests=8)] * 3, 8),
    # a median over the window's waves; the warm wave's is not among them
    ([_record(sub_requests=8), _record(sub_requests=32),
      _record(sub_requests=32)], 32),
    # the parent's record shape, in every wave or in one: nothing, no raise
    ([_record(), _record()], None),
    ([_record(sub_requests=8), _record()], None),
    (None, None),
], ids=["by request", "type by type", "the parent", "one without", "no log"])
def test_the_reader_reads_the_record_or_nothing(
        bench_run, monkeypatch, window, want):
    metric = bench_run.load_module("layer_metrics", METRIC)
    warm = _record(sub_requests=272)
    monkeypatch.setattr(
        sweep_log, "records",
        lambda run: None if window is None else (warm, window))
    assert metric.read({}) == want


def test_the_record_carries_the_counter():
    rec = SweepRecord(id=0, start_s=0.0, blocked=False)
    assert rec.sub_requests == 0 == rec.to_dict()["sub_requests"]
    rec.sub_requests = 8
    assert rec.to_dict()["sub_requests"] == 8


def test_the_tiny_openb_cells_traced_line_reads_eight(
        bench_run, capsys, compile_cache_put_back):
    for _ in range(3):
        assert bench_run.main([
            "--workload", CELL, "--seed", "3000000037", "--seconds", "0.5",
            "--trace", "1", "--rehearse"]) == 0
        got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert got["correct"] is True and got["failed"] == 0
        # a tiny wave is milliseconds: one preemption between the driver's
        # clock and the record's puts a wall outside sweep_log's 1 % and
        # every metric off the records reads as nothing
        # (tests/test_table_reuse_metric)
        if METRIC in got["metrics"]:
            break
    assert got["metrics"][METRIC] == {"value": 8, "unit": "requests"}
    for rec in program_log()[-(got["attempted"] + 1):]:
        assert rec.sub_requests == 8 and "table" in rec.engine
