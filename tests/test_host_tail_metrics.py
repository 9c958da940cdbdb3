"""The six per-layer metrics of ISSUE 36 (benchmark/layer_metrics:
`fetch_copy_s`, `fetch_unpack_s`, `fetch_bytes`, `postpass_dispatch_s`,
`host_lead_s`, `host_tail_s`) as the harness reads them: each reads its
value off a hand-built sweep record's marks and derived fields, reads as
nothing from the parent's record shape and where the window's records are
not found, and the tiny openb cell's traced line holds all six, the
fetch's three pieces summing to `fetch_s`. Here and not under
benchmark/tests: the tier-1 lane runs it, and the benchmark gains the
readers only."""

import json
import os
import types

import pytest

from benchmark.lib import sweep_log
from tests.test_table_reuse_metric import (  # noqa: F401  (fixtures)
    bench_run,
    compile_cache_put_back,
)
from tpusim.obs.spans import Span, SweepRecord

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "openb.fgd-seeds"
READBACK = "Readback (sim/fetch.py)"
HOST = "Host prep and lane slicing (sim/driver.py)"
# name -> (unit, source, layer, what the hand-built record below reads)
METRICS = {
    "fetch_copy_s": ("s", "program_span", READBACK, 0.30),
    "fetch_unpack_s": ("s", "program_span", READBACK, 0.05),
    "fetch_bytes": ("bytes", "program_counter", READBACK, 4096),
    "postpass_dispatch_s": ("s", "program_span", "Post-pass and reports",
                            0.08),
    "host_lead_s": ("s", "program_span", HOST, 0.16),
    "host_tail_s": ("s", "program_span", HOST, 0.55),
}


def _record(shift=0.0):
    """A blocked wave on a recorder whose epoch is 100 s before the call:
    lead 0.16 (the scan dispatched at 0.16), scan block 1.0, post-pass
    dispatch 0.08 and block 0.02, fetch 0.10 + 0.30 + 0.05 (+ shift on the
    copy), slice_lanes 0.20, 0.01 after the last span."""
    walls = [("specs", 0.05, 0.0, {}), ("lane_keys", 0.01, 0.0, {}),
             ("lane_ranks", 0.06, 0.0, {"stacked": 0.04}),
             ("init_tables", 0.01, 0.0, {}), ("scan", 0.03, 1.0, {}),
             ("frag_postpass", 0.08, 0.02, {"gathered": 0.001}),
             ("fetch", 0.45 + shift, 0.0,
              {"ready": 0.10, "copied": 0.40 + shift}),
             ("slice_lanes", 0.20, 0.0, {})]
    spans, at = [], 100.0
    for name, dispatch, block, marks in walls:
        spans.append(Span(name=name, start_s=at, dispatch_s=dispatch,
                          block_s=block, marks=marks))
        at += dispatch + block
    return SweepRecord(id=0, start_s=1100.0, blocked=True, epoch=1000.0,
                       lanes=3, events=64, wall_s=at - 100.0 + 0.01,
                       spans=spans, fetch_bytes=4096)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_metric_stands_as_entered_and_lists_openb_alone(bench_run, name):
    """The accepted benchmark tests pin the other three cells' lines
    exactly (PERF.md section 7), so the six list `openb.fgd-seeds` alone,
    after every metric the benchmark had."""
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("fetch_copy_s")
    assert names[first:first + 6] == [
        "fetch_copy_s", "fetch_unpack_s", "fetch_bytes",
        "postpass_dispatch_s", "host_lead_s", "host_tail_s"]
    unit, source, layer, _ = METRICS[name]
    assert bench["per_layer"][names.index(name)] == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": "wave_s", "workloads": [CELL]}
    assert layer in {m["layer"] for m in bench["per_layer"][:first]}
    assert hasattr(bench_run.load_module("layer_metrics", name), "read")


@pytest.mark.parametrize("name", sorted(METRICS))
def test_each_reader_reads_the_record_or_nothing(bench_run, monkeypatch, name):
    metric = bench_run.load_module("layer_metrics", name)
    rec = _record()
    assert (rec.host_lead_s, rec.covered_s, rec.device_block_s,
            rec.device_wait_s, rec.host_tail_s) == pytest.approx(
        (0.16, 0.08, 1.02, 0.10, 0.55))
    # the warm wave's record is not the window's; a median over the waves
    window = [_record(), _record(shift=0.2), _record()]
    monkeypatch.setattr(sweep_log, "records", lambda run: (None, window))
    assert metric.read({}) == pytest.approx(METRICS[name][3])
    # the parent's record shape (no marks on a span, no derived field, no
    # counter; tests/test_table_reuse_metric.py's): nothing, and no raise
    old = types.SimpleNamespace(lanes=3, events=64, spans=[])
    monkeypatch.setattr(sweep_log, "records", lambda run: (old, [old, old]))
    assert metric.read({}) is None
    monkeypatch.setattr(sweep_log, "records", lambda run: (old, [rec, old]))
    assert metric.read({}) is None
    monkeypatch.setattr(sweep_log, "records", lambda run: None)
    assert metric.read({}) is None


def test_a_span_of_the_parents_shape_reads_as_nothing(bench_run, monkeypatch):
    """The parent's spans have the eight names and no `marks` attribute,
    its record no derived field and no `fetch_bytes`: five of the six read
    nothing there; `frag_postpass.dispatch_s` is the parent's too."""
    def bare(sp):
        return types.SimpleNamespace(
            name=sp.name, start_s=sp.start_s, dispatch_s=sp.dispatch_s,
            block_s=sp.block_s, total_s=sp.total_s)

    old = types.SimpleNamespace(
        lanes=3, events=64, spans=[bare(sp) for sp in _record().spans])
    monkeypatch.setattr(sweep_log, "records", lambda run: (old, [old, old]))
    for name in METRICS:
        value = bench_run.load_module("layer_metrics", name).read({})
        if name == "postpass_dispatch_s":
            assert value == pytest.approx(0.08)
        else:
            assert value is None, name


def test_the_tiny_openb_cells_traced_line_holds_the_six(
        bench_run, capsys, compile_cache_put_back):
    for _ in range(3):
        assert bench_run.main([
            "--workload", CELL, "--seed", "3000000019", "--seconds", "0.5",
            "--trace", "1", "--rehearse"]) == 0
        got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert got["correct"] is True and got["failed"] == 0
        # a tiny wave is milliseconds: one preemption between the driver's
        # clock and the record's puts a wall outside sweep_log's 1 % and
        # every span metric reads as nothing (tests/test_table_reuse_metric)
        if "host_tail_s" in got["metrics"]:
            break
    metrics = got["metrics"]
    assert set(METRICS) | {"host_s", "scan_s", "fetch_s",
                           "table_reuse_share"} <= set(metrics)
    for name, (unit, _, _, _) in METRICS.items():
        assert metrics[name]["unit"] == unit and metrics[name]["value"] > 0
    # the window's records: wait + copy + unpack is the fetch span, and the
    # five derived fields the call up to its last span
    from tpusim.obs import sweep_log as program_log

    for rec in program_log()[-(got["attempted"] + 1):]:
        fetch = next(s for s in rec.spans if s.name == "fetch")
        assert rec.blocked and rec.fetch_bytes == fetch.meta["bytes"] > 0
        assert (rec.device_wait_s
                + (fetch.marks["copied"] - fetch.marks["ready"])
                + (fetch.total_s - fetch.marks["copied"])
                ) == pytest.approx(fetch.total_s, abs=1e-9)
        assert rec.device_block_s > 0
        total = (rec.host_lead_s + rec.covered_s + rec.device_block_s
                 + rec.device_wait_s + rec.host_tail_s)
        assert 0.95 * rec.wall_s <= total <= rec.wall_s
