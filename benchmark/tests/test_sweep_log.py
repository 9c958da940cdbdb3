"""CPU checks of the sweep-record helper (benchmark/lib/sweep_log.py) on a
hand-built log, and a rehearsal of the cell that prints the metrics that
read it beside the span metrics the benchmark already had.

Run: JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402  (benchmark/run.py)
from benchmark.lib import sweep_log  # noqa: E402
from tpusim.obs import spans  # noqa: E402

from test_harness import CELL, rehearse  # noqa: E402

NEW = {"spec_prep_s", "lane_inputs_s", "slice_lanes_s", "table_build_s",
       "frag_postpass_s", "programs_requested_per_wave",
       "warm_wave_dispatch_s"}
PHASES = ["specs", "lane_keys", "lane_ranks", "init_tables", "scan",
          "frag_postpass", "fetch", "slice_lanes"]


def record(i, wall_s, blocked=True, requested=1):
    """A sweep whose eight spans last (i + 1) x 1..8 ms dispatch and as
    much again blocked."""
    unit = 0.001 * (i + 1)
    return spans.SweepRecord(
        id=100 + i, start_s=10.0 * i, blocked=blocked, lanes=3, events=64,
        engine="table (3-config vmap sweep)", wall_s=wall_s,
        spans=[spans.Span(name, 0.0, unit * (k + 1), unit * (k + 1), sweep=100 + i)
               for k, name in enumerate(PHASES)],
        programs_requested=requested)


def hand_built(monkeypatch, walls, log=None, **run):
    """A log of: a foreign sweep, the warm wave, one window wave for each
    of `walls` (recorded at 99.5 % of it), the traced wave."""
    if log is None:
        log = [record(0, 1.0), record(1, 9.0)]
        log += [record(2 + i, 0.995 * w) for i, w in enumerate(walls)]
        log.append(record(2 + len(walls), 1.2))
    monkeypatch.setattr(spans, "sweep_log", lambda: list(log))
    return {"spans_blocked": True, "attempted": len(walls),
            "waves": [{"wall_s": w} for w in walls], **run}


def test_aligned_walls_give_the_windows_records(monkeypatch):
    run = hand_built(monkeypatch, [1.0, 1.1, 1.05])
    warm, window = sweep_log.records(run)
    assert warm.id == 101 and [r.id for r in window] == [102, 103, 104]
    # records 2, 3, 4: units 3, 4, 5 ms; the median wave is record 3
    assert sweep_log.median_span_seconds(run, "specs") == pytest.approx(0.008)
    assert sweep_log.median_span_seconds(
        run, "lane_keys", "lane_ranks") == pytest.approx(0.004 * 2 * 5)
    assert sweep_log.window_median(
        run, lambda r: r.programs_requested) == 1
    # the warm wave is the record before the window: 2 ms x (1 + .. + 6),
    # its `fetch` and `slice_lanes` (7 and 8) are no dispatch (PR 48)
    warm_dispatch = bench_run.load_module(
        "layer_metrics", "warm_wave_dispatch_s").read(run)
    assert warm_dispatch == pytest.approx(0.002 * 21)


@pytest.mark.parametrize("damage", [
    "a wall 5 % off", "a record longer than the driver's wall",
    "a short log", "an unblocked run", "an unblocked record",
    "a foreign sweep in between", "no log in the program"])
def test_what_does_not_line_up_reads_as_nothing(monkeypatch, damage):
    run = hand_built(monkeypatch, [1.0, 1.1, 1.05])
    log = spans.sweep_log()
    if damage == "a wall 5 % off":
        run["waves"][1]["wall_s"] *= 1.05
    elif damage == "a record longer than the driver's wall":
        log[3].wall_s = 1.1001
    elif damage == "a short log":
        log = log[2:]
    elif damage == "an unblocked run":
        run["spans_blocked"] = False
    elif damage == "an unblocked record":
        log[3].blocked = False
    elif damage == "a foreign sweep in between":
        log[3].id = 900
    monkeypatch.setattr(spans, "sweep_log", lambda: list(log))
    if damage == "no log in the program":
        monkeypatch.delattr(spans, "sweep_log")
    assert sweep_log.records(run) is None
    for name in NEW:
        assert bench_run.load_module("layer_metrics", name).read(run) is None


def test_a_rehearsal_prints_the_seven_beside_the_span_metrics_it_had(capsys):
    got = rehearse(capsys, trace=1)
    # the two device metrics find no device plane in a rehearsal
    assert set(got["metrics"]) >= NEW | {"host_s", "scan_s", "fetch_s"}
    value = {k: v["value"] for k, v in got["metrics"].items()}
    assert value["programs_requested_per_wave"] >= 1
    # `host_s` no longer holds the table build and the post-pass (PR 48);
    # medians of parts against a median of sums, less the scan's dispatch
    parts = sum(value[k] for k in ("spec_prep_s", "lane_inputs_s",
                                   "slice_lanes_s"))
    assert 0 < parts <= 1.1 * value["host_s"]
    assert rehearse(capsys, trace=0)["metrics"].keys() == {
        "lane_events_per_s", "wave_s", "setup_s"}
    bench = bench_run.load_json(os.path.join(REPO, "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(listed) == NEW
    assert all(m["workloads"] == [CELL] for m in listed.values())


def _proto(*fields) -> bytes:
    """Wire format of one message: (number, int) a varint, (number,
    bytes) length-delimited."""
    def varint(n):
        out = b""
        while n >= 0x80:
            out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
        return out + bytes([n])

    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_scopes_on_chip_splits_a_hand_built_trace_by_innermost_scope(
        monkeypatch, tmp_path):
    """The by-scope reduction of scopes_on_chip.py on a fake xplane: one
    device, one module of 10 s holding a `while` of 8 s with two children,
    and an op outside the while. One child's scope is in a stat of the
    event, the other's only in its event metadata, in the file itself."""
    from types import SimpleNamespace as NS

    import jax

    import scopes_on_chip

    def ev(name, start, end, **stats):
        return NS(name=name, start_ns=start * 1e9, end_ns=end * 1e9,
                  duration_ns=(end - start) * 1e9, stats=list(stats.items()))

    path = "jit(run)/while/body/"
    ops = [
        ev("%while.1", 1, 9, tf_op="jit(run)/while"),
        ev("%fusion.2", 1, 4, tf_op=path + "tpusim.summary/cond/tpusim.select/x"),
        ev("%copy.3", 4, 9, long_name="c"),
        ev("%fusion.4", 9, 11, hlo_category="fusion"),
    ]
    planes = [
        NS(name="/host:CPU", lines=[NS(name="python", events=[
            ev(scopes_on_chip.trace_reduce.WAVE_ANNOTATION, 0, 12)])]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_run(1)", 1, 11)]),
            NS(name="XLA Ops", events=ops)]),
    ]
    # XSpace{planes{name, lines (skipped), event_metadata{7: {name, a
    # string stat and a ref stat}}, stat_metadata{1, 2, 3}}}
    copy_meta = _proto(
        (1, 7), (2, b"%copy.3"),
        (5, _proto((1, 1), (5, (path + "tpusim.commit/scatter").encode()))),
        (5, _proto((1, 2), (7, 3))))
    xspace = tmp_path / "t.xplane.pb"
    xspace.write_bytes(_proto((1, _proto(
        (1, 1), (2, b"/device:TPU:0"), (3, _proto((2, b"XLA Ops"))),
        (4, _proto((1, 7), (2, copy_meta))),
        (5, _proto((1, 1), (2, _proto((1, 1), (2, b"tf_op"))))),
        (5, _proto((1, 2), (2, _proto((1, 2), (2, b"hlo_category"))))),
        (5, _proto((1, 3), (2, _proto((1, 3), (2, b"data formatting")))))))))
    assert scopes_on_chip.metadata_stats(str(xspace)) == {"/device:TPU:0": {
        "%copy.3": {"tf_op": path + "tpusim.commit/scatter",
                    "hlo_category": "data formatting"}}}
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda _path: NS(planes=planes)))
    found = scopes_on_chip.by_scope(str(xspace))
    assert found["carriers"] == {"event stat tf_op": 1,
                                 "metadata stat tf_op": 1}
    assert found["by_scope_s"] == pytest.approx({
        "tpusim.commit": 5.0, "tpusim.select": 3.0, "unscoped": 2.0})
    assert found["busy_s"] == pytest.approx(10.0)
    assert scopes_on_chip.verdict(found, ["tpusim.commit"]) == []
    wrong = scopes_on_chip.verdict(found, ["tpusim.refresh"])
    assert wrong == ["scope tpusim.refresh is nowhere in the trace"]
    del ops[2], ops[0]  # 5 s of the module with no operation in them
    assert any("sum to 5.0000 s" in w for w in scopes_on_chip.verdict(
        scopes_on_chip.by_scope(str(xspace)), []))
