"""The trace-to-metrics reduction and the bytes function, against a
hand-built trace with known answers (CPU, no JAX backend needed)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.lib import device, roofline, trace_reduce as tr  # noqa: E402

# a 10 s wave starting at t=100 on the trace's clock. The device runs a
# table build (1 s), then the scan: a `while` of 6 s with two kinds of
# body op nested in it, then a small post-pass; it idles during host prep
# (100-101), between scan and post-pass (108-108.5) and from 109 on.
W0 = 100.0
OPS = [
    ("build_tables", W0 + 1.0, W0 + 2.0),
    ("while.1", W0 + 2.0, W0 + 8.0),
    ("fusion.select", W0 + 2.0, W0 + 3.0),
    ("scatter.7", W0 + 3.0, W0 + 4.5),
    ("fusion.select", W0 + 5.0, W0 + 6.0),
    ("scatter.7", W0 + 6.0, W0 + 7.5),
    ("frag_amounts", W0 + 8.5, W0 + 9.0),
    ("before_the_wave", W0 - 5.0, W0 - 4.0),
]
MODULES = [("jit_build(1)", W0 + 1.0, W0 + 2.0), ("jit_replay(2)", W0 + 2.0, W0 + 8.0),
           ("jit_amounts(3)", W0 + 8.5, W0 + 9.0)]
PHASES = [("host prep", 0.0, 1.0), ("init_tables", 1.0, 1.2), ("scan", 1.2, 8.0),
          ("after scan", 8.0, 9.0), ("fetch", 9.0, 9.6), ("lane slicing", 9.6, 10.0)]
TRACE = {"devices": {"/device:TPU:0": {"ops": OPS, "modules": MODULES}},
         "wave": (W0, W0 + 10.0)}


def test_merge_and_busy_union_counts_nested_and_overlapping_once():
    assert tr.merge([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [(0, 3), (5, 7)]
    spans = [(s, e) for _, s, e in OPS]
    assert tr.busy_seconds(spans, (W0, W0 + 10.0)) == pytest.approx(7.5)
    # an interval that straddles the window's edge counts only its inside
    assert tr.busy_seconds([(W0 - 1, W0 + 1)], (W0, W0 + 10.0)) == pytest.approx(1.0)


def test_idle_share_and_gaps():
    spans = [(s, e) for _, s, e in OPS]
    got = tr.gaps(spans, (W0, W0 + 10.0))
    assert got == [pytest.approx((W0, W0 + 1.0)), pytest.approx((W0 + 8.0, W0 + 8.5)),
                   pytest.approx((W0 + 9.0, W0 + 10.0))]
    assert tr.idle_share_pct(7.5, 10.0) == pytest.approx(25.0)


def test_gaps_go_to_the_host_phase_that_covers_them():
    idle = [(0.0, 1.0), (8.0, 8.5), (9.0, 10.0), (20.0, 20.25)]
    got = dict(tr.attribute_gaps(idle, PHASES))
    assert got == {"host prep": pytest.approx(1.0), "after scan": pytest.approx(0.5),
                   "fetch": pytest.approx(0.6), "lane slicing": pytest.approx(0.4),
                   "unattributed": pytest.approx(0.25)}
    assert [k for k, _ in tr.attribute_gaps(idle, PHASES, top=2)] == ["host prep", "fetch"]


def test_self_time_takes_children_out_of_the_while():
    got = tr.self_times([ev for ev in OPS if ev[0] != "before_the_wave"])
    assert got["while.1"] == pytest.approx(1.0)  # 6 s less 5 s of body ops
    assert got["scatter.7"] == pytest.approx(3.0)
    assert got["fusion.select"] == pytest.approx(2.0)
    assert got["build_tables"] == pytest.approx(1.0)
    assert sum(got.values()) == pytest.approx(7.5)  # = the busy union


def test_reduce_wave_on_the_hand_built_trace():
    got = tr.reduce_wave(TRACE, PHASES)
    assert got["busy_s"] == pytest.approx(7.5)
    assert got["window_s"] == pytest.approx(10.0)
    assert got["scan_device_s"] == pytest.approx(6.0)  # the longest module
    assert got["device_ops"][0] == ["scatter.7", pytest.approx(3.0)]
    assert "before_the_wave" not in dict(got["device_ops"])
    assert dict(got["idle_gaps"])["host prep"] == pytest.approx(1.0)
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(2.5)


def test_a_capped_walk_of_the_ops_is_scaled_to_the_whole_wave():
    # only the operations that end by t=104.5 were walked: build_tables,
    # one select, one scatter, and the while cut short. Busy in the part
    # walked is 3.5 s of the wave's 7.5 s.
    walked = [ev for ev in OPS if W0 < ev[2] <= W0 + 4.5]
    capped = {"devices": {"/device:TPU:0": {"ops": walked, "modules": MODULES}},
              "wave": TRACE["wave"]}
    got = dict(tr.reduce_wave(capped, PHASES)["device_ops"])
    assert got["scatter.7"] == pytest.approx(1.5 * 7.5 / 3.5)
    assert got["build_tables"] == pytest.approx(1.0 * 7.5 / 3.5)
    assert tr.reduce_wave(capped, PHASES)["busy_s"] == pytest.approx(7.5)


def test_reduce_wave_refuses_a_trace_without_annotation_or_device():
    with pytest.raises(ValueError, match="annotation"):
        tr.reduce_wave({**TRACE, "wave": None}, PHASES)
    with pytest.raises(ValueError, match="device plane"):
        tr.reduce_wave({**TRACE, "devices": {}}, PHASES)


def test_scan_bytes_and_roofline_share():
    # openb under FGD: one policy, 1,213 nodes, 151 pod types
    assert roofline.scan_bytes_per_lane_event(1213, 151, 1) == 1213 * 5 + 151 * 9
    assert roofline.scan_bytes_per_lane_event(1213, 151, 2) == 1213 * 9 + 151 * 13
    per_wave = roofline.scan_bytes_per_lane_event(1213, 151, 1) * 2560 * 10240
    least_s = per_wave / 819e9
    assert roofline.roofline_share_pct(per_wave, 10 * least_s, 819e9) == pytest.approx(10.0)


def test_carried_bytes_of_a_lane_and_the_reported_memory_peak():
    # 100,000 nodes, 71 pod types, FGD alone, 512 creates
    assert roofline.carry_bytes_per_lane(100_000, 71, 1, 512, 512) == (
        9 * 71 * 100_000 + 96 * 100_000 + 13 * 512 + 12 * 512)
    assert device.memory_peak_bytes(
        {"peak_bytes_in_use": 9, "peak_bytes_reserved": 102}) == 102


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    assert device.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert device.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        device.peaks_for("TPU v9 imaginary")
