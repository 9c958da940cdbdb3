"""From a profiler trace and host spans to busy time, idle gaps and the
operations that took most of the device.

Pure functions over plain tuples, so they can be checked against a
hand-built trace (benchmark/tests/test_reduction.py); `read_xplane` is the
one place that touches the profiler's file format.

Device planes are named `/device:TPU:<n>`. Their "XLA Modules" line holds
one event per program execution: the union of those intervals is the time
in which an operation ran on the device (inside a program the operations
follow each other; a scan is one `while` that never goes back to the
host). Their "XLA Ops" line holds one event per executed operation, a
`while` being one long event with its body's operations nested inside it,
so operations are ranked by SELF time (an event's duration minus what its
children cover). A wide wave executes tens of millions of operations (the
scatters of a vmapped step run as a loop over the lanes), more than Python
can walk in a run: `read_xplane` walks the first OPS_CAP of them and
`reduce_wave` scales their self times by busy time in the window over busy
time in the part walked. The profiler's own device buffer holds about six
million of them and silently drops what comes later; the driver refuses a
trace that shows less device time than the host waited.
"""

from __future__ import annotations

import glob
import os
from typing import Iterable, Sequence

Interval = tuple[float, float]  # (start_s, end_s)
Event = tuple[str, float, float]  # (name, start_s, end_s)

WAVE_ANNOTATION = "bench_wave"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
OPS_CAP = 3_000_000
NAME_CHARS = 120  # an XLA op's name is its whole HLO line


def merge(intervals: Iterable[Interval]) -> list[Interval]:
    """Sorted, non-overlapping union of the intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> list[Interval]:
    w0, w1 = window
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if min(e, w1) > max(s, w0)]


def busy_seconds(intervals: Iterable[Interval], window: Interval) -> float:
    return sum(e - s for s, e in merge(clip(intervals, window)))


def idle_share_pct(busy_s: float, window_s: float) -> float:
    return 100.0 * (1.0 - busy_s / window_s)


def gaps(intervals: Iterable[Interval], window: Interval) -> list[Interval]:
    """The parts of the window no interval covers."""
    out, at = [], window[0]
    for s, e in merge(clip(intervals, window)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def attribute_gaps(idle: Sequence[Interval], phases: Sequence[Event],
                   top: int = 10) -> list[list]:
    """Idle seconds by what the host was doing: each gap is cut along the
    host phases and every piece goes to the phase that covers it (pieces
    no phase covers go to `unattributed`). Returns [[phase, seconds]],
    longest first, at most `top` entries."""
    total: dict[str, float] = {}
    for g0, g1 in idle:
        covered = 0.0
        for name, p0, p1 in phases:
            part = min(g1, p1) - max(g0, p0)
            if part > 0:
                total[name] = total.get(name, 0.0) + part
                covered += part
        rest = (g1 - g0) - covered
        if rest > 1e-9:
            total["unattributed"] = total.get("unattributed", 0.0) + rest
    ranked = sorted(total.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]


def self_times(events: Sequence[Event]) -> dict[str, float]:
    """Seconds per operation name, counting each instant once: an event's
    self time is its duration minus the union of the events nested in it."""
    evs = sorted(events, key=lambda ev: (ev[1], -(ev[2])))
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self_s, cursor]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, end, self_s, cursor = stack.pop()
            self_s += max(end - cursor, 0.0)
            out[name] = out.get(name, 0.0) + self_s
            if stack:
                stack[-1][3] = max(stack[-1][3], end)

    for name, s, e in evs:
        close(s)
        if stack:
            parent = stack[-1]
            parent[2] += max(s - parent[3], 0.0)
            parent[3] = max(parent[3], s)
        stack.append([name, e, 0.0, s])
    close(float("inf"))
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str, ops_cap: int = OPS_CAP) -> dict:
    """{"devices": {plane: {"ops": [Event], "modules": [Event]}},
    "wave": (start_s, end_s) of the harness's annotation or None}; times
    in seconds on the trace's own clock. Of each device's operations the
    first `ops_cap` that end after the wave began are kept. On a backend with
    no device plane (the CPU rehearsal) the PjRt client threads stand in,
    so the code path runs; its numbers mean nothing."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = list(data.planes)
    wave = None
    stand_in: list[Event] = []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            pjrt = line.name.startswith("tf_XLAPjRtCpuClient")
            for ev in line.events:
                if ev.name == WAVE_ANNOTATION:
                    wave = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                elif pjrt and ev.duration_ns > 0:
                    stand_in.append((ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9))
    w0 = wave[0] if wave is not None else float("-inf")
    devices: dict[str, dict] = {}
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
        for line in plane.lines:
            if line.name == MODULES_LINE:
                dev["modules"] = [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                                  for ev in line.events]
            elif line.name == OPS_LINE:
                ops = dev["ops"]
                for ev in line.events:
                    end = ev.end_ns * 1e-9
                    if end > w0:
                        ops.append((ev.name, ev.start_ns * 1e-9, end))
                        if len(ops) >= ops_cap:
                            break
    if not devices and stand_in:
        devices["/host:CPU (stand-in)"] = {"ops": stand_in, "modules": stand_in}
    return {"devices": devices, "wave": wave}


def reduce_wave(trace: dict, phases: Sequence[Event], top: int = 10) -> dict:
    """Busy seconds (mean over devices), window, the longest program's
    device seconds, and the breakdown, for the traced wave. `phases` are
    host phases relative to the wave's start."""
    if trace["wave"] is None:
        raise ValueError(f"the trace holds no {WAVE_ANNOTATION!r} annotation")
    if not trace["devices"]:
        raise ValueError("the trace holds no device plane")
    w0, w1 = window = trace["wave"]
    busy, scan_s, idle = [], [], []
    self_s: dict[str, float] = {}
    for dev in trace["devices"].values():
        runs = [(s, e) for _, s, e in dev["modules"]]
        busy.append(busy_seconds(runs, window))
        by_module: dict[str, float] = {}
        for name, s, e in dev["modules"]:
            if e > w0 and s < w1:
                by_module[name] = by_module.get(name, 0.0) + min(e, w1) - max(s, w0)
        scan_s.append(max(by_module.values(), default=0.0))
        in_wave = [ev for ev in dev["ops"] if ev[2] > w0 and ev[1] < w1]
        if in_wave:
            walked = (w0, max(e for _, _, e in in_wave))
            scale = busy[-1] / max(busy_seconds(runs, walked), 1e-12)
            for name, secs in self_times(in_wave).items():
                self_s[name] = (self_s.get(name, 0.0)
                                + scale * secs / len(trace["devices"]))
        idle = idle or gaps(runs, window)
    abs_phases = [(n, w0 + s, w0 + e) for n, s, e in phases]
    ranked = sorted(self_s.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": w1 - w0,
        "scan_device_s": sum(scan_s) / len(scan_s),
        "device_ops": [[k[:NAME_CHARS], v] for k, v in ranked[:top]],
        "idle_gaps": attribute_gaps(idle, abs_phases, top),
    }
