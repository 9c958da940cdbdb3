"""Bytes the per-event report has to move, from shapes alone, and the
device time of the program that makes it.

The report program (tpusim/sim/metrics.py, vmapped over the lanes) reads a
lane's record of its events and writes its series: per real lane-event 12
bytes in (`event_node` i32, `event_dev` bool[8]) and 60 bytes out (seven
f32 frag amounts, six i32 counters, two f32 watts); once a lane it reads
the cluster's rows it starts from (the initial NodeState, 96 bytes a
node). The sorted temporaries, the gathers by node and the [E, T, 8] frag
block in between are the implementation's, not the algorithm's, so the
share says how far the program is from streaming its inputs and outputs
once.
"""

from __future__ import annotations

IN_BYTES_PER_EVENT = 12
OUT_BYTES_PER_EVENT = 60
STATE_BYTES_PER_NODE = 96
REPORT_PROGRAM = "compute_event_metrics"  # the jitted function's name


def report_bytes(nodes: int, lanes: int, real_events: int) -> int:
    """Algorithm bytes of one wave's report: its lanes' real events in and
    out, and the cluster's rows once a lane."""
    return (real_events * (IN_BYTES_PER_EVENT + OUT_BYTES_PER_EVENT)
            + lanes * nodes * STATE_BYTES_PER_NODE)


def report_device_seconds(trace: dict):
    """Device seconds of the report program inside the traced wave, from
    the profiler's module line (`trace_reduce.read_xplane`'s dict): the
    runs of the modules named after the report's jitted function, mean
    over the devices; None where the trace holds no such module."""
    if trace.get("wave") is None or not trace.get("devices"):
        return None
    w0, w1 = trace["wave"]
    per_device = []
    for dev in trace["devices"].values():
        per_device.append(sum(
            min(e, w1) - max(s, w0) for name, s, e in dev["modules"]
            if REPORT_PROGRAM in name and e > w0 and s < w1))
    total = sum(per_device) / len(per_device)
    return total or None
