"""Bytes the table step has to move, from shapes alone (ENGINES.md's
bytes-per-node reasoning, now divided by a measured time).

Per lane and per event the step reads the event's pod-type row of every
policy's score table and of the feasibility table over all N nodes
(4*n_pol + 1 bytes a node), picks a node, and rewrites that node's column
over all K pod types in the score tables, the device-choice table and the
feasibility table (4*n_pol + 4 + 1 bytes a type). The blocked select
reads block summaries instead of every node, so for it this is the flat
algorithm's traffic, an upper estimate of what it needs.
"""

from __future__ import annotations


def scan_bytes_per_lane_event(nodes: int, pod_types: int, policies: int) -> int:
    return nodes * (4 * policies + 1) + pod_types * (4 * policies + 5)


def stream_bytes_per_lane_event(nodes: int, pod_types: int, policies: int,
                                delete_share: float) -> float:
    """The same, averaged over a stream of which `delete_share` of the
    events are deletions. A deletion picks no node, so it reads no node row
    of any table (the select's `nodes * (4 * policies + 1)` bytes); the
    node it frees is dirty as a bound one is, so its column is rewritten
    over all K pod types as a creation's is. With no deletions this is
    `scan_bytes_per_lane_event`."""
    return (scan_bytes_per_lane_event(nodes, pod_types, policies)
            - delete_share * nodes * (4 * policies + 1))


def carry_bytes_per_lane(nodes: int, pod_types: int, policies: int,
                         pods: int, events: int) -> int:
    """What one lane of a wave carries through its scan, from shapes alone
    (FlatTableCarry / BlockedTableCarry, tpusim/sim/table_engine.py): the
    i32 score table of each policy, the i32 device-choice table and the
    bool feasibility table over pod types x nodes, the NodeState (96 bytes
    a node), placed/mask/failed (13 bytes a pod) and the event outputs (12
    bytes an event). The state a deployment holds; the program's
    temporaries come on top and are not in it."""
    return ((4 * policies + 5) * pod_types * nodes + 96 * nodes
            + 13 * pods + 12 * events)


def roofline_share_pct(bytes_moved: float, seconds: float,
                       peak_bytes_per_s: float) -> float:
    """Least time the bytes could take at the peak, over the time taken."""
    return 100.0 * (bytes_moved / peak_bytes_per_s) / seconds
