"""Device self time of one traced wave by the program's named scopes, at a
cell's own size on the chip (not run by the benchmark's own runs, nor by
pytest: start it by hand through the chip tool): `python
benchmark/tests/scopes_on_chip.py --workload <cell> --seed 11`.

The step body, the table build and the frag post-pass name their stages
with `jax.named_scope` (`tpusim.commit`, `.refresh`, `.summary`, `.select`,
`.table_build`, `.frag_postpass`). XLA keeps the scope path in each
operation's metadata; which stat of the trace's "XLA Ops" events holds it
depends on the backend, so this script looks for it: it runs one short
traced run of the cell, reads the same trace file the harness reduces,
looks for a `tpusim.` scope in each operation's own stats, in its name and
in the stats of its event metadata (which jax.profiler.ProfileData does
not show, so they are read from the file's wire format), and sums device
SELF time (trace_reduce.self_times) by the innermost scope, the remainder as
`unscoped`. It fails if an expected scope is nowhere in the trace, or if
the operations' self times do not add up to the device's busy time within
1 % (then the split is not one of the whole wave). The table and the top
operations with the scope each carries go to `chiprun_out/`.
"""

import argparse
import bisect
import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

import run as bench_run  # noqa: E402
from benchmark.lib import trace_reduce  # noqa: E402

SCOPES = ("tpusim.commit", "tpusim.refresh", "tpusim.summary",
          "tpusim.select", "tpusim.table_build", "tpusim.frag_postpass")
SCOPE_RE = re.compile(r"tpusim\.[a-z_]+")
UNSCOPED = "unscoped"
TOLERANCE = 0.01


def _varint(buf, at: int):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf, at: int, end: int):
    """(field number, value) of one protobuf message in buf[at:end]: an
    int for a varint, (start, end) for a length-delimited field; fixed
    64- and 32-bit fields are skipped."""
    while at < end:
        tag, at = _varint(buf, at)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, at = _varint(buf, at)
            yield number, value
        elif wire == 2:
            size, at = _varint(buf, at)
            yield number, (at, at + size)
            at += size
        elif wire in (1, 5):
            at += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")


def metadata_stats(path: str) -> dict:
    """{device plane: {event name: {stat name: text}}} from the EVENT
    METADATA of an .xplane.pb (XPlane.event_metadata, fields 4 and 5 of
    tsl/profiler/protobuf/xplane.proto). jax.profiler.ProfileData shows an
    event's own stats only; what XLA knows of an operation (its op_name
    with the scope path among it) is kept once per operation in its
    metadata, so it is read here from the wire format, lines skipped."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict = {}
    for number, span in _fields(buf, 0, len(buf)):
        if number != 1:  # XSpace.planes
            continue
        name, stat_names, events = "", {}, []
        for field, value in _fields(buf, *span):
            if field == 2:
                name = bytes(buf[value[0]:value[1]]).decode()
            elif field in (4, 5):  # map entries: key = 1, value = 2
                for part, inner in _fields(buf, *value):
                    if part == 2 and field == 5:
                        meta = dict(_fields(buf, *inner))  # XStatMetadata
                        if 2 in meta:
                            stat_names[meta.get(1, 0)] = bytes(
                                buf[meta[2][0]:meta[2][1]]).decode()
                    elif part == 2:
                        events.append(inner)
        if not name.startswith("/device:TPU:"):
            continue
        plane = out.setdefault(name, {})
        for inner in events:  # XEventMetadata: name = 2, display = 4, stats = 5
            names, stats = [], {}
            for field, value in _fields(buf, *inner):
                if field in (2, 4):
                    names.append(bytes(buf[value[0]:value[1]]).decode())
                elif field == 5:  # XStat: metadata_id = 1, str = 5, ref = 7
                    stat = dict(_fields(buf, *value))
                    key = stat_names.get(stat.get(1, 0), str(stat.get(1)))
                    if 5 in stat:
                        stats[key] = bytes(
                            buf[stat[5][0]:stat[5][1]]).decode(errors="replace")
                    elif 7 in stat:
                        stats[key] = stat_names.get(stat[7], "")
            for ev_name in names:
                plane.setdefault(ev_name, {}).update(stats)
    return out


def scope_of(stats, carriers: dict) -> str:
    """The innermost `tpusim.` scope any of the (source, text) pairs
    names; counts the sources that carried one in `carriers`."""
    found = UNSCOPED
    for key, value in stats:
        if isinstance(value, str) and "tpusim." in value:
            carriers[key] = carriers.get(key, 0) + 1
            found = SCOPE_RE.findall(value)[-1]
    return found


def sources(ev, plane_meta: dict) -> list:
    """Everywhere the trace could keep an operation's scope path: the
    event's own stats, its name (the HLO line) and its metadata's stats."""
    return ([(f"event stat {k}", v) for k, v in ev.stats]
            + [("event name", ev.name)]
            + [(f"metadata stat {k}", v)
               for k, v in plane_meta.get(ev.name, {}).items()])


def by_scope(path: str, ops_cap: int = trace_reduce.OPS_CAP) -> dict:
    """Self seconds by scope over the traced wave of the xplane at `path`,
    scaled from the operations walked to the wave as reduce_wave does."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    meta = metadata_stats(path)
    planes = list(data.planes)
    wave = None
    for plane in planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WAVE_ANNOTATION:
                        wave = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
    if wave is None:
        raise ValueError("the trace holds no wave annotation")
    w0, w1 = wave
    devices = 0
    seconds: dict = {}
    carriers: dict = {}
    seen: dict = {}  # label -> where its scope was looked for
    stat_names: set = set()
    busy_s = walked_busy_s = walked_self_s = 0.0
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        devices += 1
        modules, events = [], []
        labels: dict = {}
        for line in plane.lines:
            if line.name == trace_reduce.MODULES_LINE:
                modules = sorted((ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name)
                                 for ev in line.events)
        starts = [m[0] for m in modules]
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                start, end = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                if end <= w0 or start >= w1:
                    continue
                at = bisect.bisect_right(starts, start) - 1
                module = modules[at][2] if at >= 0 else ""
                key = (module, ev.name)
                if key not in labels:  # an instruction's metadata is fixed
                    stats = sources(ev, meta.get(plane.name, {}))
                    stat_names.update(k for k, _ in stats)
                    labels[key] = (scope_of(stats, carriers) + "\t"
                                   + module.split("(")[0] + " "
                                   + ev.name[:trace_reduce.NAME_CHARS])
                    seen[labels[key]] = stats
                events.append((labels[key], start, end))
                if len(events) >= ops_cap:
                    break
        runs = [(s, e) for s, e, _ in modules]
        busy = trace_reduce.busy_seconds(runs, wave)
        busy_s += busy
        if not events:
            continue
        walked = (w0, max(e for _, _, e in events))
        walked_busy = trace_reduce.busy_seconds(runs, walked)
        walked_busy_s += walked_busy
        scale = busy / max(walked_busy, 1e-12)
        for label, secs in trace_reduce.self_times(events).items():
            walked_self_s += secs
            seconds[label] = seconds.get(label, 0.0) + scale * secs
    n = max(devices, 1)
    scopes: dict = {}
    for label, secs in seconds.items():
        scope = label.split("\t")[0]
        scopes[scope] = scopes.get(scope, 0.0) + secs / n
    top = sorted(seconds.items(), key=lambda kv: -kv[1])[:20]
    return {
        "devices": devices,
        "busy_s": busy_s / n,
        "walked_busy_s": walked_busy_s / n,
        "walked_self_s": walked_self_s / n,
        "by_scope_s": dict(sorted(scopes.items(), key=lambda kv: -kv[1])),
        "top_ops": [[label.split("\t")[0], label.split("\t")[1], secs / n]
                    for label, secs in top],
        "carriers": carriers,
        "stat_names_seen": sorted(stat_names),
        # everything the trace holds of each of those operations
        "top_ops_sources": [[[k, str(v)[:600]] for k, v in seen[label]]
                            for label, _ in top],
    }


def verdict(found: dict, expect) -> list:
    """What is wrong with the split, as sentences; empty when sound."""
    wrong = []
    if not found["carriers"]:
        wrong.append("no stat of the XLA Ops events names a tpusim. scope; "
                     f"stats seen: {found['stat_names_seen']}")
    for scope in expect:
        if scope not in found["by_scope_s"]:
            wrong.append(f"scope {scope} is nowhere in the trace")
    total = sum(found["by_scope_s"].values())
    if abs(total - found["busy_s"]) > TOLERANCE * found["busy_s"]:
        wrong.append(f"the scopes sum to {total:.4f} s, the device was busy "
                     f"{found['busy_s']:.4f} s")
    return wrong


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--expect", nargs="*", default=list(SCOPES))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend: the code path only")
    args = ap.parse_args()

    found = {}
    real = trace_reduce.read_xplane

    def reading(path, *a, **kw):
        found.update(by_scope(path))
        return real(path, *a, **kw)

    trace_reduce.read_xplane = reading
    try:
        result = bench_run.execute(bench_run.parse(
            ["--workload", args.workload, "--seed", str(args.seed),
             "--seconds", "1", "--trace", "1"]
            + ["--rehearse"] * args.rehearse))
    finally:
        trace_reduce.read_xplane = real
    wrong = [] if args.rehearse else verdict(found, args.expect)
    out = {"workload": args.workload, "seed": args.seed,
           "device": result["device"], "correct": result["correct"],
           "sound": not wrong, "wrong": wrong, **found}
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           f"scopes_{args.workload}.json"), "w") as f:
        json.dump(out, f, indent=1)
    busy = max(found.get("busy_s", 0.0), 1e-12)
    print(f"scope carried by stat(s): {found.get('carriers')}")
    print(f"{'scope':24s} {'self s':>10s} {'% of busy':>10s}")
    for scope, secs in found.get("by_scope_s", {}).items():
        print(f"{scope:24s} {secs:10.4f} {100 * secs / busy:10.2f}")
    print(f"{'device busy':24s} {found.get('busy_s', 0.0):10.4f}")
    for (scope, name, secs), held in zip(found.get("top_ops", []),
                                         found.get("top_ops_sources", [])):
        print(f"  {secs:9.4f} s  {scope:22s} {name[:90]}")
        if scope == UNSCOPED:  # what an operation of no stage carries
            print("".join(f"{'':16s}{k}: {v.splitlines()[0][:160]}\n"
                          for k, v in held if k.startswith("metadata stat")
                          and v), end="")
    for line in wrong:
        print(f"WRONG: {line}")
    print(json.dumps({"sound": not wrong, "correct": result["correct"]}))
    return 0 if not wrong and result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
