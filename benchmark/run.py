"""One run of one cell: `python benchmark/run.py --workload <cell> --seed
<n> --seconds <s> --trace <0|1>`. The last line of standard output is the
result object; its last key, and the last lines of standard error, are the
numbers `correct` compared, each beside its limit.

This file only resolves names. BENCHMARK.json names the cell's
configuration file and traffic mix; `traffic/<mix>.json` names its driver
(`drivers/<kind>.py`, a `run(ctx)`); each per-layer metric is
`layer_metrics/<name>.py` with a `read(run)`. A new cell, metric or driver
kind is new files plus BENCHMARK.json entries.
"""

import time

T_START = time.perf_counter()  # before any import that costs something

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float
    say: object


def say(text: str) -> None:
    print(f"[bench] {text}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module; a name with no file is an
    error that lists what is there."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        there = sorted(f[:-3] for f in os.listdir(os.path.join(HERE, kind))
                       if f.endswith(".py"))
        raise KeyError(f"no {kind}/{name}.py; there are: {there}")
    spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def by_name(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"BENCHMARK.json has no {what} {name!r}; it has: "
                   f"{[e['name'] for e in entries]}")


def plain(number):
    """A numpy scalar as the Python number json can print."""
    return number.item() if hasattr(number, "item") else number


def listed_for(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def check_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it. Anything but a TPU with the chips the
    cell asks for is an error; `--rehearse` (never given by the driver)
    lets the code paths run on whatever is there."""
    from benchmark.lib import device

    stamp = device.device_stamp()
    if not rehearse:
        if stamp["platform"] != "tpu":
            raise RuntimeError(f"JAX came up on {stamp['platform']!r} "
                               f"({stamp['kind']}), not on a TPU")
        if stamp["count"] < chips:
            raise RuntimeError(f"the cell needs {chips} chips, JAX sees "
                               f"{stamp['count']}")
        device.peaks_for(stamp["kind"])  # an unknown device is an error
    return stamp


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints nothing a "
                    "device metric may be taken from")
    return ap.parse_args(argv)


def execute(args) -> dict:
    """One run of one cell; returns the result object."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)

    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = by_name(bench["workloads"], args.workload, "workload")
    config_entry = by_name(bench["configs"], cell["config"], "config")
    config = load_json(os.path.join(REPO, config_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    driver = load_module("drivers", traffic["driver"])
    stamp = check_device(int(cell["chips"]), args.rehearse)

    ctx = Context(cell=cell, config=config, traffic=traffic, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  rehearse=args.rehearse, t_start=T_START, say=say)
    run = driver.run(ctx)
    run["device_kind"] = stamp["kind"]
    run["rehearsal"] = args.rehearse

    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if listed_for(m, cell["name"]):
                value = load_module("layer_metrics", m["name"]).read(run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if listed_for(m, cell["name"]):
                metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": bool(run["correct"]), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics,
              "device": {"platform": stamp["platform"], "kind": stamp["kind"],
                         "count": stamp["count"],
                         "memory_peak_bytes": run["memory_peak_bytes"]}}
    if args.trace and run.get("traced"):
        result["device"]["busy_s"] = run["traced"]["busy_s"]
        result["device"]["window_s"] = run["traced"]["window_s"]
        result["breakdown"] = {"device_ops": run["traced"]["device_ops"],
                               "idle_gaps": run["traced"]["idle_gaps"]}
    if args.rehearse:
        result["rehearsal"] = True
    # what the driver ignores and a reader of the line wants: the window's
    # walls, how many of them stalled, set-up by its parts
    if run.get("waves"):
        result["window"] = {
            "wall_s": [w["wall_s"] for w in run["waves"]],
            "stalled_waves": run.get("stalled_waves"),
            "warm_waves": run.get("warm_waves", 1),
            "setup_parts": run.get("setup_parts")}
    # last: every number `correct` compared, beside its limit
    result["checks"] = {what: {"value": plain(got), "limit": plain(limit)}
                        for what, got, limit in run.get("checks", [])}
    return result


def main(argv=None) -> int:
    result = execute(parse(argv))
    print(json.dumps(result), flush=True)
    for what, c in result["checks"].items():
        print(f"check: {what}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
