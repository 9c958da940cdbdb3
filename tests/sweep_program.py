"""Helpers for tests that look at the sweep's program without running it:
capture the jitted vmapped replay with the shapes a cell calls it on, list
the large `copy` operations of a compiled module's scan, its loops and how
they nest, the operations of a loop that produce, or read, a given
shape, its gathers, and the arrays the module gives back."""

import json
import math
import os
import re

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmark", "configs")


class _Captured(Exception):
    pass


def capture_sweep(sim, trace, weights, seeds, run: bool = False, **kw):
    """(fn, shapes, lanes): the wrapper schedule_pods_sweep dispatches
    (driver._sweep_engine's, read off the operands), the ShapeDtypeStructs
    of its operands, and the sweep's lanes, or None when the sweep is
    stopped before it runs (`run=False`). `kw` goes to the sweep
    (lane_pods, fault_specs)."""
    from tpusim.sim import driver

    called = {}
    real = driver._dispatch_counting_lane_sites

    def spy(fn, lanes, *args):
        called["fn"] = fn
        called["shapes"] = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        if not run:
            raise _Captured()
        return real(fn, lanes, *args)

    driver._dispatch_counting_lane_sites = spy
    lanes = None
    try:
        lanes = driver.schedule_pods_sweep(sim, trace, weights, seeds, **kw)
    except _Captured:
        pass
    finally:
        driver._dispatch_counting_lane_sites = real
    return called["fn"], called["shapes"], lanes


def cell_simulator(nodes, depth: int, seed: int = 7,
                   config: str = "synth100k", **over):
    """One of the benchmark's configurations at `nodes` nodes (None: as
    the file has it): the Simulator and its trace of `depth` creates."""
    from benchmark.drivers import wave
    from benchmark.lib import inputs

    with open(os.path.join(CONFIGS, f"{config}.json")) as f:
        config = json.load(f)
    if nodes is not None:
        config["cluster"]["nodes"] = nodes
    node_list, pods = inputs.build(config, seed, depth)
    cfg = wave.simulator_config(config["simulator"], seed, profile=False,
                                **over)
    sim = wave.build_simulator(node_list, pods, cfg)
    return sim, sim.prepare_pods()[:depth], cfg


def cell_weights(cfg, lanes: int):
    return np.tile(np.asarray([w for _, w in cfg.policies], np.int32),
                   (lanes, 1))


# ------------------------------------------------------------ HLO text
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(?[^=]*?\)?) ([\w\-]+)\((.*)$")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")
_CALLED = re.compile(
    r"(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def _computations(text: str) -> dict:
    comps, cur = {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _called(lines) -> set:
    out = set()
    for line in lines:
        for one, many in _CALLED.findall(line):
            if one:
                out.add(one)
            out.update(n.strip().lstrip("%") for n in many.split(",") if n)
    return out


_WHILE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) while\(")


def while_loops(text: str) -> list:
    """(computation, instruction, result shape) of every `while` of a
    compiled module."""
    return [(name, m.group(1), m.group(2))
            for name, lines in _computations(text).items()
            for m in map(_WHILE.match, lines) if m]


def loop_bodies(text: str) -> dict:
    """{body computation: computation that holds its `while`} for every
    loop of a compiled module: a body whose holder is itself a body is an
    inner loop."""
    return {re.search(r"body=%?([\w.\-]+)", line).group(1): name
            for name, lines in _computations(text).items()
            for line in lines if _WHILE.match(line)}


def _reachable(comps: dict, root: str) -> set:
    reach, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in reach or name not in comps:
            continue
        reach.add(name)
        todo.extend(_called(comps[name]))
    return reach


def _instructions_in(text: str, root: str):
    """(computation, parsed instruction, line, computations) for every
    instruction of `root` and the computations it calls (inner loops,
    branches, fusions)."""
    comps = _computations(text)
    for name in sorted(_reachable(comps, root)):
        for line in comps[name]:
            m = _INSTRUCTION.match(line)
            if m:
                yield name, m, line, comps


_ROOT = re.compile(r"^\s*ROOT %?[\w.\-]+ = (.*?) [\w\-]+\(")


def entry_results(text: str) -> list:
    """The arrays a compiled module gives back, in order: ("s32", (4, 10))
    for every element of its ENTRY computation's ROOT."""
    lines = text.splitlines()
    at = next(i for i, line in enumerate(lines) if line.startswith("ENTRY "))
    for line in lines[at + 1:]:
        if line.startswith("}"):
            break
        m = _ROOT.match(line)
        if m:
            return [(dt, tuple(int(d) for d in dims.split(",") if d))
                    for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                               m.group(1))]
    raise ValueError("the module's ENTRY computation has no ROOT")


def producers_in(text: str, root: str, shape: str) -> list:
    """(computation, name, opcode) of every instruction in `root` and the
    computations it calls (inner loops, branches, fusions) whose result
    matches the regular expression `shape`, less those that only hand a
    buffer on (parameter, get-tuple-element, tuple, bitcast)."""
    return [(name, m.group(1), m.group(3))
            for name, m, _, _ in _instructions_in(text, root)
            if re.search(shape, m.group(2)) and m.group(3) not in (
                "parameter", "get-tuple-element", "tuple", "bitcast")]


def gathers_in(text: str, root: str) -> list:
    """(computation, name, result shape) of every `gather` in `root` and
    the computations it calls: what the program there still reads through
    an index."""
    return [(name, m.group(1), m.group(2).strip())
            for name, m, _, _ in _instructions_in(text, root)
            if m.group(3) == "gather"]


def gather_slices_in(text: str, root: str) -> list:
    """gathers_in with each gather's `slice_sizes`: (computation, name,
    result shape, (sizes))."""
    return [(name, m.group(1), m.group(2).strip(), tuple(
                int(d) for d in re.search(
                    r"slice_sizes=\{([\d,]*)\}", line).group(1).split(",")))
            for name, m, line, _ in _instructions_in(text, root)
            if m.group(3) == "gather"]


def fusions_reading(text: str, root: str, shape: str) -> list:
    """(computation, name, result shape) of every fusion in `root` and
    the computations it calls whose fused computation takes a parameter
    that matches the regular expression `shape`: what reads an array of
    that shape there, and what it makes of it."""
    found = []
    for name, m, line, comps in _instructions_in(text, root):
        if m.group(3) != "fusion":
            continue
        fused = comps[re.search(r"calls=%?([\w.\-]+)", line).group(1)]
        if any(p and p.group(3) == "parameter"
               and re.search(shape, p.group(2))
               for p in map(_INSTRUCTION.match, fused)):
            found.append((name, m.group(1), m.group(2).strip()))
    return found


def fusion_shapes(text: str, root: str) -> list:
    """(fusion, [dims of its result(s) and of its fused computation's
    parameters]) for every fusion in `root` and the computations it calls:
    the arrays the program moves between fusions there."""
    found = []
    for _, m, line, comps in _instructions_in(text, root):
        if m.group(3) != "fusion":
            continue
        fused = comps[re.search(r"calls=%?([\w.\-]+)", line).group(1)]
        shapes = _SHAPE.findall(m.group(2))
        for p in map(_INSTRUCTION.match, fused):
            if p and p.group(3) == "parameter":
                shapes += _SHAPE.findall(p.group(2))
        found.append((m.group(1), [
            tuple(int(d) for d in dims.split(",") if d) for dims in shapes]))
    return found


def big_copies_in_scan(text: str, min_elems: int) -> list:
    """`copy` / `copy-start` instructions whose result holds at least
    `min_elems` elements, in the largest while body of a compiled module
    and every computation it calls (inner loops, branches, fusions). Each
    as (computation, name, result shape with layout, source line)."""
    comps = _computations(text)
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    if not bodies:
        raise ValueError("the module holds no while loop")
    scan = max(bodies, key=lambda b: len(comps.get(b, ())))
    found = []
    for name in sorted(_reachable(comps, scan)):
        for line in comps[name]:
            m = _INSTRUCTION.match(line)
            if not m or m.group(3) not in ("copy", "copy-start"):
                continue
            sizes = [math.prod(int(d) for d in dims.split(",") if d)
                     for dims in _SHAPE.findall(m.group(2))]
            if sizes and max(sizes) >= min_elems:
                found.append((name, m.group(1), m.group(2).strip(),
                              line.strip()[:300]))
    return found
