"""The benchmark cells' sweeps, compiled for a described TPU v5e (no chip:
the TPU's compiler is installed; nothing runs): the blocked scan of
synth100k may hold no copy of a whole carried array (ISSUE 27), the flat
scan of openb no loop over the lanes (ISSUE 28) and no whole-table
operation inside its per-event step (ISSUE 29), with one shared trace or
with a trace a lane (ISSUE 33), under one raw-score policy or under two with
a normalizer in the scan (ISSUE 34), and no gather of an entry a (lane, type)
in that step (ISSUE 35); under GpuClustering the event loops hold the
affinity counts nodes minor (ISSUE 46). tests/test_tpu.py holds the same
checks on the chip itself."""

import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from tests import sweep_program
from tpusim.sim import lane_write

NODES, LANES, DEPTH = 100_000, 40, 512
# compiles in 9 s, the cell's 2,560 x 512 in 30 s; from 256 lanes the
# compiler no longer keeps a whole table in fast memory between steps
OPENB_LANES, OPENB_DEPTH = 256, 64
# with a trace a lane the rows are read by a gather, and at 256 lanes the
# compiler brings the whole 40 MB table into fast memory for it inside the
# per-event loop; from 1,024 (159 MB a table, over the chip's 128 MiB) it
# reads the rows from HBM, as it must with the family cell's 1.16 GB
LANE_TRACE_LANES = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_parser_finds_a_copy_in_a_loop():
    text = """HloModule m
%body.1 (p: (s32[], s32[8,64])) -> (s32[], s32[8,64]) {
  %p = (s32[], s32[8,64]{1,0}) parameter(0)
  %g = s32[8,64]{1,0} get-tuple-element(%p), index=1
  %c = s32[8,64]{0,1} copy(%g)
  %w = (s32[], s32[8,64]{1,0}) while(%p), condition=%cond.2, body=%inner.3
  ROOT %t = (s32[], s32[8,64]{1,0}) tuple(%g, %c)
}
%inner.3 (q: (s32[], s32[8,64])) -> (s32[], s32[8,64]) {
  %q = (s32[], s32[8,64]{1,0}) parameter(0)
  %small = s32[8]{0} copy(%q)
  ROOT %big = s32[8,64]{1,0} copy(%q)
}
%cond.2 (r: (s32[], s32[8,64])) -> pred[] {
  ROOT %r = pred[] constant(true)
}
ENTRY %main (a: s32[8,64]) -> s32[8,64] {
  %a = s32[8,64]{1,0} parameter(0)
  %outside = s32[8,64]{0,1} copy(%a)
  ROOT %w = (s32[], s32[8,64]{1,0}) while(%a), condition=%cond.2, body=%body.1
}
"""
    found = sweep_program.big_copies_in_scan(text, 8 * 64)
    assert [(c, n) for c, n, _, _ in found] == [
        ("body.1", "c"), ("inner.3", "big")]
    assert sweep_program.while_loops(text) == [
        ("body.1", "w", "(s32[], s32[8,64]{1,0})"),
        ("main", "w", "(s32[], s32[8,64]{1,0})")]


def test_the_parser_finds_the_gathers_of_a_loop():
    text = """HloModule m
%fused.7 (a: s32[4,15,1,8], i: s32[60]) -> s32[60] {
  %a = s32[4,15,1,8]{3,2,1,0} parameter(0)
  %i = s32[60]{0} parameter(1)
  ROOT %pick = s32[60]{0:T(1024)} gather(%a, %i), offset_dims={}
}
%body.1 (p: (s32[], s32[4,8])) -> (s32[], s32[4,8]) {
  %p = (s32[], s32[4,8]{1,0}) parameter(0)
  %g = s32[4,8]{1,0} get-tuple-element(%p), index=1
  %f = s32[60]{0} fusion(%x, %y), kind=kCustom, calls=%fused.7
  %row = s32[4,8]{1,0} gather(%g, %g), offset_dims={1}
  ROOT %t = (s32[], s32[4,8]{1,0}) tuple(%g, %row)
}
ENTRY %main (a: s32[4,8]) -> s32[4,8] {
  %a = s32[4,8]{1,0} parameter(0)
  %outside = s32[4]{0} gather(%a, %a), offset_dims={}
  ROOT %w = (s32[], s32[4,8]{1,0}) while(%a), condition=%cond.2, body=%body.1
}
"""
    assert sweep_program.gathers_in(text, "body.1") == [
        ("body.1", "row", "s32[4,8]{1,0}"),
        ("fused.7", "pick", "s32[60]{0:T(1024)}")]


def test_cell_sized_sweep_compiles_without_whole_carry_copies(one_chip):
    sim, trace, cfg = sweep_program.cell_simulator(NODES, DEPTH)
    fn, shapes, _ = sweep_program.capture_sweep(
        sim, trace, sweep_program.cell_weights(cfg, LANES),
        list(range(LANES)))
    assert shapes[9][0].shape[1] == 71  # K, the cell's pod types
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    with lane_write.counting() as sites:
        lowered = fn.lower(*shapes)
    # ten write sites in the step body and the commit's seven again in the
    # epilogue, none in the dense form: 100,000 nodes are a long axis, and
    # the lanes share the index of the short bookkeeping rows
    assert (len(sites), len(sites.dense)) == (17, 0)
    assert sites.table_pass_events == 0  # no table is written densely
    compiled = lowered.compile()
    _assert_the_capacity_leaves_leave_without_a_lane_axis(
        compiled.as_text(), LANES, NODES)
    found = sweep_program.big_copies_in_scan(
        compiled.as_text(), LANES * NODES)
    assert not found, "\n".join(f"{c}: {n} = copy -> {s}"
                                for c, n, s, _ in found)
    # the parent's program held 6.72 GB of temporaries, sixteen copies
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5e9


def _assert_the_capacity_leaves_leave_without_a_lane_axis(
        text, lanes, nodes, kept=2):
    """ISSUE 43: of a final state's seven [nodes] leaves the program gives
    back `kept` with the lane axis (cpu_left and mem_left; a fault plan's
    carry has one more, the step each node went down at) and the five that
    no step writes (types.CAPACITY_LEAVES) once, as they came in: the
    parent's module gave seven s32[lanes,nodes] and broadcast five of them
    from its own parameters. The other two leaves keep their lanes too.
    tests/test_sweep_shared.py holds the rule by value on every body."""
    results = sweep_program.entry_results(text)
    assert results.count(("s32", (lanes, nodes))) == kept, results
    assert results.count(("s32", (nodes,))) == 5, results
    for rest in ((lanes, nodes, 8), (lanes, nodes, 9)):  # gpu_left, aff_cnt
        assert results.count(("s32", rest)) == 1, results


def _assert_no_gather_a_lane_and_type(text, inner, lanes):
    """What the per-event loop reads through an index is one entry a lane
    ([lanes]: a rank, a pick), a lane's eight devices ([lanes,8]: Sub's
    `gpu_left[order]`) or a table row a lane ([lanes,(2,)1213]). The column
    computation's device pick is ops/resource.first_max, reductions alone:
    `dev_scores[argmax(dev_scores)]` was a gather to [lanes,k], k a type
    group's size, which the chip ran serialized at 10 ns an element (a
    third of the mix cell's scan: ISSUE 35). The take by request (ISSUE 37:
    each whole type's terms out of the terms of the type set's distinct
    requests) goes through ONE index the lanes share: a slice of it holds
    every lane, [lanes,1,(1,)T] out of [lanes,G,(1,)T], K_whole of them."""
    gathers = sweep_program.gather_slices_in(text, inner)
    assert gathers  # the loop is the right one: Sub's and the picks are there
    shared = [g for g in gathers if g[3][0] == lanes]
    for _, name, out, sizes in gathers:
        assert sizes[0] == lanes or re.match(
            rf"(s32|pred)\[{lanes}(,8|,(2,)?1213)?\]", out), (name, out, sizes)
    return shared


def _assert_one_hypothetical_a_request(text, inner, lanes, types, t):
    """FGD's whole-branch hypothetical (Sub, then the stacked [T,8,2] terms
    of the device vector it leaves) runs once a distinct REQUEST of the type
    set: no fusion of the per-event loop takes or gives an array of
    [lanes, K_whole, .., T, .., 8, ..] any more (the parent materialized
    f32[lanes,K_whole,1,T,8,1] and reduced it to [lanes,K_whole,T,2], the
    largest piece of every flat cell's scan); the block is
    [lanes, G, .., T, .., 8, ..], and each type takes its request's terms by
    the index the lanes share."""
    ks, kw = types.share.cpu.shape[-1], types.whole.cpu.shape[-1]
    g = types.requests.shape[0]
    assert g == 8 and types.request_of.shape == (kw,)  # no lane axis
    found = sweep_program.fusion_shapes(text, inner)

    def stacked(group):
        """_share_terms' stack and its sum, as the compiler shapes them."""
        return [(name, dims) for name, shapes in found for dims in shapes
                if dims in ((lanes, group, 1, t, 8, 1), (lanes, group, t, 2))]

    assert kw != g and not stacked(kw), stacked(kw)
    assert stacked(g)  # the same block, a request
    if kw != ks:
        # (the share branch holds a [lanes,K_share,1,T,8] block of its own)
        held = [(name, dims) for name, shapes in found for dims in shapes
                if dims[:2] == (lanes, kw) and t in dims[2:]
                and 8 in dims[dims.index(t, 2) + 1:]]
        assert not held, held
    shared = _assert_no_gather_a_lane_and_type(text, inner, lanes)
    # two [T] terms and the total a column; the scan unrolls by 4
    assert sorted(out.split("{")[0] for _, _, out, _ in shared) == sorted(
        [f"f32[{lanes},{kw},{t}]"] * 8 + [f"f32[{lanes},{kw}]"] * 4), shared


def _assert_the_affinity_add_left_the_event_loops(text, loops, outer, lanes):
    """ISSUE 42: no scoring kernel of these programs reads aff_cnt, so the
    flat body's commit does not add into it: the event loops neither carry
    `s32[lanes,1213,9]` (unread and unwritten, it passes them by; the
    parent carried it through both and wrote it every event, a
    `select_add_fusion` over the whole leaf) nor hold an operation that
    produces an array of that shape. What the scans do carry is the rest of
    the node state (`gpu_left`). The leaf is made after them, under
    `tpusim.affinity` (table_engine.chunk_affinity): no scatter there."""
    leaf = rf"s32\[{lanes},1213,9\]"
    for holder, _, carried in loops:
        assert f"s32[{lanes},1213,8]" in carried  # the scan's carry
        assert not re.search(leaf, carried), (holder, carried[:200])
    assert not sweep_program.producers_in(text, outer, leaf)
    made = [line for line in text.splitlines() if "tpusim.affinity" in line]
    assert made  # the epilogue is in the module
    assert not [line for line in made if re.search(r" scatter\(", line)]


def _lane_operands(operands, sim, trace, lanes):
    """The sweep's keywords for `operands`: copies of the trace a lane,
    and with "typical pods a family" two typical-pod sets, lanes in turn."""
    if operands == "one shared trace":
        return {}
    kw = {"lane_pods": [trace] * lanes}
    if operands == "typical pods a family":
        from tpusim.sim.typical import pad_typical_pods
        from tpusim.types import make_typical_pods

        other = pad_typical_pods(make_typical_pods(
            [(4000 + 100 * i, 250 + 10 * i, 1, 0, 1 / 70) for i in range(70)]))
        assert (sim.typical.cpu.shape, other.cpu.shape) == ((48,), (80,))
        kw["lane_typical"] = [sim.typical, other] * (lanes // 2)
    return kw


@pytest.mark.parametrize("operands", [
    "one shared trace", "a trace a lane", "typical pods a family"])
def test_the_openb_flat_sweep_loops_over_events_only(one_chip, operands):
    """1,213 nodes on the flat step body: XLA runs a scatter or a gather
    with one index row a lane as a `while` over the lanes (PR 27's program
    held 21 at 128 lanes and 32 at the cell's 2,560 lanes x 512 events,
    eleven-odd inside every scan step). In the dense form the loops of the
    module are the event loops and nothing else: the scan over groups of
    FLAT_GROUP_EVENTS events and, inside it, the scan over a group's
    events. The step inside the inner loop produces no array of a whole
    table's shape (its column goes into the pending block); the flush in
    the outer loop is one fusion a table, written in place.

    With a trace a lane (ISSUE 33; pods, type ids and event streams carry
    the lane axis, the bookkeeping rows' writes and the three picks out of
    the pending block take the dense form: 31 dense sites where the shared
    trace has 22) it is the same two loops: what reads a table inside the
    per-event loop is a row gather a lane (`feas_tbl[t_id]`,
    `score_tbl[i, t_id]`, and read_entry's row of `sdev_tbl`),
    [lanes, K, N] -> [lanes, N], never a reduction of the whole table to
    [lanes]. Lanes of two workload families (ISSUE 32:
    typical pods and score tables stacked a SET and a set index a lane)
    are the same program with two more picks in front of the scan: each
    lane's rows of the stacked sets are selects over the lane axis, no
    loop."""
    from tpusim.sim.table_engine import FLAT_GROUP_EVENTS

    sim, trace, cfg = sweep_program.cell_simulator(
        None, OPENB_DEPTH, config="openb")
    assert len(sim.nodes) == 1213
    own = operands != "one shared trace"
    families = operands == "typical pods a family"
    lanes = LANE_TRACE_LANES if own else OPENB_LANES
    with lane_write.counting() as sites:
        fn, shapes, _ = sweep_program.capture_sweep(
            sim, None if own else trace,
            sweep_program.cell_weights(cfg, lanes), list(range(lanes)),
            **_lane_operands(operands, sim, trace, lanes))
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        lowered = fn.lower(*shapes)
    # counted while the program is traced: the epilogue's jit (`finish`,
    # seven writes, all dense) is served from the process's cache where "a
    # trace a lane" has traced it on the same shapes before
    # (ISSUE 42: one write site, a dense one, fewer than before: the body's
    # commit leaves aff_cnt to chunk_affinity, 17 -> 16, 31 -> 30, 22 -> 21)
    assert (len(sites), len(sites.dense)) in (
        ((16, 30), (9, 23)) if families else ((16, 30 if own else 21),))
    assert sites.table_pass_events == FLAT_GROUP_EVENTS
    assert OPENB_DEPTH % FLAT_GROUP_EVENTS == 0  # no tail group to trace
    assert shapes[1].cpu.shape == (
        (lanes, OPENB_DEPTH) if own else (OPENB_DEPTH,))
    assert len(shapes[3].shape) == (2 if own else 1)  # a stream a lane
    if families:
        # two sets on the larger one's bucket, two table sets, a set a lane
        assert shapes[5].cpu.shape == (2, 80)
        assert shapes[9][0].shape[:2] == (2, 1)
        assert shapes[9][1].shape[0] == 2 and shapes[9][2].shape[0] == 2
        assert shapes[-1].shape == (lanes,)
    compiled = lowered.compile()
    text = compiled.as_text()
    k = shapes[9][0].shape[-2]  # the trace's pod types at this depth
    table = rf"\[{lanes},(1,)?{k},1213\]"

    # the loops: one over the groups (it carries the node state and the
    # tables), one over a group's events inside it; none over the lanes
    loops = sweep_program.while_loops(text)
    bodies = sweep_program.loop_bodies(text)
    (outer,) = [b for b, holder in bodies.items() if holder not in bodies]
    (inner,) = [b for b, holder in bodies.items() if holder == outer]
    # (64 events are one block of chunk_affinity's sum: no third loop)
    assert len(loops) == 2, loops
    _assert_the_affinity_add_left_the_event_loops(text, loops, outer, lanes)
    _assert_the_capacity_leaves_leave_without_a_lane_axis(text, lanes, 1213)
    held = {holder: carried for holder, _, carried in loops}
    assert re.search(rf"s32{table}", held[bodies[outer]])
    assert f"s32[{lanes},{FLAT_GROUP_EVENTS},{k}]" in held[outer]

    # no whole-table operation inside the per-event step
    assert not sweep_program.producers_in(text, inner, table)
    _assert_one_hypothetical_a_request(
        text, inner, lanes, shapes[2], shapes[5].cpu.shape[-1])
    if own:
        # what takes a table in there is a row gather, [lanes, N] out: the
        # step's three reads an event (the scan unrolls by 4), none a
        # reduction of the table to [lanes]. (The lanes of a shared trace
        # slice their one row inside whatever fusion reads it.)
        reads = sweep_program.fusions_reading(text, inner, table)
        assert len(reads) == 3 * 4, reads
        for _, name, out in reads:
            assert re.match(rf"(s32|pred)\[{lanes},1213\]", out), (name, out)
    # the flush: one select-chain fusion a table, in the outer loop only
    flush = [(c, n, op) for c, n, op in sweep_program.producers_in(
        text, outer, table) if op == "fusion"]
    assert len(flush) == 3 and {c for c, _, _ in flush} == {outer}, flush
    if not own:
        # written in place: the temporaries hold the tables (4 + 4 + 1
        # bytes an entry) once, not twice
        tables = lanes * k * 1213 * 9
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * tables


MIX = (("PWRScore", 500), ("FGDScore", 500))


@pytest.mark.parametrize("operands", ["one shared trace", "a trace a lane"])
def test_the_normalized_two_policy_sweep_loops_over_events_only(
        one_chip, operands):
    """The fork's PWR+FGD mix on openb (ISSUE 34): a second raw-score
    table, PWR's NormalizeScore (global extrema over the feasible nodes,
    every event) and the weighted total of two policies in the grouped flat
    body, a weight row a lane. The module still holds the two event loops
    and none over the lanes. Inside the per-event loop nothing produces a
    whole table, and what takes a table in gives at least a row a lane: the
    extrema reduce ROWS ([lanes, N] -> [lanes]), never a table."""
    from tpusim.sim.table_engine import FLAT_GROUP_EVENTS

    sim, trace, cfg = sweep_program.cell_simulator(
        None, OPENB_DEPTH, config="openb", policies=MIX)
    own = operands != "one shared trace"
    lanes = LANE_TRACE_LANES if own else OPENB_LANES
    rows = np.asarray([[500, 500], [100, 900], [50, 950]], np.int32)
    with lane_write.counting() as sites:
        fn, shapes, _ = sweep_program.capture_sweep(
            sim, None if own else trace, rows[np.arange(lanes) % 3],
            list(range(lanes)), **_lane_operands(operands, sim, trace, lanes))
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        lowered = fn.lower(*shapes)
    # with a type id a lane the second policy's pending column is one more
    # pick out of the block (lane_write.read_pending's dense form)
    # (less the commit's aff_cnt add since ISSUE 42: 17, 32 | 22 before)
    assert (len(sites), len(sites.dense)) == (16, 31 if own else 21)
    assert sites.table_pass_events == FLAT_GROUP_EVENTS
    assert shapes[7].shape == (lanes, 2)  # a weight row a lane
    assert "tpusim.normalize" in lowered.as_text(debug_info=True)
    text = lowered.compile().as_text()
    k = shapes[9][1].shape[-2]  # the trace's pod types at this depth
    assert shapes[9][0].shape[-3:] == (2, k, 1213)  # two raw-score tables
    table = rf"\[{lanes},(1,|2,)?{k},1213\]"

    loops = sweep_program.while_loops(text)
    bodies = sweep_program.loop_bodies(text)
    (outer,) = [b for b, holder in bodies.items() if holder not in bodies]
    (inner,) = [b for b, holder in bodies.items() if holder == outer]
    assert len(loops) == 2, loops
    _assert_the_affinity_add_left_the_event_loops(text, loops, outer, lanes)
    assert not sweep_program.producers_in(text, inner, table)
    # PWR's kernel has no branches: its share path, and the pick in it, runs
    # over the whole-GPU type group too, so two groups' sizes are at stake;
    # FGD takes its whole types by request beside it
    _assert_one_hypothetical_a_request(
        text, inner, lanes, shapes[2], shapes[5].cpu.shape[-1])
    if own:
        # a row a lane, or both policies' rows. (The lanes of a shared
        # trace slice their one row inside whatever fusion reads it, the
        # extrema's reduction of that row to [lanes] among them.)
        reads = sweep_program.fusions_reading(text, inner, table)
        assert reads
        for _, name, out in reads:
            assert re.match(rf"(s32|pred)\[{lanes},(2,)?1213\]", out), (
                name, out)


CLUSTERING = (("GpuClusteringScore", 1000),)


def test_the_clustering_sweep_carries_the_counts_nodes_minor(one_chip):
    """GpuClustering with `best` devices and a trace a lane on openb's
    1,213 nodes (ISSUE 46): its kernel reads the dirty node's nine affinity
    counts every event, so the commit's add stays in the event loop
    (`tpusim.commit.affinity` inside it) and so does the read. The loops
    carry the leaf as the body holds it, s32[lanes,9,1213] with the nodes
    (or the lanes) minor: every tile full, where s32[lanes,1213,9]{2,1,0}
    used nine of a tile's 128 minor entries in each of the five passes an
    unrolled iteration makes (four column reads, one of them fused with the
    four adds). Nothing inside the loops produces or reads an array of the
    [N, 9] form, which is transposed before and after them, and no copy of
    the leaf stands in a loop."""
    from tpusim.sim.step import COMMIT_AFFINITY_SCOPE
    from tpusim.sim.table_engine import FLAT_GROUP_EVENTS

    sim, trace, cfg = sweep_program.cell_simulator(
        None, OPENB_DEPTH, config="openb", policies=CLUSTERING,
        gpu_sel_method="best")
    lanes = LANE_TRACE_LANES
    with lane_write.counting() as sites:
        fn, shapes, _ = sweep_program.capture_sweep(
            sim, None, sweep_program.cell_weights(cfg, lanes),
            list(range(lanes)), **_lane_operands("a trace a lane", sim,
                                                 trace, lanes))
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        lowered = fn.lower(*shapes)
    # the FGD program's sites and the add, a dense write, back among them;
    # the column read is one site where the row read of [N, 9] was one
    assert (len(sites), len(sites.dense)) == (17, 31)
    assert sites.table_pass_events == FLAT_GROUP_EVENTS
    text = lowered.compile().as_text()
    loops = sweep_program.while_loops(text)
    bodies = sweep_program.loop_bodies(text)
    (outer,) = [b for b, holder in bodies.items() if holder not in bodies]
    (inner,) = [b for b, holder in bodies.items() if holder == outer]
    assert len(loops) == 2, loops

    rows, held = rf"s32\[{lanes},1213,9\]", rf"s32\[{lanes},9,1213\]"
    for holder, _, carried in loops:
        assert not re.search(rows, carried), (holder, carried[:200])
        (minor,) = set(re.findall(held + r"\{(\d)", carried))
        assert minor in "20", carried[:200]  # nodes or lanes; 1: the classes
    assert not sweep_program.producers_in(text, outer, rows)
    assert not sweep_program.fusions_reading(text, outer, rows)
    copied = [(c, n, s) for c, n, s, _ in sweep_program.big_copies_in_scan(
        text, lanes * 9 * 1213) if re.match(held, s)]
    assert not copied, copied
    # the add is in the per-event loop, under its scope, on the held form
    adds = [line for _, m, line, _ in sweep_program._instructions_in(
        text, inner) if COMMIT_AFFINITY_SCOPE in line
        and m.group(3) == "add"]
    assert adds and all(re.match(held, m.group(2)) for m in map(
        sweep_program._INSTRUCTION.match, adds))
    # and the reads: four an unrolled iteration, a column a lane each
    reads = sweep_program.fusions_reading(text, inner, held)
    assert len(reads) == 4, reads
    for _, name, out in reads:
        assert re.match(rf"\(?s32\[{lanes},9\]", out), (name, out)


def _fault_specs(lanes):
    from tpusim.sim.faults import FaultConfig

    return [FaultConfig(
        mtbf_events=20 + i % 5, mttr_events=8, evict_every_events=7,
        seed=5 + i, backoff_base=2, backoff_cap=8, max_retries=2,
        queue_capacity=8) for i in range(lanes)]


@pytest.mark.parametrize("operands", ["a fault plan a lane"])
def test_the_plain_flat_sweeps_loop_over_events_only(one_chip, operands):
    """The sweep that keeps the plain flat body (driver._sweep_engine:
    fault plans) holds ONE loop, the scan over the events, and none over
    the lanes, although its event streams carry the lane axis: the
    bookkeeping rows' writes, whose index the lanes no longer share, take
    the dense form (28 dense sites where the shared trace has 22). One
    dense column write an event, no group."""
    sim, trace, cfg = sweep_program.cell_simulator(
        None, OPENB_DEPTH, config="openb")
    with lane_write.counting() as sites:
        fn, shapes, _ = sweep_program.capture_sweep(
            sim, trace, sweep_program.cell_weights(cfg, OPENB_LANES),
            list(range(OPENB_LANES)),
            fault_specs=_fault_specs(OPENB_LANES))
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        lowered = fn.lower(*shapes)
    assert (len(sites), len(sites.dense)) == (17, 28)
    assert sites.table_pass_events == 1
    assert shapes[1].cpu.shape == (OPENB_DEPTH,)
    assert shapes[3].shape[0] == OPENB_LANES  # a stream a lane
    text = lowered.compile().as_text()
    (loop,) = sweep_program.while_loops(text)
    assert f"s32[{OPENB_LANES},1213,9]" in loop[2]  # the scan's carry
    _assert_the_capacity_leaves_leave_without_a_lane_axis(
        text, OPENB_LANES, 1213, kept=3)


@pytest.mark.parametrize("operands", ["one shared trace", "a trace a lane"])
def test_a_stream_with_deletions_loops_over_events_only(one_chip, operands):
    """openb by its own clock (ISSUE 38: `use_timestamps`, so the stream
    holds a deletion a pod and the pod axis is half the event axis): the
    delete branch reads `placed[idx]` and `masks[idx]`, lane-batched
    bookkeeping rows, at an index the lanes share or, with a trace a lane,
    at one a lane; the commit gives the node back through the same rows it
    binds through. The module holds the two event loops and none over the
    lanes, and the per-event step produces no whole table."""
    from tpusim.io.trace import build_events
    from tpusim.sim.table_engine import FLAT_GROUP_EVENTS

    sim, trace, cfg = sweep_program.cell_simulator(
        None, OPENB_DEPTH, config="openb-clock", use_timestamps=True)
    kinds, _ = build_events(trace, True)
    assert (len(kinds), int((kinds == 1).sum())) == (2 * OPENB_DEPTH,
                                                     OPENB_DEPTH)
    own = operands != "one shared trace"
    lanes = LANE_TRACE_LANES if own else OPENB_LANES
    with lane_write.counting() as sites:
        fn, shapes, _ = sweep_program.capture_sweep(
            sim, None if own else trace,
            sweep_program.cell_weights(cfg, lanes), list(range(lanes)),
            **_lane_operands(operands, sim, trace, lanes))
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        lowered = fn.lower(*shapes)
    # the sites of a creation stream: a delete adds no access of its own
    # (ISSUE 42 took the commit's aff_cnt add out: 17, 31 | 22 before)
    assert (len(sites), len(sites.dense)) == (16, 30 if own else 21)
    assert sites.table_pass_events == FLAT_GROUP_EVENTS
    # pods on the events' bucket, a stream a lane where the traces are
    assert shapes[1].cpu.shape[-1] == shapes[3].shape[-1] == 2 * OPENB_DEPTH
    assert len(shapes[3].shape) == (2 if own else 1)
    text = lowered.compile().as_text()
    k = shapes[9][0].shape[-2]
    table = rf"\[{lanes},(1,)?{k},1213\]"
    loops = sweep_program.while_loops(text)
    bodies = sweep_program.loop_bodies(text)
    (outer,) = [b for b, holder in bodies.items() if holder not in bodies]
    (inner,) = [b for b, holder in bodies.items() if holder == outer]
    assert len(loops) == 2, loops
    _assert_the_affinity_add_left_the_event_loops(text, loops, outer, lanes)
    _assert_the_capacity_leaves_leave_without_a_lane_axis(text, lanes, 1213)
    assert not sweep_program.producers_in(text, inner, table)
    _assert_no_gather_a_lane_and_type(text, inner, lanes)


def test_a_whole_tuned_trace_a_lane_loops_over_events_only(one_chip):
    """The load cell's program at its own shape (ISSUE 41): 320 lanes, each
    a WHOLE tuned trace, so the bookkeeping rows hold 11,265 pods, over
    `lane_write`'s line for a short leaf: their three writes an event are
    the scatters `vmap` derives (25 + 1 dense sites where a short pod axis
    has 31), in place on the pods-minor layout the carry holds. The delete
    branch's read of `masks` is `lane_write.read_pod`'s masked reduction
    over that layout: as a plain gather it wanted the eight devices minor,
    and the parent's program copied the whole pred[320,11265,8] leaf to
    that layout after every event's write (9.6 s of a 20.2 s scan on the
    chip); as `read_row`'s tile gather it ran as four `while`s over the
    lanes' 640 windows. The module holds the two event loops, none over
    the lanes, and no copy of the leaf to another layout."""
    import json
    import os

    from benchmark.drivers import wave
    from benchmark.lib import inputs
    from tpusim.io.trace import load_node_csv, load_pod_csv

    with open(os.path.join(sweep_program.CONFIGS, "openb-load130.json")) as f:
        config = json.load(f)
    cfg = wave.simulator_config(config["simulator"], 42, profile=False,
                                report_per_event=True)
    sim = wave.build_simulator(load_node_csv(inputs.NODE_CSV),
                               load_pod_csv(inputs.POD_CSV), cfg)
    traces = [sim.prepare_pods(tuning_seed=s)
              for s in config["workload"]["tuning_seeds"]]
    lane_pods = [t for t in traces for _ in range(32)]
    lanes = len(lane_pods)
    with lane_write.counting() as sites:
        fn, shapes, _ = sweep_program.capture_sweep(
            sim, None, sweep_program.cell_weights(cfg, lanes),
            list(range(lanes)), lane_pods=lane_pods)
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        lowered = fn.lower(*shapes)
    # (17, 26) before ISSUE 42: the body's commit no longer adds into
    # aff_cnt, the leaf is made once a chunk from the events' own record
    assert (len(sites), len(sites.dense)) == (16, 25)
    assert shapes[1].cpu.shape == (lanes, 11264)  # a whole trace a lane
    compiled = lowered.compile()
    text = compiled.as_text()
    loops = sweep_program.while_loops(text)
    bodies = sweep_program.loop_bodies(text)
    # the two event loops, and behind them the one loop of the affinity
    # counts (ISSUE 42): over the 88 blocks of AFFINITY_EVENTS events, not
    # over the lanes; it alone carries, and writes, s32[lanes,1213,9]
    from tpusim.sim.table_engine import AFFINITY_EVENTS

    blocks = 11264 // AFFINITY_EVENTS
    assert len(loops) == 3, loops
    (counts,) = [carried for _, _, carried in loops
                 if f"s32[{lanes},1213,9]" in carried]
    assert f"s32[{blocks},{lanes},{AFFINITY_EVENTS}]" in counts
    scans = [loop for loop in loops if loop[2] is not counts]
    (outer,) = [holder for holder in bodies.values() if holder in bodies]
    _assert_the_affinity_add_left_the_event_loops(text, scans, outer, lanes)
    masks = rf"pred\[{lanes},(11265,8|8,11265)\]"
    relaid = [(c, n, s) for c, n, s, _ in sweep_program.big_copies_in_scan(
        text, lanes * 11265 * 8)
        if re.match(masks, s) and not n.startswith("copy-start")]
    assert not relaid, relaid
    # the parent's program held 1.89 GB of temporaries, the four copies
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9
