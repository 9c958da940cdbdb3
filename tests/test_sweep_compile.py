"""The benchmark cells' sweeps, compiled for a described TPU v5e (no chip:
the TPU's compiler is installed; nothing runs): the blocked scan of
synth100k may hold no copy of a whole carried array (ISSUE 27), the flat
scan of openb no loop over the lanes (ISSUE 28). tests/test_tpu.py holds the
same checks on the chip itself."""

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from tests import sweep_program
from tpusim.sim import lane_write

NODES, LANES, DEPTH = 100_000, 40, 512
OPENB_LANES, OPENB_DEPTH = 128, 64  # compiles in 6 s; the cell's in 36 s


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
    compiled = lowered.compile()
    found = sweep_program.big_copies_in_scan(
        compiled.as_text(), LANES * NODES)
    assert not found, "\n".join(f"{c}: {n} = copy -> {s}"
                                for c, n, s, _ in found)
    # the parent's program held 6.72 GB of temporaries, sixteen copies
    assert compiled.memory_analysis().temp_size_in_bytes < 3.5e9


def test_the_openb_flat_sweep_loops_over_events_only(one_chip):
    """1,213 nodes on the flat step body: XLA runs a scatter or a gather
    with one index row a lane as a `while` over the lanes (the parent's
    program held 21 at this size and 32 at the cell's 2,560 lanes x 512
    events, eleven-odd inside every scan step). In the dense form the only
    loop of the module is the event scan."""
    sim, trace, cfg = sweep_program.cell_simulator(
        None, OPENB_DEPTH, config="openb")
    assert len(sim.nodes) == 1213
    with lane_write.counting() as sites:
        fn, shapes, _ = sweep_program.capture_sweep(
            sim, trace, sweep_program.cell_weights(cfg, OPENB_LANES),
            list(range(OPENB_LANES)))
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), shapes)
        lowered = fn.lower(*shapes)
    assert len(sites) == 17 and len(sites.dense) == 22
    loops = sweep_program.while_loops(lowered.compile().as_text())
    assert len(loops) == 1, loops
    assert f"s32[{OPENB_LANES},1213,9]" in loops[0][2]  # the scan's carry
