"""sim/lane_write.py: under jax.vmap every access goes through the module's
own batching rule and must equal, bit for bit, vmap of the plain expression
the step bodies used to hold; without vmap it IS that expression. Every
access is held at two sizes: a SHORT node axis takes the dense form (masks
over the whole leaf, no scatter, no gather), a LONG one (BLOCKED_MIN_NODES
or more) the scatters and window gathers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from tpusim.sim import lane_write as lw

from tpusim.sim.table_engine import BLOCKED_MIN_NODES

L, K, POL, P, BSZ = 5, 6, 2, 11, 8
# nodes, and nodes padded to whole blocks as the blocked tables are
SIZES = {"short": (37, 40), "long": (BLOCKED_MIN_NODES + 37,
                                     BLOCKED_MIN_NODES + 40)}
LONG_N, LONG_PAD = SIZES["long"]
# the bookkeeping leaves (placed, masks, failed) have a pod axis
POD_ROWS = {"short": P, "long": BLOCKED_MIN_NODES + P}


def _rng():
    return np.random.default_rng(7)


def _ints(shape, lo=-50, hi=50):
    return jnp.asarray(_rng().integers(lo, hi, shape), jnp.int32)


def _bools(shape):
    return jnp.asarray(_rng().integers(0, 2, shape).astype(bool))


# indices a sweep produces: the same node in several lanes, node 0, the
# last real node / column, and the dummy bookkeeping row [P]
def _node_idx(n):
    return jnp.asarray([3, 0, n - 1, 3, 3], jnp.int32)


def _pod_idx(p):
    return jnp.asarray([p, 0, p - 1, 4, 4], jnp.int32)


# which operands carry the lane axis: everything (a steady scan step), the
# leaf alone shared (first pass of a vmapped scan: tables and state come in
# unbatched), the index shared by the lanes (one event stream), and only
# the leaf batched
PATTERNS = {
    "all": (True, True, True),
    "leaf_shared": (False, True, True),
    "index_shared": (True, True, False),
    "leaf_only": (True, False, False),
}


def _axes(args, batched):
    """Operands with the lane axis dropped where `batched` says shared, and
    the matching in_axes."""
    out = tuple(a if b else a[0] for a, b in zip(args, batched))
    return out, tuple(0 if b else None for b in batched)


def _one_lane(args, axes):
    """Lane 0 of the operands: not vmapped, the plain expression itself."""
    return tuple(a[0] if ax == 0 else a for a, ax in zip(args, axes))


def _same(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, (g, w)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------- columns
def _plain_column(tbl, col, idx):
    return lax.dynamic_update_slice(
        tbl, col[..., None], (0,) * (tbl.ndim - 1) + (idx,))


def _plain_column_block(tbl, col, idx):
    out = _plain_column(tbl, col, idx)
    start = (idx // BSZ) * BSZ
    return out, lax.dynamic_slice(
        out, (0,) * (tbl.ndim - 1) + (start,), tbl.shape[:-1] + (BSZ,))


COLUMN_LEAVES = {
    "score": lambda n: (_ints((L, POL, K, n)), _ints((L, POL, K))),
    "sdev": lambda n: (_ints((L, K, n), -1, 8), _ints((L, K), -1, 8)),
    "feas": lambda n: (_bools((L, K, n)), _bools((L, K))),
}


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("block", [False, True])
@pytest.mark.parametrize("leaf", sorted(COLUMN_LEAVES))
def test_write_column_equals_vmap_of_the_plain_write(leaf, block, pattern,
                                                     size):
    n, n_pad = SIZES[size]
    tbl, col = COLUMN_LEAVES[leaf](n_pad)
    args, axes = _axes((tbl, col, _node_idx(n)), PATTERNS[pattern])
    if block:
        mine = lambda t, c, i: lw.write_column(  # noqa: E731
            t, c, i, block=((i // BSZ) * BSZ, BSZ))
        plain = _plain_column_block
    else:
        mine, plain = lw.write_column, _plain_column
    _same(jax.jit(jax.vmap(mine, in_axes=axes))(*args),
          jax.vmap(plain, in_axes=axes)(*args))
    _same(mine(*_one_lane(args, axes)), plain(*_one_lane(args, axes)))


# ------------------------------------------------------------------- rows
def _aff(leaf, idx, val):
    return lw.add_row(leaf, (idx, jnp.int32(2)), val)


ROW_WRITES = {
    # name: n nodes, p pods -> (leaf, value a lane, index, mine, plain)
    "cpu_left": lambda n, p: (
        _ints((L, n)), _ints((L,)), _node_idx(n), lw.add_row,
        lambda a, i, v: a.at[i].add(v)),
    "mem_left": lambda n, p: (
        _ints((L, n)), _ints((L,)), _node_idx(n), lw.add_row,
        lambda a, i, v: a.at[i].add(v)),
    "gpu_left": lambda n, p: (
        _ints((L, n, 8)), _ints((L, 8)), _node_idx(n), lw.add_row,
        lambda a, i, v: a.at[i].add(v)),
    # node == -1 commits add a zero at the clipped row
    "gpu_left_zero_delta": lambda n, p: (
        _ints((L, n, 8)), jnp.zeros((L, 8), jnp.int32),
        jnp.zeros(L, jnp.int32), lw.add_row, lambda a, i, v: a.at[i].add(v)),
    "aff_cnt": lambda n, p: (
        _ints((L, n, 9)), _ints((L,)), _node_idx(n), _aff,
        lambda a, i, v: a.at[i, jnp.int32(2)].add(v)),
    "placed": lambda n, p: (
        _ints((L, p + 1)), _ints((L,)), _pod_idx(p), lw.set_row,
        lambda a, i, v: a.at[i].set(v)),
    "masks": lambda n, p: (
        _bools((L, p + 1, 8)), _bools((L, 8)), _pod_idx(p), lw.set_row,
        lambda a, i, v: a.at[i].set(v)),
    "failed": lambda n, p: (
        _bools((L, p + 1)), _bools((L,)), _pod_idx(p), lw.set_row,
        lambda a, i, v: a.at[i].set(v)),
}


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("leaf", sorted(ROW_WRITES))
def test_row_writes_equal_vmap_of_the_plain_update(leaf, pattern, size):
    arr, val, idx, mine, plain = ROW_WRITES[leaf](
        SIZES[size][0], POD_ROWS[size])
    leaf_b, val_b, idx_b = PATTERNS[pattern]
    args, axes = _axes((arr, idx, val), (leaf_b, idx_b, val_b))
    _same(jax.jit(jax.vmap(mine, in_axes=axes))(*args),
          jax.vmap(plain, in_axes=axes)(*args))
    _same(mine(*_one_lane(args, axes)), plain(*_one_lane(args, axes)))


ROW_READS = {
    "cpu_left": lambda n: _ints((L, n)),
    "gpu_left": lambda n: _ints((L, n, 8)),
    "aff_cnt": lambda n: _ints((L, n, 9)),
    "masks": lambda n: _bools((L, n, 8)),
}
READ_PATTERNS = {"all": (True, True), "leaf_shared": (False, True),
                 "index_shared": (True, False)}


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("pattern", sorted(READ_PATTERNS))
@pytest.mark.parametrize("keepdims", [True, False])
@pytest.mark.parametrize("leaf", sorted(ROW_READS))
def test_read_row_equals_vmap_of_the_plain_slice(leaf, keepdims, pattern,
                                                 size):
    n = SIZES[size][0]
    idx = _node_idx(n)
    if not keepdims:  # leaf[idx] wraps a negative index once
        idx = idx.at[3].set(-2)
    args, axes = _axes((ROW_READS[leaf](n), idx), READ_PATTERNS[pattern])
    mine = lambda a, i: lw.read_row(a, i, keepdims=keepdims)  # noqa: E731
    if keepdims:
        plain = lambda a, i: lax.dynamic_slice_in_dim(a, i, 1, 0)  # noqa: E731
    else:
        plain = lambda a, i: a[i]  # noqa: E731
    _same(jax.jit(jax.vmap(mine, in_axes=axes))(*args),
          jax.vmap(plain, in_axes=axes)(*args))
    _same(mine(*_one_lane(args, axes)), plain(*_one_lane(args, axes)))


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("leaf", ["sdev", "feas"])
def test_read_entry_equals_vmap_of_the_plain_slice(leaf, pattern, size):
    n, n_pad = SIZES[size]
    tbl, _ = COLUMN_LEAVES[leaf](n_pad)
    rows = jnp.asarray([0, K - 1, 2, 2, 5], jnp.int32)
    args, axes = _axes((tbl, rows, _node_idx(n)), PATTERNS[pattern])
    plain = lambda t, r, c: lax.dynamic_slice(  # noqa: E731
        t, (r, c), (1, 1))[0, 0]
    _same(jax.jit(jax.vmap(lw.read_entry, in_axes=axes))(*args),
          jax.vmap(plain, in_axes=axes)(*args))
    one = _one_lane(args, axes)
    _same(lw.read_entry(*one), plain(*one))


@pytest.mark.parametrize("rows", ["a row a lane", "one shared row"])
@pytest.mark.parametrize("leaf", ["sdev", "feas"])
def test_a_dense_read_entry_takes_the_row_then_the_entry(leaf, rows):
    """On a short leaf the entry is picked out of the lane's ROW, [L, N]:
    the row the lanes share sliced, a row a lane (a sweep of a trace a
    lane, ISSUE 33) gathered as the step gathers `feas_tbl[t_id]`. No
    reduction runs over the whole [L, K, N] table, and the node index is
    one a lane either way."""
    n, n_pad = SIZES["short"]
    tbl, _ = COLUMN_LEAVES[leaf](n_pad)
    args, axes = _axes(
        (tbl, jnp.asarray([0, K - 1, 2, 2, 5], jnp.int32), _node_idx(n)),
        (True, rows == "a row a lane", True))
    plain = lambda t, r, c: lax.dynamic_slice(  # noqa: E731
        t, (r, c), (1, 1))[0, 0]
    fn = jax.jit(jax.vmap(lw.read_entry, in_axes=axes))
    _same(fn(*args), jax.vmap(plain, in_axes=axes)(*args))
    kind = "i1" if leaf == "feas" else "i32"
    text = fn.lower(*args).as_text()
    reduces = [ln for ln in text.splitlines() if "stablehlo.reduce" in ln]
    assert len(reduces) == 1 and f"tensor<{L}x{n_pad}x{kind}>" in reduces[0]
    assert f"tensor<{L}x{K}x{n_pad}x{kind}>" not in reduces[0], reduces
    row_reads = [ln for ln in text.splitlines()
                 if f"(tensor<{L}x{K}x{n_pad}x{kind}>" in ln]
    # one window of a row: across the lanes, or one a lane
    window = f"1, 1, {n_pad}" if rows == "a row a lane" else f"{L}, 1, {n_pad}"
    assert len(row_reads) == 1 and "stablehlo.gather" in row_reads[0]
    assert f"slice_sizes = array<i64: {window}>" in row_reads[0], row_reads


PENDING = {"sdev": lambda: _ints((L, 4, K), -1, 8),
           "feas": lambda: _bools((L, 4, K))}


@pytest.mark.parametrize("pattern", sorted(READ_PATTERNS))
@pytest.mark.parametrize("leaf", sorted(PENDING))
def test_read_pending_equals_vmap_of_the_plain_slice(leaf, pattern):
    """Column `row` of a pending block [G, K]: the slice it always was,
    and with a row a lane a masked reduction over K, no gather (which
    would have the block carried slots-minor: ISSUE 33)."""
    rows = jnp.asarray([0, K - 1, 2, 2, 5], jnp.int32)
    args, axes = _axes((PENDING[leaf](), rows), READ_PATTERNS[pattern])
    plain = lambda c, r: lax.dynamic_index_in_dim(  # noqa: E731
        c, r, 1, keepdims=False)
    fn = jax.jit(jax.vmap(lw.read_pending, in_axes=axes))
    with lw.counting() as sites:
        text = fn.lower(*args).as_text()
    _same(fn(*args), jax.vmap(plain, in_axes=axes)(*args))
    one = _one_lane(args, axes)
    _same(lw.read_pending(*one), plain(*one))
    a_lane = READ_PATTERNS[pattern][1]
    assert (len(sites), len(sites.dense)) == (0, 1 if a_lane else 0)
    assert ("stablehlo.reduce" in text) == a_lane
    assert "stablehlo.gather" not in text or not a_lane


# ------------------------------------------------------- the rule's shape
def _step(tbl, left, col, idx, delta):
    tbl, blk = lw.write_column(tbl, col, idx, block=((idx // BSZ) * BSZ, BSZ))
    left = lw.add_row(left, idx, delta)
    return tbl, left, blk, lw.read_row(left, idx)


def _plain_step(tbl, left, col, idx, delta):
    tbl, blk = _plain_column_block(tbl, col, idx)
    left = left.at[idx].add(delta)
    return tbl, left, blk, lax.dynamic_slice_in_dim(left, idx, 1, 0)


def _step_args(size="long"):
    n, n_pad = SIZES[size]
    return (_ints((L, K, n_pad)), _ints((L, n, 8)), _ints((L, K)),
            _node_idx(n), _ints((L, 8)))


def test_reads_are_windows_batched_over_the_lanes():
    """What the rule is for on a long node axis: a vmapped step writes
    through the scatters vmap derives and reads through gathers of (rows,
    nodes) windows batched over the lane axis, none holding every row of
    its leaf, so the layout that serves the scatters serves the reads too
    (on the TPU; tests/test_sweep_compile.py)."""
    n, n_pad = LONG_N + 263, LONG_PAD + 344  # not a whole 128-node tile
    args = (_ints((L, K, n_pad)), _ints((L, n, 8)), _ints((L, K)),
            jnp.asarray([3, 0, n - 1, 3, 200], jnp.int32), _ints((L, 8)))
    text = jax.jit(jax.vmap(_step)).lower(*args).as_text()
    _same(jax.jit(jax.vmap(_step))(*args), jax.vmap(_plain_step)(*args))
    assert text.count('"stablehlo.scatter"') == 2
    # gathers that read a carried leaf (the rest pick from a gathered tile)
    gathers = [ln for ln in text.splitlines() if '"stablehlo.gather"' in ln
               and (f": (tensor<{L}x{K}x{n_pad}xi32>, " in ln
                    or f": (tensor<{L}x8x{n}xi32>, " in ln)]
    sizes = [ln.split("slice_sizes = array<i64: ")[1].split(">")[0]
             for ln in gathers]
    # the block of the [K, N] table; the tile that holds gpu_left's row
    assert sorted(sizes) == [f"1, {K // 2}, {BSZ}", "1, 4, 128"], sizes
    for ln in gathers:
        assert "operand_batching_dims = [0]" in ln, ln


def _every_access(tbl, sdev, left, aff, placed, col, idx, delta, pod):
    """Each access of the module once, as the step bodies call them."""
    tbl, blk = lw.write_column(tbl, col, idx, block=((idx // BSZ) * BSZ, BSZ))
    sdev = lw.write_column(sdev, col, idx)
    left = lw.add_row(left, idx, delta)
    aff = lw.add_row(aff, (idx, jnp.int32(2)), delta[0])
    placed = lw.set_row(placed, pod, idx)
    return (tbl, sdev, left, aff, placed, blk, lw.read_row(left, idx),
            lw.read_row(left, idx, keepdims=False),
            lw.read_entry(sdev, jnp.int32(2), idx))


def _every_access_args(size):
    n, n_pad = SIZES[size]
    return (_ints((L, K, n_pad)), _ints((L, K, n_pad)), _ints((L, n, 8)),
            _ints((L, n, 9)), _ints((L, POD_ROWS[size] + 1)), _ints((L, K)),
            _node_idx(n), _ints((L, 8)), _pod_idx(POD_ROWS[size]))


# the affinity counts as the flat event loop holds them where a kernel
# reads them: a small leaf with the NODES on its last axis, one entry added
# and one column read an event. Operands: which of (leaf, class, node,
# delta) carry the lane axis
NODES_LAST = {
    "unbatched": None,
    "a shared index": (True, False, False, False),
    "an index and a class a lane": (True, True, True, True),
    "the leaf shared": (False, True, True, True),
    # a commit that touched no node (`owns` False): 0 at the clipped row
    "a no-op delta a lane": (True, True, True, True),
}


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("form", sorted(NODES_LAST))
def test_the_nodes_last_pair_equals_the_plain_add_and_the_plain_column(
        form, size):
    """add_entry is `.at[c, n].add(d)` and read_column `leaf[:, n]` as a
    [1, C] row, unbatched and under vmap; on a short node axis at width
    both are dense (no scatter, no gather, no loop), on a long one what
    vmap derives."""
    n = SIZES[size][0]
    leaf, node = _ints((L, 9, n)), _node_idx(n)
    cls = jnp.asarray([8, 0, 2, 2, 5], jnp.int32)
    delta = jnp.asarray([1, -1, 1, -1, 1], jnp.int32)
    if form.startswith("a no-op"):
        node, cls, delta = (jnp.zeros(L, jnp.int32),) * 3

    def plain_add(a, c, i, d):
        return a.at[c, i].add(d)

    def plain_column(a, i):
        return a[:, i][None]

    if NODES_LAST[form] is None:
        args = (leaf[0], cls[0], node[0], delta[0])
        _same(lw.add_entry(*args), plain_add(*args))
        _same(lw.read_column(leaf[0], node[0]), plain_column(leaf[0], node[0]))
        return
    args, axes = _axes((leaf, cls, node, delta), NODES_LAST[form])
    with lw.counting() as sites:
        add = jax.jit(jax.vmap(lw.add_entry, in_axes=axes))
        text = add.lower(*args).as_text()
    got = add(*args)
    _same(got, jax.vmap(plain_add, in_axes=axes)(*args))
    if form.startswith("a no-op"):
        _same(got, leaf)
    # (an index the lanes share stays the one update vmap derives)
    dense = size == "short" and any(NODES_LAST[form][1:])
    assert (len(sites), len(sites.dense)) == (1, int(dense))
    if dense:
        assert "stablehlo.scatter" not in text and "while" not in text
    read_axes = (axes[0], axes[2])
    with lw.counting() as sites:
        read = jax.jit(jax.vmap(lw.read_column, in_axes=read_axes))
        text = read.lower(args[0], args[2]).as_text()
    _same(read(args[0], args[2]),
          jax.vmap(plain_column, in_axes=read_axes)(args[0], args[2]))
    assert (len(sites), len(sites.dense)) == (0, int(size == "short"))
    if size == "short":
        assert "stablehlo.gather" not in text and "while" not in text


@pytest.mark.parametrize("size", sorted(SIZES))
def test_a_short_node_axis_is_served_without_scatter_or_gather(size):
    """The dense forms: on a short node axis the vmapped program of every
    access holds no scatter and no gather (what XLA runs as a loop over the
    lanes on the TPU), only selects and reductions over the whole leaf; a
    long axis keeps both. The choice follows from the leaf's shape alone."""
    args = _every_access_args(size)
    with lw.counting() as sites:
        text = jax.jit(jax.vmap(_every_access)).lower(*args).as_text()
    scatters = text.count('"stablehlo.scatter"')
    # gathers with one index row a lane; the slice of a table's row that
    # the lanes share is a gather with ONE index, a dynamic slice to XLA
    gathers = sum(1 for ln in text.splitlines() if '"stablehlo.gather"' in ln
                  and f", tensor<{L}x" in ln.split(") -> ")[0])
    assert len(sites) == 5
    if size == "short":
        assert (scatters, gathers) == (0, 0)
        assert "stablehlo.while" not in text
        assert len(sites.dense) == 8  # every site, the three reads too
    else:
        assert scatters == 5 and gathers >= 4
        assert len(sites.dense) == 0


def test_a_table_not_of_whole_blocks_keeps_the_window_read():
    """The dense block read views the table as whole blocks; a short table
    that is not (no caller makes one) writes densely and reads its block
    through the windows, from any start."""
    tbl, col = _ints((L, K, 37)), _ints((L, K))
    idx = _node_idx(37)
    mine = lambda t, c, i: lw.write_column(  # noqa: E731
        t, c, i, block=(jnp.minimum(i, 37 - BSZ), BSZ))

    def plain(t, c, i):
        out = _plain_column(t, c, i)
        return out, lax.dynamic_slice(
            out, (0, jnp.minimum(i, 37 - BSZ)), (K, BSZ))

    _same(jax.jit(jax.vmap(mine))(tbl, col, idx),
          jax.vmap(plain)(tbl, col, idx))


@pytest.mark.parametrize("k,dtype", [
    (71, jnp.int32), (71, jnp.bool_), (16, jnp.int32), (9, jnp.int32),
    (2, jnp.int32), (1, jnp.int32)])
def test_a_block_is_read_as_two_half_windows(k, dtype):
    """The dirty block of a [K, N] table comes in two windows of
    ceil(K / 2) rows (overlapping by a row when K is odd; one window when
    K is 1); equal to the plain slice for every K."""
    tbl = (_bools((L, k, LONG_PAD)) if dtype == jnp.bool_
           else _ints((L, k, LONG_PAD)))
    col = tbl[:, :, 0]
    idx = _node_idx(LONG_N)
    mine = lambda t, c, i: lw.write_column(  # noqa: E731
        t, c, i, block=((i // BSZ) * BSZ, BSZ))
    fn = jax.jit(jax.vmap(mine))
    _same(fn(tbl, col, idx), jax.vmap(_plain_column_block)(tbl, col, idx))
    text = fn.lower(tbl, col, idx).as_text()
    (gather,) = [ln for ln in text.splitlines() if '"stablehlo.gather"' in ln
                 and f"x{LONG_PAD}x" in ln.split("->")[0]]
    h = -(-k // 2)
    assert f"slice_sizes = array<i64: 1, {h}, {BSZ}>" in gather
    assert f"-> tensor<{L}x{min(k, 2)}x{h}x{BSZ}x" in gather, gather


@pytest.mark.parametrize("size", sorted(SIZES))
def test_the_unbatched_program_is_the_plain_one(size):
    one = tuple(x[0] for x in _step_args(size))
    plain = jax.jit(lambda t, a, c, i, d: (
        *_plain_column_block(t, c, i), a.at[i].add(d))).lower(*one).as_text()
    mine = jax.jit(lambda t, a, c, i, d: (
        *lw.write_column(t, c, i, block=((i // BSZ) * BSZ, BSZ)),
        lw.add_row(a, i, d))).lower(*one).as_text()
    for op in ("dynamic_update_slice", "dynamic_slice", "scatter", "gather",
               "custom_call"):
        assert mine.count(f"stablehlo.{op}") == plain.count(
            f"stablehlo.{op}"), op
    assert "stablehlo.gather" not in mine and "custom_call" not in mine
    assert mine.count("stablehlo.dynamic_update_slice") == 1


@pytest.mark.parametrize("size", sorted(SIZES))
def test_counting_sees_write_sites_once_and_only_under_vmap(size):
    dense = 3 if size == "short" else 0  # the read is a dense site too
    with lw.counting() as sites:
        jax.jit(_step).lower(*(x[0] for x in _step_args(size)))
    assert len(sites) == 0 and len(sites.dense) == 0
    with lw.counting() as sites:
        jax.jit(jax.vmap(_step)).lower(*_step_args(size))
    assert len(sites) == 2  # write_column and add_row; read_row is no write
    assert len(sites.dense) == dense

    def scanned(tbl, left, col, idx, delta):
        def body(carry, _):
            tbl, left = carry
            tbl, left, _, _ = _step(tbl, left, col, idx, delta)
            return (tbl, left), None
        return lax.scan(body, (tbl, left), None, length=3, unroll=2)[0]

    # tables and state come in shared: the scan's batching runs to a
    # fixpoint and visits each site more than once
    axes = (None, None, 0, 0, 0)
    args = tuple(a if ax == 0 else a[0]
                 for a, ax in zip(_step_args(size), axes))
    with lw.counting() as sites:
        got = jax.jit(jax.vmap(scanned, in_axes=axes))(*args)
    assert len(sites) == 2 and len(sites.dense) == dense
    want = jax.vmap(scanned, in_axes=axes)(*args)
    _same(got, want)


@pytest.mark.parametrize("size", sorted(SIZES))
def test_the_rule_can_be_vmapped_again(size):
    """A config axis over a seed axis: the rule's output is plain lax, so
    an outer vmap batches it like any other program."""
    args = jax.tree.map(lambda a: jnp.stack([a, a + 1 if a.dtype != bool
                                             else ~a]), _step_args(size))
    idx = _node_idx(SIZES[size][0])
    args = (args[0], args[1], args[2], jnp.stack([idx, idx[::-1]]), args[4])
    got = jax.jit(jax.vmap(jax.vmap(_step)))(*args)
    want = [jax.vmap(_step)(*(a[i] for a in args)) for i in range(2)]
    _same(got, jax.tree.map(lambda *x: jnp.stack(x), *want))


# ------------------------------------------------- a group of columns
SLOTS = 4


def _group_idx(n):
    """One index row a lane: a node named twice (the later slot wins), an
    unused slot (-1) in the middle and at the end, a lane with nothing
    pending, two nodes written alternately, the last node."""
    return jnp.asarray([[3, 3, -1, 0], [0, n - 1, n - 1, -1],
                        [-1, -1, -1, -1], [5, 4, 5, 4], [n - 1, 0, 3, 3]],
                       jnp.int32)


def _group_cols(leaf, n_pad):
    tbl, col = COLUMN_LEAVES[leaf](n_pad)
    cols = jnp.stack([jnp.roll(col, s, axis=-1) if s % 2 else ~col
                      if col.dtype == jnp.bool_ else col + s
                      for s in range(SLOTS)], axis=1)  # [L, SLOTS, ...]
    return tbl, cols


def _plain_columns(tbl, cols, idxs):
    """SLOTS sequential write_columns, a slot at -1 left out."""
    for s in range(SLOTS):
        tbl = jnp.where(idxs[s] >= 0,
                        _plain_column(tbl, cols[s], jnp.maximum(idxs[s], 0)),
                        tbl)
    return tbl


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("leaf", sorted(COLUMN_LEAVES))
def test_write_columns_equals_the_sequential_column_writes(leaf, pattern,
                                                           size):
    n, n_pad = SIZES[size]
    tbl, cols = _group_cols(leaf, n_pad)
    args, axes = _axes((tbl, cols, _group_idx(n)), PATTERNS[pattern])
    _same(jax.jit(jax.vmap(lw.write_columns, in_axes=axes))(*args),
          jax.vmap(_plain_columns, in_axes=axes)(*args))
    for lane in range(L if pattern == "all" else 1):
        one = tuple(a[lane] if ax == 0 else a for a, ax in zip(args, axes))
        _same(lw.write_columns(*one), _plain_columns(*one))
        # through write_column itself, the flat step's former form
        want = one[0]
        for s in range(SLOTS):
            if int(one[2][s]) >= 0:
                want = lw.write_column(want, one[1][s], one[2][s])
        _same(lw.write_columns(*one), want)


@pytest.mark.parametrize("size", sorted(SIZES))
def test_write_columns_is_one_pass_on_a_short_axis_and_counts_its_slots(size):
    """Dense (short axis, one index row a lane): selects over the leaf, no
    scatter, gather or loop, and counting() reports the group's depth as
    the events one table pass writes; a long axis keeps the scatters vmap
    derives and reports no dense pass; unbatched, it is SLOTS in-place
    column updates and nothing is counted."""
    n, n_pad = SIZES[size]
    tbl, cols = _group_cols("score", n_pad)
    args = (tbl, cols, _group_idx(n))
    with lw.counting() as sites:
        text = jax.jit(jax.vmap(lw.write_columns)).lower(*args).as_text()
    assert len(sites) == 1
    if size == "short":
        assert len(sites.dense) == 1 and sites.table_pass_events == SLOTS
        for op in ("scatter", "gather", "while", "dynamic_update_slice"):
            assert f"stablehlo.{op}" not in text, op
    else:
        assert len(sites.dense) == 0 and sites.table_pass_events == 0
        assert text.count('"stablehlo.scatter"') == SLOTS
    with lw.counting() as sites:
        jax.jit(jax.vmap(lw.write_column)).lower(tbl, cols[:, 0], args[2][:, 0])
    assert sites.table_pass_events == (1 if size == "short" else 0)
    with lw.counting() as sites:
        one = jax.jit(lw.write_columns).lower(
            *(a[0] for a in args)).as_text()
    assert not sites and sites.table_pass_events == 0
    assert one.count("stablehlo.dynamic_update_slice") == SLOTS
    assert "stablehlo.scatter" not in one and "stablehlo.gather" not in one


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("leaf", sorted(COLUMN_LEAVES))
def test_a_patched_read_equals_the_read_after_the_write(leaf, size):
    """patch_row / patch_entry give the row and the entry of the table as
    write_columns would leave it, without writing it."""
    n, n_pad = SIZES[size]
    tbl, cols = _group_cols(leaf, n_pad)
    idxs = _group_idx(n)
    rows = jnp.asarray([0, K - 1, 2, 2, 5], jnp.int32)
    at = _node_idx(n)

    def patched(tbl, cols, idxs, row, col):
        t2, c2 = (tbl[0], cols[:, 0]) if tbl.ndim == 3 else (tbl, cols)
        vals = c2[:, row]
        return (lw.patch_row(t2[row], idxs, vals),
                lw.patch_entry(lw.read_entry(t2, row, col), col, idxs, vals))

    def written(tbl, cols, idxs, row, col):
        out = _plain_columns(tbl, cols, idxs)
        out = out[0] if out.ndim == 3 else out
        return out[row], out[row, col]

    args = (tbl, cols, idxs, rows, at)
    _same(jax.jit(jax.vmap(patched))(*args), jax.vmap(written)(*args))
    one = tuple(a[0] for a in args)
    _same(patched(*one), written(*one))
