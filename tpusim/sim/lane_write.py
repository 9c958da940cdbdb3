"""One lane's access to its dirty column, row and block of a carried array,
with a batching rule of its own.

The table engines touch ONE node an event: they write one column of the
score / device / feasibility tables, add one row into the node state, set
one bookkeeping row, and read the same column, row or block back. Standalone
(the replay, run_chunk, checkpoint/resume, the shard engine) each of these is
a `dynamic_update_slice`, a `dynamic_slice` or an `.at[]` update, and stays
exactly that: the functions here are `jax.custom_batching.custom_vmap`s whose
unbatched expression is the one the step bodies always had.

Under `jax.vmap` (every sweep: `driver._sweep_engine`; the seed batch, the
fork wave) each carried leaf gains a leading lane axis and
the derived forms are a `scatter` for a write and a `gather` for a read. On
the TPU the scatters run in place on the carry's own layout (tables
row-major with nodes minor, `NodeState.gpu_left` nodes minor too, and
`aff_cnt` on a long node axis). The READERS were the cost: XLA's gather
wants the axis it windows major-most, so a block read of
`[lanes, n_pol, K, N]` asked for the score table with N major, a row
read of `gpu_left[lanes, N, 8]` for the 8 minor,
a `dynamic_slice` with an index shared by the lanes for the lanes minor, and
each got a copy of the whole carried array, every event (PERF.md section 5:
sixteen copies, 7.7 s of a 9.86 s scan at 100,000 nodes x 40 lanes). The
rule below gives every access ONE shape the carry's layout serves as it is:

- a write is the scatter `vmap` derives (it never needed a copy);
- a read is a gather, batched over the lane axis (and a table's policy
  axis), of (rows, nodes) windows that never hold all the rows of the leaf
  (`_windows`: two windows of half the rows each), with its index made
  per-lane even when the lanes share it. A row of `gpu_left` (or of
  `aff_cnt`, whose [N, 9] rows the blocked body keeps) is picked out of
  the 128-node tile that holds it. The dirty block comes
  back from `write_column` itself, so the step body never gathers a block
  from the table.

Those forms are for leaves of `table_engine.BLOCKED_MIN_NODES` nodes or
more. On a SHORT node axis (the flat step body's clusters, openb's 1,213
nodes) at sweep width they are the wrong expression: XLA runs a scatter or a
gather with one index row a lane as a `while` over the lanes, about 0.9 us a
lane an access (2,560 lanes x 24 accesses an event; PERF.md section 6, PR
28), and the profiler's buffer overflows on them. There every access takes
its DENSE form, one pass over the whole lane-batched leaf with a one-hot
mask on the node axis and no `scatter` or `gather` at all:

- a write is `where(node_iota == idx, new, leaf)`, an add
  `leaf + where(node_iota == idx, delta, 0)` (where the lanes bring their
  own index; one they share, as the bookkeeping rows' is in a sweep of one
  event stream, stays the single update across the lanes vmap derives);
- a read is the masked reduction over the node axis (a sum; an `any` for a
  `bool` leaf), the block `write_column` returns the masked reduction over
  the block axis of the table viewed as whole blocks. `read_entry` takes
  its ROW of the [K, N] table first and reduces over that row's nodes: a
  slice where the lanes share the row (one event stream), with a row a
  lane (a sweep of a trace a lane) the row gather the step's own
  `feas_tbl[t_id]` is, which fuses and is no loop; never a reduction over
  [lanes, K, N] (0.2 s an event's read at 600 lanes x K = 400; PERF.md
  section 6, PR 33).

A dense write is a pass over the whole leaf whatever it writes. One of them
left the flat step body for that reason (PERF.md section 6, PR 42): the
commit's add into `aff_cnt`, with a trace a lane the largest operation of a
wave (its class index is then a lane's own, so the pass took all of
s32[lanes, N, 9] every event), wrote a leaf that no kernel but
GpuClustering's reads. Where the program's kernels say they do not, the
flat body's commit leaves it out and `table_engine.chunk_affinity` sums the
chunk's events once, a contraction of two one-hots over the event axis:
no write site of this module, no scatter, no index row a lane. Where a
kernel does read it (GpuClustering) the add and the dirty node's read stay
in the loop, and what was wrong there was the leaf's shape: with a class a
lane XLA carried s32[lanes, N, 9] classes minor, nine of a tile's 128
minor entries in use, and the add and the four reads of an unrolled
iteration each passed over 14 x the useful bytes (59 % of that scan;
PERF.md section 6, PR 46). The flat loop holds that leaf [9, N] a lane and
touches it through `add_entry` and `read_column`: the same two accesses
with the nodes on the LAST axis, every tile full. And at sweep
width (`table_engine.flat_group_events`) the flat step body does not write
its tables every event: it holds the dirty columns of a group of events
(`table_engine.LateColumns`) and puts them down together. `write_columns(tbl, cols, idxs)` is that access: unbatched, the
group's `dynamic_update_slice`s in slot order (a slot at -1 left out);
batched on a long node axis that expression vmapped; dense, ONE pass with
the group's select chain, `where(node_iota == idxs[s], cols[s], leaf)` for
s = 0..B-1, the node compare made once per (lane, node). Until the group is
written, `patch_row` and `patch_entry` give a row or an entry of the table
as it will read afterwards (the same chain over `[lanes, N]`, which fuses
into the pass that reads the row); they are elementwise and need no rule.
What the slots hold for that row comes from `read_pending(cols, row)`: the
slice of the pending block [G, K] at `row`, and with a row a lane a masked
reduction over K, for the block's sake and not the read's: a gather a lane
makes XLA carry the block with its slot axis minor-most, and then every
event's store of one slot rewrites the whole padded block.

The bookkeeping rows (`placed`, `masks`, `failed`: pods on the first axis)
are short in every sweep of a trace's first events, and their writes are
dense. A WHOLE tuned trace a lane (10,9xx pods) puts them over the line:
the writes are then the scatters `vmap` derives, in place on the layout the
carry holds (`masks[lanes, P, 8]` pods minor, like `gpu_left`). The delete
branch's `masks[idx]` was a plain gather, which wants the 8 devices minor,
so the whole leaf was copied to that layout after every event's write (four
copies of pred[320, 11265, 8] a group of four events, 9.6 s of a 20.2 s
scan; PERF.md section 6, PR 41). `read_pod` is that read: on a long pod
axis the masked reduction over the pods, which reads the layout the writes
keep (read_row's tile gather would too, but runs as a `while` over the
lanes' windows there); on a short one the plain index it always was.

Which form an access takes follows from the leaf's static shape alone
(`_short`): no option selects it.

What XLA accepts was found by compiling the cell's program for a described
v5e (tests/test_sweep_compile.py keeps that compile): one window of all K
rows, or of all 8 devices and one node, brings the copies back; one window
a row (2,840 an event for a block of K = 71) leaves no copy but costs 0.9
us a window on the chip (PERF.md section 6, PR 27).

Results are the same integers in the same order: the rule changes how an
access is expressed, not what it reads or writes. Indices are in range at
every call site (the callers clip them), which is where `.at[]`,
`dynamic_slice` and a gather agree.

`counting()` observes, at trace time, which write sites were lowered through
the rule, which sites, reads too, took the dense form, and how many events'
columns a dense table write puts down in its pass:
`SweepRecord.lane_writes`, `.dense_accesses` and `.table_pass_events`.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_batching import custom_vmap

TILE_NODES = 128  # nodes in one tile of a nodes-minor leaf


class Sites(set):
    """What counting() yields: the write sites lowered through the rule;
    `.dense` holds the sites, reads among them, that took the dense form;
    `.table_pass_events` is the number of events whose columns the deepest
    dense TABLE write of the program puts down in its one pass over the
    leaf (write_column: 1, write_columns: its slots; 0 with no such site)."""

    def __init__(self):
        super().__init__()
        self.dense: set = set()
        self.table_pass_events = 0


_counting: list = []  # open counting() Sites; the rule adds its site to each


@contextlib.contextmanager
def counting():
    """Yields a Sites that gains one entry for every write SITE (one call of
    write_column / add_row / set_row in a traced program) lowered through
    the batching rule while the block runs, and in `.dense` every site,
    read or write, whose batched form was the dense one. A site batched
    again (the fixpoint of a vmapped scan) counts once; a program served
    from a jit cache traces nothing and adds nothing."""
    sites = Sites()
    _counting.append(sites)
    try:
        yield sites
    finally:  # by identity: nested blocks may hold equal sets
        _counting[:] = [s for s in _counting if s is not sites]


def _short(nodes: int) -> bool:
    """A node axis this short is served by the dense forms: the clusters
    the flat step body runs (table_engine.resolve_block_size)."""
    from tpusim.sim.table_engine import BLOCKED_MIN_NODES  # imports this file

    return nodes < BLOCKED_MIN_NODES


def _lane_batched(expr, lanes, write: bool, per_lane=(), dense=None,
                  table_pass: int = 0):
    """custom_vmap of `expr` for one call site. Under vmap it runs `lanes`
    (an expression equal to `expr` lane by lane) vmapped over whatever
    operands are batched; the operands at `per_lane` are stacked first, so
    an index the lanes share still reads as one index a lane. A site whose
    leaf has a short node axis gives `dense`: `dense(in_batched)` is the
    site's dense form for those operands, run in place of `lanes` with
    nothing stacked (it is elementwise in the lanes), or None to decline.
    `table_pass` is, for a table's column write, the events one dense pass
    of the site writes (Sites.table_pass_events)."""
    fn = custom_vmap(expr)
    site = object()

    @fn.def_vmap
    def rule(size, in_batched, *args):
        per = dense(in_batched) if dense is not None else None
        for sites in _counting:
            if write:
                sites.add(site)
            if per is not None:
                sites.dense.add(site)
                sites.table_pass_events = max(
                    sites.table_pass_events, table_pass)
        if per is not None:
            stacked = list(in_batched)
        else:
            stacked = [b or i in per_lane for i, b in enumerate(in_batched)]
            per = lanes
        args = [
            jnp.broadcast_to(a[None], (size,) + a.shape) if s and not b else a
            for a, s, b in zip(args, stacked, in_batched)
        ]
        out = jax.vmap(
            per, in_axes=[0 if s else None for s in stacked],
            axis_size=size,
        )(*args)
        return out, jax.tree.map(lambda _: True, out)

    return fn


def _dense_write(form):
    """The `dense` of a write site (leaf, value, *index): `form` when the
    lanes bring their own index. An index they share needs no form of its
    own: the update vmap derives is ONE slice written across all the lanes."""
    return lambda in_batched: form if any(in_batched[2:]) else None


def _one_hot(n: int, idx, trailing: int = 0):
    """bool[n, 1 x trailing]: True at idx."""
    mask = lax.iota(jnp.int32, n) == idx
    return mask.reshape((n,) + (1,) * trailing)


def _picked(leaf, mask, axis):
    """The entries of `leaf` where the one-hot `mask` holds along `axis`,
    as a masked reduction: no gather."""
    if leaf.dtype == jnp.bool_:
        return jnp.any(leaf & mask, axis=axis)
    return jnp.sum(jnp.where(mask, leaf, 0), axis=axis, dtype=leaf.dtype)


def _windows(arr, start, width: int):
    """arr[..., start:start+width] for arr [*lead, K, N]: ONE gather,
    batched over every leading axis, of two (rows, nodes) windows that
    each hold half of K (overlapping in the middle row when K is odd).
    Never one window of all K rows: for that XLA lays the operand out
    nodes-major again, the copy this module exists to avoid."""
    *lead, k, _ = arr.shape
    h = -(-k // 2)
    rows = jnp.asarray([0, k - h] if k > h else [0], jnp.int32)

    def fn(a2, j):  # a2 [K, N]
        return jax.vmap(
            lambda r: lax.dynamic_slice(a2, (r, j), (h, width)))(rows)

    for _ in lead:
        fn = jax.vmap(fn)
    w = fn(arr, jnp.broadcast_to(start, tuple(lead)))  # [*lead, 1|2, h, w]
    at = len(lead)  # the windows' axis
    first = lax.index_in_dim(w, 0, at, keepdims=False)
    if k == h:
        return first
    last = lax.index_in_dim(w, 1, at, keepdims=False)
    return jnp.concatenate(
        [first, lax.slice_in_dim(last, 2 * h - k, h, axis=at)], axis=at)


# ---------------------------------------------------------------- tables
def write_column(tbl, col, idx, block=None):
    """tbl[..., idx] = col for a table with nodes on its last axis.
    With block=(start, width) also returns the written table's
    [..., start:start+width] block (the dirty block of the blocked select,
    which holds column idx; `start` is a multiple of `width`)."""
    lead = (0,) * (tbl.ndim - 1)
    n = tbl.shape[-1]

    def written(tbl, col, idx):
        return lax.dynamic_update_slice(tbl, col[..., None], lead + (idx,))

    def dense_written(tbl, col, idx):
        return jnp.where(_one_hot(n, idx), col[..., None], tbl)

    if block is None:
        return _lane_batched(
            written, written, write=True,
            dense=_dense_write(dense_written) if _short(n) else None,
            table_pass=1,
        )(tbl, col, idx)
    start, width = block

    def expr(tbl, col, idx, start):
        out = written(tbl, col, idx)
        return out, lax.dynamic_slice(
            out, lead + (start,), tbl.shape[:-1] + (width,))

    def lanes(tbl, col, idx, start):
        out = written(tbl, col, idx)
        return out, _windows(out, start, width)

    def dense(tbl, col, idx, start):
        out = dense_written(tbl, col, idx)
        blocks = out.reshape(tbl.shape[:-1] + (n // width, width))
        return out, _picked(
            blocks, _one_hot(n // width, start // width, 1), axis=-2)

    # a table of whole blocks (the blocked layout pads to them); any other
    # keeps the windows, which read from any start
    return _lane_batched(
        expr, lanes, write=True,
        dense=_dense_write(dense) if _short(n) and n % width == 0 else None,
        table_pass=1,
    )(tbl, col, idx, start)


def write_columns(tbl, cols, idxs):
    """tbl[..., idxs[s]] = cols[s] for s = 0..B-1 in slot order, a later
    slot winning where two name one node and a slot with index -1 left
    out: the columns of B events in ONE access of the table (the flat step
    body's flush). cols is [B, *tbl.shape[:-1]], idxs i32[B]."""
    lead = (0,) * (tbl.ndim - 1)
    n = tbl.shape[-1]
    slots = cols.shape[0]

    def written(tbl, cols, idxs):
        for s in range(slots):
            at = lead + (jnp.maximum(idxs[s], 0),)
            old = lax.dynamic_slice(tbl, at, tbl.shape[:-1] + (1,))
            tbl = lax.dynamic_update_slice(
                tbl, jnp.where(idxs[s] >= 0, cols[s][..., None], old), at)
        return tbl

    def dense_written(tbl, cols, idxs):
        return patch_row(tbl, idxs, cols)

    return _lane_batched(
        written, written, write=True,
        dense=_dense_write(dense_written) if _short(n) else None,
        table_pass=slots,
    )(tbl, cols, idxs)


def patch_row(leaf, idxs, vals):
    """leaf [..., N] with leaf[..., idxs[s]] = vals[s] applied in slot
    order (a later slot wins, -1 matches no node; vals is
    [B, *leaf.shape[:-1]]): a row of a table as it will read once
    write_columns has put the pending columns down, and, over a whole
    table, write_columns' dense form itself. One select chain over the
    leaf, the node compare made per node and broadcast over the rows;
    elementwise, so it has no batching rule of its own and fuses into the
    pass that reads the row."""
    node = lax.iota(jnp.int32, leaf.shape[-1])
    for s in range(idxs.shape[0]):
        leaf = jnp.where(node == idxs[s], vals[s][..., None], leaf)
    return leaf


def patch_entry(val, col, idxs, vals):
    """patch_row for the one entry read_entry returns: `val` read at node
    `col`, with the pending slots that name that node applied in order."""
    for s in range(idxs.shape[0]):
        val = jnp.where(idxs[s] == col, vals[s], val)
    return val


def read_pending(cols, row):
    """cols[:, row] of a pending block [G, K] (table_engine.LateColumns):
    what the group's G slots hold for table row `row`, the `vals` of
    patch_row and patch_entry. With a row a lane (a sweep of a trace a
    lane) it is a masked reduction over K, whatever the nodes: the block
    is small (G x K entries a lane), and a gather a lane would have the
    block carried slots-minor, which makes every slot's store a pass over
    the whole padded block (1.7 s of a 6.84 s scan at 600 lanes x K = 400;
    PERF.md section 6, PR 33). A row the lanes share stays the slice."""
    k = cols.shape[1]

    def expr(cols, row):
        return lax.dynamic_index_in_dim(cols, row, 1, keepdims=False)

    def dense(cols, row):
        return _picked(cols, _one_hot(k, row), axis=1)

    return _lane_batched(
        expr, expr, write=False,
        dense=lambda in_batched: dense if in_batched[1] else None,
    )(cols, row)


def read_entry(tbl, row, col):
    """tbl[row, col] of a [K, N] table, as the step bodies slice it."""
    n = tbl.shape[1]

    def expr(tbl, row, col):
        return lax.dynamic_slice(tbl, (row, col), (1, 1))[0, 0]

    def dense(tbl, row, col):
        # the row first, then one pass over [lanes, N], never [lanes, K, N]:
        # a slice of the row axis where the lanes share the row (one event
        # stream), and with a row a lane (a trace a lane) the row gather
        # the step's own `feas_tbl[t_id]` is, which fuses
        return _picked(lax.dynamic_index_in_dim(tbl, row, 0, keepdims=False),
                       _one_hot(n, col), axis=0)

    return _lane_batched(
        expr, expr, write=False, per_lane=(1, 2),
        dense=(lambda _: dense) if _short(n) else None,
    )(tbl, row, col)


# ------------------------------------------------------------------ rows
def _row_write(leaf, idx, val, add: bool, nodes_axis: int = 0):
    def expr(leaf, val, *idx):
        ref = leaf.at[idx if len(idx) > 1 else idx[0]]
        return ref.add(val) if add else ref.set(val)

    def dense(leaf, val, *idx):
        mask = _one_hot(leaf.shape[0], idx[0], leaf.ndim - 1)
        for axis, i in enumerate(idx[1:], 1):
            mask = mask & _one_hot(leaf.shape[axis], i, leaf.ndim - 1 - axis)
        val = jnp.asarray(val, leaf.dtype)  # one row: leaf.shape[len(idx):]
        return leaf + jnp.where(mask, val, 0) if add else jnp.where(
            mask, val, leaf)

    idx = idx if isinstance(idx, tuple) else (idx,)
    return _lane_batched(
        expr, expr, write=True,
        dense=_dense_write(dense) if _short(leaf.shape[nodes_axis]) else None,
    )(leaf, val, *idx)


def add_row(leaf, idx, val):
    """leaf.at[idx].add(val); idx is a row or a tuple of leading indices."""
    return _row_write(leaf, idx, val, add=True)


def set_row(leaf, idx, val):
    """leaf.at[idx].set(val); idx is a row or a tuple of leading indices."""
    return _row_write(leaf, idx, val, add=False)


def read_row(leaf, idx, keepdims: bool = True):
    """Row idx of a leaf with nodes on its first axis: the [1, ...] slice
    `dynamic_slice_in_dim` gives (keepdims) or `leaf[idx]`."""
    n = leaf.shape[0]

    def expr(leaf, idx):
        if keepdims:
            return lax.dynamic_slice_in_dim(leaf, idx, 1, axis=0)
        return leaf[idx]

    def wrapped(idx):  # leaf[idx] wraps a negative index once
        return idx if keepdims else jnp.where(idx < 0, idx + n, idx)

    def lanes(leaf, idx):
        if leaf.ndim == 1:
            return expr(leaf, idx)
        idx = wrapped(idx)
        if leaf.ndim != 2:
            raise NotImplementedError(leaf.shape)
        width = min(TILE_NODES, n)
        start = jnp.minimum((idx // width) * width, n - width)
        tile = _windows(leaf.T, start, width)  # [C, width]
        row = lax.dynamic_slice_in_dim(tile, idx - start, 1, axis=1).T
        return row if keepdims else row[0]

    def dense(leaf, idx):
        row = _picked(leaf, _one_hot(n, wrapped(idx), leaf.ndim - 1), axis=0)
        return row[None] if keepdims else row

    return _lane_batched(
        expr, lanes, write=False, per_lane=(1,),
        dense=(lambda _: dense) if _short(n) else None,
    )(leaf, idx)


# ------------------------------------------- a small leaf, nodes last
def add_entry(leaf, cls, node, delta):
    """leaf.at[cls, node].add(delta) of a leaf [C, N] with the NODES on its
    last axis (the affinity counts as the flat event loop holds them where
    a kernel reads them: table_engine._run_chunk_impl). add_row's rule with
    the node axis last: dense, one pass `leaf + where(class_iota == cls &
    node_iota == node, delta, 0)` over full tiles."""
    return _row_write(leaf, (cls, node), delta, add=True, nodes_axis=1)


def read_column(leaf, node):
    """Column `node` of such a leaf as the [1, C] row read_row gives of its
    transpose: the `dynamic_slice` it always was; dense, the masked sum
    over the LAST axis."""
    n = leaf.shape[1]

    def expr(leaf, node):
        return lax.dynamic_slice_in_dim(leaf, node, 1, axis=1).T

    def dense(leaf, node):
        return _picked(leaf, _one_hot(n, node), axis=1)[None]

    return _lane_batched(
        expr, expr, write=False,
        dense=(lambda _: dense) if _short(n) else None,
    )(leaf, node)


def read_pod(leaf, idx):
    """leaf[idx] of a bookkeeping row (pods on the first axis: `placed`,
    `masks`, `failed`), as the delete branch reads what a pod holds. On a
    long pod axis (a whole tuned trace a lane) a row of `masks[P, 8]` with
    an index a lane is the masked reduction over the pods, one pass over
    the leaf in the pods-minor layout its scatters keep (no tile gather
    either: XLA runs that one as a `while` over the lanes' windows). A
    short axis, a 1-D leaf and an index the lanes share stay the plain
    index."""
    n = leaf.shape[0]
    if _short(n) or leaf.ndim == 1:
        return leaf[idx]

    def expr(leaf, idx):
        return leaf[idx]

    def dense(leaf, idx):
        at = jnp.where(idx < 0, idx + n, idx)  # leaf[idx] wraps once
        return _picked(leaf, _one_hot(n, at, leaf.ndim - 1), axis=0)

    return _lane_batched(
        expr, expr, write=False,
        dense=lambda in_batched: dense if in_batched[1] else None,
    )(leaf, idx)
