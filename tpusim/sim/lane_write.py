"""One lane's access to its dirty column, row and block of a carried array,
with a batching rule of its own.

The table engines touch ONE node an event: they write one column of the
score / device / feasibility tables, add one row into the node state, set
one bookkeeping row, and read the same column, row or block back. Standalone
(the replay, run_chunk, checkpoint/resume, the shard engine) each of these is
a `dynamic_update_slice`, a `dynamic_slice` or an `.at[]` update, and stays
exactly that: the functions here are `jax.custom_batching.custom_vmap`s whose
unbatched expression is the one the step bodies always had.

Under `jax.vmap` (every sweep: `_sweep_engine`, `_sweep_engine_multi`, the
fault twins, the fork wave) each carried leaf gains a leading lane axis and
the derived forms are a `scatter` for a write and a `gather` for a read. On
the TPU the scatters run in place on the carry's own layout (tables
row-major with nodes minor, `NodeState.gpu_left` / `aff_cnt` nodes minor
too). The READERS were the cost: XLA's gather wants the axis it windows
major-most, so a block read of `[lanes, n_pol, K, N]` asked for the score
table with N major, a row read of `gpu_left[lanes, N, 8]` for the 8 minor,
a `dynamic_slice` with an index shared by the lanes for the lanes minor, and
each got a copy of the whole carried array, every event (PERF.md section 5:
sixteen copies, 7.7 s of a 9.86 s scan at 100,000 nodes x 40 lanes). The
rule below gives every access ONE shape the carry's layout serves as it is:

- a write is the scatter `vmap` derives (it never needed a copy);
- a read is a gather, batched over the lane axis (and a table's policy
  axis), of (rows, nodes) windows that never hold all the rows of the leaf
  (`_windows`: two windows of half the rows each), with its index made
  per-lane even when the lanes share it. A row of `gpu_left` or `aff_cnt`
  is picked out of the 128-node tile that holds it. The dirty block comes
  back from `write_column` itself, so the step body never gathers a block
  from the table.

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
the rule: `SweepRecord.lane_writes`.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_batching import custom_vmap

TILE_NODES = 128  # nodes in one tile of a nodes-minor leaf

_counting: list = []  # open counting() sets; the rule adds its site to each


@contextlib.contextmanager
def counting():
    """Yields a set that gains one entry for every write SITE (one call of
    write_column / add_row / set_row in a traced program) lowered through
    the batching rule while the block runs. A site batched again (the
    fixpoint of a vmapped scan) counts once; a program served from a jit
    cache traces nothing and adds nothing."""
    sites: set = set()
    _counting.append(sites)
    try:
        yield sites
    finally:
        _counting.remove(sites)


def _lane_batched(expr, lanes, write: bool, per_lane=()):
    """custom_vmap of `expr` for one call site. Under vmap it runs `lanes`
    (an expression equal to `expr` lane by lane) vmapped over whatever
    operands are batched; the operands at `per_lane` are stacked first, so
    an index the lanes share still reads as one index a lane."""
    fn = custom_vmap(expr)
    site = object()

    @fn.def_vmap
    def rule(size, in_batched, *args):
        if write:
            for sites in _counting:
                sites.add(site)
        stacked = [b or i in per_lane for i, b in enumerate(in_batched)]
        args = [
            jnp.broadcast_to(a[None], (size,) + a.shape) if s and not b else a
            for a, s, b in zip(args, stacked, in_batched)
        ]
        out = jax.vmap(
            lanes, in_axes=[0 if s else None for s in stacked],
            axis_size=size,
        )(*args)
        return out, jax.tree.map(lambda _: True, out)

    return fn


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
    which holds column idx)."""
    lead = (0,) * (tbl.ndim - 1)

    def written(tbl, col, idx):
        return lax.dynamic_update_slice(tbl, col[..., None], lead + (idx,))

    if block is None:
        return _lane_batched(written, written, write=True)(tbl, col, idx)
    start, width = block

    def expr(tbl, col, idx, start):
        out = written(tbl, col, idx)
        return out, lax.dynamic_slice(
            out, lead + (start,), tbl.shape[:-1] + (width,))

    def lanes(tbl, col, idx, start):
        out = written(tbl, col, idx)
        return out, _windows(out, start, width)

    return _lane_batched(expr, lanes, write=True)(tbl, col, idx, start)


def read_entry(tbl, row, col):
    """tbl[row, col] of a [K, N] table, as the step bodies slice it."""

    def expr(tbl, row, col):
        return lax.dynamic_slice(tbl, (row, col), (1, 1))[0, 0]

    return _lane_batched(expr, expr, write=False, per_lane=(1, 2))(
        tbl, row, col)


# ------------------------------------------------------------------ rows
def _row_write(leaf, idx, val, add: bool):
    def expr(leaf, val, *idx):
        ref = leaf.at[idx if len(idx) > 1 else idx[0]]
        return ref.add(val) if add else ref.set(val)

    idx = idx if isinstance(idx, tuple) else (idx,)
    return _lane_batched(expr, expr, write=True)(leaf, val, *idx)


def add_row(leaf, idx, val):
    """leaf.at[idx].add(val); idx is a row or a tuple of leading indices."""
    return _row_write(leaf, idx, val, add=True)


def set_row(leaf, idx, val):
    """leaf.at[idx].set(val); idx is a row or a tuple of leading indices."""
    return _row_write(leaf, idx, val, add=False)


def read_row(leaf, idx, keepdims: bool = True):
    """Row idx of a leaf with nodes on its first axis: the [1, ...] slice
    `dynamic_slice_in_dim` gives (keepdims) or `leaf[idx]`."""

    def expr(leaf, idx):
        if keepdims:
            return lax.dynamic_slice_in_dim(leaf, idx, 1, axis=0)
        return leaf[idx]

    def lanes(leaf, idx):
        if leaf.ndim == 1:
            return expr(leaf, idx)
        if not keepdims:  # leaf[idx] wraps a negative index once
            idx = jnp.where(idx < 0, idx + leaf.shape[0], idx)
        if leaf.ndim != 2:
            raise NotImplementedError(leaf.shape)
        n = leaf.shape[0]
        width = min(TILE_NODES, n)
        start = jnp.minimum((idx // width) * width, n - width)
        tile = _windows(leaf.T, start, width)  # [C, width]
        row = lax.dynamic_slice_in_dim(tile, idx - start, 1, axis=1).T
        return row if keepdims else row[0]

    return _lane_batched(expr, lanes, write=False, per_lane=(1,))(leaf, idx)
