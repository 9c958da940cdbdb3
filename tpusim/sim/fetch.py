"""Device→host fetch: one packed buffer, read back in pieces that are all
in flight at once.

Every device→host readback pays a fixed latency regardless of payload
size, and a single replay's output is ~20 small leaves; a sweep's is the
same leaves a lane, 0.06-0.35 GB at the benchmark's widths (0.27 GB for
2,560 lanes of 1,213 nodes, 0.31 GB for 40 lanes of 100,000), where the
cost is the bytes AND the form of the copy: one whole-buffer transfer ran
at 0.70-0.73 GB/s on a TPU v5e, the same bytes as 8 MB pieces with every
copy started before the first is taken at 4 GB/s (PERF.md section 6,
PR 43 and PR 47). device_fetch() packs every device leaf of a pytree into
ONE run of uint8 on the device (bitcast, so f32/i32 bits survive exactly)
and the same program hands that run out cut at fixed byte offsets into
pieces of PIECE_BYTES (the last one ragged; no padding, nothing left out;
the whole run is never an array of its own). Then:

  at most one piece   one transfer, resliced where the runtime put it: a
                      single replay, the typical pods, a start state, a
                      small service batch. No landing block is touched.
  more than one       copy_to_host_async() on EVERY piece, then each piece
                      in order copied into its byte range of one host
                      block and dropped at once, so the runtime's buffers
                      die young and their pages are handed out again.

Either way the leaves are cut from the bytes host-side as read-only views
(a bool leaf is a cast, so a copy). It packs the shapes it is given and
knows nothing of lanes: what a sweep's lanes share reaches it once,
without a lane axis (driver._sweep_engine: the five capacity leaves of
the final states), and leaves as one read-only view.

Landing blocks. Fresh host pages cost as much as the whole old copy did
(0.29 s for 0.27 GB), so the module keeps blocks from call to call, under
ONE rule: a block is handed to a new fetch only when no array cut from it
by an earlier fetch is alive. Each fetch wraps its block in a fresh
np.frombuffer owner, which every view it returns has as its `.base`; when
the last of them dies the block comes free (weakref.finalize on the owner;
no collector is called). So an earlier fetch's arrays NEVER change under
a later one. A caller that holds the last sweep's lanes while the next
runs alternates between two blocks; one that holds everything gets a
fresh block each time, as before this module kept any. The host memory
the module may hold beyond what callers hold: at most FREE_BLOCKS free
blocks, the largest that came free (a smaller third is let go), each of
the size of the fetch that made it.

A caller's span handle (obs.Recorder.span) gets two marks, `ready` (the
device has finished the pack, every piece, and whatever it still owed
before it) and `copied` (the last byte is on the host where the leaves are
cut from: the piece copies and the memcpys into the block; the rest of the
span is the reslicing and the bool leaves' casts), and notes the packed
size as `bytes`, the number of transfers as `fetch_pieces` and whether
the bytes landed in a block an earlier fetch had touched as
`landing_reused`; a sweep's record reads them as device_wait_s,
fetch_bytes, fetch_pieces, landing_reused and the head of host_tail_s.

The reference has no equivalent host/device boundary — its "transfer" is
the in-memory fake API server (SURVEY.md §5.8); this helper is the cost
model that boundary turns into on real accelerator hardware.
"""

from __future__ import annotations

import functools
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np

# The size of a piece: on the chip 8 MB ran ahead of 32 and 64 in every
# form of the copy (PERF.md section 6, PR 43).
PIECE_BYTES = 8 * 2**20
FREE_BLOCKS = 2


class _LandingBlocks:
    """The host blocks no live array is cut from, and the rule that frees
    them. A block is a bytearray: not an ndarray, so numpy stops at the
    owner when it collapses a view's `.base` chain."""

    def __init__(self):
        self.free = []
        # reentrant: a finalizer may run inside take() on its own thread
        self._lock = threading.RLock()

    def take(self, nbytes: int):
        """(owner, reused): a writable uint8[nbytes] over the smallest
        free block that holds it, else over a new one."""
        with self._lock:
            fit = min((b for b in self.free if len(b) >= nbytes),
                      key=len, default=None)
            if fit is not None:
                self.free.remove(fit)
        block = bytearray(nbytes) if fit is None else fit
        owner = np.frombuffer(block, np.uint8, nbytes)
        weakref.finalize(owner, self._release, block).atexit = False
        return owner, int(fit is not None)

    def _release(self, block) -> None:
        with self._lock:
            self.free.append(block)
            if len(self.free) > FREE_BLOCKS:
                self.free.remove(min(self.free, key=len))


_landing = _LandingBlocks()


@functools.lru_cache(maxsize=None)
def _packer(sig):
    """Jitted byte-packer for a fixed (shape, dtype) leaf signature: the
    leaves as one run of uint8, handed back as a tuple of its pieces."""

    def pack(leaves):
        parts = []
        for x in leaves:
            if x.dtype == jnp.bool_:
                x = x.astype(jnp.uint8)
            if x.dtype != jnp.uint8:
                x = jax.lax.bitcast_convert_type(x, jnp.uint8)
            parts.append(x.reshape(-1))
        starts = np.cumsum([0] + [p.shape[0] for p in parts])
        if starts[-1] <= PIECE_BYTES:
            return (jnp.concatenate(parts),)
        # each piece from the leaves' own bytes in its range: cut from the
        # whole run, the program would hold that run beside its pieces
        pieces = []
        for a in range(0, starts[-1], PIECE_BYTES):
            b = min(a + PIECE_BYTES, starts[-1])
            pieces.append(jnp.concatenate([
                p[max(a - s, 0) : b - s] for p, s in zip(parts, starts)
                if s < b and a < s + p.shape[0]]))
        return tuple(pieces)

    return jax.jit(pack)


def _land(pieces):
    """The pieces' bytes as one read-only host array, and whether they
    landed in a block an earlier fetch had touched."""
    if len(pieces) == 1:
        return np.asarray(pieces[0]), 0
    for p in pieces:
        p.copy_to_host_async()
    buf, reused = _landing.take(sum(p.nbytes for p in pieces))
    off = 0
    for i in range(len(pieces)):
        host = np.asarray(pieces[i])
        buf[off : off + host.nbytes] = host
        off += host.nbytes
        # a jax.Array keeps its host copy: drop both before the next
        pieces[i] = host = None
    buf.flags.writeable = False
    return buf, reused


def device_fetch(tree, marks=None):
    """Return `tree` with every jax.Array leaf replaced by a host numpy
    array, moving all of them as one packed buffer (in pieces, all in
    flight, where it is larger than one). Non-array leaves (None, python
    scalars, numpy arrays) pass through untouched. `marks`: a span handle
    to stamp `ready` and `copied` on and to note the packed `bytes`,
    `fetch_pieces` and `landing_reused` in; the wait it stamps is the one
    the copy would have made."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    idx = [i for i, l in enumerate(leaves) if isinstance(l, jax.Array)]
    if not idx:
        return tree
    dev = [leaves[i] for i in idx]
    sig = tuple((tuple(l.shape), str(l.dtype)) for l in dev)
    pieces = list(_packer(sig)(dev))
    n_pieces = len(pieces)
    for p in pieces:
        p.block_until_ready()
    del p  # _land drops each piece as it lands
    if marks is not None:
        marks.mark("ready", then="copy")
    buf, reused = _land(pieces)
    if marks is not None:
        marks.mark("copied", then="unpack")
        marks.note(bytes=int(buf.nbytes), fetch_pieces=n_pieces,
                   landing_reused=reused)
    off = 0
    for i, l in zip(idx, dev):
        if l.dtype == jnp.bool_:
            dt, out_dt = np.dtype(np.uint8), None
        else:
            dt = out_dt = np.dtype(str(l.dtype))
        n = int(np.prod(l.shape, dtype=np.int64)) * dt.itemsize
        arr = buf[off : off + n].view(dt).reshape(l.shape)
        leaves[i] = arr.astype(bool) if out_dt is None else arr
        off += n
    return jax.tree_util.tree_unflatten(treedef, leaves)
