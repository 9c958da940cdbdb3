"""Single-transfer device→host fetch.

Every device→host readback pays a fixed latency regardless of payload
size, and a single replay's output is ~20 small leaves; a sweep's is the
same leaves a lane, 0.06-0.35 GB at the benchmark's widths (0.27 GB for
2,560 lanes of 1,213 nodes, 0.31 GB for 40 lanes of 100,000), where the
cost is the bytes (PERF.md section 7). device_fetch() packs every
device leaf of a pytree into ONE uint8 buffer on device (bitcast, so
f32/i32 bits survive exactly) and reads it back in a single transfer, then
reslices host-side. It packs the shapes it is given and knows nothing of
lanes: what a sweep's lanes share reaches it once, without a lane axis
(driver._sweep_engine: the five capacity leaves of the final states), and
leaves as one read-only view. A caller's span handle (obs.Recorder.span) gets two
marks, `ready` (the device has finished the pack and whatever it still
owed before it) and `copied` (the bytes are on the host; the rest of the
span is the reslicing and the bool leaves' casts), and the buffer's size as
`bytes`; a sweep's record reads them as device_wait_s, fetch_bytes and the
head of host_tail_s. What the chip gave for copy against unpack: PERF.md,
sections 5 and 6 (PR 36).

The reference has no equivalent host/device boundary — its "transfer" is
the in-memory fake API server (SURVEY.md §5.8); this helper is the cost
model that boundary turns into on real accelerator hardware.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _packer(sig):
    """Jitted byte-packer for a fixed (shape, dtype) leaf signature."""

    def pack(leaves):
        parts = []
        for x in leaves:
            if x.dtype == jnp.bool_:
                x = x.astype(jnp.uint8)
            if x.dtype != jnp.uint8:
                x = jax.lax.bitcast_convert_type(x, jnp.uint8)
            parts.append(x.reshape(-1))
        return jnp.concatenate(parts)

    return jax.jit(pack)


def device_fetch(tree, marks=None):
    """Return `tree` with every jax.Array leaf replaced by a host numpy
    array, moving all of them in one device→host transfer. Non-array
    leaves (None, python scalars, numpy arrays) pass through untouched.
    `marks`: a span handle to stamp `ready` and `copied` on and to note
    the packed `bytes` in; the wait it stamps is the one the copy would
    have made."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    idx = [i for i, l in enumerate(leaves) if isinstance(l, jax.Array)]
    if not idx:
        return tree
    dev = [leaves[i] for i in idx]
    sig = tuple((tuple(l.shape), str(l.dtype)) for l in dev)
    packed = _packer(sig)(dev)
    packed.block_until_ready()
    if marks is not None:
        marks.mark("ready", then="copy")
    buf = np.asarray(packed)
    if marks is not None:
        marks.mark("copied", then="unpack")
        marks.note(bytes=int(buf.nbytes))
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
