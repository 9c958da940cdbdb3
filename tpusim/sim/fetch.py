"""Single-transfer device→host fetch.

Every device→host readback pays a fixed latency regardless of payload
size, and a replay output is ~20 small leaves. device_fetch() packs every
device leaf of a pytree into ONE uint8 buffer on device (bitcast, so
f32/i32 bits survive exactly) and reads it back in a single transfer, then
reslices host-side. What one packed transfer saves over per-leaf readbacks
on the chip this repo runs on has not been measured (ROADMAP S1).

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


def device_fetch(tree):
    """Return `tree` with every jax.Array leaf replaced by a host numpy
    array, moving all of them in one device→host transfer. Non-array
    leaves (None, python scalars, numpy arrays) pass through untouched."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    idx = [i for i, l in enumerate(leaves) if isinstance(l, jax.Array)]
    if not idx:
        return tree
    dev = [leaves[i] for i in idx]
    sig = tuple((tuple(l.shape), str(l.dtype)) for l in dev)
    buf = np.asarray(_packer(sig)(dev))
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
