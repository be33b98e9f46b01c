"""Single-buffer host→device transport.

Every `device_put` leaf is a host→device transfer of its own, so a 40-field
pytree pays 40 transfer set-ups per upload — far more than the bytes
themselves cost.  This module flattens any pytree of numpy
arrays into ONE contiguous byte buffer on the host, ships it in a single
transfer, and reconstructs the tree on device inside a cached jit (static
offsets → XLA slices + bitcasts, fused with whatever consumes them).

This is the host↔HBM half of the snapshot delta protocol (SURVEY.md §2.4):
the informer delta stream becomes one append-only buffer DMA'd per batch.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

_ALIGN = 8


def pack_tree(tree) -> Tuple[np.ndarray, tuple, object]:
    """Flatten a pytree of numpy arrays into (byte_buffer, spec, treedef).

    spec is hashable (dtype/shape/offset per leaf) — the jit cache key for
    the device-side unpacker.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    metas = []
    chunks = []
    off = 0
    for a in leaves:
        shape = np.shape(a)  # before ascontiguousarray (it promotes 0-d → 1-d)
        a = np.ascontiguousarray(a)
        off += (-off) % _ALIGN
        metas.append((str(a.dtype), shape, off))
        chunks.append((off, a))
        off += a.nbytes
    buf = np.zeros(off, np.uint8)
    for o, a in chunks:
        if a.nbytes:
            buf[o : o + a.nbytes] = np.frombuffer(a.tobytes(), np.uint8)
    return buf, tuple(metas), treedef


def unpack(buf, spec):
    """Device-side leaf reconstruction (inside jit): static slices of the
    uint8 buffer, bitcast to each leaf's dtype and shape."""
    leaves = []
    for dtype_str, shape, off in spec:
        dt = np.dtype(dtype_str)
        n = int(np.prod(shape, dtype=np.int64))
        nb = n * dt.itemsize
        raw = jax.lax.slice(buf, (off,), (off + nb,))
        if dt == np.bool_:
            leaf = raw.astype(jnp.bool_)
        elif dt.itemsize == 1:
            leaf = jax.lax.bitcast_convert_type(raw, jnp.dtype(dt))
        else:
            leaf = jax.lax.bitcast_convert_type(
                raw.reshape(n, dt.itemsize), jnp.dtype(dt)
            )
        leaves.append(leaf.reshape(shape))
    return leaves


@functools.lru_cache(maxsize=512)
def _unpacker(spec, treedef):
    # ktpu: axes(buf=u8[B])
    # ktpu: noinstantiate — shapes live in the lru_cache key (spec,
    #   treedef), not in the signature; nothing to instantiate statically
    @jax.jit
    def run(buf):
        return jax.tree_util.tree_unflatten(treedef, unpack(buf, spec))

    return run


def device_put_packed(tree):
    """device_put an entire numpy pytree in ONE transfer."""
    buf, spec, treedef = pack_tree(tree)
    return _unpacker(spec, treedef)(jax.device_put(buf))
