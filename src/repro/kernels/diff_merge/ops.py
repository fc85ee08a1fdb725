"""jit'd wrapper: diff+merge a whole state pytree leaf against a snapshot.

Pads flat leaves into (n_chunks, CHUNK) tiles and runs the fused kernel;
returns (merged leaf, dirty chunk mask) — the jit-side dense-diff path of
``core.diffsync`` accelerated for TPU deployment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.diffsync import CHUNK
from repro.kernels.diff_merge import kernel as _k


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def diff_merge_leaf(a0, b0, b1, *, op: str = "sum",
                    interpret: bool = False):
    """a0 = main value, b0 = fork snapshot, b1 = child value (same shape).

    Returns (merged like a0, dirty (n_chunks,) bool)."""
    shape, dtype = a0.shape, a0.dtype
    flat = lambda x: x.reshape(-1)
    fa, fb0, fb1 = flat(a0), flat(b0), flat(b1)
    # whole (BLOCK_ROWS, CHUNK) blocks only: a one-row block breaks the
    # TPU's (8, 128) tiling, so ragged leaves pad to a block multiple
    pad = (-fa.size) % (CHUNK * _k.BLOCK_ROWS)
    if pad:
        fa = jnp.pad(fa, (0, pad))
        fb0 = jnp.pad(fb0, (0, pad))
        fb1 = jnp.pad(fb1, (0, pad))
    tiles = lambda x: x.reshape(-1, CHUNK)
    n_chunks = -(-a0.size // CHUNK)
    a1, dirty = _k.diff_merge(tiles(fa), tiles(fb0), tiles(fb1), op=op,
                              interpret=interpret)
    out = a1.reshape(-1)[: a0.size].reshape(shape).astype(dtype)
    return out, dirty[:n_chunks, 0]
