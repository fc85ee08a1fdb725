"""jit'd wrapper: threshold-select a flat gradient shard into a
fixed-size sparse (vals, idx) message plus error-feedback residual.

Pads the shard into ``(k, m)`` chunk rows (``k = max(1, int(n·frac))``
selected elements — the same message size as the old global top-k) and
runs the fused chunk-select kernel; large shards route through the
Pallas kernel, small ones use the bit-identical jnp reference (the
same large-leaf routing ``kernels/diff_merge`` uses in ``diffsync``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.collective_codec import kernel as _k
from repro.kernels.collective_codec.ref import chunk_select_ref

#: below this flat size the pallas_call launch costs more than it saves
#: (TPU routing threshold; non-TPU backends always use the jnp ref)
KERNEL_MIN_SIZE = 1 << 16


def codec_geometry(n: int, frac: float):
    """(k, m, padded) chunk geometry for an ``n``-element shard:
    ``k`` selected elements (chunk rows), chunk width ``m = ceil(n/k)``.
    ``frac = 1.0`` degenerates to ``m = 1`` — every element selected,
    which makes the compressed collective bit-exact to hierarchical."""
    n = int(n)
    k = max(1, min(n, int(n * frac)))
    m = -(-n // k)
    return k, m, k * m


@functools.partial(jax.jit,
                   static_argnames=("frac", "use_kernel", "interpret"))
def select_codec(vec, *, frac: float,
                 use_kernel: bool | None = None,
                 interpret: bool = False):
    """vec: flat (n,) -> (vals (k,), idx (k,) int32, resid (n,)).

    ``vals[i] = vec[idx[i]]`` is the largest-magnitude element of chunk
    ``i``; ``resid`` is ``vec`` with the selected elements zeroed, so
    ``scatter(vals, idx) + resid == vec`` exactly (error feedback)."""
    n = vec.shape[0]
    k, m, padded = codec_geometry(n, frac)
    if use_kernel is None:
        # same routing as core.diffsync: the kernel is a TPU fast path;
        # CPU hosts stay on the vectorized jnp ref (running the kernel
        # interpreted per grid row would be orders of magnitude slower)
        use_kernel = (n >= KERNEL_MIN_SIZE
                      and jax.default_backend() == "tpu")
    x = vec
    if padded != n:
        x = jnp.pad(x, (0, padded - n))
    x = x.reshape(k, m)
    if use_kernel:
        # whole (BLOCK_ROWS, m) blocks only: a (1, m) block breaks the
        # TPU's (8, 128) tiling.  Zero pad rows pick (0.0, col 0) and are
        # sliced off, so the exact scatter + resid == vec invariant holds.
        rows = -(-k // _k.BLOCK_ROWS) * _k.BLOCK_ROWS
        if rows != k:
            x = jnp.pad(x, ((0, rows - k), (0, 0)))
        vals, col, resid = _k.chunk_select(x, interpret=interpret)
        vals, col, resid = vals[:k], col[:k], resid[:k]
    else:
        vals, col, resid = chunk_select_ref(x)
    idx = jnp.arange(k, dtype=jnp.int32) * m + col[:, 0]
    # padding lanes are zero, so a padded-chunk pick is (0.0, idx < n)
    # clamped into range: scatter-adding 0.0 is a no-op either way
    idx = jnp.minimum(idx, n - 1)
    return vals[:, 0], idx, resid.reshape(-1)[:n]
