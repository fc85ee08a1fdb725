"""Chunk-wise threshold-select codec kernel (Pallas) for compressed
collectives (DESIGN.md §11).

The old codec ran ``jax.lax.top_k`` over the whole shard — a global
O(n log n) sort that cost more than the slow link saved (ROADMAP item
5).  The replacement is a *chunk-max* selection: the shard is reshaped
into ``(k, m)`` chunks and each chunk contributes its single
largest-magnitude element.  Selection becomes a row-wise
max/first-argmax — one O(n) streaming pass with no data-dependent
control flow, mapping onto a VPU-friendly reduce over the lane
dimension.  The per-chunk max is the selection *threshold* within that
chunk, hence threshold-select; k chunks yield exactly k (value, index)
pairs, a fixed-size message like top-k's.

One fused pass emits, per chunk row:
    col[r]   = first argmax of |x[r, :]|          (int32 column)
    vals[r]  = x[r, col[r]]
    resid[r] = x[r, :] with the selected lane zeroed
so the error-feedback residual costs no second pass.

Grid: (k / block_rows,); blocks are (block_rows, m) tiles in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BLOCK_ROWS = 8  # chunk rows per block; the chunk width is the lane dim


def _select_kernel(x_ref, vals_ref, col_ref, resid_ref):
    x = x_ref[...]                                   # (rows, m)
    rows, m = x.shape
    mag = jnp.abs(x)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, m), 1)
    rowmax = jnp.max(mag, axis=1, keepdims=True)
    # first-occurrence argmax: min lane index among the maxima
    col = jnp.min(jnp.where(mag == rowmax, lane, m), axis=1,
                  keepdims=True)                     # (rows, 1)
    picked = lane == col
    vals_ref[...] = jnp.sum(jnp.where(picked, x, 0), axis=1,
                            keepdims=True).astype(x.dtype)
    col_ref[...] = col.astype(jnp.int32)
    resid_ref[...] = jnp.where(picked, jnp.zeros_like(x), x)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def chunk_select(x, *, block_rows: int = BLOCK_ROWS,
                 interpret: bool = False):
    """x: (k, m) f32 -> (vals (k, 1), col (k, 1) int32, resid (k, m))."""
    k, m = x.shape
    block_rows = min(block_rows, k)
    assert k % block_rows == 0
    grid = (k // block_rows,)
    return pl.pallas_call(
        _select_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, m), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, m), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((k, 1), x.dtype),
                   jax.ShapeDtypeStruct((k, 1), jnp.int32),
                   jax.ShapeDtypeStruct((k, m), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="chunk_select",
    )(x)
