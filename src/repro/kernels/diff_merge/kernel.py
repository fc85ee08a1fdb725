"""Chunk-diff + merge-op kernel (Pallas): the paper's byte-wise diff engine
(§4.1, Table 3) as a TPU streaming kernel.

Faabric traps dirty pages with mprotect and compares bytes on the host; a
TPU has no page faults inside a program, so dirty tracking is an explicit
compare-against-snapshot — a pure bandwidth-bound streaming op, exactly
what a Pallas kernel with large VMEM blocks does at HBM speed.

One fused pass computes, per chunk (the page analogue):
    dirty[c] = any(b0[c] != b1[c])
    a1[c]    = merge_op(a0[c], b0[c], b1[c])        (Table 3)
so the diff *detection* and the *merge-apply* read the operands once.

Grid: (n_chunks / chunk_rows,); blocks are (chunk_rows, CHUNK) tiles in
VMEM.  The merge op is a compile-time specialisation (one kernel per op,
like the paper's per-diff merge-op tag).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


MERGE_OPS = ("sum", "subtract", "multiply", "divide", "overwrite")
BLOCK_ROWS = 8  # chunks per block (rows); chunk width is the lane dim


def compute_dtype(dtype, op: str):
    """Dtype the merge maths run in, derived from the *leaf* dtype:
    integer leaves stay integer for the exact ops (sum/subtract/
    overwrite — a float round-trip silently corrupts large ints),
    f32/f64 keep their own precision, and only low-precision floats
    (bf16/f16) promote to f32.  Shared with ``diffsync.dense_merge``'s
    rule so kernel and host dense paths agree bit-for-bit."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        if op in ("sum", "subtract", "overwrite"):
            return dtype
        return jnp.float32
    if dtype in (jnp.float32, jnp.float64):
        return dtype
    return jnp.float32


def _merge(a0, b0, b1, op: str):
    if op == "sum":
        return a0 + (b1 - b0)
    if op == "subtract":
        return a0 - (b0 - b1)
    if op == "multiply":
        return a0 * jnp.where(b0 == 0, 1.0, b1 / b0)
    if op == "divide":
        return a0 / jnp.where(b1 == 0, 1.0,
                              jnp.where(b0 == 0, 1.0, b0 / b1))
    if op == "overwrite":
        return b1
    raise ValueError(op)


def _dm_kernel(a0_ref, b0_ref, b1_ref, a1_ref, dirty_ref, *, op: str):
    cdt = compute_dtype(a0_ref.dtype, op)
    a0 = a0_ref[...].astype(cdt)
    b0 = b0_ref[...].astype(cdt)
    b1 = b1_ref[...].astype(cdt)
    # dirty detection compares the raw stored values (exact for every
    # dtype), not the possibly-promoted compute values
    dirty_rows = jnp.any(b0_ref[...] != b1_ref[...],
                         axis=1, keepdims=True)               # (rows, 1)
    merged = _merge(a0, b0, b1, op)
    # clean chunks keep the main value untouched (sparse diff semantics)
    a1_ref[...] = jnp.where(dirty_rows, merged, a0).astype(a1_ref.dtype)
    dirty_ref[...] = dirty_rows


@functools.partial(jax.jit,
                   static_argnames=("op", "block_rows", "interpret"))
def diff_merge(a0, b0, b1, *, op: str = "sum",
               block_rows: int = BLOCK_ROWS, interpret: bool = False):
    """a0/b0/b1: (n_chunks, chunk) f32/bf16 -> (a1, dirty (n_chunks, 1))."""
    n, c = a0.shape
    block_rows = min(block_rows, n)
    assert n % block_rows == 0
    grid = (n // block_rows,)
    kernel = functools.partial(_dm_kernel, op=op)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0))] * 3,
        out_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, c), a0.dtype),
                   jax.ShapeDtypeStruct((n, 1), jnp.bool_)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="diff_merge",
    )(a0, b0, b1)
