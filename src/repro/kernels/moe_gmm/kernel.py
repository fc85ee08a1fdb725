"""Fused expert-FFN kernel (Pallas): the MoE hot loop.

After capacity dispatch, expert inputs are a dense (E, M, d) tensor
(M = groups x capacity).  This kernel runs the whole SwiGLU expert FFN —
h = silu(x @ w1) * (x @ w3); y = h @ w2 — in VMEM per (expert, M-tile)
block, so the (M, ff) hidden activations never round-trip to HBM (the
reference path writes h twice and reads it once: 3 x M x ff x 2 bytes of
traffic that this kernel eliminates; see EXPERIMENTS.md §Perf).

Grid: (E, M/bm) — experts parallel, M-tiles parallel; the ff dimension is
processed in a VMEM loop with an f32 accumulator for y.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BLOCK_M = 128
BLOCK_F = 512


def _ffn_kernel(x_ref, w1_ref, w3_ref, w2_ref, y_ref, acc_scr, *,
                act: str, n_f_blocks: int):
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)                 # (bm, d)
    w1 = w1_ref[0].astype(jnp.float32)               # (d, bf)
    w2 = w2_ref[0].astype(jnp.float32)               # (bf, d)
    h = jax.lax.dot_general(x, w1, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if act == "silu":
        w3 = w3_ref[0].astype(jnp.float32)
        up = jax.lax.dot_general(x, w3, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        h = jax.nn.silu(h) * up
    else:
        h = jax.nn.gelu(h)
    acc_scr[...] += jax.lax.dot_general(
        h, w2, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(fi == n_f_blocks - 1)
    def _finish():
        y_ref[0] = acc_scr[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "block_m", "block_f",
                                             "interpret"))
def expert_ffn(x, w1, w2, w3, *, act: str = "silu",
               block_m: int = BLOCK_M, block_f: int = BLOCK_F,
               interpret: bool = False):
    """x: (E, M, d); w1/w3: (E, d, ff); w2: (E, ff, d) -> (E, M, d)."""
    e, m, d = x.shape
    ff = w1.shape[-1]
    block_m = min(block_m, m)
    block_f = min(block_f, ff)
    assert m % block_m == 0 and ff % block_f == 0
    nf = ff // block_f
    grid = (e, m // block_m, nf)
    kernel = functools.partial(_ffn_kernel, act=act, n_f_blocks=nf)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, d), lambda ee, mi, fi: (ee, mi, 0)),
            pl.BlockSpec((1, d, block_f), lambda ee, mi, fi: (ee, 0, fi)),
            pl.BlockSpec((1, d, block_f), lambda ee, mi, fi: (ee, 0, fi)),
            pl.BlockSpec((1, block_f, d), lambda ee, mi, fi: (ee, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, d),
                               lambda ee, mi, fi: (ee, mi, 0)),
        out_shape=jax.ShapeDtypeStruct((e, m, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="moe_expert_ffn",
    )(x, w1, w3, w2)
