"""Mamba2 chunked selective-scan kernel (Pallas, SSD algorithm).

TPU adaptation of the SSD chunked scan (DESIGN.md §6): the grid iterates
(batch, head, chunk) with the chunk dimension sequential; the (P, N)
selective state persists in VMEM scratch across chunk steps, so the
inter-chunk recurrence never leaves the chip.  Within a chunk everything is
(q x q) / (q x N) / (q x P) matmul work on the MXU.

Per chunk (all f32 in VMEM):
    cum     = cumsum(dt * a)                   (q,)  masked row sum
    decay   = exp(cum_i - cum_j) masked i>=j   (q, q)
    y_intra = ((C B^T) .* decay .* dt_j) x
    y_inter = exp(cum) * (C . state)
    state   = exp(total) * state + B^T ((exp(total - cum) dt) .* x)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, sfin_ref,
                s_scr, *, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (q, 1)
    a = a_ref[0, 0]                              # (1, 1) f32
    b = b_ref[0].astype(jnp.float32)             # (q, N)
    c = c_ref[0].astype(jnp.float32)             # (q, N)
    q = x.shape[0]

    da = dt * a                                  # (q, 1), negative
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = ii >= jj
    # inclusive cumsum as a masked lower-triangular row sum (Mosaic has
    # no cumsum lowering)
    cum = jnp.sum(jnp.where(lower, da.T, 0.0), axis=1,
                  keepdims=True)                 # (q, 1)
    total = cum[-1:, :]                          # (1, 1)

    # within-chunk
    seg = cum - cum.T                            # (q, q): cum_i - cum_j
    decay = jnp.exp(jnp.where(lower, seg, -1e30))  # mask before exp
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    m = scores * decay * dt.T                    # (q, q)
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += exp(cum) * C . S_in   (S_in: (P, N) scratch)
    y = y + jnp.exp(cum) * jax.lax.dot_general(
        c, s_scr[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: S = exp(total) S + sum_j w_j x_j B_j^T
    w = jnp.exp(total - cum) * dt                # (q, 1)
    s_new = jax.lax.dot_general(x * w, b, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    s_scr[...] = jnp.exp(total) * s_scr[...] + s_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _finish():
        sfin_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, b, c, *, chunk: int = 64, interpret: bool = False):
    """x: (B,H,L,P); dt: (B,H,L,1); a: (H,1,1); b,c: (B,L,N).

    Returns y: (B,H,L,P), final state (B,H,P,N)."""
    bs, h, l, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, l)
    assert l % chunk == 0
    nc = l // chunk
    grid = (bs, h, nc)
    kernel = functools.partial(_ssd_kernel, nc=nc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bb, hh, ci: (bb, hh, ci,
                                                               0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bb, hh, ci: (bb, hh, ci,
                                                               0)),
            pl.BlockSpec((1, 1, 1), lambda bb, hh, ci: (hh, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, ci: (bb, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bb, hh, ci: (bb, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bb, hh, ci: (bb, hh, ci,
                                                               0)),
            pl.BlockSpec((1, 1, p, n), lambda bb, hh, ci: (bb, hh, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bs, h, l, p), x.dtype),
                   jax.ShapeDtypeStruct((bs, h, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, a, b, c)
