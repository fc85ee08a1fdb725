"""Chunkwise-parallel mLSTM kernel (Pallas): stabilised matrix-memory scan.

Same sequential-chunk-grid pattern as the Mamba2 kernel: grid
(batch, head, chunk) with the chunk dimension sequential; the per-head
matrix memory C (hd x hd), normaliser n (hd) and max-stabiliser m persist
in VMEM scratch.  Within a chunk the computation is the attention-like
stabilised parallel form (exactly ``models.xlstm.mlstm_chunk_body``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, h_ref,
                  cfin_ref, nfin_ref, mfin_ref,
                  c_scr, n_scr, m_scr, *, nc: int, scale: float):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        c_scr[...] = jnp.zeros_like(c_scr)
        n_scr[...] = jnp.zeros_like(n_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG)

    q = q_ref[0, 0].astype(jnp.float32)          # (q, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    logi = li_ref[0, 0].astype(jnp.float32)      # (q, 1)
    logf = lf_ref[0, 0].astype(jnp.float32)      # (q, 1)
    qq = q.shape[0]

    m_in = m_scr[0, 0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (qq, qq), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (qq, qq), 1)
    lower = ii >= jj
    # inclusive cumsum as a masked lower-triangular row sum (Mosaic has
    # no cumsum lowering)
    cumf = jnp.sum(jnp.where(lower, logf.T, 0.0), axis=1,
                   keepdims=True)                # (q, 1)
    total = cumf[qq - 1:, :]                     # (1, 1)

    # intra decay matrix (stabilised)
    dt = cumf - cumf.T + logi.T                  # (i, j)
    dt = jnp.where(lower, dt, NEG)
    m_intra = jnp.max(dt, axis=1, keepdims=True)          # (q, 1)
    b_inter = cumf + m_in                                 # (q, 1)
    m_comb = jnp.maximum(m_intra, b_inter)
    d = jnp.exp(dt - m_comb)
    inter_scale = jnp.exp(b_inter - m_comb)               # (q, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s * d
    num = jax.lax.dot_general(s, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    num = num + inter_scale * jax.lax.dot_general(
        q, c_scr[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    den = (jnp.sum(s, axis=1, keepdims=True)
           + inter_scale * jax.lax.dot_general(
               q, n_scr[...], (((1,), (1,)), ((), ())),
               preferred_element_type=jnp.float32) * scale)
    den = jnp.maximum(jnp.abs(den), jnp.exp(-m_comb))
    h_ref[0, 0] = (num / den).astype(h_ref.dtype)

    # state update
    w = total - cumf + logi                      # (q, 1)
    m_out = jnp.maximum(m_in + total, jnp.max(w))
    wexp = jnp.exp(w - m_out)                    # (q, 1)
    carry = jnp.exp(m_in + total - m_out)
    c_scr[...] = carry * c_scr[...] + jax.lax.dot_general(
        v * wexp, k, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (hd_v, hd_k)
    n_scr[...] = carry * n_scr[...] + jax.lax.dot_general(
        wexp, k, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (1, hd_k)
    m_scr[...] = m_out

    @pl.when(ci == nc - 1)
    def _finish():
        cfin_ref[0, 0] = c_scr[...]
        nfin_ref[0, 0] = n_scr[...]
        mfin_ref[0, 0] = m_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_scan(q, k, v, logi, logf, *, chunk: int = 128,
               interpret: bool = False):
    """q,k,v: (B,H,L,hd); logi/logf: (B,H,L,1).

    Returns h (B,H,L,hd), (C (B,H,hd,hd), n (B,H,1,hd), m (B,H,1,1))."""
    bs, h, l, hd = q.shape
    chunk = min(chunk, l)
    assert l % chunk == 0
    nc = l // chunk
    grid = (bs, h, nc)
    kernel = functools.partial(_mlstm_kernel, nc=nc, scale=hd ** -0.5)
    seq_spec = pl.BlockSpec((1, 1, chunk, hd),
                            lambda bb, hh, ci: (bb, hh, ci, 0))
    gate_spec = pl.BlockSpec((1, 1, chunk, 1),
                             lambda bb, hh, ci: (bb, hh, ci, 0))
    fin = lambda p_, q_: pl.BlockSpec((1, 1, p_, q_),
                                      lambda bb, hh, ci: (bb, hh, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, gate_spec, gate_spec],
        out_specs=[seq_spec, fin(hd, hd), fin(1, hd), fin(1, 1)],
        out_shape=[jax.ShapeDtypeStruct((bs, h, l, hd), q.dtype),
                   jax.ShapeDtypeStruct((bs, h, hd, hd), jnp.float32),
                   jax.ShapeDtypeStruct((bs, h, 1, hd), jnp.float32),
                   jax.ShapeDtypeStruct((bs, h, 1, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32),
                        pltpu.VMEM((1, hd), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mlstm_scan",
    )(q, k, v, logi, logf)
