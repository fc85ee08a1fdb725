"""FlashAttention-2 for TPU (Pallas): fused causal GQA attention.

TPU adaptation of the FA2 algorithm (DESIGN.md §6): the online-softmax
recurrence runs over KV blocks streamed HBM->VMEM; per-(batch, head,
q-block) running max / denominator / f32 accumulator live in VMEM scratch
that persists across the sequential k-block grid dimension.  Block shapes
are MXU-aligned (128x128 tiles); the attention matrix never touches HBM —
this removes the O(S^2) logits traffic that makes the reference path
memory-bound in the roofline analysis.

Grid: (B, H, S/bq, S/bk) with the last dimension sequential ("arbitrary"),
so scratch carries across k-blocks.  Causal/window masking happens
block-wise: fully-masked blocks are skipped via the index bounds, the
diagonal block applies an elementwise mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: int,
               block_q: int, block_k: int, seq_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level skip: causal => k block cannot start after q block end;
    # window => k block cannot end before the window's left edge
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window:
        run = jnp.logical_and(
            run, k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= qpos >= kpos
        if window:
            ok &= (qpos - kpos) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                       # (bq, bk)
        corr = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "scale", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    scale: float = 0.0,
                    interpret: bool = False):
    """q: (B, H, S, hd);  k, v: (B, KV, S, hd) with KV | H.

    Returns (B, H, S, hd).  GQA is expressed in the k/v index maps: head h
    reads kv head h // (H // KV).
    """
    b, h, s, hd = q.shape
    kv = k.shape[1]
    group = h // kv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    scale = scale or hd ** -0.5
    grid = (b, h, s // block_q, s // block_k)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_len=s)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bb, hh, qi, ki, g=group: (bb, hh // g, ki,
                                                          0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bb, hh, qi, ki, g=group: (bb, hh // g, ki,
                                                          0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bb, hh, qi, ki: (bb, hh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom
            pltpu.VMEM((block_q, hd), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
