"""GQA attention: full-sequence (train/prefill), blocked-causal for long
sequences, sliding-window, and single-token decode against a KV cache.

Grouped-query attention is computed *without* materialising repeated KV
heads: queries are reshaped to (B, S, kv, group, hd) and contracted against
(B, S, kv, hd) keys directly — less HBM traffic and exact FLOP accounting.

For causal sequences longer than ``BLOCK_Q`` the query axis is processed in
an unrolled block loop; block i only reads keys ``[lo, hi)`` allowed by the
causal/window structure, so the lowered HLO contains only useful FLOPs
(roughly the S^2/2 triangle rather than the full square).  This is the
pure-jnp analogue of the ``kernels.flash_attention`` Pallas kernel, which is
selected on TPU via ``cfg.use_pallas_kernels``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import (apply_rope, dense_init, matmul,
                                 matmul_rp)

NEG_INF = -1e30
BLOCK_Q = 1024  # blocked-causal query block (q-chunks of the lowered loop)


def init_attention(key, cfg, d_model=None):
    d = d_model or cfg.d_model
    hd = cfg.hd()
    kq, kk, kv, ko = jax.random.split(key, 4)
    dtype = cfg.param_dtype()
    return {
        "wq": dense_init(kq, (d, cfg.n_heads * hd), dtype),
        "wk": dense_init(kk, (d, cfg.n_kv_heads * hd), dtype),
        "wv": dense_init(kv, (d, cfg.n_kv_heads * hd), dtype),
        "wo": dense_init(ko, (cfg.n_heads * hd, d), dtype),
    }


def _split_heads(x, n_heads, hd):
    return x.reshape(*x.shape[:-1], n_heads, hd)


def sdpa(q, k, v, mask=None, causal=False, window: int = 0,
         q_offset: int = 0):
    """Grouped scaled-dot-product attention.

    q: (B,Sq,H,hd);  k,v: (B,Sk,KV,hd) with KV | H;  mask broadcastable to
    (B,KV,G,Sq,Sk).  ``q_offset``: absolute position of query 0 minus
    absolute position of key 0 (used by the blocked loop and decode).
    """
    b, sq, h, hd = q.shape
    skv = k.shape[2]
    g = h // skv
    sk = k.shape[1]
    scale = hd ** -0.5
    qg = q.reshape(b, sq, skv, g, hd)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if causal or window:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(sk)[None, :]
        ok = jnp.ones((sq, sk), bool)
        if causal:
            ok &= qpos >= kpos
        if window:
            ok &= (qpos - kpos) < window
        logits = jnp.where(ok[None, None, None], logits, NEG_INF)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(b, sq, h, hd)


def sdpa_blocked(q, k, v, window: int = 0, block_q: int = BLOCK_Q):
    """Causal attention via an unrolled query-block loop.

    Each block only contracts against the keys its causal/window footprint
    allows, bounding live memory to (B,KV,G,block_q,hi) and keeping the
    lowered FLOPs ~S^2/2.
    """
    b, sq, h, hd = q.shape
    outs = []
    for i in range(0, sq, block_q):
        hi = min(i + block_q, sq)
        lo = max(0, i - window + 1) if window else 0
        qi = q[:, i:hi]
        ki, vi = k[:, lo:hi], v[:, lo:hi]
        outs.append(sdpa(qi, ki, vi, causal=True, window=window,
                         q_offset=i - lo))
    return jnp.concatenate(outs, axis=1)


def sdpa_blocked_scan(q, k, v, window: int = 0, block_q: int = BLOCK_Q):
    """Deploy-mode blocked attention: lax.scan over uniform query blocks.

    Blocks attend the full key range with dynamic causal masking (uniform
    shapes for the loop); buffer reuse across iterations bounds live memory
    to one block.  FLOP accounting uses the unrolled twin above.
    """
    b, sq, h, hd = q.shape
    # cap the live logits tile: bq x Sk <= 4M elements per (b, head)
    block_q = max(128, min(block_q, (1 << 22) // sq))
    nb = sq // block_q
    qb = jnp.moveaxis(q.reshape(b, nb, block_q, h, hd), 1, 0)

    @jax.checkpoint
    def body(_, inp):
        i, qi = inp
        off = i * block_q
        skv = k.shape[2]
        qg = qi.reshape(b, block_q, skv, h // skv, hd)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                            preferred_element_type=jnp.float32) * hd ** -0.5
        qpos = jnp.arange(block_q)[:, None] + off
        kpos = jnp.arange(sq)[None, :]
        ok = qpos >= kpos
        if window:
            ok &= (qpos - kpos) < window
        logits = jnp.where(ok[None, None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v,
                         preferred_element_type=jnp.float32).astype(q.dtype)
        return None, out.reshape(b, block_q, h, hd)

    _, outs = jax.lax.scan(body, None, (jnp.arange(nb), qb))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, hd)


def attention(params, x, cfg, positions, *, causal=True, window=0,
              kv_x=None, use_rope=True):
    """Full attention over a sequence (training / prefill).

    kv_x: optional separate kv source (cross-attention).
    Returns (out, (k, v)) so prefill can build the cache.
    """
    hd = cfg.hd()
    q = _split_heads(matmul(x, params["wq"]), cfg.n_heads, hd)
    src = kv_x if kv_x is not None else x
    k = _split_heads(matmul(src, params["wk"]), cfg.n_kv_heads, hd)
    v = _split_heads(matmul(src, params["wv"]), cfg.n_kv_heads, hd)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        if kv_x is None:
            k = apply_rope(k, positions, cfg.rope_theta)
    if cfg.use_pallas_kernels and causal and kv_x is None:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=True, window=window,
                                     interpret=cfg.interpret_kernels)
    elif causal and kv_x is None and q.shape[1] > BLOCK_Q:
        blocked = sdpa_blocked_scan if cfg.deploy else sdpa_blocked
        out = blocked(q, k, v, window=window)
    else:
        out = sdpa(q, k, v, causal=causal and kv_x is None, window=window)
    out = out.reshape(*x.shape[:-1], cfg.n_heads * hd)
    return matmul_rp(out, params["wo"], cfg), (k, v)


def init_kv_cache(cfg, batch, max_len, dtype, window: int = 0):
    """Ring-buffer KV cache. With ``window`` the buffer is window-sized."""
    size = min(max_len, window) if window else max_len
    hd = cfg.hd()
    return {
        "k": jnp.zeros((batch, size, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, size, cfg.n_kv_heads, hd), dtype),
    }


def _decode_qkv(params, x, cfg, positions, use_rope):
    """The new token's query and its key/value row, each (B,1,heads,hd)."""
    hd = cfg.hd()
    q = _split_heads(matmul(x, params["wq"]), cfg.n_heads, hd)
    k_new = _split_heads(matmul(x, params["wk"]), cfg.n_kv_heads, hd)
    v_new = _split_heads(matmul(x, params["wv"]), cfg.n_kv_heads, hd)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k_new = apply_rope(k_new, positions, cfg.rope_theta)
    return q, k_new, v_new


def _decode_slot(positions, size, window):
    """Cache row each lane's new token goes to (ring buffer with a window)."""
    return (positions[:, 0] % size) if window else positions[:, 0]


def _decode_attend(params, q, k, v, cfg, positions, window):
    """Attend the new token over one layer's cache k, v (B,size,KV,hd),
    which already holds its row."""
    size = k.shape[1]
    # Valid-position mask: ring buffer slot j holds a token iff it has been
    # written and (windowed) is within ``window`` of the current position.
    pos = positions[:, 0][:, None]                      # (B,1)
    j = jnp.arange(size)[None, :]                       # (1,size)
    if window:
        # slot j holds absolute position: the largest p<=pos with p%size==j
        age = (pos - j) % size                          # 0..size-1
        abs_pos = pos - age
        valid = (abs_pos >= 0) & (pos - abs_pos < window)
    else:
        valid = j <= pos
    mask = valid[:, None, None, None, :]                # (B,KV,G,1,size)
    out = sdpa(q, k, v, mask=mask)
    out = out.reshape(*q.shape[:2], cfg.n_heads * cfg.hd())
    return matmul_rp(out, params["wo"], cfg)


def decode_attention(params, x, cache, cfg, positions, *, window=0,
                     kv_x=None, use_rope=True):
    """One-token decode step: append to cache, attend over it.

    x: (B,1,d); positions: (B,1) absolute position of the new token.
    Returns (out, new_cache).
    """
    if kv_x is not None:
        # Cross-attention: cache holds the (static) encoder/image K/V.
        hd = cfg.hd()
        q = _split_heads(matmul(x, params["wq"]), cfg.n_heads, hd)
        out = sdpa(q, cache["k"], cache["v"])
        out = out.reshape(*x.shape[:-1], cfg.n_heads * hd)
        return matmul_rp(out, params["wo"], cfg), cache
    q, k_new, v_new = _decode_qkv(params, x, cfg, positions, use_rope)
    slot = _decode_slot(positions, cache["k"].shape[1], window)
    bidx = jnp.arange(x.shape[0])
    k = cache["k"].at[bidx, slot].set(k_new[:, 0])
    v = cache["v"].at[bidx, slot].set(v_new[:, 0])
    return (_decode_attend(params, q, k, v, cfg, positions, window),
            {"k": k, "v": v})


def decode_attention_stacked(params, x, stack, layer, cfg, positions, *,
                             window=0, use_rope=True):
    """``decode_attention`` for layer ``layer`` of a stacked cache.

    stack: {"k","v"} of (L,B,size,KV,hd).  Each lane's new row is written
    at ``[layer, b, slot]`` of the whole stack, in place when the stack
    rides in a loop carry, and the token attends over ``stack[layer]``:
    no layer of the cache is copied out and written back.  Without a
    window, positions must lie inside the cache.
    Returns (out, new_stack).
    """
    q, k_new, v_new = _decode_qkv(params, x, cfg, positions, use_rope)
    slot = _decode_slot(positions, stack["k"].shape[2], window)
    k, v = stack["k"], stack["v"]
    # One dynamic-update-slice per lane rather than one scatter: a TPU
    # keeps a (..., KV, 64) bf16 cache with the position axis minor, a
    # scatter of (KV, hd) rows wants it row-major, and the compiler then
    # converts the whole carried cache to that layout and back.
    for b in range(x.shape[0]):
        at = (layer, b, slot[b], 0, 0)
        k = jax.lax.dynamic_update_slice(k, k_new[b:b + 1, None], at)
        v = jax.lax.dynamic_update_slice(v, v_new[b:b + 1, None], at)
    return (_decode_attend(params, q, k[layer], v[layer], cfg, positions,
                           window),
            {"k": k, "v": v})
