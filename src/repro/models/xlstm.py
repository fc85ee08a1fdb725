"""xLSTM blocks: mLSTM (matrix memory, chunkwise-parallel) and sLSTM
(scalar memory, sequential recurrence).

Training/prefill uses the *stabilised chunkwise* form of mLSTM: the
sequence is processed in chunks of ``CHUNK`` tokens; within a chunk the
computation is attention-like (quadratic in the chunk, MXU-friendly), and a
per-head matrix memory (C: (hd,hd), n: (hd,), m: ()) carries state across
chunks — mathematically identical to the token recurrence, including the
max-stabiliser.  The chunk loop is a Python loop (exact HLO FLOP
accounting); the fused Pallas version lives in ``kernels.mlstm``.

Tensor-parallel layout: q/k are per-head block-diagonal and replicated
(their hd_k contraction must be whole); v and the matrix-memory value axis
(hd_v) shard over ``model``.

sLSTM carries a true hidden-state recurrence (h feeds the gates), so the
sequence dimension is scanned; per-head recurrent weights are
block-diagonal.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, matmul, matmul_rp, rms_norm

D_CONV = 4
CHUNK = 1024
NEG = -1e30


def mlstm_dims(cfg):
    du = int(cfg.xlstm_proj_factor * cfg.d_model)
    hd = du // cfg.n_heads
    return du, hd


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------
def init_mlstm(key, cfg):
    d = cfg.d_model
    du, hd = mlstm_dims(cfg)
    h = cfg.n_heads
    kx, kz, kconv, kq, kk, kv, ki, kf, kd = jax.random.split(key, 9)
    dtype = cfg.param_dtype()
    return {
        "up_x": dense_init(kx, (d, du), dtype),
        "up_z": dense_init(kz, (d, du), dtype),
        "conv_w": dense_init(kconv, (D_CONV, du), dtype, scale=0.5),
        # block-diagonal per-head q/k/v (mLSTM cells are head-independent)
        "wq": dense_init(kq, (h, hd, hd), dtype, scale=hd ** -0.5),
        "wk": dense_init(kk, (h, hd, hd), dtype, scale=hd ** -0.5),
        "wv": dense_init(kv, (h, hd, hd), dtype, scale=hd ** -0.5),
        "wi": dense_init(ki, (du, h), jnp.float32),
        "wf": dense_init(kf, (du, h), jnp.float32),
        "bi": jnp.zeros((h,), jnp.float32),
        "bf": jnp.full((h,), 3.0, jnp.float32),   # open forget gates at init
        "skip": jnp.ones((du,), dtype),
        "norm_w": jnp.ones((du,), dtype),
        "down": dense_init(kd, (du, d), dtype),
    }


def _conv1d(x, w):
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for k in range(D_CONV):
        shift = D_CONV - 1 - k
        xs = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
        y = y + xs.astype(jnp.float32) * w[k].astype(jnp.float32)
    return y.astype(x.dtype)


def _heads(x, h, hd):
    return x.reshape(*x.shape[:-1], h, hd)


def mlstm_chunk_body(q, k, v, logi, logf, state):
    """One stabilised chunk.  q,k,v: (B,q,H,hd) f32; logi/logf: (B,q,H).

    state: (c (B,H,hdv,hdk), n (B,H,hdk), m (B,H)).  Returns (h, new state).
    Exactly equivalent to the per-token recurrence.
    """
    bs, qq, h, hd = q.shape
    scale = hd ** -0.5
    c_in, n_in, m_in = state
    cumf = jnp.cumsum(logf, axis=1)                       # (B,q,H)
    total = cumf[:, -1]                                   # (B,H)

    # ---- intra-chunk decay matrix (stabilised) ----
    dt = (cumf[:, :, None, :] - cumf[:, None, :, :]
          + logi[:, None, :, :])                          # (B,i,j,H)
    causal = jnp.tril(jnp.ones((qq, qq), bool))
    dt = jnp.where(causal[None, :, :, None], dt, NEG)
    m_intra = jnp.max(dt, axis=2)                         # (B,i,H)
    b_inter = cumf + m_in[:, None, :]                     # (B,i,H)
    m_comb = jnp.maximum(m_intra, b_inter)
    d = jnp.exp(dt - m_comb[:, :, None, :])
    inter_scale = jnp.exp(b_inter - m_comb)               # (B,i,H)

    scores = jnp.einsum("bihd,bjhd->bijh", q, k) * scale  # (B,i,j,H)
    s = scores * d
    num = jnp.einsum("bijh,bjhd->bihd", s, v)
    num = num + inter_scale[..., None] * jnp.einsum(
        "bhde,bihe->bihd", c_in, q) * scale
    den = jnp.sum(s, axis=2) + inter_scale * jnp.einsum(
        "bhe,bihe->bih", n_in, q) * scale
    den = jnp.maximum(jnp.abs(den), jnp.exp(-m_comb))
    ht = num / den[..., None]

    # ---- state update ----
    w = total[:, None, :] - cumf + logi                   # (B,j,H)
    m_out = jnp.maximum(m_in + total, jnp.max(w, axis=1))
    wexp = jnp.exp(w - m_out[:, None, :])
    carry = jnp.exp(m_in + total - m_out)
    c_out = carry[:, :, None, None] * c_in + jnp.einsum(
        "bjh,bjhd,bjhe->bhde", wexp, v, k)
    n_out = carry[:, :, None] * n_in + jnp.einsum(
        "bjh,bjhe->bhe", wexp, k)
    return ht, (c_out, n_out, m_out)


def mlstm_chunked(q, k, v, logi, logf, state=None, chunk: int = CHUNK,
                  use_scan: bool = False):
    """Full-sequence chunkwise mLSTM.

    Python chunk loop by default (exact HLO FLOP accounting); deploy mode
    uses lax.scan over chunks (buffer reuse, one chunk live at a time).
    """
    bs, l, h, hd = q.shape
    chunk = min(chunk, l)
    if state is None:
        state = (jnp.zeros((bs, h, hd, hd), jnp.float32),
                 jnp.zeros((bs, h, hd), jnp.float32),
                 jnp.full((bs, h), NEG, jnp.float32))
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if use_scan and l % chunk == 0 and l > chunk:
        nc = l // chunk
        move = lambda x: jnp.moveaxis(
            x.reshape(bs, nc, chunk, *x.shape[2:]), 1, 0)
        xs = tuple(move(a) for a in (qf, kf, vf, logi, logf))

        @jax.checkpoint
        def body(st, inp):
            ht, st = mlstm_chunk_body(*inp, st)
            return st, ht
        state, outs = jax.lax.scan(body, state, xs)
        return (jnp.moveaxis(outs, 0, 1).reshape(bs, l, h, hd)
                .astype(q.dtype), state)
    outs = []
    for i in range(0, l, chunk):
        j = min(i + chunk, l)
        ht, state = mlstm_chunk_body(qf[:, i:j], kf[:, i:j], vf[:, i:j],
                                     logi[:, i:j], logf[:, i:j], state)
        outs.append(ht)
    return jnp.concatenate(outs, axis=1).astype(q.dtype), state


def _gates(params, xm):
    logi = jnp.log(jax.nn.sigmoid(
        xm.astype(jnp.float32) @ params["wi"] + params["bi"]) + 1e-9)
    logf = jnp.log(jax.nn.sigmoid(
        xm.astype(jnp.float32) @ params["wf"] + params["bf"]) + 1e-9)
    return logi, logf


def mlstm_forward(params, x, cfg, state=None) -> Tuple[jnp.ndarray, dict]:
    """Full-sequence mLSTM block body. x: (B,L,d)."""
    bs, l, _ = x.shape
    du, hd = mlstm_dims(cfg)
    h = cfg.n_heads
    xm = matmul(x, params["up_x"])
    z = matmul(x, params["up_z"])
    xc = jax.nn.silu(_conv1d(xm, params["conv_w"]))
    q = jnp.einsum("blhd,hde->blhe", _heads(xc, h, hd), params["wq"])
    k = jnp.einsum("blhd,hde->blhe", _heads(xc, h, hd), params["wk"])
    v = jnp.einsum("blhd,hde->blhe", _heads(xm, h, hd), params["wv"])
    logi, logf = _gates(params, xm)
    st = None
    if state is not None:
        st = (state["c"], state["n"], state["m"])
    if cfg.use_pallas_kernels:
        from repro.kernels.mlstm import ops as mlstm_ops
        ht, st_fin = mlstm_ops.mlstm(q, k, v, logi, logf,
                                     interpret=cfg.interpret_kernels)
    else:
        ht, st_fin = mlstm_chunked(q, k, v, logi, logf, st,
                                   use_scan=cfg.deploy)
    ht = ht.reshape(bs, l, du) + params["skip"] * xc
    y = rms_norm(params["norm_w"], ht, cfg.norm_eps) * jax.nn.silu(z)
    conv_tail = jnp.pad(
        xm, ((0, 0), (D_CONV - 1, 0), (0, 0)))[:, -(D_CONV - 1):]
    new_state = {"c": st_fin[0], "n": st_fin[1], "m": st_fin[2],
                 "conv": conv_tail}
    return matmul_rp(y, params["down"], cfg), new_state


def init_mlstm_state(cfg, batch, dtype):
    du, hd = mlstm_dims(cfg)
    h = cfg.n_heads
    return {
        "c": jnp.zeros((batch, h, hd, hd), jnp.float32),
        "n": jnp.zeros((batch, h, hd), jnp.float32),
        "m": jnp.full((batch, h), NEG, jnp.float32),
        "conv": jnp.zeros((batch, D_CONV - 1, du), dtype),
    }


def mlstm_decode(params, x, state, cfg):
    """One-token mLSTM step via the chunk body with q=1."""
    bs = x.shape[0]
    du, hd = mlstm_dims(cfg)
    h = cfg.n_heads
    xm = matmul(x[:, 0], params["up_x"])                  # (B,du)
    z = matmul(x[:, 0], params["up_z"])
    window = jnp.concatenate([state["conv"], xm[:, None]], axis=1)
    xc = jax.nn.silu(jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                                params["conv_w"].astype(jnp.float32))
                     ).astype(x.dtype)
    q = jnp.einsum("bhd,hde->bhe", _heads(xc, h, hd), params["wq"])
    k = jnp.einsum("bhd,hde->bhe", _heads(xc, h, hd), params["wk"])
    v = jnp.einsum("bhd,hde->bhe", _heads(xm, h, hd), params["wv"])
    logi, logf = _gates(params, xm)
    ht, (c, n, m) = mlstm_chunk_body(
        q[:, None].astype(jnp.float32), k[:, None].astype(jnp.float32),
        v[:, None].astype(jnp.float32), logi[:, None], logf[:, None],
        (state["c"], state["n"], state["m"]))
    ht = ht[:, 0].reshape(bs, du).astype(x.dtype) + params["skip"] * xc
    y = rms_norm(params["norm_w"], ht, cfg.norm_eps) * jax.nn.silu(z)
    new_state = {"c": c, "n": n, "m": m, "conv": window[:, 1:]}
    return matmul_rp(y, params["down"], cfg)[:, None], new_state


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------
def init_slstm(key, cfg):
    d = cfg.d_model
    h = cfg.n_heads
    hd = d // h
    kw, kr, ku, kd2 = jax.random.split(key, 4)
    dtype = cfg.param_dtype()
    ffd = int(4 * d / 3)
    return {
        "w": dense_init(kw, (d, 4 * d), dtype),           # i,f,z,o from x
        "r": dense_init(kr, (h, hd, 4 * hd), dtype, scale=hd ** -0.5),
        "bf": jnp.full((d,), 3.0, jnp.float32),
        "norm_w": jnp.ones((d,), dtype),
        "ff_up": dense_init(ku, (d, 2 * ffd), dtype),     # GeGLU
        "ff_down": dense_init(kd2, (ffd, d), dtype),
    }


def init_slstm_state(cfg, batch, dtype):
    d = cfg.d_model
    return {k: jnp.zeros((batch, d), jnp.float32) for k in ("c", "n", "h")} | {
        "m": jnp.full((batch, d), NEG, jnp.float32)}


def _slstm_cell(params, gx, state, cfg):
    """One sLSTM step.  gx: (B,4d) input-gate preactivations."""
    h_heads = state["h"].reshape(gx.shape[0], cfg.n_heads, -1)
    gr = jnp.einsum("bhd,hde->bhe", h_heads,
                    params["r"].astype(jnp.float32))
    g = gx + gr.reshape(gx.shape[0], -1)                    # (B,4d)
    gi, gf, gz, go = jnp.split(g, 4, axis=-1)
    logf = jnp.log(jax.nn.sigmoid(gf + params["bf"]) + 1e-9)
    m_new = jnp.maximum(logf + state["m"], gi)
    fi = jnp.exp(logf + state["m"] - m_new)
    ii = jnp.exp(gi - m_new)
    c = fi * state["c"] + ii * jnp.tanh(gz)
    n = fi * state["n"] + ii
    hy = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1e-6)
    return {"c": c, "n": n, "h": hy, "m": m_new}


def slstm_forward(params, x, cfg, state=None) -> Tuple[jnp.ndarray, dict]:
    """Sequential sLSTM over the sequence. x: (B,L,d)."""
    bs, l, d = x.shape
    gx = matmul(x, params["w"]).astype(jnp.float32)         # (B,L,4d)
    st = state or init_slstm_state(cfg, bs, x.dtype)

    def step(s, g):
        s_new = _slstm_cell(params, g, s, cfg)
        return s_new, s_new["h"]
    st_fin, hs = jax.lax.scan(step, st, jnp.moveaxis(gx, 1, 0))
    y = jnp.moveaxis(hs, 0, 1).astype(x.dtype)              # (B,L,d)
    y = rms_norm(params["norm_w"], y, cfg.norm_eps)
    up, gate = jnp.split(matmul(y, params["ff_up"]), 2, axis=-1)
    y = matmul(jax.nn.gelu(up) * gate, params["ff_down"])
    return y, st_fin


def slstm_decode(params, x, state, cfg):
    gx = matmul(x[:, 0], params["w"]).astype(jnp.float32)
    st = _slstm_cell(params, gx, state, cfg)
    y = rms_norm(params["norm_w"], st["h"].astype(x.dtype), cfg.norm_eps)
    up, gate = jnp.split(matmul(y, params["ff_up"]), 2, axis=-1)
    y = matmul(jax.nn.gelu(up) * gate, params["ff_down"])
    return y[:, None], st
