"""Mamba2 (SSD) blocks: chunked-parallel training path + recurrent decode.

The chunked selective-state-space algorithm (SSD) splits the sequence into
chunks of ``cfg.ssm_chunk`` tokens.  Within a chunk the computation is an
attention-like batched matmul (MXU-friendly); across chunks a tiny
associative recurrence carries the (P, N) state.  The pure-jnp path below is
the reference/dry-run implementation; ``kernels.mamba_scan`` is the fused
Pallas version selected by ``cfg.use_pallas_kernels``.

Tensor parallelism: projections are *split* (z / x / B / C / dt) rather than
fused so that head-structured tensors (x, dt, per-head A/D) shard cleanly
over the ``model`` axis while the small shared B/C streams stay replicated —
the TPU-native layout of Mamba2 TP.

State layout per layer (decode):
  conv_x/b/c: (B, d_conv-1, ·)   rolling windows of conv inputs
  ssm:        (B, H, P, N)       selective state (f32)
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, matmul, matmul_rp, rms_norm

D_CONV = 4  # depthwise conv kernel width


def dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_headdim
    return d_inner, n_heads


def init_mamba(key, cfg):
    d = cfg.d_model
    d_inner, h = dims(cfg)
    n = cfg.ssm_state
    kz, kx, kb, kc, kdt, kcx, kcb, kcc, kout = jax.random.split(key, 9)
    dtype = cfg.param_dtype()
    return {
        "in_z": dense_init(kz, (d, d_inner), dtype),
        "in_x": dense_init(kx, (d, d_inner), dtype),
        "in_b": dense_init(kb, (d, n), dtype),
        "in_c": dense_init(kc, (d, n), dtype),
        "in_dt": dense_init(kdt, (d, h), dtype),
        "conv_x": dense_init(kcx, (D_CONV, d_inner), dtype, scale=0.5),
        "conv_b": dense_init(kcb, (D_CONV, n), dtype, scale=0.5),
        "conv_c": dense_init(kcc, (D_CONV, n), dtype, scale=0.5),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "a_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm_w": jnp.ones((d_inner,), dtype),
        "out_proj": dense_init(kout, (d_inner, d), dtype),
    }


def _conv1d(x, w):
    """Causal depthwise conv, kernel width D_CONV.  x: (B,L,C), w: (K,C)."""
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for k in range(D_CONV):
        shift = D_CONV - 1 - k
        xs = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
        y = y + xs.astype(jnp.float32) * w[k].astype(jnp.float32)
    return y.astype(x.dtype)


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """Chunked SSD scan (reference).

    x: (B,L,H,P)  dt: (B,L,H)  a: (H,) negative  b,c: (B,L,N)
    Returns y: (B,L,H,P), final_state: (B,H,P,N).
    """
    bs, l, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk, l)
    nc = l // q
    xc = x.reshape(bs, nc, q, h, p).astype(jnp.float32)
    dtc = dt.reshape(bs, nc, q, h).astype(jnp.float32)
    bc = b.reshape(bs, nc, q, n).astype(jnp.float32)
    cc = c.reshape(bs, nc, q, n).astype(jnp.float32)

    da = dtc * a  # (B,nc,q,H), negative
    cum = jnp.cumsum(da, axis=2)                       # inclusive cumsum
    total = cum[:, :, -1]                              # (B,nc,H)

    # --- within-chunk (attention-like) ---
    # decay(i,j) = exp(cum_i - cum_j) for i >= j
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # (B,nc,i,j,H)
    causal = jnp.tril(jnp.ones((q, q), bool))
    # mask BEFORE exp: masked entries are +large, and grad-of-where would
    # propagate inf*0=NaN through the unselected exp branch otherwise
    seg = jnp.where(causal[None, None, :, :, None], seg, -1e30)
    decay = jnp.exp(seg)
    scores = jnp.einsum("bcin,bcjn->bcij", cc, bc)          # (B,nc,i,j)
    m = scores[..., None] * decay * dtc[:, :, None, :, :]   # (B,nc,i,j,H)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", m, xc)

    # --- chunk states: S_c = sum_j exp(total - cum_j) dt_j B_j (x) x_j ---
    w = jnp.exp(total[:, :, None, :] - cum) * dtc           # (B,nc,q,H)
    states = jnp.einsum("bcjh,bcjn,bcjhp->bchpn", w, bc, xc)  # (B,nc,H,P,N)

    # --- inter-chunk recurrence over the nc axis (tiny sequential scan) ---
    gamma = jnp.exp(total)                                  # (B,nc,H)

    def step(s, inp):
        g, st = inp                                         # g:(B,H) st:(B,H,P,N)
        s_new = s * g[:, :, None, None] + st
        return s_new, s
    s0 = jnp.zeros((bs, h, p, n), jnp.float32)
    s_fin, s_in = jax.lax.scan(
        step, s0, (jnp.moveaxis(gamma, 1, 0), jnp.moveaxis(states, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1)                         # state entering chunk

    # --- inter-chunk output: y_i += exp(cum_i) * C_i . S_in ---
    y_inter = jnp.einsum("bcin,bchpn,bcih->bcihp",
                         cc, s_in, jnp.exp(cum))
    y = (y_intra + y_inter).reshape(bs, l, h, p)
    return y.astype(x.dtype), s_fin


def mamba_forward(params, x, cfg) -> Tuple[jnp.ndarray, dict]:
    """Full-sequence Mamba2 block. x: (B,L,d) -> (y, final_state)."""
    bs, l, d = x.shape
    d_inner, h = dims(cfg)
    n = cfg.ssm_state
    p = cfg.ssm_headdim

    z = matmul(x, params["in_z"])
    xr = matmul(x, params["in_x"])                     # pre-conv x stream
    br = matmul(x, params["in_b"])
    cr = matmul(x, params["in_c"])
    xs = jax.nn.silu(_conv1d(xr, params["conv_x"]))
    b = jax.nn.silu(_conv1d(br, params["conv_b"]))
    c = jax.nn.silu(_conv1d(cr, params["conv_c"]))
    dt = jax.nn.softplus(
        matmul(x, params["in_dt"]).astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])

    xh = xs.reshape(bs, l, h, p)
    if cfg.use_pallas_kernels:
        from repro.kernels.mamba_scan import ops as scan_ops
        y, s_fin = scan_ops.ssd(xh, dt, a, b, c, chunk=cfg.ssm_chunk,
                                interpret=cfg.interpret_kernels)
    else:
        y, s_fin = ssd_chunked(xh, dt, a, b, c, cfg.ssm_chunk)
    y = y + xh.astype(y.dtype) * params["d_skip"].astype(
        y.dtype)[None, None, :, None]
    y = y.reshape(bs, l, d_inner) * jax.nn.silu(z)
    y = rms_norm(params["norm_w"], y, cfg.norm_eps)
    tail = lambda r: jnp.pad(
        r, ((0, 0), (D_CONV - 1, 0), (0, 0)))[:, -(D_CONV - 1):]
    state = {"ssm": s_fin, "conv_x": tail(xr), "conv_b": tail(br),
             "conv_c": tail(cr)}
    return matmul_rp(y, params["out_proj"], cfg), state


def init_mamba_state(cfg, batch, dtype):
    d_inner, h = dims(cfg)
    n = cfg.ssm_state
    return {
        "conv_x": jnp.zeros((batch, D_CONV - 1, d_inner), dtype),
        "conv_b": jnp.zeros((batch, D_CONV - 1, n), dtype),
        "conv_c": jnp.zeros((batch, D_CONV - 1, n), dtype),
        "ssm": jnp.zeros((batch, h, cfg.ssm_headdim, n), jnp.float32),
    }


def _conv_step(window, w):
    """window: (B,K,C) including current input; w: (K,C)."""
    return jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                      w.astype(jnp.float32))


def mamba_decode(params, x, state, cfg):
    """Single-token decode. x: (B,1,d) -> (y, new_state)."""
    bs = x.shape[0]
    d_inner, h = dims(cfg)
    n = cfg.ssm_state
    p = cfg.ssm_headdim

    xt = x[:, 0]
    z = matmul(xt, params["in_z"])
    xr = matmul(xt, params["in_x"])
    br = matmul(xt, params["in_b"])
    cr = matmul(xt, params["in_c"])
    wx = jnp.concatenate([state["conv_x"], xr[:, None]], axis=1)
    wb = jnp.concatenate([state["conv_b"], br[:, None]], axis=1)
    wc = jnp.concatenate([state["conv_c"], cr[:, None]], axis=1)
    xs = jax.nn.silu(_conv_step(wx, params["conv_x"])).astype(x.dtype)
    b = jax.nn.silu(_conv_step(wb, params["conv_b"]))
    c = jax.nn.silu(_conv_step(wc, params["conv_c"]))
    dt = jax.nn.softplus(
        matmul(xt, params["in_dt"]).astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])

    xh = xs.reshape(bs, h, p).astype(jnp.float32)
    da = jnp.exp(dt * a)                                    # (B,H)
    s = state["ssm"] * da[:, :, None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dt, b, xh)
    y = jnp.einsum("bn,bhpn->bhp", c, s)
    y = y + xh * params["d_skip"][None, :, None]
    y = y.reshape(bs, d_inner).astype(x.dtype) * jax.nn.silu(z)
    y = rms_norm(params["norm_w"], y, cfg.norm_eps)
    out = matmul_rp(y, params["out_proj"], cfg)[:, None]
    return out, {"ssm": s, "conv_x": wx[:, 1:], "conv_b": wb[:, 1:],
                 "conv_c": wc[:, 1:]}
