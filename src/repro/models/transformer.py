"""Unified block stack for all 10 assigned architectures.

The stack is ``n_periods`` repetitions of a *period* — a short heterogeneous
pattern of block kinds (see ``ArchConfig.period()``).  Parameters are stacked
per period-position, so a single ``lax.scan`` over periods covers dense,
MoE, hybrid (zamba2: 5 mamba + 1 shared-attention), ssm (xlstm: 1 sLSTM +
7 mLSTM), vlm (4 attn + 1 cross-attn) and audio (enc-dec) stacks.  With
``cfg.scan_layers=False`` the periods are unrolled (used by the dry-run so
XLA's cost analysis counts every layer's FLOPs exactly).

Block state (for decode) is likewise stacked per period-position.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import base as cb
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import dense_init, matmul, mlp, init_mlp, rms_norm


# ---------------------------------------------------------------------------
# Per-kind block init
# ---------------------------------------------------------------------------
def init_block(key, kind: str, cfg) -> Dict[str, Any]:
    dtype = cfg.param_dtype()
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    ln = lambda: jnp.ones((d,), dtype)
    if kind == cb.ATTN or kind == cb.SHARED_ATTN:
        return {"ln1": ln(), "attn": attn.init_attention(ks[0], cfg),
                "ln2": ln(), "mlp": init_mlp(ks[1], d, cfg.d_ff, cfg.act, dtype)}
    if kind == cb.MOE:
        return {"ln1": ln(), "attn": attn.init_attention(ks[0], cfg),
                "ln2": ln(), "moe": moe_mod.init_moe(ks[1], cfg)}
    if kind == cb.CROSS_ATTN:
        # llama3.2-vision style: tanh-gated cross-attention + gated MLP.
        return {"ln1": ln(), "xattn": attn.init_attention(ks[0], cfg),
                "ln2": ln(), "mlp": init_mlp(ks[1], d, cfg.d_ff, cfg.act, dtype),
                "gate_attn": jnp.zeros((), jnp.float32),
                "gate_mlp": jnp.zeros((), jnp.float32)}
    if kind == cb.ENCDEC:
        return {"ln1": ln(), "attn": attn.init_attention(ks[0], cfg),
                "lnx": ln(), "xattn": attn.init_attention(ks[1], cfg),
                "ln2": ln(), "mlp": init_mlp(ks[2], d, cfg.d_ff, cfg.act, dtype)}
    if kind == cb.MAMBA:
        return {"ln1": ln(), "mamba": ssm_mod.init_mamba(ks[0], cfg)}
    if kind == cb.MLSTM:
        return {"ln1": ln(), "mlstm": xlstm_mod.init_mlstm(ks[0], cfg)}
    if kind == cb.SLSTM:
        return {"ln1": ln(), "slstm": xlstm_mod.init_slstm(ks[0], cfg)}
    raise ValueError(kind)


def init_block_state(kind: str, cfg, batch: int, max_len: int, dtype,
                     window: int = 0):
    """Decode-time state for one block (unstacked)."""
    if kind in (cb.ATTN, cb.MOE, cb.SHARED_ATTN):
        return attn.init_kv_cache(cfg, batch, max_len, dtype, window=window)
    if kind == cb.CROSS_ATTN:
        hd = cfg.hd()
        return {"k": jnp.zeros((batch, cfg.n_img_tokens, cfg.n_kv_heads, hd),
                               dtype),
                "v": jnp.zeros((batch, cfg.n_img_tokens, cfg.n_kv_heads, hd),
                               dtype)}
    if kind == cb.ENCDEC:
        hd = cfg.hd()
        c = attn.init_kv_cache(cfg, batch, max_len, dtype)
        c["xk"] = jnp.zeros((batch, cfg.enc_seq, cfg.n_kv_heads, hd), dtype)
        c["xv"] = jnp.zeros((batch, cfg.enc_seq, cfg.n_kv_heads, hd), dtype)
        return c
    if kind == cb.MAMBA:
        return ssm_mod.init_mamba_state(cfg, batch, dtype)
    if kind == cb.MLSTM:
        return xlstm_mod.init_mlstm_state(cfg, batch, dtype)
    if kind == cb.SLSTM:
        return xlstm_mod.init_slstm_state(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Per-kind block apply — full sequence (train / prefill)
# ---------------------------------------------------------------------------
def apply_block_seq(kind: str, p, x, cfg, ctx) -> Tuple[jnp.ndarray,
                                                        jnp.ndarray, Any]:
    """x: (B,S,d) -> (x', aux_loss, state).

    ``state`` is the decode-time handover state (KV cache / SSM state) when
    ``ctx["collect_state"]`` is set; otherwise None (train path).
    """
    aux = jnp.zeros((), jnp.float32)
    pos = ctx["positions"]
    collect = ctx.get("collect_state", False)
    state = None
    if kind in (cb.ATTN, cb.SHARED_ATTN, cb.MOE):
        h, (k, v) = attn.attention(
            p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps), cfg, pos,
            causal=True, window=ctx.get("window", 0))
        if collect:
            state = {"k": k, "v": v}
        x = x + h
        if kind == cb.MOE:
            h, aux = moe_mod.moe_ffn(p["moe"],
                                     rms_norm(p["ln2"], x, cfg.norm_eps), cfg)
        else:
            h = mlp(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps), cfg.act,
                cfg)
        return x + h, aux, state
    if kind == cb.CROSS_ATTN:
        h, (k, v) = attn.attention(
            p["xattn"], rms_norm(p["ln1"], x, cfg.norm_eps), cfg, pos,
            causal=False, kv_x=ctx["img"], use_rope=False)
        if collect:
            state = {"k": k, "v": v}
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
        h = mlp(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps), cfg.act,
                cfg)
        return x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * h, aux, state
    if kind == cb.ENCDEC:
        h, (k, v) = attn.attention(
            p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps), cfg, pos,
            causal=True)
        x = x + h
        h, (xk, xv) = attn.attention(
            p["xattn"], rms_norm(p["lnx"], x, cfg.norm_eps), cfg, pos,
            causal=False, kv_x=ctx["enc"], use_rope=False)
        if collect:
            state = {"k": k, "v": v, "xk": xk, "xv": xv}
        x = x + h
        h = mlp(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps), cfg.act,
                cfg)
        return x + h, aux, state
    if kind == cb.MAMBA:
        h, st = ssm_mod.mamba_forward(p["mamba"],
                                      rms_norm(p["ln1"], x, cfg.norm_eps),
                                      cfg)
        return x + h, aux, (st if collect else None)
    if kind == cb.MLSTM:
        h, st = xlstm_mod.mlstm_forward(p["mlstm"],
                                        rms_norm(p["ln1"], x, cfg.norm_eps),
                                        cfg)
        return x + h, aux, (st if collect else None)
    if kind == cb.SLSTM:
        h, st = xlstm_mod.slstm_forward(p["slstm"],
                                        rms_norm(p["ln1"], x, cfg.norm_eps),
                                        cfg)
        return x + h, aux, (st if collect else None)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Per-kind block apply — single-token decode
# ---------------------------------------------------------------------------
#: decode-state leaves a block writes one row of per token: the decode
#: scan carries them whole and writes the rows in place
ROW_WRITTEN = {cb.ATTN: ("k", "v"), cb.MOE: ("k", "v"),
               cb.SHARED_ATTN: ("k", "v"), cb.ENCDEC: ("k", "v")}
#: decode-state leaves a block only reads (cross-attention K/V)
READ_ONLY = {cb.CROSS_ATTN: ("k", "v"), cb.ENCDEC: ("xk", "xv")}
#: block kinds whose whole state is rewritten every token
RECURRENT = (cb.MAMBA, cb.MLSTM, cb.SLSTM)


def apply_block_decode(kind: str, p, x, rows, fixed, recur, layer, cfg,
                       ctx):
    """x: (B,1,d) -> (x', new_rows, new_recur).

    The block's state comes split by how decoding uses it: ``rows`` the
    whole stacked (L,...) leaves of ``ROW_WRITTEN``, written at
    ``layer``; ``fixed`` this layer's ``READ_ONLY`` leaves; ``recur``
    this layer's recurrent state.
    """
    pos = ctx["positions"]          # (B,1) absolute positions
    if kind in (cb.ATTN, cb.SHARED_ATTN, cb.MOE):
        h, rows = attn.decode_attention_stacked(
            p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps), rows, layer, cfg,
            pos, window=ctx.get("window", 0))
        x = x + h
        if kind == cb.MOE:
            h, _ = moe_mod.moe_ffn(p["moe"],
                                   rms_norm(p["ln2"], x, cfg.norm_eps), cfg)
        else:
            h = mlp(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps), cfg.act,
                cfg)
        return x + h, rows, recur
    if kind == cb.CROSS_ATTN:
        h, _ = attn.decode_attention(
            p["xattn"], rms_norm(p["ln1"], x, cfg.norm_eps), fixed, cfg, pos,
            kv_x=True, use_rope=False)
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
        h = mlp(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps), cfg.act,
                cfg)
        return x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * h, rows, recur
    if kind == cb.ENCDEC:
        h, rows = attn.decode_attention_stacked(
            p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps), rows, layer, cfg,
            pos)
        x = x + h
        h, _ = attn.decode_attention(
            p["xattn"], rms_norm(p["lnx"], x, cfg.norm_eps),
            {"k": fixed["xk"], "v": fixed["xv"]}, cfg, pos, kv_x=True,
            use_rope=False)
        x = x + h
        h = mlp(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps), cfg.act,
                cfg)
        return x + h, rows, recur
    if kind == cb.MAMBA:
        h, recur = ssm_mod.mamba_decode(
            p["mamba"], rms_norm(p["ln1"], x, cfg.norm_eps), recur, cfg)
        return x + h, rows, recur
    if kind == cb.MLSTM:
        h, recur = xlstm_mod.mlstm_decode(
            p["mlstm"], rms_norm(p["ln1"], x, cfg.norm_eps), recur, cfg)
        return x + h, rows, recur
    if kind == cb.SLSTM:
        h, recur = xlstm_mod.slstm_decode(
            p["slstm"], rms_norm(p["ln1"], x, cfg.norm_eps), recur, cfg)
        return x + h, rows, recur
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------
def init_params(key, cfg) -> Dict[str, Any]:
    dtype = cfg.param_dtype()
    period = cfg.period()
    n_per = cfg.n_periods()
    kemb, khead, kblocks, kenc, kshared = jax.random.split(key, 5)

    params: Dict[str, Any] = {
        "embed": dense_init(kemb, (cfg.vocab, cfg.d_model), dtype, scale=0.02),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(khead, (cfg.d_model, cfg.vocab), dtype)

    # Stacked per-period-position block params.
    blocks = []
    pkeys = jax.random.split(kblocks, len(period))
    for pos_idx, kind in enumerate(period):
        keys = jax.random.split(pkeys[pos_idx], n_per)
        if kind == cb.SHARED_ATTN:
            blocks.append(None)  # shared weights live in params["shared"]
            continue
        stacked = jax.vmap(lambda k: init_block(k, kind, cfg))(keys)
        blocks.append(stacked)
    params["blocks"] = blocks
    if cb.SHARED_ATTN in period:
        params["shared"] = init_block(kshared, cb.SHARED_ATTN, cfg)

    if cfg.family == "audio":
        ekeys = jax.random.split(kenc, cfg.n_enc_layers)
        params["encoder"] = {
            "blocks": jax.vmap(lambda k: init_block(k, cb.ATTN, cfg))(ekeys),
            "norm": jnp.ones((cfg.d_model,), dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Encoder (audio): bidirectional attention over pre-embedded frames
# ---------------------------------------------------------------------------
def _sinusoid(seq: int, d: int):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(-jnp.log(10000.0) * jnp.arange(0, d, 2, jnp.float32) / d)
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def encode(params, frames, cfg):
    """frames: (B, enc_seq, d) stub frontend output -> encoder states."""
    x = frames + _sinusoid(frames.shape[1], cfg.d_model).astype(frames.dtype)
    enc = params["encoder"]
    positions = jnp.arange(frames.shape[1])[None, :]
    ctx = {"positions": positions}

    def body(h, p):
        h2, _ = attn.attention(p["attn"], rms_norm(p["ln1"], h, cfg.norm_eps),
                               cfg, positions, causal=False, use_rope=False)
        h = h + h2
        h = h + mlp(p["mlp"], rms_norm(p["ln2"], h, cfg.norm_eps),
                    cfg.act, cfg)
        return h, None

    if cfg.scan_layers:
        x, _ = jax.lax.scan(lambda h, p: body(h, p), x, enc["blocks"])
    else:
        for i in range(cfg.n_enc_layers):
            x, _ = body(x, jax.tree.map(lambda a: a[i], enc["blocks"]))
    return rms_norm(enc["norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------
def forward(params, tokens, cfg, ctx: Optional[Dict[str, Any]] = None):
    """tokens: (B,S) int32 -> (logits (B,S,V), aux_loss, states).

    ``states`` is a list of stacked per-period-position decode states when
    ``ctx["collect_state"]`` (prefill), else None.
    """
    ctx = dict(ctx or {})
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    ctx.setdefault("positions", jnp.arange(s)[None, :])
    if cfg.family == "audio":
        ctx["enc"] = encode(params, ctx["frames"], cfg)
    collect = ctx.get("collect_state", False)

    period = cfg.period()
    scanned = tuple(p for p in params["blocks"] if p is not None)

    def period_body(carry, stacked):
        x, aux = carry
        it = iter(stacked)
        states = []
        for kind in period:
            p = params["shared"] if kind == cb.SHARED_ATTN else next(it)
            x, a, st = apply_block_seq(kind, p, x, cfg, ctx)
            aux = aux + a
            states.append(st)
        return (x, aux), (tuple(states) if collect else None)

    body = period_body
    if cfg.remat and not collect:
        # prevent_cse=False is only safe under scan (no cross-iteration CSE);
        # unrolled bodies need the default True or CSE undoes the remat.
        body = jax.checkpoint(period_body, prevent_cse=not cfg.scan_layers)

    aux0 = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:
        (x, aux), states = jax.lax.scan(body, (x, aux0), scanned)
    else:
        x, aux = x, aux0
        per_period = []
        for i in range(cfg.n_periods()):
            sl = jax.tree.map(lambda a: a[i], scanned)
            (x, aux), st = body((x, aux), sl)
            per_period.append(st)
        states = (jax.tree.map(lambda *xs: jnp.stack(xs), *per_period)
                  if collect else None)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    if ctx.get("return_hidden"):
        return x, aux, (list(states) if collect else None)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = matmul(x, head)
    return logits, aux, (list(states) if collect else None)


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------
def init_decode_state(cfg, batch: int, max_len: int, dtype, window: int = 0):
    """Stacked per-period-position decode state (pytree of (n_per, ...))."""
    n_per = cfg.n_periods()
    states = []
    for kind in cfg.period():
        one = init_block_state(kind, cfg, batch, max_len, dtype,
                               window=window)
        states.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_per,) + a.shape), one))
    return states


def decode_step(params, tokens, states, positions, cfg,
                ctx: Optional[Dict[str, Any]] = None):
    """One-token decode. tokens: (B,1); positions: (B,1) absolute.

    states: output of ``init_decode_state`` (possibly filled by prefill).
    Returns (logits (B,1,V), new_states).

    The layer scan carries the ``ROW_WRITTEN`` leaves whole and each
    layer writes its lanes' new rows into them in place; ``READ_ONLY``
    leaves go in as ``xs`` and come back as given; only the recurrent
    states go through the scan's ``ys``.
    """
    ctx = dict(ctx or {})
    ctx["positions"] = positions
    x = jnp.take(params["embed"], tokens, axis=0)
    period = cfg.period()
    scanned_params = tuple(p for p in params["blocks"] if p is not None)
    pick = lambda st, names: {n: st[n] for n in names} or None
    rows = tuple(pick(st, ROW_WRITTEN.get(k, ()))
                 for k, st in zip(period, states))
    fixed = tuple(pick(st, READ_ONLY.get(k, ()))
                  for k, st in zip(period, states))
    recur = tuple(st if k in RECURRENT else None
                  for k, st in zip(period, states))

    def period_body(carry, xs):
        x, rows = carry
        layer, ps, fixed, recur = xs
        it = iter(ps)
        new_rows, new_recur = [], []
        for kind, r, f, s in zip(period, rows, fixed, recur):
            p = params["shared"] if kind == cb.SHARED_ATTN else next(it)
            x, r, s = apply_block_decode(kind, p, x, r, f, s, layer, cfg,
                                         ctx)
            new_rows.append(r)
            new_recur.append(s)
        return (x, tuple(new_rows)), tuple(new_recur)

    n_per = cfg.n_periods()
    if cfg.scan_layers:
        (x, rows), recur = jax.lax.scan(
            period_body, (x, rows),
            (jnp.arange(n_per), scanned_params, fixed, recur))
    else:
        outs = []
        for i in range(n_per):
            sl = jax.tree.map(lambda a: a[i], (scanned_params, fixed, recur))
            (x, rows), st2 = period_body((x, rows), (i, *sl))
            outs.append(st2)
        recur = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)

    new_states = [s if k in RECURRENT else {**(r or {}), **(f or {})}
                  for k, r, f, s in zip(period, rows, fixed, recur)]
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return matmul(x, head), new_states
