"""Plain float32 reference of the granite-moe-1b-a400m block stack as
configured in ``granite-moe-1b-a400m.json``: token embedding, 24 blocks of
[RMS norm -> grouped-query causal attention with rotary positions ->
residual; RMS norm -> softmax router, top-8 of 32 experts, renormalised
gates, SwiGLU experts -> residual], a final RMS norm and the head, with
granite's embedding, attention, residual and logits multipliers, and the
head tied to the embedding where the file says so.

Straight ``jax.numpy``: one sequence at a time, every expert computed on
every token and weighted by its gate (zero where it was not chosen), no
cache, no kernels, no capacity.  Imports nothing of the program under
test.  ``init_weights`` is the benchmark's own weight generator; it lays
the tree out the way the program's parameter loader expects it (a list
with one layer-stacked dict), and ``granite-moe-1b-a400m.weights.py``
folds the multipliers into a copy of it for the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def sizes(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return dict(L=c["num_hidden_layers"], d=d, H=h,
                KV=c["num_key_value_heads"], hd=d // h,
                f=c["intermediate_size"], E=c["num_local_experts"],
                k=c["num_experts_per_tok"], V=c["vocab_size"])


def init_weights(c, key, dtype):
    """Random weights from ``key``: N(0, std) truncated at 3 sigma, norms
    at one; the embedding over ``embedding_multiplier`` and the output
    projections over sqrt(2 L) and ``residual_multiplier``, so that the
    residual stream starts and grows at a plain block's scales; the
    router in float32, everything else in ``dtype``."""
    s = sizes(c)
    L, d, H, KV, hd, f, E, V = (s[n] for n in
                                ("L", "d", "H", "KV", "hd", "f", "E", "V"))
    std = c["init"]["std"]
    out = std / math.sqrt(2 * L) / c["residual_multiplier"]
    ks = jax.random.split(key, 10)

    def tn(k, shape, scale, dt=dtype):
        return (jax.random.truncated_normal(k, -3, 3, shape, F32)
                * scale).astype(dt)
    blocks = {
        "ln1": jnp.ones((L, d), dtype),
        "attn": {"wq": tn(ks[0], (L, d, H * hd), std),
                 "wk": tn(ks[1], (L, d, KV * hd), std),
                 "wv": tn(ks[2], (L, d, KV * hd), std),
                 "wo": tn(ks[3], (L, H * hd, d), out)},
        "ln2": jnp.ones((L, d), dtype),
        "moe": {"router": tn(ks[4], (L, d, E), std, F32),
                "w1": tn(ks[5], (L, E, d, f), std),
                "w2": tn(ks[6], (L, E, f, d), out),
                "w3": tn(ks[7], (L, E, d, f), std)},
    }
    w = {"embed": tn(ks[8], (V, d), std / c["embedding_multiplier"]),
         "final_norm": jnp.ones((d,), dtype),
         "blocks": [blocks]}
    if not c["tie_word_embeddings"]:
        w["lm_head"] = tn(ks[9], (d, V), std)
    return w


def matmul_f32(a, b):
    """The reference's matrix product: float32 at full precision."""
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HIGHEST)


def rms_norm(w, x, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, pos, theta):
    """Rotary positions on (S, heads, hd), rotating the two halves."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None, None].astype(F32) * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def block(c, p, x, mm):
    s = sizes(c)
    S = x.shape[0]
    H, KV, hd = s["H"], s["KV"], s["hd"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    pos = jnp.arange(S)
    h = rms_norm(p["ln1"], x, eps)
    q = rope(mm(h, p["attn"]["wq"]).reshape(S, H, hd), pos, theta)
    k = rope(mm(h, p["attn"]["wk"]).reshape(S, KV, hd), pos, theta)
    v = mm(h, p["attn"]["wv"]).reshape(S, KV, hd)
    q = q.reshape(S, KV, H // KV, hd)
    scores = jnp.einsum("qkgd,skd->kgqs", q, k, precision=HIGHEST) \
        * c["attention_multiplier"]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    att = jnp.einsum("kgqs,skd->qkgd", probs, v, precision=HIGHEST)
    x = x + mm(att.reshape(S, H * hd), p["attn"]["wo"]) \
        * c["residual_multiplier"]

    h = rms_norm(p["ln2"], x, eps)
    router = jax.nn.softmax(
        jnp.matmul(h, p["moe"]["router"].astype(F32), precision=HIGHEST), -1)
    top, idx = jax.lax.top_k(router, s["k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(router).at[jnp.arange(S)[:, None], idx].set(top)

    def expert(y, we):
        w1, w2, w3, gate = we
        out = mm(jax.nn.silu(mm(h, w1)) * mm(h, w3), w2)
        return y + gate[:, None] * out, None
    moe = p["moe"]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (moe["w1"], moe["w2"], moe["w3"], gates.T))
    return x + y * c["residual_multiplier"]


def logits(c, w, tokens, mm=matmul_f32):
    """tokens (S,) -> float32 logits (S, V) of every position."""
    x = w["embed"].astype(F32)[tokens] * c["embedding_multiplier"]

    def body(x, p):
        return block(c, p, x, mm), None
    x, _ = jax.lax.scan(jax.checkpoint(body), x, w["blocks"][0])
    x = rms_norm(w["final_norm"], x, c["rms_norm_eps"])
    head = w["embed"].T if c["tie_word_embeddings"] else w["lm_head"]
    return mm(x, head) / c["logits_scaling"]
