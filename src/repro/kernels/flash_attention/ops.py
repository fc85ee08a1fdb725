"""jit'd public wrapper for the flash_attention kernel.

Accepts model-layout tensors (B, S, H, hd) / (B, S, KV, hd), transposes to
the kernel's (B, H, S, hd) blocking layout, pads the head dim to a
lane-aligned multiple of 128 when necessary (e.g. zamba2's hd=80).
Interpret mode (off-TPU testing) runs only when the caller asks for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as _k


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    interpret: bool = False):
    """q: (B,S,H,hd); k,v: (B,S,KV,hd) -> (B,S,H,hd)."""
    b, s, h, hd = q.shape
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    pad = (-hd) % 128 if hd > 64 else (-hd) % 64
    if pad:
        zp = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, pad)))
        qt, kt, vt = zp(qt), zp(kt), zp(vt)
    block_q = min(_k.DEFAULT_BLOCK_Q, s)
    block_k = min(_k.DEFAULT_BLOCK_K, s)
    out = _k.flash_attention(qt, kt, vt, causal=causal, window=window,
                             block_q=block_q, block_k=block_k,
                             scale=hd ** -0.5,  # unpadded head dim
                             interpret=interpret)
    if pad:
        out = out[..., :hd]
    return jnp.swapaxes(out, 1, 2)
