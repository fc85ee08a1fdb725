"""jit'd wrapper matching the model's mLSTM call signature."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mlstm import kernel as _k


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm(q, k, v, logi, logf, *, chunk: int = 128,
          interpret: bool = False):
    """Model layout: q/k/v (B,L,H,hd); logi/logf (B,L,H).

    Returns h (B,L,H,hd) and state tuple (c (B,H,hd,hd), n (B,H,hd),
    m (B,H)) — same as ``models.xlstm.mlstm_chunked``."""
    move = lambda x: jnp.moveaxis(x, 2, 1)
    h, c, n, m = _k.mlstm_scan(
        move(q), move(k), move(v),
        jnp.moveaxis(logi, 2, 1)[..., None],
        jnp.moveaxis(logf, 2, 1)[..., None],
        chunk=chunk, interpret=interpret)
    return jnp.moveaxis(h, 1, 2), (c, n[:, :, 0, :], m[:, :, 0, 0])
