"""jit'd wrapper matching the model's SSD call signature."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.mamba_scan import kernel as _k


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, a, b, c, *, chunk: int = 64, interpret: bool = False):
    """Model layout: x (B,L,H,P), dt (B,L,H), a (H,), b/c (B,L,N).

    Returns y (B,L,H,P), final state (B,H,P,N) — same as
    ``models.ssm.ssd_chunked``."""
    xk = jnp.moveaxis(x, 2, 1)                       # (B,H,L,P)
    dtk = jnp.moveaxis(dt, 2, 1)[..., None]          # (B,H,L,1)
    ak = a[:, None, None]                            # (H,1,1)
    y, s_fin = _k.ssd_scan(xk, dtk, ak.astype(jnp.float32), b, c,
                           chunk=chunk, interpret=interpret)
    return jnp.moveaxis(y, 1, 2), s_fin
