"""jit'd wrapper: fused expert FFN over capacity-dispatched MoE inputs."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm import kernel as _k


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def expert_ffn(xe, w1, w2, w3, *, act: str = "silu",
               interpret: bool = False):
    """xe: (G, E, C, d) dispatched tokens -> (G, E, C, d).

    Reshapes to the kernel's (E, G*C, d) layout (experts outermost so one
    expert's weights load once per tile row)."""
    g, e, c, d = xe.shape
    x = jnp.swapaxes(xe, 0, 1).reshape(e, g * c, d)
    m = g * c
    # row blocks are the whole M or a multiple of 8 (the TPU's sublane
    # tiling): pad M to a multiple of 8; zero rows come out zero and go
    if m <= 8:
        bm, pad = m, 0
    else:
        pad = (-m) % 8
        bm = 128
        while (m + pad) % bm:
            bm //= 2
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    y = _k.expert_ffn(x, w1, w2, w3, act=act, block_m=bm,
                      interpret=interpret)
    return jnp.swapaxes(y[:, :m].reshape(e, g, c, d), 0, 1)
