"""The controls' lower precisions: the step below the one that a
configuration states, as matrix products accumulated in float32.  Below
bfloat16, float8 (e4m3, each operand scaled so that its largest
magnitude sits at the format's maximum)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3 = jnp.float8_e4m3fn
E4M3_MAX = float(jnp.finfo(E4M3).max)


def to_fp8(x):
    x = x.astype(jnp.float32)
    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX)
    return (x / scale).astype(E4M3).astype(jnp.float32) * scale


def matmul_fp8(a, b):
    return jnp.matmul(to_fp8(a), to_fp8(b),
                      precision=jax.lax.Precision.HIGHEST)


#: the control's matrix product, by the precision it is in
CONTROLS = {"float8_e4m3fn": matmul_fp8}
