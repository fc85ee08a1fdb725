"""Mixture-of-Experts FFN: token-choice top-k router with grouped capacity
dispatch (GShard-style einsum dispatch).

Sharding: experts live on the leading axis of the expert weights and are
sharded over the ``model`` mesh axis (expert parallelism); token groups are
sharded over ``data``.  The dispatch/combine einsums lower to all-to-all-like
collectives under pjit.

The expert matmul has two execution paths:
  * reference (default / dry-run): dense einsum over the dispatched
    ``(groups, experts, capacity, d)`` tensor — XLA counts its FLOPs.
  * ``cfg.use_pallas_kernels``: sort-based ragged grouped matmul via the
    ``kernels.moe_gmm`` Pallas kernel (TPU deployment path).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init

# Tokens are routed within groups of this size, so the dispatch tensor is
# (G, GROUP, E, C) with C ~ GROUP*top_k*cf/E — keeping it VMEM-friendly.
GROUP = 512


def init_moe(key, cfg):
    d, e = cfg.d_model, cfg.n_experts
    ff = cfg.moe_d_ff or cfg.d_ff
    kr, k1, k2, k3 = jax.random.split(key, 4)
    dtype = cfg.param_dtype()
    return {
        "router": dense_init(kr, (d, e), jnp.float32),  # router kept in f32
        "w1": dense_init(k1, (e, d, ff), dtype),
        "w2": dense_init(k2, (e, ff, d), dtype),
        "w3": dense_init(k3, (e, d, ff), dtype),
    }


def expert_capacity(cfg, group: int) -> int:
    cap = int(group * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(cap, cfg.top_k)  # never below top_k slots


def _route(router_w, x, cfg) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-k routing. x: (G,S,d) -> gates (G,S,k), idx (G,S,k), aux loss."""
    logits = jnp.einsum("gsd,de->gse", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss.
    e = cfg.n_experts
    me = jnp.mean(probs, axis=(0, 1))                       # mean router prob
    pe = jnp.mean(jax.nn.one_hot(idx[..., 0], e), axis=(0, 1))  # top-1 fraction
    aux = e * jnp.sum(me * pe)
    return gates, idx, aux


def _dispatch_tensors(gates, idx, cfg, capacity):
    """Build dispatch (G,S,E,C) one-hot and combine (G,S,E,C) weighted.

    Position-in-expert is assigned in (s, k) priority order via a cumulative
    sum over the flattened (S*k) one-hot routing mask, exactly GShard's
    capacity algorithm; tokens past capacity are dropped.  The (S*k, E, C)
    one-hot product is never materialised: the k slots are accumulated one
    at a time (peak memory k-fold smaller).
    """
    g, s, k = idx.shape
    e = cfg.n_experts
    onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)       # (G,S,k,E)
    flat = onehot.reshape(g, s * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat                    # slots before me
    keep = ((pos < capacity) * flat).reshape(g, s, k, e)
    pos = pos.reshape(g, s, k, e)
    dispatch = jnp.zeros((g, s, e, capacity), jnp.bfloat16)
    combine = jnp.zeros((g, s, e, capacity), jnp.bfloat16)
    for kk in range(k):                                      # per-slot
        d_k = (jax.nn.one_hot(pos[:, :, kk].astype(jnp.int32), capacity,
                              dtype=jnp.float32)
               * keep[:, :, kk, :, None])                    # (G,S,E,C)
        dispatch = dispatch + d_k.astype(jnp.bfloat16)
        combine = combine + (gates[:, :, kk, None, None]
                             * d_k).astype(jnp.bfloat16)
    return dispatch, combine


def moe_ffn(params, x, cfg):
    """MoE feed-forward. x: (B,S,d) -> (y, aux_loss)."""
    b, s, d = x.shape
    tokens = b * s
    group = min(GROUP, tokens)
    g = tokens // group
    xg = x.reshape(g, group, d)
    cap = expert_capacity(cfg, group)

    gates, idx, aux = _route(params["router"], xg, cfg)
    dispatch, combine = _dispatch_tensors(gates, idx, cfg, cap)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(jnp.float32)
    # pin the E dim of dispatch/combine to the expert-parallel axis —
    # propagation otherwise replicates them and all-gathers per layer
    # (§Perf #10; ~310 GB/device/step observed on granite before the pin).
    # Only a mesh set with ``jax.set_mesh`` that has a "model" axis has
    # one to pin to; gang and serve meshes are data-parallel only.
    if (cfg.n_experts % 16 == 0
            and "model" in jax.sharding.get_abstract_mesh().axis_names):
        spec = P(None, None, "model", None)
        dispatch = jax.lax.with_sharding_constraint(dispatch, spec)
        combine = jax.lax.with_sharding_constraint(combine, spec)

    # Gather expert inputs: (G,E,C,d)
    xe = jnp.einsum("gsec,gsd->gecd", dispatch, xg,
                    preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.use_pallas_kernels:
        from repro.kernels.moe_gmm import ops as gmm_ops
        ye = gmm_ops.expert_ffn(xe, params["w1"], params["w2"], params["w3"],
                                act=cfg.act,
                                interpret=cfg.interpret_kernels)
    else:
        h = jnp.einsum("gecd,edf->gecf", xe, params["w1"],
                       preferred_element_type=jnp.float32)
        if cfg.act == "silu":
            up = jnp.einsum("gecd,edf->gecf", xe, params["w3"],
                            preferred_element_type=jnp.float32)
            h = jax.nn.silu(h) * up
        else:
            h = jax.nn.gelu(h)
        ye = jnp.einsum("gecf,efd->gecd", h.astype(x.dtype), params["w2"],
                        preferred_element_type=jnp.float32).astype(x.dtype)
    # Scatter back with gate weights: (G,S,d)
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return y.reshape(b, s, d), cfg.router_aux_weight * aux
