"""Serving-path correctness: token-by-token decode must reproduce the
full-sequence forward logits for every architecture family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base as cb
from repro.configs.registry import ARCH_IDS, reduced_config
from repro.models import attention as attn
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tf
from repro.models import xlstm as xlstm_mod
from repro.models.layers import matmul, mlp, rms_norm

B, S = 2, 16

DECODE_ARCHS = [a for a in ARCH_IDS if reduced_config(a).family
                not in ("audio", "vlm")]
PREFILL_ARCHS = [a for a in ARCH_IDS if reduced_config(a).family
                 in ("audio", "vlm")]


def _setup(arch, no_drop=False):
    cfg = reduced_config(arch)
    if no_drop and cfg.n_experts:
        cfg = cfg.with_(capacity_factor=8.0)
    params = jax.jit(lambda k: tf.init_params(k, cfg))(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.enc_seq, cfg.d_model),
            cfg.param_dtype())
    if cfg.family == "vlm":
        batch["img"] = jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.n_img_tokens, cfg.d_model),
            cfg.param_dtype())
    ctx = {k: batch[k] for k in ("frames", "img") if k in batch}
    logits_full, _, _ = jax.jit(
        lambda p, t: tf.forward(p, t, cfg, ctx))(params, tokens)
    return cfg, params, batch, tokens, logits_full


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_forward(arch):
    cfg, params, batch, tokens, logits_full = _setup(arch, no_drop=True)
    serve = jax.jit(M.make_serve_step(cfg))
    states = tf.init_decode_state(cfg, B, S, cfg.param_dtype())
    for t in range(S):
        lg, states = serve(params, states, tokens[:, t:t + 1],
                           jnp.full((B, 1), t, jnp.int32))
        np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                                   np.asarray(logits_full[:, t], np.float32),
                                   atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("arch", PREFILL_ARCHS)
def test_prefill_then_decode_matches_forward(arch):
    cfg, params, batch, tokens, logits_full = _setup(arch)
    prefill = jax.jit(M.make_prefill_step(cfg))
    serve = jax.jit(M.make_serve_step(cfg))
    _, st = prefill(params, {**batch, "tokens": tokens[:, :S - 1]})

    def pad(x):
        if x.ndim == 5 and x.shape[2] == S - 1:
            spec = [(0, 0)] * x.ndim
            spec[2] = (0, 1)
            return jnp.pad(x, spec)
        return x
    states = [jax.tree.map(pad, s) for s in st]
    lg, _ = serve(params, states, tokens[:, S - 1:S],
                  jnp.full((B, 1), S - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                               np.asarray(logits_full[:, S - 1], np.float32),
                               atol=5e-4, rtol=1e-3)


def test_prefill_state_matches_decode_state_ssm():
    """Prefill handover: running prefill then decoding must equal decoding
    from scratch (exact recurrent-state extraction for mamba/mlstm)."""
    arch = "zamba2-2.7b"
    cfg, params, batch, tokens, logits_full = _setup(arch)
    prefill = jax.jit(M.make_prefill_step(cfg))
    serve = jax.jit(M.make_serve_step(cfg))
    _, st = prefill(params, {"tokens": tokens[:, :S - 1]})

    def pad(x):
        if x.ndim == 5 and x.shape[2] == S - 1:
            spec = [(0, 0)] * x.ndim
            spec[2] = (0, 1)
            return jnp.pad(x, spec)
        return x
    states = [jax.tree.map(pad, s) for s in st]
    lg, _ = serve(params, states, tokens[:, S - 1:S],
                  jnp.full((B, 1), S - 1, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                               np.asarray(logits_full[:, S - 1], np.float32),
                               atol=5e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# The carried-cache decode scan against the whole-state xs/ys formulation
# ---------------------------------------------------------------------------
def _ref_block_decode(kind, p, x, state, cfg, ctx):
    """One block's decode on its own layer of state (the reference)."""
    pos = ctx["positions"]
    norm = lambda w, h: rms_norm(w, h, cfg.norm_eps)
    if kind in (cb.ATTN, cb.SHARED_ATTN, cb.MOE):
        h, state = attn.decode_attention(p["attn"], norm(p["ln1"], x), state,
                                         cfg, pos, window=ctx.get("window", 0))
        x = x + h
        if kind == cb.MOE:
            h, _ = moe_mod.moe_ffn(p["moe"], norm(p["ln2"], x), cfg)
        else:
            h = mlp(p["mlp"], norm(p["ln2"], x), cfg.act, cfg)
        return x + h, state
    if kind == cb.CROSS_ATTN:
        h, _ = attn.decode_attention(p["xattn"], norm(p["ln1"], x), state,
                                     cfg, pos, kv_x=True, use_rope=False)
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
        h = mlp(p["mlp"], norm(p["ln2"], x), cfg.act, cfg)
        return x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * h, state
    if kind == cb.ENCDEC:
        h, own = attn.decode_attention(
            p["attn"], norm(p["ln1"], x), {"k": state["k"], "v": state["v"]},
            cfg, pos)
        x = x + h
        h, _ = attn.decode_attention(
            p["xattn"], norm(p["lnx"], x),
            {"k": state["xk"], "v": state["xv"]}, cfg, pos, kv_x=True,
            use_rope=False)
        x = x + h
        h = mlp(p["mlp"], norm(p["ln2"], x), cfg.act, cfg)
        return x + h, {**own, "xk": state["xk"], "xv": state["xv"]}
    step = {cb.MAMBA: (ssm_mod.mamba_decode, "mamba"),
            cb.MLSTM: (xlstm_mod.mlstm_decode, "mlstm"),
            cb.SLSTM: (xlstm_mod.slstm_decode, "slstm")}
    fn, key = step[kind]
    h, state = fn(p[key], norm(p["ln1"], x), state, cfg)
    return x + h, state


def _ref_decode_step(params, tokens, states, positions, cfg, ctx):
    """The decode step as a scan that takes every layer's state in as
    ``xs`` and stacks every new state back through ``ys``."""
    ctx = {**ctx, "positions": positions}
    x = jnp.take(params["embed"], tokens, axis=0)
    period = cfg.period()
    scanned = tuple(p for p in params["blocks"] if p is not None)

    def body(x, xs):
        ps, sts = xs
        it = iter(ps)
        new = []
        for kind, st in zip(period, sts):
            p = params["shared"] if kind == cb.SHARED_ATTN else next(it)
            x, st = _ref_block_decode(kind, p, x, st, cfg, ctx)
            new.append(st)
        return x, tuple(new)

    if cfg.scan_layers:
        x, new = jax.lax.scan(body, x, (scanned, tuple(states)))
    else:
        outs = []
        for i in range(cfg.n_periods()):
            x, st = body(x, jax.tree.map(lambda a: a[i],
                                         (scanned, tuple(states))))
            outs.append(st)
        new = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return matmul(x, head), list(new)


def _filled_states(cfg, lanes, max_len, window, key):
    """A decode state with every leaf drawn at random, as a filled cache."""
    states = tf.init_decode_state(cfg, lanes, max_len, cfg.param_dtype(),
                                  window=window)
    leaves, tree = jax.tree.flatten(states)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])


def _bits(a):
    a = np.asarray(a)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


# every architecture, and zamba2's shared attention on a ring buffer
BITEXACT_CASES = [(a, 0) for a in ARCH_IDS] + [("zamba2-2.7b", 8)]


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("arch,window", BITEXACT_CASES)
def test_carried_cache_decode_is_bit_exact(arch, window, scan):
    """Writing each layer's rows into the carried cache returns the same
    logits and states, bit for bit, as the xs/ys formulation, from a
    filled cache with ragged lanes and idle lanes at position 0."""
    cfg = reduced_config(arch).with_(scan_layers=scan)
    lanes, max_len = 5, 16
    params = jax.jit(lambda k: tf.init_params(k, cfg))(jax.random.PRNGKey(0))
    states = _filled_states(cfg, lanes, max_len, window,
                            jax.random.PRNGKey(1))
    ref_states = states
    ctx = {"window": window}
    new = jax.jit(lambda p, t, s, q: tf.decode_step(p, t, s, q, cfg, ctx))
    ref = jax.jit(lambda p, t, s, q: _ref_decode_step(p, t, s, q, cfg, ctx))
    pos = np.asarray([0, 9, 13, 3, 0])      # past the ring's 8 rows too
    live = np.asarray([0, 1, 1, 1, 0])      # idle slots stay at 0
    for step in range(3):
        tokens = jax.random.randint(jax.random.PRNGKey(10 + step),
                                    (lanes, 1), 0, cfg.vocab)
        positions = jnp.asarray(pos[:, None], jnp.int32)
        lg, states = new(params, tokens, states, positions)
        rlg, ref_states = ref(params, tokens, ref_states, positions)
        np.testing.assert_array_equal(_bits(lg), _bits(rlg))
        got, want = jax.tree.leaves(states), jax.tree.leaves(ref_states)
        assert (jax.tree.structure(states)
                == jax.tree.structure(ref_states))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(_bits(g), _bits(w))
        pos = pos + live


def _scan_eqn(jaxpr):
    return next(e for e in jaxpr.eqns if e.primitive.name == "scan")


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m",
                                  "llama-3.2-vision-11b", "whisper-small"])
def test_decode_scan_carries_caches_and_stacks_no_cache(arch):
    """The layer scan of ``decode_step`` carries the self-attention caches
    whole, and its ``ys`` hold no per-layer leaf of a self-attention or
    cross-attention cache."""
    cfg = reduced_config(arch).with_(scan_layers=True)
    lanes, max_len = 3, 16
    params = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    states = jax.eval_shape(lambda: tf.init_decode_state(
        cfg, lanes, max_len, cfg.param_dtype()))
    tok = jax.ShapeDtypeStruct((lanes, 1), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, s, t, q: tf.decode_step(
        p, t, s, q, cfg))(params, states, tok, tok).jaxpr
    scan = _scan_eqn(jaxpr)
    n_carry = scan.params["num_carry"]
    carried = [v.aval.shape for v in scan.outvars[:n_carry]]
    ys = [v.aval.shape[1:] for v in scan.outvars[n_carry:]]
    hd = cfg.hd()
    self_layer = (lanes, max_len, cfg.n_kv_heads, hd)
    cross_layers = {(lanes, cfg.n_img_tokens, cfg.n_kv_heads, hd),
                    (lanes, cfg.enc_seq, cfg.n_kv_heads, hd)}
    assert (cfg.n_periods(),) + self_layer in carried
    assert self_layer not in ys
    assert not cross_layers & set(ys)
    if cfg.family == "moe":
        assert ys == []
