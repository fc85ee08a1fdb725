"""The main paths' Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` chip, which refuses what interpret mode accepts —
a lowering Mosaic lacks, a block that breaks the (8, 128) tiling, more
VMEM than a kernel may use.  The topology is described inside a fixture
(only the worker that runs this file loads the TPU compiler), and the
persistent compilation cache is off around the compiles.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Lower and compile ``fn`` for the described chip; returns the
    names of the Pallas kernels the program calls."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    lowered = jax.jit(fn).lower(*args)
    lowered.compile()               # raises what the chip's compiler would
    return set(re.findall(r'tpu_custom_call.*?kernel_name = "([^"]+)"',
                          lowered.as_text()))


@pytest.mark.parametrize("seq", [8, 2048])
def test_flash_attention_at_granite_width(one_chip, seq):
    from repro.kernels.flash_attention import ops
    cfg = get_config("granite-moe-1b-a400m")
    bf = jnp.bfloat16
    q = ((1, seq, cfg.n_heads, cfg.hd()), bf)
    kv = ((1, seq, cfg.n_kv_heads, cfg.hd()), bf)
    assert _compile(lambda q, k, v: ops.flash_attention(q, k, v),
                    one_chip, q, kv, kv) == {"flash_attention"}


# M = 8 decode, 10 and 20 prefill buckets 32 and 64 (capacity rows)
@pytest.mark.parametrize("m", [8, 10, 20, 320])
def test_expert_ffn_at_granite_width(one_chip, m):
    from repro.kernels.moe_gmm import ops
    cfg = get_config("granite-moe-1b-a400m")
    e, d, ff, bf = cfg.n_experts, cfg.d_model, cfg.moe_d_ff, jnp.bfloat16
    assert _compile(lambda x, w1, w2, w3: ops.expert_ffn(x, w1, w2, w3),
                    one_chip, ((1, e, m, d), bf), ((e, d, ff), bf),
                    ((e, ff, d), bf), ((e, d, ff), bf)) == {"moe_expert_ffn"}


def test_mlstm_at_xlstm_width(one_chip):
    from repro.kernels.mlstm import ops
    from repro.models.xlstm import mlstm_dims
    cfg = get_config("xlstm-1.3b")
    _, hd = mlstm_dims(cfg)
    seq, h, bf, f32 = 256, cfg.n_heads, jnp.bfloat16, jnp.float32
    qkv = ((1, seq, h, hd), bf)
    gate = ((1, seq, h), f32)
    assert _compile(lambda q, k, v, i, f: ops.mlstm(q, k, v, i, f),
                    one_chip, qkv, qkv, qkv, gate, gate) == {"mlstm_scan"}


def test_mamba_scan_at_zamba2_width(one_chip):
    from repro.kernels.mamba_scan import ops
    from repro.models.ssm import dims
    cfg = get_config("zamba2-2.7b")
    _, h = dims(cfg)
    seq, p, n = 256, cfg.ssm_headdim, cfg.ssm_state
    bf, f32 = jnp.bfloat16, jnp.float32
    assert _compile(
        lambda x, dt, a, b, c: ops.ssd(x, dt, a, b, c, chunk=cfg.ssm_chunk),
        one_chip, ((1, seq, h, p), bf), ((1, seq, h), f32), ((h,), f32),
        ((1, seq, n), bf), ((1, seq, n), bf)) == {"ssd_scan"}


@pytest.mark.parametrize("n", [4 << 20, 3000])
def test_diff_merge_on_large_and_ragged_leaves(one_chip, n):
    from repro.kernels.diff_merge import ops
    leaf = ((n,), jnp.float32)
    assert _compile(lambda a, b, c: ops.diff_merge_leaf(a, b, c),
                    one_chip, leaf, leaf, leaf) == {"diff_merge"}


@pytest.mark.parametrize("frac", [0.05, 0.01])
def test_select_codec_with_ragged_chunk_rows(one_chip, frac):
    from repro.kernels.collective_codec import ops
    n = ops.KERNEL_MIN_SIZE           # the smallest shard TPU routing sends
    k, _, _ = ops.codec_geometry(n, frac)
    assert k % 8, "the case must have k not a multiple of 8"
    assert _compile(
        lambda v: ops.select_codec(v, frac=frac, use_kernel=True),
        one_chip, ((n,), jnp.float32)) == {"chunk_select"}


# ---------------------------------------------------------------------------
# Whole-cache traffic of the decode step, read from the optimized HLO
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}
_MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")


def _computations(hlo):
    """name -> instruction lines of each computation, and the entry's name."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) \(", line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps, entry


def _instrs(lines):
    """name -> (dtype, dims, opcode, operands and attributes, is_root);
    a tuple-shaped instruction reads as dtype "tuple"."""
    out = {}
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)", line)
        if not m:
            continue
        name, rhs = m.groups()
        if rhs.startswith("("):
            depth = 0
            for end, c in enumerate(rhs):
                depth += {"(": 1, ")": -1}.get(c, 0)
                if depth == 0:
                    break
            dt, dims, rhs = "tuple", "", rhs[end + 2:]
        else:
            shape, _, rhs = rhs.partition(" ")
            dt, _, dims = shape.partition("[")
            dims = dims.split("]")[0]
        op, _, rest = rhs.partition("(")
        out[name] = (dt, tuple(int(d) for d in dims.split(",") if d), op,
                     rest, line.lstrip().startswith("ROOT"))
    return out


def cache_bytes_moved(hlo, cache_shapes):
    """Bytes written per run of the program by copies, dynamic slices and
    dynamic updates (the update's bytes) of an array shaped as one of
    ``cache_shapes`` (leading 1s dropped).  Only ops the device runs as
    their own kernel count, or fusions rooted at one; an op in a loop
    counts once per trip (the trip count read from the loop's condition)."""
    comps, entry = _computations(hlo)
    tables = {name: _instrs(lines) for name, lines in comps.items()}
    want = {tuple(s) for s in cache_shapes}

    def squeeze(shape):
        while shape and shape[0] == 1:
            shape = shape[1:]
        return shape

    def walk(comp, trips):
        total = 0
        table = tables[comp]
        for dt, shape, op, rest, _ in table.values():
            if op == "while":
                body = re.search(r"body=%([\w.-]+)", rest).group(1)
                cond = re.search(r"condition=%([\w.-]+)", rest).group(1)
                n = max(int(c) for c in re.findall(
                    r"s32\[\]\S* constant\((\d+)\)", "\n".join(comps[cond])))
                total += walk(body, trips * n)
                continue
            inner = table
            if op == "fusion":
                inner = tables[re.search(r"calls=%([\w.-]+)", rest).group(1)]
                dt, shape, op, rest, _ = next(v for v in inner.values()
                                              if v[4])
            if op not in _MOVES:
                continue
            if op == "dynamic-update-slice":
                dt, shape = inner[re.findall(r"%([\w.-]+)", rest)[1]][:2]
            if squeeze(shape) in want:
                total += trips * _DTYPE_BYTES[dt] * math.prod(shape)
        return total

    return walk(entry, 1)


def test_serve_step_moves_at_most_two_whole_caches(one_chip):
    """The decode step at granite width, 28 slots of 4096: copies, slices
    and updates of cache-shaped arrays write at most two whole caches a
    step.  (Slicing each layer out of the scan's ``xs``, a scatter copy,
    layout copies and re-stacking through ``ys`` wrote four.)"""
    from repro.models import model as M
    from repro.models import transformer as tf
    cfg = get_config("granite-moe-1b-a400m").with_(
        capacity_factor=4.0, use_pallas_kernels=True)
    slots, max_len = 28, 4096

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(lambda k: tf.init_params(k, cfg),
                                   jax.random.PRNGKey(0)))
    states = shaped(jax.eval_shape(lambda: tf.init_decode_state(
        cfg, slots, max_len, cfg.param_dtype())))
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)
    hlo = jax.jit(M.make_serve_step(cfg)).lower(
        params, states, tok, tok).compile().as_text()
    layer = (slots, max_len, cfg.n_kv_heads, cfg.hd())
    whole = sum(math.prod(a.shape) * a.dtype.itemsize
                for a in jax.tree.leaves(states))
    moved = cache_bytes_moved(hlo, [layer, (cfg.n_periods(),) + layer])
    assert moved <= 2 * whole, f"{moved / whole:.2f} whole caches a step"
