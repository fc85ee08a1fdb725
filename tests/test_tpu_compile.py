"""The main paths' Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for a described (not
attached) ``v5e:2x2`` chip, which refuses what interpret mode accepts —
a lowering Mosaic lacks, a block that breaks the (8, 128) tiling, more
VMEM than a kernel may use.  The topology is described inside a fixture
(only the worker that runs this file loads the TPU compiler), and the
persistent compilation cache is off around the compiles.
"""
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
