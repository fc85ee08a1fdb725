"""Per-kernel validation: shape/dtype sweeps in interpret mode against the
pure-jnp ref.py oracles (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

key = jax.random.PRNGKey(0)
sub = lambda i: jax.random.fold_in(key, i)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,kv,s,hd", [
    (2, 4, 4, 256, 64), (1, 8, 2, 256, 64), (2, 4, 2, 512, 128),
    (1, 2, 1, 128, 64),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 128),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, h, kv, s, hd, causal, window, dtype):
    from repro.kernels.flash_attention import kernel as K, ref as R
    q = jax.random.normal(sub(1), (b, h, s, hd), dtype)
    k = jax.random.normal(sub(2), (b, kv, s, hd), dtype)
    v = jax.random.normal(sub(3), (b, kv, s, hd), dtype)
    out = K.flash_attention(q, k, v, causal=causal, window=window,
                            interpret=True)
    expect = R.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol,
                               rtol=tol)


def test_flash_attention_ops_layout_and_padding():
    from repro.kernels.flash_attention import ops as O
    from repro.models.attention import sdpa
    b, s, h, kv, hd = 2, 256, 4, 2, 80   # hd=80: exercises lane padding
    q = jax.random.normal(sub(4), (b, s, h, hd))
    k = jax.random.normal(sub(5), (b, s, kv, hd))
    v = jax.random.normal(sub(6), (b, s, kv, hd))
    out = O.flash_attention(q, k, v, causal=True, interpret=True)
    expect = sdpa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=2e-5,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# diff_merge
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op", ["sum", "subtract", "multiply", "divide",
                                "overwrite"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_diff_merge(op, dtype):
    from repro.kernels.diff_merge import kernel as K, ref as R
    a0 = (jax.random.normal(sub(7), (32, 1024)) + 2.0).astype(dtype)
    b0 = a0 + jnp.zeros_like(a0)
    b1 = b0.at[3:7].add(1.5).at[20].multiply(1.25)
    out, dirty = K.diff_merge(a0, b0, b1, op=op, interpret=True)
    eout, edirty = R.diff_merge_ref(a0, b0, b1, op=op)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(eout, np.float32), atol=1e-5,
                               rtol=1e-5)
    assert bool((dirty == edirty).all())
    assert int(dirty.sum()) == 5


def test_diff_merge_leaf_wrapper_odd_shapes():
    from repro.kernels.diff_merge import ops as O
    x0 = jax.random.normal(sub(8), (13, 77))
    b0 = x0 + 0.0
    b1 = b0.at[5].add(1.0)
    m, d = O.diff_merge_leaf(x0, b0, b1, op="sum", interpret=True)
    np.testing.assert_allclose(np.asarray(m), np.asarray(x0 + (b1 - b0)),
                               atol=1e-6)
    assert m.shape == x0.shape


@pytest.mark.parametrize("op", ["sum", "subtract", "overwrite"])
def test_diff_merge_int32_exact(op):
    """Integer leaves merge exactly in the kernel — no float cast."""
    from repro.kernels.diff_merge import kernel as K, ref as R
    rng = np.random.default_rng(0)
    a0 = jnp.asarray(rng.integers(-2**30, 2**30, (16, 1024)),
                     dtype=jnp.int32)
    b0 = a0 + jnp.zeros_like(a0)
    b1 = b0.at[3:5].add(7)
    out, dirty = K.diff_merge(a0, b0, b1, op=op, interpret=True)
    eout, edirty = R.diff_merge_ref(a0, b0, b1, op=op)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(eout))
    if op == "overwrite":
        expect = np.asarray(a0).copy()
        expect[3:5] = np.asarray(b1)[3:5]
    else:
        expect = np.asarray(a0).copy()
        expect[3:5] += 7
    np.testing.assert_array_equal(np.asarray(out), expect)
    assert int(dirty.sum()) == 2


def test_diff_merge_leaf_f64_keeps_precision():
    """f64 leaves keep full precision through the kernel path (the old
    blanket float32 cast flattened sub-f32 deltas)."""
    from jax import enable_x64
    from repro.kernels.diff_merge import ops as O
    with enable_x64():
        a0 = jnp.full((3000,), 1.0, dtype=jnp.float64)
        b0 = a0 + 0.0
        b1 = b0.at[:1024].add(1e-12)
        m, d = O.diff_merge_leaf(a0, b0, b1, op="sum", interpret=True)
        assert m.dtype == jnp.float64
        np.testing.assert_array_equal(np.asarray(m), np.asarray(b1))
        assert int(d.sum()) == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("op", ["sum", "overwrite", "multiply"])
def test_diff_merge_leaf_roundtrip_ragged(op, dtype):
    """Kernel-path diff -> merge on a ragged leaf reproduces the child
    under op semantics, across dtypes (satellite 3)."""
    from repro.kernels.diff_merge import ops as O
    if jnp.issubdtype(dtype, jnp.integer):
        a0 = jnp.arange(3333, dtype=dtype) % 100 + 1
    else:
        a0 = (jax.random.uniform(sub(9), (3333,)) + 1.0).astype(dtype)
    b0 = a0 + jnp.zeros_like(a0)
    if op == "multiply":
        b1 = b0.at[100:400].multiply(2)
    else:
        b1 = b0.at[100:400].add(3)
    m, _ = O.diff_merge_leaf(a0, b0, b1, op=op, interpret=True)
    assert m.dtype == a0.dtype and m.shape == a0.shape
    if op == "overwrite" or op == "sum":
        np.testing.assert_allclose(np.asarray(m, np.float64),
                                   np.asarray(b1, np.float64),
                                   rtol=1e-2 if dtype == jnp.bfloat16
                                   else 0)
    else:
        np.testing.assert_allclose(np.asarray(m, np.float64),
                                   np.asarray(b1, np.float64),
                                   rtol=1e-2 if dtype == jnp.bfloat16
                                   else 1e-6)


# ---------------------------------------------------------------------------
# moe_gmm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("e,m,d,ff,act", [
    (4, 256, 64, 256, "silu"), (2, 128, 128, 512, "gelu"),
    (8, 64, 32, 128, "silu"),
])
def test_moe_gmm(e, m, d, ff, act):
    from repro.kernels.moe_gmm import kernel as K, ref as R
    x = jax.random.normal(sub(9), (e, m, d)) * 0.5
    w1 = jax.random.normal(sub(10), (e, d, ff)) * 0.05
    w2 = jax.random.normal(sub(11), (e, ff, d)) * 0.05
    w3 = jax.random.normal(sub(12), (e, d, ff)) * 0.05
    out = K.expert_ffn(x, w1, w2, w3, act=act, block_m=64, block_f=128,
                       interpret=True)
    expect = R.expert_ffn_ref(x, w1, w2, w3, act=act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("g,c", [(1, 5), (1, 10), (2, 10), (4, 160)])
def test_expert_ffn_ops_pads_rows_to_sublane_tiles(g, c):
    """M = G*C rows not a multiple of 8 pad to whole blocks (the TPU
    tiling) and the padding never reaches the output."""
    from repro.kernels.moe_gmm import ops as O, ref as R
    e, d, ff = 4, 64, 256
    xe = jax.random.normal(sub(35), (g, e, c, d)) * 0.5
    w1 = jax.random.normal(sub(36), (e, d, ff)) * 0.05
    w2 = jax.random.normal(sub(37), (e, ff, d)) * 0.05
    w3 = jax.random.normal(sub(38), (e, d, ff)) * 0.05
    out = O.expert_ffn(xe, w1, w2, w3, interpret=True)
    x = jnp.swapaxes(xe, 0, 1).reshape(e, g * c, d)
    expect = R.expert_ffn_ref(x, w1, w2, w3, act="silu")
    expect = jnp.swapaxes(expect.reshape(e, g, c, d), 0, 1)
    assert out.shape == xe.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-4)


def test_moe_gmm_matches_model_path():
    """Kernel path through moe_ffn == reference einsum path."""
    from repro.configs.registry import reduced_config
    from repro.models import moe as moe_mod
    cfg = reduced_config("granite-moe-1b-a400m").with_(capacity_factor=8.0)
    params = jax.jit(lambda k: moe_mod.init_moe(k, cfg))(sub(13))
    x = jax.random.normal(sub(14), (2, 64, cfg.d_model))
    y_ref, aux_ref = jax.jit(
        lambda p, x: moe_mod.moe_ffn(p, x, cfg))(params, x)
    cfg_k = cfg.with_(use_pallas_kernels=True, interpret_kernels=True)
    y_k, aux_k = jax.jit(
        lambda p, x: moe_mod.moe_ffn(p, x, cfg_k))(params, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k),
                               atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# mamba_scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,l,p,n,chunk", [
    (2, 3, 128, 32, 16, 32), (1, 2, 256, 64, 64, 64), (2, 2, 64, 16, 8, 16),
])
def test_mamba_scan(b, h, l, p, n, chunk):
    from repro.kernels.mamba_scan import kernel as K, ref as R
    x = jax.random.normal(sub(15), (b, h, l, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(sub(16), (b, h, l, 1)))
    a = -jnp.exp(jax.random.normal(sub(17), (h, 1, 1)) * 0.3)
    bb = jax.random.normal(sub(18), (b, l, n)) * 0.5
    cc = jax.random.normal(sub(19), (b, l, n)) * 0.5
    y, s = K.ssd_scan(x, dt, a.astype(jnp.float32), bb, cc, chunk=chunk,
                      interpret=True)
    ye, se = R.ssd_ref(x, dt, a, bb, cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=5e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(se), atol=5e-5,
                               rtol=1e-3)


def test_mamba_ops_matches_model_chunked():
    from repro.kernels.mamba_scan import ops as O
    from repro.models.ssm import ssd_chunked
    b, l, h, p, n = 2, 128, 4, 16, 8
    x = jax.random.normal(sub(20), (b, l, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(sub(21), (b, l, h)))
    a = -jnp.exp(jax.random.normal(sub(22), (h,)) * 0.3)
    bb = jax.random.normal(sub(23), (b, l, n)) * 0.5
    cc = jax.random.normal(sub(24), (b, l, n)) * 0.5
    y_k, s_k = O.ssd(x, dt, a, bb, cc, chunk=32, interpret=True)
    y_r, s_r = ssd_chunked(x, dt, a, bb, cc, chunk=32)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=5e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=5e-5,
                               rtol=1e-3)


# ---------------------------------------------------------------------------
# mlstm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,l,hd,chunk", [
    (2, 2, 128, 32, 32), (1, 4, 256, 64, 64), (2, 1, 64, 16, 16),
])
def test_mlstm_kernel(b, h, l, hd, chunk):
    from repro.kernels.mlstm import kernel as K, ref as R
    q = jax.random.normal(sub(25), (b, h, l, hd))
    k = jax.random.normal(sub(26), (b, h, l, hd))
    v = jax.random.normal(sub(27), (b, h, l, hd))
    li = jax.random.normal(sub(28), (b, h, l, 1)) - 1
    lf = -jax.nn.softplus(jax.random.normal(sub(29), (b, h, l, 1)))
    hh, c, n, m = K.mlstm_scan(q, k, v, li, lf, chunk=chunk, interpret=True)
    he, (ce, ne, me) = R.mlstm_ref(q, k, v, li, lf)
    np.testing.assert_allclose(np.asarray(hh), np.asarray(he), atol=1e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(c), np.asarray(ce), atol=1e-4,
                               rtol=1e-3)
    # m is a log-domain stabiliser: only exp-differences matter
    np.testing.assert_allclose(np.asarray(m[..., 0, 0]),
                               np.asarray(me[..., 0, 0]), atol=1e-3)


def test_mlstm_ops_matches_model_chunked():
    from repro.kernels.mlstm import ops as O
    from repro.models.xlstm import mlstm_chunked
    b, l, h, hd = 2, 128, 2, 32
    q = jax.random.normal(sub(30), (b, l, h, hd))
    k = jax.random.normal(sub(31), (b, l, h, hd))
    v = jax.random.normal(sub(32), (b, l, h, hd))
    li = jax.random.normal(sub(33), (b, l, h)) - 1
    lf = -jax.nn.softplus(jax.random.normal(sub(34), (b, l, h)))
    h_k, (c_k, n_k, m_k) = O.mlstm(q, k, v, li, lf, chunk=32,
                                   interpret=True)
    h_r, (c_r, n_r, m_r) = mlstm_chunked(q, k, v, li, lf, chunk=32)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(c_k), np.asarray(c_r), atol=1e-4,
                               rtol=1e-3)


# ---------------------------------------------------------------------------
# collective_codec (chunk-max threshold select)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k,m", [(8, 16), (8, 128), (16, 1), (24, 33),
                                 (1, 64)])
def test_collective_codec_kernel_matches_ref(k, m):
    from repro.kernels.collective_codec import kernel as K
    from repro.kernels.collective_codec import ref as R
    x = jax.random.normal(sub(40), (k, m))
    rows = K.BLOCK_ROWS if k % K.BLOCK_ROWS == 0 else 1
    vals, col, resid = K.chunk_select(x, block_rows=rows, interpret=True)
    v_r, c_r, r_r = R.chunk_select_ref(x)
    # bit-exact: the kernel and ref share the min-lane-argmax formulation
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(v_r))
    np.testing.assert_array_equal(np.asarray(col), np.asarray(c_r))
    np.testing.assert_array_equal(np.asarray(resid), np.asarray(r_r))


@pytest.mark.parametrize("n,frac", [(1000, 0.1), (7, 0.3), (4096, 0.05),
                                    (100, 1.0), (1, 0.5), (1 << 17, 0.05),
                                    ((1 << 16) + 3, 0.01)])
def test_collective_codec_roundtrip_exact(n, frac):
    from repro.kernels.collective_codec import ops as O
    vec = jax.random.normal(sub(41), (n,))
    # big sizes force the kernel path explicitly (default routing keeps
    # non-TPU backends on the ref)
    kw = dict(use_kernel=True, interpret=True) if n >= O.KERNEL_MIN_SIZE \
        else {}
    vals, idx, resid = O.select_codec(vec, frac=frac, **kw)
    k, m, _ = O.codec_geometry(n, frac)
    # both kernel-path sizes have k % 8 != 0: the kernel sees whole
    # 8-row blocks padded past k, which must not leak into the message
    assert vals.shape == (k,) and idx.shape == (k,)
    assert idx.dtype == jnp.int32
    recon = jnp.zeros((n,)).at[idx].add(vals) + resid
    # selected + residual reconstructs the input exactly (error feedback
    # invariant), for both the ref path and the kernel path (n = 2^17)
    np.testing.assert_array_equal(np.asarray(recon), np.asarray(vec))
    # each chunk's pick is its own largest-|x| element
    mag = np.abs(np.asarray(vec))
    for i in range(k):
        lo, hi = i * m, min((i + 1) * m, n)
        if lo >= n:
            continue
        assert mag[int(idx[i])] == mag[lo:hi].max()


def test_collective_codec_frac_one_is_identity():
    from repro.kernels.collective_codec import ops as O
    vec = jax.random.normal(sub(42), (257,))
    vals, idx, resid = O.select_codec(vec, frac=1.0)
    np.testing.assert_array_equal(np.asarray(idx), np.arange(257))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(vec))
    assert not np.asarray(resid).any()
