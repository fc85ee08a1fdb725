"""Property-based tests (hypothesis) for the byte-wise diff protocol —
Table 3 merge-op algebra and diff/apply invariants (paper §4)."""
import jax
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import diffsync as D


def _arrays(st):
    return st.integers(1, 4000).flatmap(
        lambda n: st.builds(
            lambda seed: np.random.default_rng(seed).normal(
                size=n).astype(np.float32) + 2.0,
            st.integers(0, 2 ** 16)))


@settings(max_examples=40, deadline=None)
@given(_arrays(st), st.integers(0, 2 ** 16))
def test_sum_merge_is_grad_accumulation(a0, seed):
    """A1 = A0 + (B1 - B0): merging N children == summing their deltas."""
    rng = np.random.default_rng(seed)
    b0 = a0.copy()
    deltas = [np.zeros_like(a0) for _ in range(3)]
    for d in deltas:
        idx = rng.integers(0, a0.size, size=max(1, a0.size // 7))
        d[idx] = rng.normal(size=idx.size).astype(np.float32)
    main = a0.copy()
    for d in deltas:
        main = D.apply_leaf(main, D.diff_leaf(b0, b0 + d, op="sum"))
    np.testing.assert_allclose(main, a0 + sum(deltas), atol=1e-5)


@settings(max_examples=40, deadline=None)
@given(_arrays(st))
def test_overwrite_roundtrip(a0):
    """diff(old, new) applied to old reproduces new exactly."""
    rng = np.random.default_rng(1)
    new = a0.copy()
    idx = rng.integers(0, a0.size, size=max(1, a0.size // 5))
    new[idx] += 1.0
    d = D.diff_leaf(a0, new, op="overwrite")
    np.testing.assert_array_equal(D.apply_leaf(a0, d), new)


@settings(max_examples=40, deadline=None)
@given(_arrays(st))
def test_clean_state_empty_diff(a0):
    d = D.diff_leaf(a0, a0.copy())
    assert d.idx.size == 0
    np.testing.assert_array_equal(D.apply_leaf(a0, d), a0)


@settings(max_examples=40, deadline=None)
@given(_arrays(st), st.sampled_from(["sum", "subtract"]))
def test_sum_subtract_inverse(a0, op):
    """subtract(A0, B0, B1) == sum(A0, B1, B0): Table 3 algebra."""
    rng = np.random.default_rng(2)
    b0 = a0.copy()
    b1 = b0 + rng.normal(size=a0.shape).astype(np.float32)
    via_sub = D.apply_leaf(a0, D.diff_leaf(b0, b1, op="subtract"))
    via_sum = D.apply_leaf(a0, D.diff_leaf(b1, b0, op="sum"))
    np.testing.assert_allclose(via_sub + via_sum, 2 * a0, atol=1e-4)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2 ** 16))
def test_multiply_merge(seed):
    rng = np.random.default_rng(seed)
    a0 = rng.uniform(1, 2, 2048).astype(np.float32)
    b0 = rng.uniform(1, 2, 2048).astype(np.float32)
    scale = rng.uniform(0.5, 2.0)
    b1 = (b0 * scale).astype(np.float32)
    merged = D.apply_leaf(a0, D.diff_leaf(b0, b1, op="multiply"))
    np.testing.assert_allclose(merged, a0 * scale, rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 16))
def test_tree_diff_only_ships_dirty_bytes(seed):
    rng = np.random.default_rng(seed)
    tree = {"a": rng.normal(size=(64, 64)).astype(np.float32),
            "b": rng.normal(size=(10,)).astype(np.float32)}
    new = {"a": tree["a"].copy(), "b": tree["b"].copy()}
    new["a"][0, 0] += 1.0
    diffs = D.diff_tree(tree, new)
    assert len(diffs) == 1                    # only leaf 'a' is dirty
    assert D.diff_nbytes(diffs) < tree["a"].nbytes + tree["b"].nbytes
    merged = D.apply_tree(tree, diffs)
    np.testing.assert_array_equal(merged["a"], new["a"])
    np.testing.assert_array_equal(merged["b"], tree["b"])


def test_dense_diff_matches_sparse():
    rng = np.random.default_rng(0)
    old = rng.normal(size=5000).astype(np.float32)
    new = old.copy()
    new[100:200] += 1.5
    import jax.numpy as jnp
    mask, delta = jax.jit(D.dense_diff)(jnp.asarray(old), jnp.asarray(new))
    sparse = D.diff_leaf(old, new, op="sum")
    np.testing.assert_array_equal(np.nonzero(np.asarray(mask))[0],
                                  sparse.idx)
    merged = jax.jit(lambda m, ms, p: D.dense_merge(m, ms, p, op="sum"))(
        jnp.asarray(old), mask, delta)
    np.testing.assert_allclose(np.asarray(merged), new, atol=1e-6)


# ---------------------------------------------------------------------------
# Parity suite: the vectorized hot path is pinned bit-exact against the
# pre-vectorization reference implementations (ISSUE 6 tentpole)
# ---------------------------------------------------------------------------
_PARITY_SIZES = (1, 7, 1023, 1024, 1025, 4000, 65536)


def _dirty_pair(n, dtype, seed, frac=9):
    rng = np.random.default_rng(seed)
    b0 = (rng.normal(size=n) + 2.0).astype(dtype)
    b1 = b0.copy()
    idx = rng.integers(0, n, size=max(1, n // frac))
    b1[idx] = (rng.normal(size=idx.size) + 3.0).astype(dtype)
    return b0, b1


@settings(max_examples=40, deadline=None)
@given(st.sampled_from(list(D.MERGE_OPS)),
       st.sampled_from(list(_PARITY_SIZES)),
       st.integers(0, 2 ** 16))
def test_parity_with_reference_float(op, n, seed):
    """diff_leaf/apply_leaf match reference_* bit-for-bit on floats."""
    rng = np.random.default_rng(seed)
    a0 = (rng.normal(size=n) + 2.0).astype(np.float32)
    b0, b1 = _dirty_pair(n, np.float32, seed + 1)
    d_new = D.diff_leaf(b0, b1, op=op)
    d_ref = D.reference_diff_leaf(b0, b1, op=op)
    np.testing.assert_array_equal(d_new.idx, d_ref.idx)
    np.testing.assert_array_equal(d_new.new, d_ref.new)
    np.testing.assert_array_equal(d_new.old, d_ref.old)
    np.testing.assert_array_equal(D.apply_leaf(a0, d_new),
                                  D.reference_apply_leaf(a0, d_ref))


def test_parity_with_reference_tree():
    rng = np.random.default_rng(3)
    tree = {"w": rng.normal(size=(80, 33)).astype(np.float32),
            "b": rng.normal(size=(130,)).astype(np.float64),
            "clean": rng.normal(size=(50,)).astype(np.float32)}
    new = {k: v.copy() for k, v in tree.items()}
    new["w"][5, :] += 1.0
    new["b"][100:] *= 1.5
    diffs = D.diff_tree(tree, new, op="overwrite")
    got = D.apply_tree(tree, diffs)
    ref = D.reference_apply_tree(tree, diffs)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(ref[k]))
    # untouched leaves pass through as the same object (no copy)
    assert got["clean"] is tree["clean"]


# ---------------------------------------------------------------------------
# Round-trips across dtypes / ragged shapes / all five ops (satellite 3)
# ---------------------------------------------------------------------------
def _dtypes():
    import ml_dtypes
    return [np.float32, np.float64, np.int32, ml_dtypes.bfloat16]


@settings(max_examples=40, deadline=None)
@given(st.sampled_from(_dtypes()),
       st.sampled_from([1, 13, 1023, 1025, 5000]),
       st.integers(0, 2 ** 16))
def test_overwrite_roundtrip_dtypes_ragged(dtype, n, seed):
    """diff -> apply reproduces the child exactly for every dtype,
    including ragged non-multiple-of-CHUNK shapes."""
    rng = np.random.default_rng(seed)
    if np.issubdtype(np.dtype(dtype), np.integer):
        old = rng.integers(-1000, 1000, size=n).astype(dtype)
        new = old.copy()
        new[rng.integers(0, n, size=max(1, n // 5))] += 7
    else:
        old = (rng.normal(size=n) + 2.0).astype(dtype)
        new = old.copy()
        idx = rng.integers(0, n, size=max(1, n // 5))
        new[idx] = (rng.normal(size=idx.size) + 3.0).astype(dtype)
    d = D.diff_leaf(old, new, op="overwrite")
    got = D.apply_leaf(old, d)
    assert got.dtype == old.dtype
    np.testing.assert_array_equal(got, new)


@settings(max_examples=40, deadline=None)
@given(st.sampled_from(list(D.MERGE_OPS)),
       st.integers(0, 2 ** 16))
def test_all_ops_roundtrip_ragged(op, seed):
    """Five-op merge algebra on a ragged leaf: merged value matches the
    scalarwise oracle applied to the dirty chunks."""
    n = 3333
    rng = np.random.default_rng(seed)
    a0 = rng.uniform(1, 2, n).astype(np.float32)
    b0 = rng.uniform(1, 2, n).astype(np.float32)
    b1 = b0.copy()
    sl = slice(100, 700)
    b1[sl] = rng.uniform(1, 2, 600).astype(np.float32)
    b1[-5:] = rng.uniform(1, 2, 5).astype(np.float32)  # dirty tail chunk
    merged = D.apply_leaf(a0, D.diff_leaf(b0, b1, op=op))
    # dirty chunks follow Table 3; clean chunks keep a0
    full = D.merge_scalarwise(a0, b0, b1, op)
    chunks = -(-n // D.CHUNK)
    fb0 = np.pad(b0, (0, chunks * D.CHUNK - n))
    fb1 = np.pad(b1, (0, chunks * D.CHUNK - n))
    dirty = np.any(fb0.reshape(-1, D.CHUNK) != fb1.reshape(-1, D.CHUNK),
                   axis=1)
    mask = np.repeat(dirty, D.CHUNK)[:n]
    np.testing.assert_array_equal(merged[mask], full[mask])
    np.testing.assert_array_equal(merged[~mask], a0[~mask])


def test_int64_sum_exact_beyond_f53():
    """Integer leaves merge exactly — the old float64 round-trip lost
    low bits above 2**53."""
    a0 = np.array([2 ** 60 + 1, 5], dtype=np.int64)
    b0 = np.array([2 ** 60 + 1, 5], dtype=np.int64)
    b1 = np.array([2 ** 60 + 4, 5], dtype=np.int64)
    got = D.apply_leaf(a0, D.diff_leaf(b0, b1, op="sum"))
    assert got.tolist() == [2 ** 60 + 4, 5]
    # the pinned reference demonstrates the old corruption
    ref = D.reference_apply_leaf(a0, D.reference_diff_leaf(b0, b1,
                                                           op="sum"))
    assert ref.tolist() != got.tolist()


def test_apply_leaf_empty_diff_passthrough_and_inplace():
    a = np.arange(5000, dtype=np.float32)
    d = D.diff_leaf(a, a.copy())
    assert D.apply_leaf(a, d) is a          # satellite 2: no copy
    b0 = a.copy()
    b1 = a.copy()
    b1[10:20] += 1
    d = D.diff_leaf(b0, b1, op="overwrite")
    out = D.apply_leaf(a, d, inplace=True)
    assert out is a
    np.testing.assert_array_equal(a, b1)


# ---------------------------------------------------------------------------
# apply_many: N-way merge == sequential application
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(st.sampled_from(["sum", "overwrite", "multiply"]),
       st.integers(0, 2 ** 16))
def test_apply_many_matches_sequential(op, seed):
    n = 9000
    rng = np.random.default_rng(seed)
    a0 = rng.uniform(1, 2, n).astype(np.float32)
    b0 = a0.copy()
    diffs = []
    for k in range(4):
        b1 = b0.copy()
        # overlapping dirty ranges across workers exercise the
        # first-touch bookkeeping
        lo = 500 * k
        b1[lo:lo + 2000] = rng.uniform(1, 2, 2000).astype(np.float32)
        diffs.append(D.diff_leaf(b0, b1, op=op))
    seq = a0.copy()
    for d in diffs:
        seq = D.apply_leaf(seq, d)
    np.testing.assert_array_equal(D.apply_many(a0, diffs), seq)
    ip = a0.copy()
    assert D.apply_many(ip, diffs, inplace=True) is ip
    np.testing.assert_array_equal(ip, seq)


def test_apply_many_ragged_tail_and_full_coverage():
    n = D.CHUNK * 3 + 17
    rng = np.random.default_rng(5)
    a0 = rng.normal(size=n).astype(np.float32)
    b0 = a0.copy()
    d1_new = b0.copy(); d1_new[: 2 * D.CHUNK] += 1.0
    d2_new = b0.copy(); d2_new[2 * D.CHUNK:] += 2.0   # covers the tail
    diffs = [D.diff_leaf(b0, d1_new, op="sum"),
             D.diff_leaf(b0, d2_new, op="sum")]
    seq = D.apply_leaf(D.apply_leaf(a0, diffs[0]), diffs[1])
    np.testing.assert_array_equal(D.apply_many(a0, diffs), seq)


# ---------------------------------------------------------------------------
# TrackedFork: chunk-granular CoW write tracking (the mprotect analogue)
# ---------------------------------------------------------------------------
def test_tracked_fork_diff_matches_compare_based():
    rng = np.random.default_rng(7)
    base = rng.normal(size=10000).astype(np.float32)
    keep = base.copy()
    f = D.TrackedFork(base)
    np.multiply(base[100:3000], 1.5,
                out=f.writable(slice(100, 3000)))
    f[5000] = 9.0
    f[9999] = -1.0                          # last (ragged-size) element
    child = base.copy()
    child[100:3000] *= 1.5
    child[5000] = 9.0
    child[9999] = -1.0
    d = f.diff(op="overwrite")
    np.testing.assert_array_equal(base, keep)   # base never written
    got = D.apply_leaf(base, d)
    np.testing.assert_array_equal(got, child)
    # tracked mask is chunk-granular: same chunks a compare would find
    ref = D.diff_leaf(base, child, op="overwrite")
    np.testing.assert_array_equal(d.idx, ref.idx)


def test_tracked_fork_verify_drops_clean_writes():
    base = np.zeros(4096, dtype=np.float32)
    f = D.TrackedFork(base)
    f[0:1024] = 0.0                          # written but unchanged
    f[2048] = 5.0
    assert f.dirty_chunks.tolist() == [0, 2]
    assert f.diff(op="overwrite", verify=True).idx.tolist() == [2]


def test_tracked_fork_read_through():
    base = np.arange(3000, dtype=np.float32)
    f = D.TrackedFork(base)
    f[1500] = -1.0
    np.testing.assert_array_equal(f[0:10], base[0:10])   # clean read
    got = f[1400:1600]                       # straddles dirty chunk
    expect = base[1400:1600].copy()
    expect[100] = -1.0
    np.testing.assert_array_equal(got, expect)


# ---------------------------------------------------------------------------
# dense_merge dtype preservation (satellite 1)
# ---------------------------------------------------------------------------
def test_dense_merge_preserves_f64_precision():
    from jax import enable_x64
    with enable_x64():
        import jax.numpy as jnp
        old = np.full(2048, 1.0, dtype=np.float64)
        new = old + 1e-12                    # invisible in float32
        mask, delta = D.dense_diff(jnp.asarray(old), jnp.asarray(new))
        merged = D.dense_merge(jnp.asarray(old), mask, delta, op="sum")
        assert merged.dtype == jnp.float64
        np.testing.assert_array_equal(np.asarray(merged), new)


def test_dense_merge_int_exact():
    import jax.numpy as jnp
    old = (np.arange(3000, dtype=np.int32) * 1000003)
    new = old.copy()
    new[100:300] += 7
    mask, delta = D.dense_diff(jnp.asarray(old), jnp.asarray(new))
    merged = D.dense_merge(jnp.asarray(old), mask, delta, op="sum")
    assert merged.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(merged), new)


# ---------------------------------------------------------------------------
# fused_diff_apply: host path vs kernels/diff_merge routing
# ---------------------------------------------------------------------------
def test_fused_diff_apply_host_vs_kernel():
    rng = np.random.default_rng(11)
    a0 = rng.normal(size=(64, 300)).astype(np.float32)
    fork = a0.copy()
    child = fork.copy()
    child[3, :50] += 1.0
    mh, dh = D.fused_diff_apply(a0, fork, child, op="sum",
                                use_kernel=False)
    mk, dk = D.fused_diff_apply(a0, fork, child, op="sum",
                                use_kernel=True, interpret=True)
    np.testing.assert_allclose(mh, np.asarray(mk), atol=1e-6)
    np.testing.assert_array_equal(dh, np.asarray(dk))
    assert dh.sum() == 1
