"""Byte-wise-diff synchronisation of shared state (paper §4, Table 3).

Faabric tracks writes to shared pages with ``mprotect`` and ships byte-wise
diffs with *merge operations* back to the main snapshot.  On TPU there is no
page-fault hook inside an XLA program, so the TPU-native adaptation is
explicit **chunk-wise diffing**: every state leaf is viewed as a sequence of
fixed-size chunks (the page analogue); dirty chunks are found by comparing
against the parent snapshot, and only dirty chunks travel.

Three representations are provided:

* **sparse** (host-side; checkpointing, migration, cross-pod delta sync):
  per-leaf ``(chunk_idx, payload)`` arrays with dynamic length — exactly the
  paper's (offset, bytes) diff list.  The hot path is fully vectorized:
  dirty detection is one batched compare per leaf, merge maths touch only
  the gathered dirty chunks, and ``apply_leaf(..., inplace=True)`` /
  ``apply_many`` never materialise clean chunks — merge cost scales with
  dirty bytes, not state bytes.
* **tracked** (``TrackedFork``): the ``mprotect`` analogue for host
  buffers — a chunk-granular copy-on-write fork that records dirty chunks
  as writes land, so neither the fork nor the diff ever scans clean state.
* **dense-mask** (jit-side; in-graph reductions): (mask, delta) with static
  shapes, consumed by the ``kernels.diff_merge`` Pallas kernel.  Large
  leaves route there from the host-side API via ``fused_diff_apply``.

Merge operations follow Table 3 exactly:
    sum        A1 = A0 + (B1 - B0)
    subtract   A1 = A0 - (B0 - B1)
    multiply   A1 = A0 * (B1 / B0)
    divide     A1 = A0 / (B0 / B1)
    overwrite  A1 = B1
where A0 = main-snapshot value, B0 = child's snapshot-at-fork value,
B1 = child's value after execution, A1 = merged main value.

Dtypes are preserved end to end: float leaves run the merge maths in
float64 and round once back to the leaf dtype (bit-identical to the
pinned ``reference_*`` implementations), integer leaves use exact integer
sum/subtract/overwrite (no float round-trip — the reference path silently
corrupted int64 values above 2**53).

The pre-vectorization implementations are kept verbatim as
``reference_merge_scalarwise`` / ``reference_diff_leaf`` /
``reference_apply_leaf`` / ``reference_apply_tree`` and pinned against the
hot path by the parity suite in ``tests/test_diffsync.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1024  # elements per chunk (the "page" size of the diff protocol)

MERGE_OPS = ("sum", "subtract", "multiply", "divide", "overwrite")

# leaves with at least this many elements route through the
# kernels/diff_merge Pallas kernel when the backend is a TPU
# (``fused_diff_apply``); smaller leaves and CPU hosts stay on the
# vectorized numpy path, where kernel dispatch overhead would dominate
KERNEL_MIN_ELEMS = 1 << 20


def _as_f64(a):
    return np.asarray(a, dtype=np.float64)


def _is_int(dtype) -> bool:
    return np.issubdtype(np.dtype(dtype), np.integer)


def merge_scalarwise(a0, b0, b1, op: str):
    """Apply one Table-3 merge op elementwise (host/numpy),
    dtype-preserving: float leaves compute in float64 and round once
    (bit-identical to ``reference_merge_scalarwise``); integer leaves
    use exact integer arithmetic for sum/subtract/overwrite."""
    a0 = np.asarray(a0)
    if op == "overwrite":
        return np.asarray(b1, dtype=a0.dtype)
    if _is_int(a0.dtype) and op in ("sum", "subtract"):
        b0i = np.asarray(b0, dtype=a0.dtype)
        b1i = np.asarray(b1, dtype=a0.dtype)
        if op == "sum":
            return a0 + (b1i - b0i)
        return a0 - (b0i - b1i)
    a0d, b0d, b1d = _as_f64(a0), _as_f64(b0), _as_f64(b1)
    if op == "sum":
        out = a0d + (b1d - b0d)
    elif op == "subtract":
        out = a0d - (b0d - b1d)
    elif op == "multiply":
        with np.errstate(divide="ignore", invalid="ignore"):
            out = np.where(b0d == 0, a0d, a0d * (b1d / b0d))
    elif op == "divide":
        with np.errstate(divide="ignore", invalid="ignore"):
            out = np.where(b1d == 0, a0d, a0d / (b0d / b1d))
    else:
        raise ValueError(op)
    return out.astype(a0.dtype)


# ---------------------------------------------------------------------------
# Sparse (host-side) diff lists — the migration/checkpoint wire format
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class LeafDiff:
    """Diff of one state leaf: dirty chunk indices + their new contents.

    ``new``/``old`` rows align with ``idx``; the tail chunk of a ragged
    leaf (size not a CHUNK multiple) is zero-padded to full width.
    ``new``/``old`` may be *views* into live buffers (contiguous dirty
    runs, ``TrackedFork.diff``) — treat a LeafDiff as immutable."""
    idx: np.ndarray        # (k,) int32 dirty chunk indices
    new: np.ndarray        # (k, CHUNK) values after execution (B1)
    old: np.ndarray        # (k, CHUNK) values at fork (B0); merge ops need it
    shape: Tuple[int, ...]
    dtype: Any
    op: str = "overwrite"

    @property
    def nbytes(self) -> int:
        return int(self.idx.nbytes + self.new.nbytes
                   + (0 if self.op == "overwrite" else self.old.nbytes))


def _flat_view(a: np.ndarray) -> np.ndarray:
    """Zero-copy flat view (host snapshots are contiguous; fall back to
    a copy only for exotic layouts)."""
    a = np.asarray(a)
    flat = a.reshape(-1) if a.flags.c_contiguous else np.ravel(a)
    return flat


def _body_tail(flat: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Split a flat buffer into a zero-copy (n_full, CHUNK) body view and
    the ragged tail (possibly empty)."""
    n_full = flat.size // CHUNK
    body = flat[:n_full * CHUNK].reshape(n_full, CHUNK)
    return body, flat[n_full * CHUNK:]


def _pad_chunk(vals: np.ndarray) -> np.ndarray:
    """One ragged tail as a zero-padded (1, CHUNK) row."""
    row = np.zeros((1, CHUNK), dtype=vals.dtype)
    row[0, :vals.size] = vals
    return row


def _gather(body: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Gather chunk rows; a contiguous run comes back as a zero-copy
    basic-slice view instead of a fancy-index copy."""
    if idx.size and int(idx[-1]) - int(idx[0]) == idx.size - 1:
        return body[int(idx[0]):int(idx[-1]) + 1]
    return body[idx]


def diff_leaf(old: np.ndarray, new: np.ndarray, op: str = "overwrite"
              ) -> LeafDiff:
    """Chunk-wise compare ``new`` against the fork snapshot ``old``.

    One vectorized compare over the chunk body plus a separate tail
    check — no pad copy of the full leaf, and payload gathers touch
    dirty chunks only."""
    old, new = np.asarray(old), np.asarray(new)
    assert old.shape == new.shape and old.dtype == new.dtype
    fo, fn = _flat_view(old), _flat_view(new)
    ob, ot = _body_tail(fo)
    nb, nt = _body_tail(fn)
    dirty = np.any(ob != nb, axis=1)
    idx = np.nonzero(dirty)[0].astype(np.int32)
    new_rows = _gather(nb, idx)
    old_rows = _gather(ob, idx)
    if ot.size and np.any(ot != nt):
        idx = np.concatenate([idx, np.asarray([ob.shape[0]],
                                              dtype=np.int32)])
        new_rows = np.concatenate([new_rows, _pad_chunk(nt)])
        old_rows = np.concatenate([old_rows, _pad_chunk(ot)])
    return LeafDiff(idx=idx, new=new_rows, old=old_rows,
                    shape=old.shape, dtype=old.dtype, op=op)


def _split_tail_idx(d: LeafDiff, n_full: int
                    ) -> Tuple[np.ndarray, bool]:
    """Row positions of body chunks in ``d`` and whether the last row is
    the ragged tail chunk."""
    has_tail = bool(d.idx.size) and int(d.idx[-1]) == n_full
    return (d.idx[:-1] if has_tail else d.idx), has_tail


def apply_leaf(main: np.ndarray, d: LeafDiff,
               inplace: bool = False) -> np.ndarray:
    """Merge a LeafDiff into the main copy (A0 -> A1, Table 3).

    An empty diff passes ``main`` through untouched; otherwise only the
    dirty chunks are gathered, merged and scattered back — the one
    O(state) cost left is the defensive copy, and ``inplace=True``
    (merge into the long-lived main snapshot, the protocol's real hot
    path) removes it too."""
    main = np.asarray(main)
    if d.idx.size == 0:
        return main
    out = main if inplace else main.copy()
    flat = _flat_view(out)
    body, tail = _body_tail(flat)
    body_idx, has_tail = _split_tail_idx(d, body.shape[0])
    k = body_idx.size
    if k:
        a0 = _gather(body, body_idx)
        merged = merge_scalarwise(a0, d.old[:k], d.new[:k], d.op)
        body[body_idx] = merged
    if has_tail:
        r = tail.size
        a0t = _pad_chunk(tail)
        mt = merge_scalarwise(a0t, d.old[-1:], d.new[-1:], d.op)
        tail[:] = mt[0, :r]
    return out


def apply_many(main: np.ndarray, diffs: Sequence[LeafDiff],
               inplace: bool = False) -> np.ndarray:
    """Merge several diffs of the same leaf into ``main`` in order
    (N parallel workers merging back, paper §4.2).

    Equivalent to folding ``apply_leaf`` but with one materialisation:
    chunks no diff touches are copied from ``main`` exactly once (or
    never, with ``inplace=True`` or when the diffs cover the leaf), so
    merge cost scales with Σ dirty bytes.  The first diff touching a
    chunk merges against ``main``'s value, later ones against the
    accumulated result — identical to sequential application."""
    main = np.asarray(main)
    diffs = [d for d in diffs if d.idx.size]
    if not diffs:
        return main
    if inplace:
        out = main
    else:
        # materialise the output without an O(state) copy: only chunks
        # NO diff touches are copied from main; dirty chunks are merged
        # into place below (the first writer reads its A0 from main)
        out = np.empty_like(main)
        flat_o = _flat_view(out)
        flat_m = _flat_view(main)
        body_o, tail_o = _body_tail(flat_o)
        n_full = body_o.shape[0]
        covered = np.zeros(n_full + (1 if tail_o.size else 0),
                           dtype=bool)
        for d in diffs:
            covered[d.idx] = True
        clean = np.nonzero(~covered[:n_full])[0]
        if clean.size:
            body_m, _ = _body_tail(flat_m)
            body_o[clean] = _gather(body_m, clean)
        if tail_o.size and not (covered.size > n_full
                                and covered[n_full]):
            tail_o[:] = flat_m[n_full * CHUNK:]
    flat = _flat_view(out)
    body, tail = _body_tail(flat)
    n_full = body.shape[0]
    flat_main = _flat_view(main)
    body_main, tail_main = _body_tail(flat_main)
    written = np.zeros(n_full + 1, dtype=bool)      # +1: tail slot
    for d in diffs:
        body_idx, has_tail = _split_tail_idx(d, n_full)
        k = body_idx.size
        if k:
            first = ~written[body_idx]
            if inplace or not first.any():
                a0 = _gather(body, body_idx)
            elif first.all():
                a0 = _gather(body_main, body_idx)
            else:
                a0 = _gather(body, body_idx).copy()
                a0[first] = body_main[body_idx[first]]
            body[body_idx] = merge_scalarwise(a0, d.old[:k],
                                              d.new[:k], d.op)
            written[body_idx] = True
        if has_tail:
            src = tail if (inplace or written[n_full]) else tail_main
            a0t = _pad_chunk(src)
            mt = merge_scalarwise(a0t, d.old[-1:], d.new[-1:], d.op)
            tail[:] = mt[0, :tail.size]
            written[n_full] = True
    return out


def diff_tree(old_tree, new_tree, op: str = "overwrite") -> Dict[str, Any]:
    """Diff two state pytrees -> {path: LeafDiff} for dirty leaves only."""
    flat_old = jax.tree_util.tree_flatten_with_path(old_tree)[0]
    flat_new = jax.tree_util.tree_leaves(new_tree)
    diffs = {}
    for (path, o), n in zip(flat_old, flat_new):
        d = diff_leaf(np.asarray(o), np.asarray(n), op=op)
        if d.idx.size:
            diffs[jax.tree_util.keystr(path)] = d
    return diffs


def apply_tree(main_tree, diffs: Dict[str, Any], inplace: bool = False):
    """Merge a diff dict into the main pytree; returns the merged tree.

    Untouched leaves pass through as-is (no copy), and the dirty
    leaves' merge maths are *stacked*: all dirty chunks sharing a
    (merge-op, dtype) are gathered across leaves into one batched
    ``merge_scalarwise`` call, so a tree with many small dirty leaves
    pays one vectorized pass instead of per-leaf dispatch."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(main_tree)
    keyed = [(jax.tree_util.keystr(path), leaf) for path, leaf in flat]
    touched = [(i, diffs[key]) for i, (key, _) in enumerate(keyed)
               if key in diffs and diffs[key].idx.size]
    out: List[Any] = [leaf for _, leaf in keyed]

    # group dirty leaves by (op, dtype): one stacked merge per group
    groups: Dict[Tuple[str, str], List[Tuple[int, LeafDiff]]] = {}
    for i, d in touched:
        groups.setdefault((d.op, np.dtype(d.dtype).str), []).append(
            (i, d))
    for (op, _), members in groups.items():
        a0_rows, old_rows, new_rows, spans = [], [], [], []
        for i, d in members:
            main = np.asarray(out[i])
            target = main if inplace else main.copy()
            out[i] = target
            flat_t = _flat_view(target)
            body, tail = _body_tail(flat_t)
            body_idx, has_tail = _split_tail_idx(d, body.shape[0])
            k = body_idx.size
            if k:
                a0_rows.append(_gather(body, body_idx))
                old_rows.append(d.old[:k])
                new_rows.append(d.new[:k])
            if has_tail:
                a0_rows.append(_pad_chunk(tail))
                old_rows.append(d.old[-1:])
                new_rows.append(d.new[-1:])
            spans.append((i, k, has_tail))
        merged = merge_scalarwise(np.concatenate(a0_rows),
                                  np.concatenate(old_rows),
                                  np.concatenate(new_rows), op)
        row = 0
        for i, k, has_tail in spans:
            target = out[i]
            flat_t = _flat_view(target)
            body, tail = _body_tail(flat_t)
            d = diffs[keyed[i][0]]
            if k:
                body[d.idx[:k]] = merged[row:row + k]
                row += k
            if has_tail:
                tail[:] = merged[row, :tail.size]
                row += 1
    return jax.tree_util.tree_unflatten(treedef, out)


def diff_nbytes(diffs: Dict[str, Any]) -> int:
    return sum(d.nbytes for d in diffs.values())


def tree_nbytes(tree) -> int:
    """Total host bytes of a state pytree (the full-snapshot size a
    delta is measured against)."""
    return int(sum(np.asarray(l).nbytes
                   for l in jax.tree_util.tree_leaves(tree)))


# ---------------------------------------------------------------------------
# TrackedFork — the mprotect write-tracking analogue for host buffers
# ---------------------------------------------------------------------------
class TrackedFork:
    """Chunk-granular copy-on-write fork of a host buffer.

    Faabric forks a Granule by marking the parent's pages read-only and
    trapping writes; here the "trap" is explicit — writes go through
    ``writable`` / ``__setitem__``, which materialise only the touched
    chunks (boundary chunks copy in from the base; fully-covered chunks
    are written directly) and record them in a dirty mask.  Fork cost
    and diff cost therefore scale with dirty bytes: ``diff`` builds a
    ``LeafDiff`` straight from the mask with no full-state compare
    (chunk-pessimistic, exactly like page-granular mprotect tracking;
    ``verify=True`` re-compares the dirty chunks to drop false
    positives).  The base buffer is never written."""

    def __init__(self, base: np.ndarray):
        self.base = np.asarray(base)
        self._flat_base = _flat_view(self.base)
        self._buf = np.empty_like(self.base)
        self._flat = _flat_view(self._buf)
        self._n_chunks = -(-self._flat.size // CHUNK)
        self._dirty = np.zeros(self._n_chunks, dtype=bool)

    def _materialize(self, lo: int, hi: int) -> None:
        """Mark chunks [lo, hi) elementwise range dirty; copy boundary
        (partially-covered) chunks in from the base first."""
        c0, c1 = lo // CHUNK, -(-hi // CHUNK)
        for c, edge_lo, edge_hi in ((c0, c0 * CHUNK, lo),
                                    (c1 - 1, hi, c1 * CHUNK)):
            if edge_lo < edge_hi and not self._dirty[c]:
                s = slice(c * CHUNK, min((c + 1) * CHUNK,
                                         self._flat.size))
                self._flat[s] = self._flat_base[s]
        self._dirty[c0:c1] = True

    def _span(self, key) -> Tuple[int, int]:
        if isinstance(key, slice):
            lo, hi, step = key.indices(self._flat.size)
            assert step == 1, "TrackedFork writes must be unit-stride"
            return lo, max(lo, hi)
        i = int(key)
        if i < 0:
            i += self._flat.size
        return i, i + 1

    def writable(self, key) -> np.ndarray:
        """A writable view of the fork's buffer for the given flat
        slice — the caller produces values directly into fork storage
        (e.g. ``np.multiply(base[sl], 1.01, out=fork.writable(sl))``),
        so a write costs one store, not a temporary plus a copy."""
        lo, hi = self._span(key)
        self._materialize(lo, hi)
        return self._flat[lo:hi]

    def __setitem__(self, key, values) -> None:
        lo, hi = self._span(key)
        self._materialize(lo, hi)
        self._flat[lo:hi] = values

    def __getitem__(self, key) -> np.ndarray:
        """Read-through: dirty chunks from the fork, clean from base."""
        lo, hi = self._span(key)
        c0, c1 = lo // CHUNK, -(-hi // CHUNK)
        if self._dirty[c0:c1].all():
            return self._flat[lo:hi]
        if not self._dirty[c0:c1].any():
            return self._flat_base[lo:hi]
        out = self._flat_base[lo:hi].copy()
        for c in range(c0, c1):
            if self._dirty[c]:
                s0 = max(lo, c * CHUNK)
                s1 = min(hi, (c + 1) * CHUNK)
                out[s0 - lo:s1 - lo] = self._flat[s0:s1]
        return out

    @property
    def dirty_chunks(self) -> np.ndarray:
        return np.nonzero(self._dirty)[0].astype(np.int32)

    def diff(self, op: str = "overwrite", verify: bool = False
             ) -> LeafDiff:
        """The fork's LeafDiff against its base, straight from the
        write-tracking mask — no state-sized compare.  ``new`` rows are
        zero-copy views into the fork buffer when the dirty set is a
        contiguous run."""
        idx = self.dirty_chunks
        if verify and idx.size:
            body_b, tail_b = _body_tail(self._flat_base)
            body_f, tail_f = _body_tail(self._flat)
            n_full = body_b.shape[0]
            body_idx = idx[idx < n_full]
            keep = np.any(body_b[body_idx] != body_f[body_idx], axis=1)
            kept = body_idx[keep]
            if idx.size and int(idx[-1]) == n_full \
                    and tail_b.size and np.any(tail_b != tail_f):
                kept = np.concatenate([kept, idx[-1:]])
            idx = kept.astype(np.int32)
        body_b, tail_b = _body_tail(self._flat_base)
        body_f, tail_f = _body_tail(self._flat)
        n_full = body_f.shape[0]
        body_idx = idx[idx < n_full]
        new_rows = _gather(body_f, body_idx)
        old_rows = _gather(body_b, body_idx)
        if idx.size and int(idx[-1]) == n_full:
            new_rows = np.concatenate([new_rows, _pad_chunk(tail_f)])
            old_rows = np.concatenate([old_rows, _pad_chunk(tail_b)])
        return LeafDiff(idx=idx, new=new_rows, old=old_rows,
                        shape=self.base.shape, dtype=self.base.dtype,
                        op=op)


# ---------------------------------------------------------------------------
# Fused diff+merge — routes large leaves through kernels/diff_merge
# ---------------------------------------------------------------------------
def _kernel_default(n_elems: int) -> bool:
    return (n_elems >= KERNEL_MIN_ELEMS
            and jax.default_backend() == "tpu")


def fused_diff_apply(main, fork, child, op: str = "sum",
                     use_kernel: Optional[bool] = None,
                     interpret: bool = False
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """One fused pass over a leaf: dirty detection against the fork
    snapshot + Table-3 merge into ``main``.  Returns
    ``(merged, dirty chunk mask)``.

    ``use_kernel=None`` routes leaves of ``KERNEL_MIN_ELEMS``+ elements
    through the ``kernels.diff_merge`` Pallas kernel when running on a
    TPU (one HBM-speed streaming pass) and keeps everything else on the
    vectorized host path; ``True``/``False`` force a side
    (``interpret`` is forwarded to the kernel for off-TPU testing)."""
    main = np.asarray(main)
    if use_kernel is None:
        use_kernel = _kernel_default(main.size)
    if use_kernel:
        from repro.kernels.diff_merge import ops as _kops
        merged, dirty = _kops.diff_merge_leaf(
            jnp.asarray(main), jnp.asarray(fork), jnp.asarray(child),
            op=op, interpret=interpret)
        return np.asarray(merged), np.asarray(dirty)
    d = diff_leaf(np.asarray(fork), np.asarray(child), op=op)
    merged = apply_leaf(main, d)
    n_chunks = -(-main.size // CHUNK)
    dirty = np.zeros(n_chunks, dtype=bool)
    dirty[d.idx] = True
    return merged, dirty


# ---------------------------------------------------------------------------
# Dense-mask (jit-side) diffs — consumed by kernels/diff_merge
# ---------------------------------------------------------------------------
def dense_diff(old, new):
    """jit-able chunk diff: returns (dirty_mask (nchunks,), delta) where
    delta = new - old (the merge-op payload for op=sum)."""
    flat_o = jnp.ravel(old)
    pad = (-flat_o.size) % CHUNK
    fo = jnp.pad(flat_o, (0, pad)).reshape(-1, CHUNK)
    fn = jnp.pad(jnp.ravel(new), (0, pad)).reshape(-1, CHUNK)
    mask = jnp.any(fo != fn, axis=1)
    return mask, (fn - fo)


def _dense_compute_dtype(dtype, op: str):
    """Dtype the dense merge maths run in: integers stay integers for
    the exact ops, f32/f64 leaves keep their own precision, and only
    low-precision floats (bf16/f16) promote to f32."""
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.integer):
        if op in ("sum", "subtract", "overwrite"):
            return dtype
        return jnp.float32
    if dtype in (jnp.float32, jnp.float64):
        return dtype
    return jnp.float32


def dense_merge(main, mask, payload, op: str = "sum"):
    """Merge a dense-mask diff into ``main`` (jit-able path).

    payload semantics: for op in {sum, subtract}: payload = B1 - B0;
    for overwrite: payload = B1; multiply/divide: payload = B1 / B0.
    The maths run in a dtype derived from the *leaf* dtype
    (``_dense_compute_dtype``): integer leaves merge exactly for
    sum/subtract/overwrite and f64 leaves keep full precision — the old
    blanket float32 cast silently corrupted both."""
    cdt = _dense_compute_dtype(main.dtype, op)
    flat = jnp.ravel(main)
    pad = (-flat.size) % CHUNK
    fm = jnp.pad(flat, (0, pad)).reshape(-1, CHUNK).astype(cdt)
    p = payload.astype(cdt)
    if op == "sum":
        merged = fm + p
    elif op == "subtract":
        merged = fm - (-p)  # A1 = A0 - (B0 - B1) = A0 + (B1 - B0)
    elif op == "multiply":
        merged = fm * p
    elif op == "divide":
        merged = fm / jnp.where(p == 0, jnp.asarray(1.0, cdt), p)
    elif op == "overwrite":
        merged = p
    else:
        raise ValueError(op)
    out = jnp.where(mask[:, None], merged, fm)
    return out.reshape(-1)[: flat.size].reshape(main.shape).astype(main.dtype)


# ---------------------------------------------------------------------------
# Reference implementations (pre-vectorization, pinned by the parity
# suite in tests/test_diffsync.py — do not "optimise" these)
# ---------------------------------------------------------------------------
def reference_merge_scalarwise(a0, b0, b1, op: str):
    """Pre-PR ``merge_scalarwise``: float64 round-trip for every dtype."""
    if op == "overwrite":
        return np.asarray(b1, dtype=np.asarray(a0).dtype)
    a0d, b0d, b1d = _as_f64(a0), _as_f64(b0), _as_f64(b1)
    if op == "sum":
        out = a0d + (b1d - b0d)
    elif op == "subtract":
        out = a0d - (b0d - b1d)
    elif op == "multiply":
        with np.errstate(divide="ignore", invalid="ignore"):
            out = np.where(b0d == 0, a0d, a0d * (b1d / b0d))
    elif op == "divide":
        with np.errstate(divide="ignore", invalid="ignore"):
            out = np.where(b1d == 0, a0d, a0d / (b0d / b1d))
    else:
        raise ValueError(op)
    return out.astype(np.asarray(a0).dtype)


def _chunk_view(a: np.ndarray) -> np.ndarray:
    flat = np.ravel(a)
    pad = (-flat.size) % CHUNK
    if pad:
        flat = np.pad(flat, (0, pad))
    return flat.reshape(-1, CHUNK)


def reference_diff_leaf(old: np.ndarray, new: np.ndarray,
                        op: str = "overwrite") -> LeafDiff:
    """Pre-PR ``diff_leaf``: full pad copy + per-leaf chunk view."""
    assert old.shape == new.shape and old.dtype == new.dtype
    oc, nc = _chunk_view(old), _chunk_view(new)
    dirty = np.any(oc != nc, axis=1)
    idx = np.nonzero(dirty)[0].astype(np.int32)
    return LeafDiff(idx=idx, new=nc[idx].copy(), old=oc[idx].copy(),
                    shape=old.shape, dtype=old.dtype, op=op)


def reference_apply_leaf(main: np.ndarray, d: LeafDiff) -> np.ndarray:
    """Pre-PR ``apply_leaf``: full chunk-view copy of clean chunks."""
    mc = _chunk_view(main).copy()
    mc[d.idx] = reference_merge_scalarwise(mc[d.idx], d.old, d.new, d.op)
    return mc.reshape(-1)[: main.size].reshape(main.shape).astype(main.dtype)


def reference_apply_tree(main_tree, diffs: Dict[str, Any]):
    """Pre-PR ``apply_tree``: every leaf re-materialised."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(main_tree)
    out = []
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if key in diffs:
            out.append(reference_apply_leaf(np.asarray(leaf), diffs[key]))
        else:
            out.append(np.asarray(leaf))
    return jax.tree_util.tree_unflatten(treedef, out)
