"""Locality-aware collectives (paper §5.3, Fig 9) as shard_map programs.

Faabric's VM-leader all-reduce sends one message per remote VM per step and
uses fast in-memory queues within a VM.  The TPU mapping: the **pod** is the
VM (slow DCI/DCN links between pods ↔ cross-VM network), the intra-pod ICI
is the in-memory queue.  The two-level schedule becomes:

    reduce-scatter over the fast (intra-pod) axis      [each chip owns 1/n]
    all-reduce over the slow (cross-pod) axis          [shard-sized traffic]
    all-gather over the fast axis                      [redistribute]

which moves ``bytes/n_fast`` over the slow link instead of ``bytes`` —
the generalisation of "one leader message per VM".  An optional top-k
delta compression (``optim.compress``) shrinks the slow hop further
(beyond-paper, DESIGN.md §5).

All functions here are *per-device* (inside shard_map).  ``build_*`` helpers
wrap them in shard_map over a mesh for direct use.
"""
from __future__ import annotations

import re
import time as _time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import comms, telemetry
from repro.kernels.collective_codec import ops as codec_ops


# ---------------------------------------------------------------------------
# Pytree <-> padded flat vector (gradient bucketing)
# ---------------------------------------------------------------------------
# flatten spec cached per (treedef, leaf layout, pad_to): a gang syncs
# the same tree structure every step, so the spec derivation (a Python
# walk over every leaf) runs once per structure, not once per trace
_SPEC_CACHE: Dict[Tuple, Tuple] = {}


def flatten_spec(tree, pad_to: int = 1):
    """(spec, pad) for ``flatten_tree``/``unflatten_tree`` of ``tree``,
    cached per tree structure."""
    leaves, treedef = jax.tree.flatten(tree)
    key = (treedef, tuple((tuple(l.shape), str(jnp.dtype(l.dtype)))
                          for l in leaves), pad_to)
    hit = _SPEC_CACHE.get(key)
    if hit is None:
        sizes = [int(l.size) for l in leaves]
        pad = (-sum(sizes)) % pad_to
        hit = ((treedef, sizes, [l.shape for l in leaves],
                [l.dtype for l in leaves]), pad)
        _SPEC_CACHE[key] = hit
    return hit


def flatten_tree(tree, pad_to: int = 1):
    """Concatenate all leaves into one f32 vector, padded to a multiple of
    ``pad_to`` (bucketing: one collective for the whole tree)."""
    spec, pad = flatten_spec(tree, pad_to)
    leaves = jax.tree.leaves(tree)
    vec = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
    if pad:
        vec = jnp.pad(vec, (0, pad))
    return vec, spec


def unflatten_tree(vec, spec):
    treedef, sizes, shapes, dtypes = spec
    cuts = np.cumsum(sizes)
    # one split instead of a per-leaf slice loop
    parts = jnp.split(vec[:int(cuts[-1])], cuts[:-1].tolist())
    return jax.tree.unflatten(
        treedef, [p.reshape(shp).astype(dt)
                  for p, shp, dt in zip(parts, shapes, dtypes)])


# ---------------------------------------------------------------------------
# Per-device collective bodies (call inside shard_map)
# ---------------------------------------------------------------------------
def hierarchical_psum(vec, fast_axis: str, slow_axis: Optional[str]):
    """Two-level all-reduce of a flat vector (paper Fig 9 schedule)."""
    vec = jax.lax.psum_scatter(vec, fast_axis, scatter_dimension=0,
                               tiled=True)
    if slow_axis is not None:
        vec = jax.lax.psum(vec, slow_axis)
    return jax.lax.all_gather(vec, fast_axis, axis=0, tiled=True)


def flat_psum(vec, axes: Sequence[str]):
    """Single flat all-reduce over all axes (the baseline schedule)."""
    return jax.lax.psum(vec, tuple(axes))


def reference_topk_select(vec, frac: float):
    """The pre-tuner codec: a *global* ``top_k`` over the whole shard —
    an O(n log n) sort that cost more than the slow link saved (ROADMAP
    item 5).  Kept as the measured reference the chunk-select codec
    must beat (``bench_message_passing`` times both)."""
    k = max(1, int(vec.size * frac))
    mag = jnp.abs(vec)
    _, idx = jax.lax.top_k(mag, k)
    sel = vec[idx]
    residual = vec.at[idx].set(0.0)
    return sel, idx, residual


def compressed_hierarchical_psum(vec, fast_axis: str, slow_axis: str,
                                 frac: float, resid_shard=None):
    """Two-level all-reduce with threshold-select delta compression on
    the slow hop.

    After the intra-pod reduce-scatter, each chip owns a disjoint shard.
    The shard is chunked and each chunk ships only its largest-magnitude
    element across the pod boundary — a fixed-size sparse (idx, val)
    message, ``frac`` of the shard (merge-op = sum on sparse diffs, the
    paper's byte-wise-diff protocol generalised to sparse deltas).  The
    codec is the vectorized ``kernels/collective_codec`` chunk-select —
    one O(n) streaming pass, not the old global ``top_k`` sort.  The
    unselected remainder stays local as an error-feedback residual
    (``resid_shard``) added to the next step's shard, preserving
    convergence; with ``frac=1.0`` the chunk width degenerates to 1 and
    the result is bit-exact to ``hierarchical_psum``.
    """
    shard = jax.lax.psum_scatter(vec, fast_axis, scatter_dimension=0,
                                 tiled=True)
    if resid_shard is not None:
        shard = shard + resid_shard
    sel, idx, residual = codec_ops.select_codec(shard, frac=float(frac))
    # ship only (idx, val) over the slow link; sum-merge on arrival
    all_sel = jax.lax.all_gather(sel, slow_axis, axis=0)       # (pods, k)
    all_idx = jax.lax.all_gather(idx, slow_axis, axis=0)
    merged = jnp.zeros_like(shard).at[all_idx.reshape(-1)].add(
        all_sel.reshape(-1))
    out = jax.lax.all_gather(merged, fast_axis, axis=0, tiled=True)
    return out, residual


def ring_allreduce(vec, axis: str):
    """Bandwidth-optimal ring all-reduce via explicit collective-permutes
    (2*(n-1) steps: reduce-scatter ring + all-gather ring).  This is the
    ppermute mapping of the paper's p2p messaging layer."""
    n = jax.lax.axis_size(axis)
    if n == 1:
        return vec
    me = jax.lax.axis_index(axis)
    chunks = vec.reshape(n, -1)
    perm_fwd = [(i, (i + 1) % n) for i in range(n)]

    def rs_step(c, chunks):
        # at step s, rank r sends chunk (r - s) mod n
        send_idx = (me - c) % n
        recv_idx = (me - c - 1) % n
        sent = jax.lax.ppermute(chunks[send_idx], axis, perm_fwd)
        return chunks.at[recv_idx].add(sent)

    for s in range(n - 1):
        chunks = rs_step(s, chunks)

    def ag_step(c, chunks):
        send_idx = (me - c + 1) % n
        recv_idx = (me - c) % n
        sent = jax.lax.ppermute(chunks[send_idx], axis, perm_fwd)
        return chunks.at[recv_idx].set(sent)

    for s in range(n - 1):
        chunks = ag_step(s, chunks)
    return chunks.reshape(vec.shape)


# ---------------------------------------------------------------------------
# Mesh-level wrappers
# ---------------------------------------------------------------------------
def dp_axes(mesh: Mesh) -> Tuple[str, Optional[str]]:
    """(fast_axis, slow_axis) for the data-parallel dimension of a mesh."""
    names = mesh.axis_names
    slow = "pod" if "pod" in names else None
    return "data", slow


def padded_size(tree, n_fast: int) -> int:
    total = sum(l.size for l in jax.tree.leaves(tree))
    return total + (-total) % n_fast


def init_residual_buffer(mesh: Mesh, tree, mode: str = "compressed"):
    """Zero error-feedback buffer: (n_pods, padded_flat_size) f32, sharded
    P('pod', 'data') so each chip holds its own scattered shard.  Other
    modes keep no residual: their placeholder is the (1, 1) per chip that
    a train step hands back, so the step's input shape never changes
    (one compile, not two)."""
    fast, slow = dp_axes(mesh)
    n_pods = mesh.shape[slow] if slow else 1
    n_total = n_pods * mesh.shape[fast]
    if mode != "compressed":
        return jnp.zeros((n_pods, mesh.shape[fast]), jnp.float32)
    return jnp.zeros((n_pods, padded_size(tree, n_total)), jnp.float32)


def tree_sync_body(tree, mode: str, fast: str, slow: Optional[str],
                   n_total: int, compress_frac: Optional[float] = None,
                   resid_shard=None):
    """Per-device gradient sync of a pytree (call inside shard_map).

    Returns (mean tree, new residual shard or None)."""
    vec, spec = flatten_tree(tree, pad_to=n_total)  # divisible by n_fast too
    if mode == "flat":
        out, resid = flat_psum(vec, [a for a in (fast, slow) if a]), None
    elif mode == "ring":
        out = ring_allreduce(vec, fast)
        if slow is not None:
            out = jax.lax.psum(out, slow)
        resid = None
    elif mode == "hierarchical":
        out, resid = hierarchical_psum(vec, fast, slow), None
    elif mode == "compressed":
        assert slow is not None and compress_frac is not None
        out, resid = compressed_hierarchical_psum(
            vec, fast, slow, compress_frac, resid_shard=resid_shard)
    else:
        raise ValueError(mode)
    return unflatten_tree(out / n_total, spec), resid


def build_tree_allreduce(mesh: Mesh, mode: str = "hierarchical",
                         compress_frac: Optional[float] = None) -> Callable:
    """Returns f(tree, resid) -> (tree_mean, new_resid): all-reduce-mean a
    tree whose leaves carry a leading device axis of size n_devices (one
    private copy per device).  ``resid`` is the (n_pods, n_pad) error
    feedback buffer for mode='compressed' (pass None otherwise)."""
    fast, slow = dp_axes(mesh)
    axes = [a for a in (fast, slow) if a is not None]
    n_total = int(np.prod([mesh.shape[a] for a in axes]))

    def per_device(tree, resid):
        rs = resid[0] if resid is not None else None
        out, new_rs = tree_sync_body(tree, mode, fast, slow, n_total,
                                     compress_frac, rs)
        return out, (new_rs[None] if new_rs is not None else None)

    # every device holds its own (different) copy: specs are fully sharded
    spec_in = P(tuple(a for a in (("pod",) if slow else ()) + (fast,)))
    resid_spec = P(slow, fast) if slow else None

    def allreduce(tree, resid=None):
        return jax.shard_map(per_device, mesh=mesh,
                             in_specs=(jax.tree.map(lambda _: spec_in, tree),
                                       resid_spec),
                             out_specs=(jax.tree.map(lambda _: spec_in, tree),
                                        (resid_spec if mode == "compressed"
                                         else None)),
                             check_vma=False)(tree, resid)

    return allreduce


_HLO_SIZES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
              "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8,
              "f8e4m3": 1, "f8e5m2": 1, "s16": 2, "u16": 2}
HLO_COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                        "all-to-all", "collective-permute")
# one collective *instruction definition* per match: the result shape is
# everything between '=' and the op name, which must be immediately
# followed by its operand list '('.  The lazy shape group accepts tuple
# shapes (with layout annotations, whose nested parens truncated the old
# single-level `\([^)]*\)` alternative), and requiring `kind(` stops
# fusion lines that merely *reference* a `%collective-permute.N` operand
# from being counted as collectives (they were, inflating ring schedules
# ~5x).
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<shape>.*?)\s*"
    r"(?P<kind>" + "|".join(HLO_COLLECTIVE_KINDS) + r")\((?P<rest>.*)$",
    re.M)
_HLO_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_HLO_GROUPS = re.compile(r"replica_groups=(\{[\d,{}]*\})")
_HLO_PAIRS = re.compile(r"source_target_pairs=\{([\d,{}]*)\}")


def _shape_bytes(shape_text: str) -> int:
    nbytes = 0
    for dt, dims in _HLO_SHAPE.findall(shape_text):
        if dt not in _HLO_SIZES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes += n * _HLO_SIZES[dt]
    return nbytes


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in an HLO dump — the
    ``collective term`` source for the roofline analysis."""
    out = {k: 0 for k in HLO_COLLECTIVE_KINDS}
    for m in _HLO_INSTR.finditer(hlo_text):
        out[m.group("kind")] += _shape_bytes(m.group("shape"))
    out["total"] = sum(out[k] for k in HLO_COLLECTIVE_KINDS)
    return out


def slowlink_bytes_from_hlo(hlo_text: str, pod_of: Sequence[int]) -> int:
    """Per-rank bytes a compiled schedule moves across the pod (slow
    link) boundary: the result bytes of every collective instruction
    whose replica group — or permute pair — spans pods.  This is the
    *measured* replacement for the old hardcoded analytical
    ``slowlink_bytes_*`` table in ``bench_message_passing``.

    ``pod_of`` maps device id -> pod id.  collective-permutes count
    only their crossing fraction of pairs (a fast-axis ring whose edges
    all stay inside one pod contributes zero)."""
    pod_of = list(pod_of)
    n_pods = len(set(pod_of))
    total = 0.0
    for m in _HLO_INSTR.finditer(hlo_text):
        nbytes = _shape_bytes(m.group("shape"))
        rest = m.group("rest")
        pm = _HLO_PAIRS.search(rest)
        if pm is not None:
            pairs = re.findall(r"\{(\d+),(\d+)\}", pm.group(1))
            if pairs:
                crossing = sum(pod_of[int(a)] != pod_of[int(b)]
                               for a, b in pairs)
                total += nbytes * crossing / len(pairs)
            continue
        gm = _HLO_GROUPS.search(rest)
        if gm is not None:
            groups = [[int(r) for r in g.split(",") if r]
                      for g in re.findall(r"\{([\d,]*)\}", gm.group(1))]
            groups = [g for g in groups if g]
            if groups:
                if any(len({pod_of[r] for r in g}) > 1 for g in groups):
                    total += nbytes
                continue
        # empty/unparseable groups mean "all devices": spans iff pods > 1
        if n_pods > 1:
            total += nbytes
    return int(total)


# ---------------------------------------------------------------------------
# Topology-tuned schedule dispatch (ROADMAP item 5, DESIGN.md §11)
# ---------------------------------------------------------------------------
def mesh_pod_of(mesh: Mesh) -> list:
    """device id -> pod index for a (pod, data) gang mesh (pod rows)."""
    devs = np.asarray(mesh.devices)
    if devs.ndim == 1:
        devs = devs[None]
    pod_of = {}
    for p, row in enumerate(devs):
        for d in np.ravel(row):
            pod_of[d.id] = p
    return [pod_of[i] for i in sorted(pod_of)]


def measure_schedule(mesh: Mesh, mode: str, nbytes: int,
                     compress_frac: float = 0.05, reps: int = 3,
                     link: Optional[comms.LinkProfile] = None,
                     emulate_slow: Optional[bool] = None) -> dict:
    """One-shot measured probe of one collective schedule.

    Times ``reps`` all-reduces of an ``nbytes`` tree on ``mesh`` and
    measures the schedule's slow-link bytes from its compiled HLO
    (``slowlink_bytes_from_hlo``).  When the fleet has no *real* slow
    link (the forced-host CPU fabric), ``emulate_slow`` adds the
    modeled slow-link transfer time — measured bytes over the profile's
    slow-link bandwidth — so schedules are compared under the topology
    they are tuned for.  Returns
    ``{"wall_s", "slowlink_bytes", "effective_s"}`` per all-reduce.
    """
    link = link or comms.LinkProfile()
    if emulate_slow is None:
        emulate_slow = jax.default_backend() == "cpu"
    n_dev = mesh.devices.size
    n = max(n_dev, int(nbytes) // 4)
    n += (-n) % n_dev
    tree = {"g": jnp.ones((n_dev, n // n_dev), jnp.float32)}
    fn = jax.jit(build_tree_allreduce(mesh, mode, compress_frac))
    resid = (init_residual_buffer(mesh, jax.tree.map(lambda x: x[0], tree))
             if mode == "compressed" else None)
    out, new_resid = fn(tree, resid)
    jax.block_until_ready(out)
    if new_resid is not None:
        # the fed-back residual is mesh-sharded while the initial one is
        # single-device; warm up the steady-state sharding so the timed
        # loop never recompiles
        resid = new_resid
        out, new_resid = fn(tree, resid)
        jax.block_until_ready(out)
    t0 = _time.perf_counter()
    for _ in range(reps):
        out, new_resid = fn(tree, resid)
        if new_resid is not None:
            resid = new_resid
    jax.block_until_ready(out)
    wall = (_time.perf_counter() - t0) / max(1, reps)
    hlo = fn.lower(tree, resid).compile().as_text()
    slow_b = slowlink_bytes_from_hlo(hlo, mesh_pod_of(mesh))
    eff = wall + (slow_b / link.slow_bps if emulate_slow else 0.0)
    return {"wall_s": wall, "slowlink_bytes": slow_b, "effective_s": eff}


class CollectiveTuner:
    """Per-(topology, message-size-bucket) collective schedule dispatch.

    The table maps ``(Topology.key, size_bucket)`` to the schedule the
    comms layer should run — flat / ring / hierarchical / compressed —
    seeded from the analytical cost model in ``core.comms`` (slow-link
    bytes x per-link bandwidth + per-step latency) and refined by
    one-shot measured probes (``probe``/``record_probe``), which
    overwrite the analytical estimate for the probed (topology, bucket,
    mode) and re-derive the dispatch entry.

    ``Fabric`` owns one; ``GangHandle`` re-derives a gang's entries
    after every placement change (attach / migrate / evacuate /
    rescale) via ``on_placement_change`` and drops them on release.
    """

    def __init__(self, link: Optional[comms.LinkProfile] = None,
                 compress_frac: float = 0.05,
                 modes: Sequence[str] = comms.MODES):
        self.link = link or comms.LinkProfile()
        self.compress_frac = float(compress_frac)
        self.modes = tuple(modes)
        # (topo.key, bucket) -> (mode, predicted seconds)
        self.table: Dict[Tuple[Tuple[int, int, int], int],
                         Tuple[str, float]] = {}
        # (topo.key, bucket) -> {mode: measured seconds} probe overrides
        self.measured: Dict[Tuple[Tuple[int, int, int], int],
                            Dict[str, float]] = {}
        self.gangs: Dict[str, comms.Topology] = {}
        self.rederivations = 0

    # ---- derivation --------------------------------------------------------
    def _derive(self, topo: comms.Topology, bucket: int,
                modes: Optional[Sequence[str]] = None
                ) -> Tuple[str, float]:
        entry = comms.best_schedule(
            topo, comms.bucket_nbytes(bucket), self.link,
            self.compress_frac, modes or self.modes,
            measured=self.measured.get((topo.key, bucket)))
        if modes is None:
            self.table[(topo.key, bucket)] = entry
        return entry

    def on_placement_change(self, job_id: str,
                            placement: Sequence[Tuple[int, int]]
                            ) -> comms.Topology:
        """Re-derive the dispatch entries for a gang whose placement
        just changed (attach / migrate / evacuate / rescale)."""
        tel = telemetry.get()
        t0 = _time.perf_counter() if tel.enabled else 0.0
        topo = comms.Topology.from_placement(placement)
        self.gangs[job_id] = topo
        self.rederivations += 1
        for b in range(comms.MIN_BUCKET, comms.MAX_BUCKET + 1):
            self._derive(topo, b)
        if tel.enabled:
            tel.count("collective.rederivations")
            tel.span_at("collective.rederive", t0, _time.perf_counter(),
                        track="collectives", clock="wall", job=job_id,
                        hosts=topo.hosts, chips=topo.chips)
        return topo

    def forget(self, job_id: str) -> None:
        self.gangs.pop(job_id, None)

    # ---- dispatch ----------------------------------------------------------
    def _topo(self, gang_or_placement) -> comms.Topology:
        if isinstance(gang_or_placement, comms.Topology):
            return gang_or_placement
        if isinstance(gang_or_placement, str):
            topo = self.gangs.get(gang_or_placement)
            return topo if topo is not None else comms.Topology(1, 1, 1)
        return comms.Topology.from_placement(gang_or_placement)

    def mode_for(self, gang_or_placement, nbytes: Optional[int] = None,
                 allowed: Optional[Sequence[str]] = None) -> str:
        """The schedule to run for one collective: dispatch-table
        lookup by (gang topology, size bucket), deriving on miss.
        ``allowed`` restricts the choice (a single-axis mesh cannot run
        the pod-level compressed/hierarchical schedules)."""
        topo = self._topo(gang_or_placement)
        bucket = comms.size_bucket(nbytes)
        if allowed is not None and set(allowed) != set(self.modes):
            mode = self._derive(topo, bucket, modes=tuple(allowed))[0]
        else:
            entry = self.table.get((topo.key, bucket))
            if entry is None:
                entry = self._derive(topo, bucket)
            mode = entry[0]
        tel = telemetry.get()
        if tel.enabled:
            tel.count(f"collective.dispatch.{mode}")
        return mode

    def predicted_time(self, gang_or_placement,
                       nbytes: Optional[int] = None) -> float:
        """Seconds for the dispatched (best) schedule — the quantity
        ``CostModel.collective_time`` prices placements with."""
        topo = self._topo(gang_or_placement)
        bucket = comms.size_bucket(nbytes)
        entry = self.table.get((topo.key, bucket))
        if entry is None:
            entry = self._derive(topo, bucket)
        return entry[1]

    # ---- measured refinement ----------------------------------------------
    def record_probe(self, gang_or_placement, nbytes: int, mode: str,
                     seconds: float) -> None:
        """Fold one measured (topology, bucket, mode) timing into the
        table: the measurement overrides the analytical estimate and
        the dispatch entry is re-derived."""
        topo = self._topo(gang_or_placement)
        bucket = comms.size_bucket(nbytes)
        self.measured.setdefault((topo.key, bucket), {})[mode] = \
            float(seconds)
        self._derive(topo, bucket)
        tel = telemetry.get()
        if tel.enabled:
            tel.count("collective.probes")
            tel.observe(f"collective.probe_s.{mode}", float(seconds))
            tel.instant("collective.probe", track="collectives",
                        mode=mode, bucket=bucket,
                        seconds=float(seconds))

    def probe(self, mesh: Mesh, nbytes: int = comms.DEFAULT_NBYTES,
              modes: Optional[Sequence[str]] = None, reps: int = 2
              ) -> Dict[str, float]:
        """Measure every available schedule once on ``mesh`` and refine
        the dispatch entry for its topology (expensive: compiles one
        program per mode — a one-shot calibration, not a hot path)."""
        devs = np.asarray(mesh.devices)
        pods = devs.shape[0] if devs.ndim > 1 else 1
        chips = devs.size
        topo = comms.Topology(pods, chips, max(1, chips // max(1, pods)))
        out: Dict[str, float] = {}
        for mode in (modes or self.modes):
            if mode == "compressed" and pods <= 1:
                continue
            m = measure_schedule(mesh, mode, nbytes,
                                 self.compress_frac, reps, self.link)
            out[mode] = m["effective_s"]
            self.record_probe(topo, nbytes, mode, m["effective_s"])
        return out
