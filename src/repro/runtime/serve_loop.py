"""Batched serving runtime: continuous prefill + decode with KV caches.

Requests carry a prompt; the runtime decodes one token per step for every
in-flight request.  Serving gangs are Granule groups like training gangs:
attach a ``core.fabric.GangHandle`` and the replica's **serving state** —
params + decode caches + next-token cursor — lives replicated on the
gang's mesh.  That state is the snapshot, so migration, preemption and
bit-exact resume work identically to training (a KV cache is just more
shared state to diff — paper §4 applies unchanged).  Each decode step is
a barrier control point: ``decode_step`` returns between tokens, so a
driver can interleave several gangs on one fabric and move this one
mid-generation.

Two engines share the Request/ServeStats types:

* ``ServeLoop`` — the fixed-batch baseline: one equal-length batch,
  admitted together, drained to the slowest request before the next
  batch may start.
* ``ContinuousServeLoop`` — iteration-level (continuous) batching over a
  fixed-capacity **slot array**: static shapes (no jit recompiles, one
  prefill compile per power-of-two prompt bucket), an active-slot mask
  with per-slot cursors/positions, and ragged prompts.  A finished
  request frees its slot immediately; a queued request prefills into a
  free slot *mid-generation* — its prefill state is spliced into the
  slot's lane of the decode buffers while the other lanes keep
  decoding.  Snapshots carry the slot occupancy, so a partially-filled
  batch migrates / preempts / resumes bit-exactly.

Lane independence caveat: every decode op is per-lane *except* MoE
capacity-factor routing, where expert capacity couples the batch — token
streams then depend on batch composition in either engine (the same
reason ``test_decode_consistency`` pins MoE parity with a no-drop
capacity factor).  Determinism and bit-exact resume hold regardless: the
snapshot carries the exact lane contents, garbage included.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import MAMBA, MLSTM, SLSTM, ArchConfig
from repro.core import telemetry
from repro.core.fabric import GangHandle
from repro.models import model as model_mod
from repro.models import transformer as tf


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (prompt_len,) int32
    max_new_tokens: int = 16
    out: List[int] = dataclasses.field(default_factory=list)
    priority: int = 0               # admission class (0 = highest)
    arrival: float = 0.0            # open-loop arrival time (virtual s)
    t_admit: Optional[float] = None  # when a slot/batch accepted it
    t_first: Optional[float] = None  # first decoded token emitted
    t_done: Optional[float] = None   # last token emitted (slot freed)
    t_queued: Optional[float] = None  # host clock (perf_counter) at queue push


@dataclasses.dataclass
class ServeStats:
    prefill_tokens: int = 0
    decoded_tokens: int = 0
    steps: int = 0
    admitted: int = 0
    finished: int = 0


class ServeLoop:
    """Fixed-batch serving of equal-length prompts (greedy decoding)."""

    def __init__(self, cfg: ArchConfig, params, max_len: int = 256,
                 window: int = 0, handle: Optional[GangHandle] = None):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.window = window
        self.handle: Optional[GangHandle] = None
        self._prefill = jax.jit(model_mod.make_prefill_step(cfg,
                                                            window=window))
        self._serve = jax.jit(model_mod.make_serve_step(cfg, window=window))
        self.stats = ServeStats()
        # in-flight decode batch (None when idle)
        self._reqs: Optional[List[Request]] = None
        self._states = None
        self._cur = None
        self._plen = 0
        self._t = 0
        self._max_new = 0
        if handle is not None:
            self.attach(handle)

    # ---- gang placement ----------------------------------------------------
    def attach(self, handle: GangHandle,
               state: Optional[Dict[str, Any]] = None) -> None:
        """Run this replica as a gang on a shared fabric: place params
        (and any in-flight decode state) replicated on the gang mesh.
        Re-attach after a migrate/rescale/resume to follow the new
        placement; ``state`` adopts a restored/resharded serving state in
        the same move."""
        self.handle = handle
        if state is not None:
            self.load_serve_state(state)
        else:
            self._place()

    def _replicated(self, tree):
        if self.handle is None or self.handle.mesh is None:
            return tree
        s = NamedSharding(self.handle.mesh, P())
        return jax.tree.map(lambda x: jax.device_put(x, s), tree)

    def _place(self) -> None:
        self.params = self._replicated(self.params)
        if self._reqs is not None:
            self._states = self._replicated(self._states)
            self._cur = self._replicated(self._cur)

    # ---- serving state = the snapshot (migration/preemption unit) ----------
    def serve_state(self) -> Dict[str, Any]:
        """Pytree capturing the replica mid-generation: params + decode
        caches + cursor, plus the host-side request bookkeeping — so the
        snapshot restores into a *fresh* ServeLoop, not just this one."""
        st: Dict[str, Any] = {"params": self.params}
        if self._reqs is not None:
            st["states"] = self._states
            st["cur"] = self._cur
            # int32 throughout: snapshot restore device_puts every leaf,
            # and with x64 disabled an int64 leaf would silently downcast
            # — breaking the bit-exact resume fingerprint
            st["decode"] = {
                "meta": np.asarray([self._plen, self._t, self._max_new],
                                   np.int32),
                "rids": np.asarray([r.rid for r in self._reqs], np.int32),
                "prompts": [np.asarray(r.prompt, np.int32)
                            for r in self._reqs],
                "max_new": np.asarray([r.max_new_tokens
                                       for r in self._reqs], np.int32),
                "outs": [np.asarray(r.out, np.int32) for r in self._reqs],
            }
        return st

    def load_serve_state(self, st: Dict[str, Any]) -> None:
        """Adopt a (restored or resharded) serving state; generation
        continues exactly where the snapshot was taken.  When this loop
        has no in-flight batch (fresh process / driver), the snapshot's
        request bookkeeping rebuilds it; an already-live batch keeps its
        own Request objects (same generation, callers hold references)."""
        self.params = st["params"]
        if "states" in st:
            self._states = st["states"]
            self._cur = st["cur"]
            dec = st.get("decode")
            if dec is not None:
                plen, t, max_new = (int(x) for x in np.asarray(dec["meta"]))
                self._plen, self._t, self._max_new = plen, t, max_new
                if self._reqs is None:
                    self._reqs = [
                        Request(rid=int(rid),
                                prompt=np.asarray(p, np.int32),
                                max_new_tokens=int(mn),
                                out=[int(x) for x in np.asarray(o)])
                        for rid, p, mn, o in zip(dec["rids"],
                                                 dec["prompts"],
                                                 dec["max_new"],
                                                 dec["outs"])]
        self._place()

    def _pad_states(self, states, prompt_len: int):
        """Grow prefill KV caches to max_len-sized decode buffers.

        Which leaves are seq-sized is decided against the
        ``init_decode_state`` template shapes, not a dimension
        heuristic — a recurrent state whose head axis happens to equal
        the prompt length must not be padded."""
        size = min(self.max_len, self.window) if self.window else self.max_len
        batch = jax.tree.leaves(states)[0].shape[1]
        template = jax.eval_shape(
            lambda: tf.init_decode_state(self.cfg, batch, self.max_len,
                                         self.cfg.param_dtype(),
                                         window=self.window))

        def pad(x, t):
            if x.shape == t.shape:
                return x
            if size <= x.shape[2]:
                return x[:, :, -size:]
            pad_spec = [(0, 0)] * x.ndim
            pad_spec[2] = (0, size - x.shape[2])
            return jnp.pad(x, pad_spec)
        return [jax.tree.map(pad, s, t) for s, t in zip(states, template)]

    # ---- decode lifecycle --------------------------------------------------
    def start(self, requests: Sequence[Request],
              extras: Optional[Dict[str, Any]] = None) -> None:
        """Admit + prefill a batch; decoding proceeds via decode_step."""
        reqs = list(requests)
        b = len(reqs)
        plen = len(reqs[0].prompt)
        assert all(len(r.prompt) == plen for r in reqs), "equal-length batch"
        tokens = jnp.asarray(np.stack([r.prompt for r in reqs]), jnp.int32)
        batch = self._replicated({"tokens": tokens, **(extras or {})})
        last_logits, states = self._prefill(self.params, batch)
        self.stats.prefill_tokens += b * plen
        self._reqs = reqs
        self._states = self._pad_states(states, plen)
        self._cur = jnp.argmax(last_logits[:, 0], axis=-1).astype(jnp.int32)
        self._plen = plen
        self._t = 0
        self._max_new = max(r.max_new_tokens for r in reqs)
        self._place()

    @property
    def done(self) -> bool:
        return self._reqs is None or self._t >= self._max_new

    def decode_step(self) -> bool:
        """One token for the whole batch; returns True while decoding.
        The step boundary is this gang's control point — between calls
        the replica may be migrated or snapshotted."""
        if self.done:
            return False
        reqs, t, b = self._reqs, self._t, len(self._reqs)
        live = 0
        for i, r in enumerate(reqs):
            if t < r.max_new_tokens:
                r.out.append(int(self._cur[i]))
                live += 1
        pos = jnp.full((b, 1), self._plen + t, jnp.int32)
        logits, self._states = self._serve(self.params, self._states,
                                           self._cur[:, None], pos)
        self._cur = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        # only requests still below their own max_new_tokens produced a
        # useful token this step — the lanes decoding past their budget
        # are pure fixed-batch overhead and must not inflate throughput
        self.stats.decoded_tokens += live
        self.stats.steps += 1
        self._t += 1
        if self.done:
            # drop the drained batch AND its device state — idle decode
            # buffers would otherwise pin device memory on a shared fabric
            self._reqs = None
            self._states = None
            self._cur = None
            return False
        return True

    def run(self, requests: Sequence[Request],
            extras: Optional[Dict[str, Any]] = None) -> List[Request]:
        reqs = list(requests)
        self.start(reqs, extras=extras)
        while self.decode_step():
            pass
        return reqs


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------
def _bucket(n: int, lo: int = 8) -> int:
    """Next power-of-two >= n (min ``lo``): bounds prefill compiles."""
    b = lo
    while b < n:
        b *= 2
    return b


def make_ragged_prefill(cfg: ArchConfig, window: int = 0):
    """(params, batch, length) -> (last_logits (B,1,V), decode states).

    Like ``model.make_prefill_step`` but the prompt may be right-padded
    to a static bucket: logits come from the *true* last position
    (``length - 1``, a traced scalar) rather than the padded one.  Safe
    for attention-family states because ``decode_attention`` masks
    ``j <= pos`` per lane and every padded cache row is overwritten by a
    decode write before it first becomes attendable; recurrent blocks
    must be fed exact-length prompts (see ContinuousServeLoop)."""
    def prefill(params, batch, length):
        ctx = model_mod._ctx_from_batch(cfg, batch, collect_state=True,
                                        window=window, return_hidden=True)
        hidden, _, states = tf.forward(params, batch["tokens"], cfg, ctx)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        last = jax.lax.dynamic_slice_in_dim(hidden, length - 1, 1, axis=1)
        logits = jax.lax.dot_general(
            last, head, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return logits, states
    return prefill


class ContinuousServeLoop:
    """Iteration-level batching over a fixed-capacity slot array.

    ``slots`` lanes share one set of static-shape decode buffers
    (``tf.init_decode_state`` with batch = slots).  ``admit`` prefills
    one ragged prompt (bucketed to a power of two) and splices the
    resulting per-lane state into a free slot — mid-generation, while
    other lanes keep decoding.  ``decode_step`` advances every occupied
    lane one token with per-slot positions; a lane reaching its own
    ``max_new_tokens`` frees its slot immediately.  Inactive lanes carry
    stale garbage by design: every batched op is lane-independent and a
    splice rewrites the whole lane, so garbage never leaks into live
    requests (and the engine stays deterministic for bit-exact resume).

    The snapshot (``serve_state``) is params + buffers + cursor + the
    full slot bookkeeping (occupancy mask, per-slot cursors, ragged
    prompts, partial outputs, finished rids) — restoring into a fresh
    loop resumes a partially-occupied batch exactly.
    """

    def __init__(self, cfg: ArchConfig, params, slots: int = 4,
                 max_len: int = 256, window: int = 0,
                 handle: Optional[GangHandle] = None):
        self.cfg = cfg
        self.params = params
        self.slots = int(slots)
        self.max_len = max_len
        self.window = window
        self.handle: Optional[GangHandle] = None
        self.stats = ServeStats()
        self._size = min(max_len, window) if window else max_len
        # recurrent state is a running reduction over the prompt — a
        # right-padded prefill would fold pad tokens into it, so those
        # configs prefill at exact length (one compile per length)
        self._exact_prefill = any(k in (MAMBA, MLSTM, SLSTM)
                                  for k in cfg.period())
        self._serve = jax.jit(model_mod.make_serve_step(cfg, window=window))
        self._admit_fns: Dict[int, Any] = {}   # prompt bucket -> jitted fn
        # host-side slot bookkeeping (rides in the snapshot)
        self._reqs: List[Optional[Request]] = [None] * self.slots
        self._plen = np.zeros(self.slots, np.int32)
        self._t = np.zeros(self.slots, np.int32)
        self._max_new = np.zeros(self.slots, np.int32)
        self._done_rids: List[int] = []
        # device-side slot state (lazy until the first admit)
        self._states = None
        self._cur = None
        if handle is not None:
            self.attach(handle)

    # ---- gang placement ----------------------------------------------------
    def attach(self, handle: GangHandle,
               state: Optional[Dict[str, Any]] = None) -> None:
        """Follow a (new) gang placement; ``state`` adopts a restored /
        resharded serving state in the same move (see ServeLoop)."""
        self.handle = handle
        if state is not None:
            self.load_serve_state(state)
        else:
            self._place()

    def _replicated(self, tree):
        if self.handle is None or self.handle.mesh is None:
            return tree
        s = NamedSharding(self.handle.mesh, P())
        return jax.tree.map(lambda x: jax.device_put(x, s), tree)

    def _place(self) -> None:
        self.params = self._replicated(self.params)
        if self._states is not None:
            self._states = self._replicated(self._states)
            self._cur = self._replicated(self._cur)

    # ---- slot accounting ---------------------------------------------------
    @property
    def active(self) -> int:
        return sum(1 for r in self._reqs if r is not None)

    @property
    def free_slots(self) -> int:
        return self.slots - self.active

    @property
    def done(self) -> bool:
        return self.active == 0

    def occupied_rids(self) -> List[int]:
        return [r.rid for r in self._reqs if r is not None]

    @property
    def done_rids(self) -> List[int]:
        return list(self._done_rids)

    def _occ(self) -> np.ndarray:
        return np.asarray([r is not None for r in self._reqs], bool)

    def _ensure_states(self) -> None:
        if self._states is None:
            self._states = self._replicated(tf.init_decode_state(
                self.cfg, self.slots, self.max_len,
                self.cfg.param_dtype(), window=self.window))
            self._cur = self._replicated(
                jnp.zeros((self.slots,), jnp.int32))

    # ---- admission: ragged prefill spliced into one lane -------------------
    def bucket(self, plen: int) -> int:
        """Static prefill length a ``plen``-token prompt runs at: its
        power-of-two bucket, or ``plen`` itself for recurrent configs."""
        return plen if self._exact_prefill else min(self._size, _bucket(plen))

    def lower(self, bucket: Optional[int] = None):
        """The program ``admit`` runs for prompt ``bucket`` — or, with
        None, the one ``decode_step`` runs — lowered for the current
        placement without compiling; ``.as_text()`` shows, e.g., which
        kernels are on the path."""
        self._ensure_states()
        if bucket is None:
            pos = jnp.zeros((self.slots, 1), jnp.int32)
            return self._serve.lower(self.params, self._states,
                                     self._cur[:, None], pos)
        batch = self._replicated({"tokens": jnp.zeros((1, bucket),
                                                      jnp.int32)})
        return self._admit_fn(bucket).lower(
            self.params, self._states, self._cur, batch, jnp.int32(1),
            jnp.int32(0))

    def _admit_fn(self, bucket: int):
        fn = self._admit_fns.get(bucket)
        if fn is not None:
            return fn
        prefill = make_ragged_prefill(self.cfg, self.window)

        def admit(params, states, cur, batch, length, slot):
            logits, pre = prefill(params, batch, length)

            def splice(big, row):
                row = row[:, 0]                 # drop the batch-1 axis
                if big.ndim == 5 and row.shape[1] != big.shape[2]:
                    # KV-style leaf (P, B, S, kv, hd): grow the bucket-
                    # sized prefill cache to the lane's full buffer
                    pad = [(0, 0)] * row.ndim
                    pad[1] = (0, big.shape[2] - row.shape[1])
                    row = jnp.pad(row, pad)
                return big.at[:, slot].set(row.astype(big.dtype))

            new_states = jax.tree.map(splice, states, pre)
            tok = jnp.argmax(logits[0, 0], axis=-1).astype(jnp.int32)
            return new_states, cur.at[slot].set(tok)

        fn = jax.jit(admit)
        self._admit_fns[bucket] = fn
        return fn

    def admit(self, req: Request, now: Optional[float] = None,
              extras: Optional[Dict[str, Any]] = None) -> Optional[int]:
        """Prefill ``req`` into a free slot; returns the slot index or
        None when the batch is full.  Runs between decode steps — the
        other lanes' in-flight state is untouched.

        Spans: ``serve.admit`` (rid, plen, bucket, slot, and queued_us,
        the host time since the queue's push), around
        ``serve.admit.prepare`` (padding and the host-to-device
        transfers) and ``serve.admit.dispatch`` (the jitted admit)."""
        slot = next((i for i in range(self.slots)
                     if self._reqs[i] is None), None)
        if slot is None:
            return None
        prompt = np.asarray(req.prompt, np.int32)
        plen = len(prompt)
        assert 0 < plen <= self._size, \
            f"prompt ({plen}) must fit the decode buffer ({self._size})"
        bucket = self.bucket(plen)
        tel = telemetry.get()
        attrs = {}
        if telemetry.active():
            attrs = {"rid": int(req.rid), "plen": plen, "bucket": bucket,
                     "slot": slot}
            if req.t_queued is not None:
                attrs["queued_us"] = (time.perf_counter()
                                      - req.t_queued) * 1e6
        with tel.span("serve.admit", track="serve", **attrs):
            with tel.span("serve.admit.prepare", track="serve"):
                self._ensure_states()
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :plen] = prompt
                batch = self._replicated({"tokens": jnp.asarray(tokens),
                                          **(extras or {})})
                length, lane = jnp.int32(plen), jnp.int32(slot)
            with tel.span("serve.admit.dispatch", track="serve"):
                fn = self._admit_fn(bucket)
                self._states, self._cur = fn(self.params, self._states,
                                             self._cur, batch, length, lane)
            self._reqs[slot] = req
            self._plen[slot] = plen
            self._t[slot] = 0
            self._max_new[slot] = req.max_new_tokens
        self.stats.prefill_tokens += plen
        self.stats.admitted += 1
        if now is not None:
            req.t_admit = now
        if tel.enabled:
            tel.count("serve.admitted")
        return slot

    def _free(self, slot: int) -> None:
        req = self._reqs[slot]
        if req is not None:
            self._done_rids.append(req.rid)
        self._reqs[slot] = None
        self._plen[slot] = 0
        self._t[slot] = 0
        self._max_new[slot] = 0
        self.stats.finished += 1

    # ---- decode ------------------------------------------------------------
    def decode_step(self, now: Optional[float] = None) -> int:
        """One token for every occupied slot; returns how many lanes
        decoded.  The step boundary is the gang's control point.

        Spans: ``serve.decode_step`` (lanes; ctx_tokens, the context the
        lanes' attention covers; kv_positions, the positions it reads),
        around ``serve.decode.sync`` (the fetch of the previous step's
        tokens), ``serve.decode.dispatch`` (positions and the step
        program) and ``serve.decode.select`` (the argmax and the lane
        bookkeeping).  Between the end of the sync and the end of the
        dispatch the device has nothing queued."""
        act = [i for i in range(self.slots) if self._reqs[i] is not None]
        if not act:
            return 0
        tel = telemetry.get()
        attrs = {}
        if telemetry.active():
            attrs = {"lanes": len(act),
                     "ctx_tokens": int(np.sum(self._plen[act] + self._t[act],
                                              dtype=np.int64)) + len(act),
                     "kv_positions": self.slots * self._size}
        with tel.span("serve.decode_step", track="serve", **attrs):
            with tel.span("serve.decode.sync", track="serve"):
                cur = np.asarray(self._cur)
            for i in act:
                r = self._reqs[i]
                if not r.out and now is not None:
                    r.t_first = now
                r.out.append(int(cur[i]))
            with tel.span("serve.decode.dispatch", track="serve"):
                pos = np.where(self._occ(), self._plen + self._t, 0)
                pos = jnp.asarray(pos[:, None].astype(np.int32))
                logits, self._states = self._serve(
                    self.params, self._states, self._cur[:, None], pos)
            with tel.span("serve.decode.select", track="serve"):
                self._cur = jnp.argmax(logits[:, 0],
                                       axis=-1).astype(jnp.int32)
                for i in act:
                    self._t[i] += 1
                    if self._t[i] >= self._max_new[i]:
                        if now is not None:
                            self._reqs[i].t_done = now
                        self._free(i)
        if tel.enabled:
            tel.count("serve.decoded_tokens", len(act))
        self.stats.decoded_tokens += len(act)
        self.stats.steps += 1
        return len(act)

    def run(self, requests: Sequence[Request]) -> List[Request]:
        """Closed-loop convenience: admit as capacity allows, decode to
        empty.  Open-loop drivers call admit/decode_step directly."""
        pending = list(requests)
        while pending or not self.done:
            while pending and self.admit(pending[0]) is not None:
                pending.pop(0)
            self.decode_step()
        return list(requests)

    # ---- serving state = the snapshot --------------------------------------
    def serve_state(self) -> Dict[str, Any]:
        st: Dict[str, Any] = {"params": self.params}
        if self._states is not None:
            occ = self._occ()
            st["states"] = self._states
            st["cur"] = self._cur
            # int32 bookkeeping: restore device_puts every leaf, and with
            # x64 disabled int64 would downcast and break the bit-exact
            # resume fingerprint
            st["slots"] = {
                "occ": occ.astype(np.int32),
                "plen": self._plen.copy(),
                "t": self._t.copy(),
                "max_new": self._max_new.copy(),
                "rids": np.asarray([r.rid if r is not None else -1
                                    for r in self._reqs], np.int32),
                "prompts": [np.asarray(r.prompt, np.int32) if r is not None
                            else np.zeros(0, np.int32)
                            for r in self._reqs],
                "outs": [np.asarray(r.out, np.int32) if r is not None
                         else np.zeros(0, np.int32) for r in self._reqs],
                "done_rids": np.asarray(self._done_rids, np.int32),
            }
        return st

    def load_serve_state(self, st: Dict[str, Any]) -> None:
        """Adopt a snapshot: device buffers verbatim plus the slot
        bookkeeping, reconstructing Request objects for every occupied
        lane.  Callers that own the original Request objects re-link
        them with ``adopt_requests`` (rolling their outputs back to the
        snapshot point — a restore after a hard fail must not keep
        post-checkpoint tokens)."""
        self.params = st["params"]
        if "states" not in st:
            # params-only snapshot (taken before the first admit): a
            # rollback to it restarts from an empty slot array — stale
            # in-flight lanes must not survive the restore
            self._states = None
            self._cur = None
            self._reqs = [None] * self.slots
            self._plen[:] = 0
            self._t[:] = 0
            self._max_new[:] = 0
            self._done_rids = []
        else:
            self._states = st["states"]
            self._cur = st["cur"]
            sl = st["slots"]
            occ = np.asarray(sl["occ"]).astype(bool)
            self._plen = np.asarray(sl["plen"]).copy()
            self._t = np.asarray(sl["t"]).copy()
            self._max_new = np.asarray(sl["max_new"]).copy()
            self._done_rids = [int(x) for x in np.asarray(sl["done_rids"])]
            self._reqs = [
                Request(rid=int(sl["rids"][i]),
                        prompt=np.asarray(sl["prompts"][i], np.int32),
                        max_new_tokens=int(sl["max_new"][i]),
                        out=[int(x) for x in np.asarray(sl["outs"][i])])
                if occ[i] else None
                for i in range(self.slots)]
        self._place()

    def adopt_requests(self, requests: Sequence[Request]) -> None:
        """Re-link caller-owned Request objects (matched by rid) into
        the freshly-restored slots, truncating their ``out`` lists to
        the snapshot's decoded prefix so generation resumes exactly."""
        by_rid = {r.rid: r for r in requests}
        for i, snap_req in enumerate(self._reqs):
            if snap_req is None:
                continue
            mine = by_rid.get(snap_req.rid)
            if mine is not None:
                mine.out[:] = list(snap_req.out)
                self._reqs[i] = mine
