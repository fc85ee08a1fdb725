"""Checkpointing built on Granule snapshots (paper §3.4's fault-tolerance
sketch, implemented for real).

* **Full checkpoints**: the job-state snapshot serialised to disk
  (one ``.npz`` per checkpoint + a JSON manifest with step/fingerprint).
* **Incremental checkpoints**: chunk-diffs against the last full snapshot
  (``core.diffsync``) — the paper's byte-wise diff protocol as a
  checkpoint-size optimisation.  Restore = full + replay of diffs.
* **Async save**: serialisation happens on a background thread so the
  training loop only blocks for the device->host copy.
"""
from __future__ import annotations

import json
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional

from repro.core import diffsync, snapshot as snap_mod, telemetry


class CheckpointManager:
    def __init__(self, directory: str, job_id: str = "job",
                 keep: int = 3, incremental_every: int = 0,
                 delta_chain: bool = False, rebase_every: int = 8):
        """``incremental_every``: if > 0, only every k-th checkpoint is
        full; the rest are diffs against the last full one.

        ``delta_chain``: write ``(base, delta*)`` chains instead — the
        first save (and every ``rebase_every``-th) is a full base, each
        save between diffs against the *previous save* (not the base),
        so per-save bytes track what the job dirtied since the last
        tick.  Restore replays the whole chain in order and verifies
        the recorded fingerprint (bit-exact or it raises).  Mutually
        exclusive with ``incremental_every``."""
        assert not (delta_chain and incremental_every), \
            "delta_chain and incremental_every are mutually exclusive"
        self.dir = directory
        self.job_id = job_id
        self.keep = keep
        self.incremental_every = incremental_every
        self.delta_chain = delta_chain
        self.rebase_every = max(1, int(rebase_every))
        os.makedirs(directory, exist_ok=True)
        self._last_full: Optional[snap_mod.Snapshot] = None
        self._chain_prev: Optional[snap_mod.Snapshot] = None
        self._chain_len = 0
        self._n_saved = 0
        self._pending: List[threading.Thread] = []
        self.stats: List[Dict[str, Any]] = []

    # ---- paths --------------------------------------------------------------
    def _path(self, step: int, kind: str) -> str:
        return os.path.join(self.dir, f"{self.job_id}-{step:08d}.{kind}")

    def _manifest_path(self) -> str:
        return os.path.join(self.dir, f"{self.job_id}-manifest.json")

    def _manifest(self) -> List[Dict[str, Any]]:
        try:
            with open(self._manifest_path()) as f:
                return json.load(f)
        except FileNotFoundError:
            return []

    def _write_manifest(self, entries) -> None:
        tmp = self._manifest_path() + ".tmp"
        with open(tmp, "w") as f:
            json.dump(entries, f, indent=1)
        os.replace(tmp, self._manifest_path())

    # ---- save ---------------------------------------------------------------
    def save(self, step: int, state, blocking: bool = True) -> Dict[str, Any]:
        """Checkpoint the state pytree at ``step``."""
        tel = telemetry.get()
        with tel.span("ckpt.save", track=f"gang:{self.job_id}",
                      step=step) as span:
            stat = self._save(step, state, blocking)
            span.set(kind=stat["kind"], bytes=stat["bytes"],
                     full_bytes=stat["full_bytes"])
        if tel.enabled:
            tel.count(f"ckpt.save.{stat['kind']}")
            tel.count("ckpt.save.bytes", stat["bytes"])
            tel.observe("ckpt.device_to_host_s", stat["device_to_host_s"])
            tel.gauge("ckpt.chain_len", self._chain_len)
        return stat

    def _save(self, step: int, state, blocking: bool) -> Dict[str, Any]:
        t0 = time.time()
        snap = snap_mod.take(self.job_id, step, state)
        copy_s = time.time() - t0
        incremental = (self.incremental_every > 0
                       and self._last_full is not None
                       and self._n_saved % self.incremental_every != 0)
        chained = (self.delta_chain and self._chain_prev is not None
                   and self._chain_len < self.rebase_every - 1)

        base_step = None
        if chained:
            # chain link: diff against the *previous save*, so restore
            # replays base + every delta up to the target step
            diffs = diffsync.diff_tree(self._chain_prev.state, snap.state,
                                       op="overwrite")
            payload = {"kind": "delta", "base_step": self._chain_prev.step,
                       "diffs": diffs, "step": step,
                       "fingerprint": snap.fingerprint}
            path = self._path(step, "delta.pkl")
            nbytes = diffsync.diff_nbytes(diffs)
            base_step = self._chain_prev.step
            self._chain_prev = snap
            self._chain_len += 1
        elif incremental:
            diffs = snap_mod.delta(self._last_full, state, op="overwrite")
            payload = {"kind": "diff", "base_step": self._last_full.step,
                       "diffs": diffs, "step": step,
                       "fingerprint": snap.fingerprint}
            path = self._path(step, "diff.pkl")
            nbytes = diffsync.diff_nbytes(diffs)
            base_step = self._last_full.step
        else:
            payload = {"kind": "full", "state": snap.state, "step": step,
                       "fingerprint": snap.fingerprint}
            path = self._path(step, "full.pkl")
            nbytes = snap.nbytes
            self._last_full = snap
            self._chain_prev = snap
            self._chain_len = 0
        self._n_saved += 1

        def _write():
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                pickle.dump(payload, f, protocol=4)
            os.replace(tmp, path)
            entries = self._manifest()
            entry = {"step": step, "path": path,
                     "kind": payload["kind"],
                     "fingerprint": snap.fingerprint,
                     "nbytes": nbytes}
            if base_step is not None:
                entry["base_step"] = base_step
            entries.append(entry)
            self._write_manifest(entries)
            self._gc(entries)

        if blocking:
            _write()
        else:
            t = threading.Thread(target=_write, daemon=True)
            t.start()
            self._pending.append(t)
        stat = {"step": step, "bytes": nbytes,
                "incremental": incremental or chained,
                "kind": payload["kind"],
                "full_bytes": snap.nbytes,
                "device_to_host_s": copy_s}
        self.stats.append(stat)
        return stat

    def wait(self) -> None:
        for t in self._pending:
            t.join()
        self._pending.clear()

    def _gc(self, entries) -> None:
        """Keep the last ``keep`` full checkpoints + diffs newer than the
        oldest kept full one."""
        fulls = [e for e in entries if e["kind"] == "full"]
        if len(fulls) <= self.keep:
            return
        cutoff = fulls[-self.keep]["step"]
        kept, dropped = [], []
        for e in entries:
            (kept if e["step"] >= cutoff else dropped).append(e)
        for e in dropped:
            try:
                os.remove(e["path"])
            except FileNotFoundError:
                pass
        self._write_manifest(kept)

    # ---- restore --------------------------------------------------------------
    def latest_step(self) -> Optional[int]:
        entries = self._manifest()
        return entries[-1]["step"] if entries else None

    def restore(self, step: Optional[int] = None, shardings=None):
        """Load state at ``step`` (default: latest).  Diff checkpoints are
        replayed on top of their base full checkpoint."""
        t0 = time.perf_counter()
        tel = telemetry.get()
        with tel.span("ckpt.restore", track=f"gang:{self.job_id}") as span:
            restored, payload = self._restore(step, shardings)
            span.set(step=payload["step"], kind=payload["kind"])
        if tel.enabled:
            tel.count("ckpt.restores")
            tel.observe("ckpt.restore_s", time.perf_counter() - t0)
        return restored, payload["step"]

    def _restore(self, step: Optional[int], shardings):
        self.wait()
        entries = self._manifest()
        if not entries:
            raise FileNotFoundError("no checkpoints")
        if step is None:
            entry = entries[-1]
        else:
            entry = next(e for e in entries if e["step"] == step)
        with open(entry["path"], "rb") as f:
            payload = pickle.load(f)
        if payload["kind"] == "full":
            state = payload["state"]
        elif payload["kind"] == "delta":
            # (base, delta*) chain: walk back to the base full, then
            # replay every delta in order and prove the reconstruction
            # bit-exact against the recorded fingerprint
            pos = entries.index(entry)
            chain = [payload]
            while chain[0]["kind"] != "full":
                base_step = chain[0]["base_step"]
                pos = next(i for i in range(pos - 1, -1, -1)
                           if entries[i]["step"] == base_step)
                with open(entries[pos]["path"], "rb") as f:
                    chain.insert(0, pickle.load(f))
            state = chain[0]["state"]
            for link in chain[1:]:
                state = diffsync.apply_tree(state, link["diffs"])
            import jax.tree_util as jtu
            fp = snap_mod._fingerprint(jtu.tree_leaves(state))
            if fp != payload["fingerprint"]:
                raise RuntimeError(
                    f"delta-chain restore at step {payload['step']} is "
                    f"not bit-exact (fingerprint mismatch)")
        else:
            base = next(e for e in entries
                        if e["kind"] == "full"
                        and e["step"] == payload["base_step"])
            with open(base["path"], "rb") as f:
                base_payload = pickle.load(f)
            state = diffsync.apply_tree(base_payload["state"],
                                        payload["diffs"])
        snap = snap_mod.Snapshot(self.job_id, payload["step"], state,
                                 fingerprint=payload["fingerprint"])
        return snap_mod.restore(snap, shardings), payload
