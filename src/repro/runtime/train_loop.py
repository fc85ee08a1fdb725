"""The Faabric training runtime: gang execution with control points.

This is the *executable* (CPU-fabric / real-TPU) counterpart of the pjit
production path: a data-parallel gang of Granules — one per device — each
running the full model replica on its batch slice, synchronising gradients
with the paper's hierarchical (pod-leader) collective schedule via
shard_map, and passing through a **control point** at every step boundary
where the runtime may checkpoint, recover from failure, migrate, or
elastically rescale the gang (paper §3.2/§3.3).

Multi-tenancy: the runtime is a thin driver over a ``core.fabric``
``GangHandle`` — the shared ``Fabric`` owns the device pool and the
``PlacementEngine``, so several gangs (train or serve) can coexist on one
fabric and this gang's rescale/migrate decisions go through the same
accounting every other tenant uses.  Control-point actions arrive as
``core.control.Action`` records (checkpoint / migrate / rescale /
recover) — the same vocabulary the trace simulator logs.

Fault tolerance (paper §3.4, implemented): failure -> gang restart from the
latest snapshot; the deterministic (seed, step)-keyed data pipeline makes
recovery bit-exact.  Straggler mitigation: EWMA step-time detector triggers
a migrate action.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ArchConfig
from repro.core import collectives as coll
from repro.core import control as ctl
from repro.core import elastic as elastic_mod
from repro.core.fabric import Fabric, GangHandle
from repro.data import pipeline as dp
from repro.models import model as model_mod
from repro.optim import adamw


@dataclasses.dataclass
class RuntimeConfig:
    total_steps: int = 20
    # hierarchical | flat | ring | compressed | auto ("auto" asks the
    # fabric CollectiveTuner for the best schedule for this gang's
    # placement topology and gradient size, re-resolved after every
    # migrate/rescale)
    sync_mode: str = "hierarchical"
    compress_frac: float = 0.05
    checkpoint_every: int = 10
    ckpt_dir: str = "/tmp/repro-ckpt"
    chips_per_host: int = 4           # CPU-fabric host granularity
    incremental_ckpt_every: int = 0
    # fault injection: {step: description}; a failure at step s is detected
    # at the step-s control point and triggers gang restart from the latest
    # checkpoint.
    inject_failures: Dict[int, str] = dataclasses.field(default_factory=dict)
    # elastic schedule: {step: new_world_size}
    rescale_at: Dict[int, int] = dataclasses.field(default_factory=dict)
    pods: int = 1                     # >1: two-level gang (pod, data) mesh
    # gang placement policy on the host fabric (binpack/spread/locality)
    placement_policy: str = "binpack"
    # free-chip-driven elastic policy, consulted at every control point;
    # None = only the explicit rescale_at schedule fires
    elastic: Optional[elastic_mod.ElasticPolicy] = None
    # trace job kind of this gang (mpi-compute/mpi-network/omp); routes
    # the per-kind beta of the shared CostModel into elastic grow probes
    # so they place exactly like a trace placement would
    job_kind: Optional[str] = None


def params_nbytes(tree) -> int:
    """Bytes of one flattened-f32 gradient sync of ``tree`` — the
    message size the CollectiveTuner buckets by."""
    return 4 * sum(l.size for l in jax.tree.leaves(tree))


def resolve_sync_mode(mode: str, handle: GangHandle,
                      params=None) -> str:
    """Concrete schedule for ``make_dp_train_step``: "auto" asks the
    fabric tuner for the gang's current placement/size dispatch."""
    if mode != "auto":
        return mode
    nbytes = params_nbytes(params) if params is not None else None
    return handle.best_sync_mode(nbytes)


def make_dp_train_step(cfg: ArchConfig, opt_cfg: adamw.AdamWConfig,
                       mesh: Mesh, mode: str,
                       compress_frac: Optional[float] = None) -> Callable:
    """Gang train step: per-device grads + explicit Faabric-style sync."""
    loss_fn = model_mod.make_loss_fn(cfg)
    gfn = jax.value_and_grad(loss_fn, has_aux=True)
    fast, slow = coll.dp_axes(mesh)
    axes = [a for a in (fast, slow) if a is not None]
    n_total = int(np.prod([mesh.shape[a] for a in axes]))

    def per_device(params, batch, resid):
        (_, metrics), grads = gfn(params, batch)
        rs = resid[0] if mode == "compressed" else None
        synced, new_rs = coll.tree_sync_body(
            grads, mode, fast, slow, n_total, compress_frac, rs)
        metrics = jax.tree.map(
            lambda m: jax.lax.pmean(m, tuple(axes)), metrics)
        return synced, metrics, (new_rs[None] if new_rs is not None
                                 else jnp.zeros((1, 1), jnp.float32))

    dp_spec = P(tuple(a for a in (("pod",) if slow else ()) + (fast,)))
    resid_spec = P(slow, fast) if slow else P(None, fast)

    def train_step(state, batch, resid):
        grads, metrics, new_resid = jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), jax.tree.map(
                lambda _: dp_spec, batch), resid_spec),
            out_specs=(P(), P(), resid_spec),
            check_vma=False)(state["params"], batch, resid)
        params, opt, om = adamw.apply(grads, state["opt"], state["params"],
                                      opt_cfg)
        return ({"params": params, "opt": opt}, {**metrics, **om},
                new_resid)

    return jax.jit(train_step, donate_argnums=(0, 2))


def extra_batch_specs(cfg: ArchConfig, global_batch: int) -> Dict[str, Any]:
    """Modality extras (audio frames / vision tokens) for a batch."""
    if cfg.family == "audio":
        return {"frames": jax.ShapeDtypeStruct(
            (global_batch, cfg.enc_seq, cfg.d_model), cfg.param_dtype())}
    if cfg.family == "vlm":
        return {"img": jax.ShapeDtypeStruct(
            (global_batch, cfg.n_img_tokens, cfg.d_model),
            cfg.param_dtype())}
    return {}


class FaabricTrainRuntime:
    """End-to-end training driver: a thin loop over one ``GangHandle``.

    The handle owns placement (devices, mesh, GranuleGroup) on a shared
    ``Fabric``; this class owns the training semantics — step function,
    data, checkpoints, and what to do with each control-point ``Action``.
    Pass ``fabric`` to share one fabric between several runtimes/serving
    gangs; by default the runtime builds a private fabric over all local
    devices and binds a whole-fabric gang (the single-tenant special
    case).
    """

    def __init__(self, cfg: ArchConfig, opt_cfg: adamw.AdamWConfig,
                 data_cfg: dp.DataConfig, rt: RuntimeConfig,
                 devices: Optional[Sequence[Any]] = None,
                 job_id: str = "job0", fabric: Optional[Fabric] = None,
                 priority: int = 0):
        self.cfg, self.opt_cfg, self.data_cfg, self.rt = (cfg, opt_cfg,
                                                          data_cfg, rt)
        self.job_id = job_id
        self.fabric = fabric if fabric is not None else Fabric(
            chips_per_host=rt.chips_per_host, policy=rt.placement_policy)
        gang_devices = list(devices if devices is not None
                            else self.fabric.devices)
        self.handle: GangHandle = self.fabric.bind(
            job_id, gang_devices, priority=priority, pods=rt.pods,
            policy=rt.placement_policy, kind=rt.job_kind)
        self.ckpt = CheckpointManager(
            rt.ckpt_dir, job_id=job_id,
            incremental_every=rt.incremental_ckpt_every)
        # control points consult the elastic probe, so `rescale` arrives
        # as an Action — the same vocabulary the simulator logs
        self.control = ctl.ControlPointRunner(
            checkpoint_every=rt.checkpoint_every,
            elastic_probe=self._elastic_probe)
        self.handle.control = self.control
        self._probe_step = 0
        self.log: List[Dict[str, Any]] = []
        self._step_fn = None
        self._extras = extra_batch_specs(self.cfg,
                                         self.data_cfg.global_batch)

    # ---- placement views (owned by the handle) -------------------------------
    @property
    def devices(self) -> List[Any]:
        return self.handle.devices

    @property
    def mesh(self) -> Mesh:
        return self.handle.mesh

    @property
    def group(self):
        return self.handle.group

    @property
    def engine(self):
        return self.fabric.engine

    def _shardings(self, state):
        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda _: rep, state)

    def _build(self, state=None):
        self.sync_mode = resolve_sync_mode(
            self.rt.sync_mode, self.handle,
            state["params"] if state is not None else None)
        self._step_fn = make_dp_train_step(
            self.cfg, self.opt_cfg, self.mesh, self.sync_mode,
            self.rt.compress_frac)

    def _place_batch(self, batch):
        axes = tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)
        s = NamedSharding(self.mesh, P(axes))
        return jax.tree.map(lambda x: jax.device_put(x, s), batch)

    def init_state(self, seed: int = 0):
        """Fresh train state, built replicated on every gang device (not
        on one device and then copied)."""
        key = jax.random.PRNGKey(seed)

        def init(k):
            return model_mod.init_train_state(k, self.cfg, self.opt_cfg)
        shardings = self._shardings(jax.eval_shape(init, key))
        return jax.jit(init, out_shardings=shardings)(key)

    # ---- control-point actions --------------------------------------------------
    def _elastic_probe(self, world: int) -> Optional[int]:
        """Next world size, or None: the explicit schedule first, then the
        free-chip-driven policy (through the shared engine)."""
        step = self._probe_step
        if step in self.rt.rescale_at:
            # cap at what is actually placeable on the *shared* fabric:
            # this gang's chips plus the currently-idle ones (other
            # tenants' allocations are not ours to take)
            return min(self.rt.rescale_at[step],
                       world + self.fabric.engine.idle_chips())
        if self.rt.elastic is not None:
            return self.rt.elastic.decide(world, self.fabric.engine,
                                          kind=self.rt.job_kind)
        return None

    def _recover(self, state, step):
        """Gang restart from the latest checkpoint (paper §3.4)."""
        restored, ck_step = self.ckpt.restore(
            shardings=self._shardings(state))
        return restored, ck_step

    def _migrate_gang(self, state):
        """Straggler response: live-migrate the gang (paper §3.3) through
        the handle — engine-planned consolidation, or a rank rotation
        when the gang already spans the minimum host count.  The
        GranuleGroup is re-addressed in place, so buffered control-plane
        messages and the migration epoch survive the move (Fig 8)."""
        state, _ = self.handle.migrate(state)
        self._build(state)
        return state

    def _rescale(self, state, resid, new_world: int):
        """Grow/shrink the gang to ``new_world`` chips via the handle:
        chips are released to the shared pool and the placement engine
        carves the new sub-mesh under the configured policy (§2.1)."""
        state = self.handle.rescale(state, new_world)
        self._build(state)
        resid = coll.init_residual_buffer(self.mesh, state["params"],
                                          self.sync_mode)
        return state, resid

    # ---- main loop ----------------------------------------------------------------
    def run(self, seed: int = 0, state=None):
        rt = self.rt
        if state is None:
            state = self.init_state(seed)
        self._build(state)
        resid = coll.init_residual_buffer(self.mesh, state["params"],
                                          self.sync_mode)
        # checkpoint step semantics: "state before running step k"
        self.ckpt.save(0, state, blocking=True)
        step = 0
        losses = {}
        recoveries = rescales = migrations = straggler_migrations = 0
        while step < rt.total_steps:
            # ---- control point A: failure detection before the step ----
            if step in rt.inject_failures and recoveries < 8:
                rt.inject_failures.pop(step, None)
                state, step = self._recover(state, step)
                recoveries += 1
                resid = coll.init_residual_buffer(self.mesh,
                                                  state["params"],
                                                  self.sync_mode)
                continue
            t0 = time.time()
            batch = dp.make_batch(self.data_cfg, step, self._extras)
            batch = self._place_batch(batch)
            state, metrics, resid = self._step_fn(state, batch, resid)
            step_time = time.time() - t0
            loss = float(metrics["loss"])
            losses[step] = loss
            self.log.append({"step": step, "loss": loss,
                             "time": step_time,
                             "world": len(self.devices)})
            # ---- control point B (barrier: the grad sync is complete) ----
            self._probe_step = step + 1
            actions = self.handle.control_point(step + 1, step_time)
            for act in actions:
                if act.kind == "checkpoint":
                    self.ckpt.save(step + 1, state, blocking=False)
                elif act.kind == "migrate":
                    state = self._migrate_gang(state)
                    migrations += 1
                    if act.payload.get("reason") == "straggler":
                        straggler_migrations += 1
                elif act.kind == "rescale":
                    state, resid = self._rescale(state, resid,
                                                 act.payload["to"])
                    rescales += 1
            step += 1
        self.ckpt.wait()
        return state, {"losses": [losses[s] for s in sorted(losses)],
                       "recoveries": recoveries, "rescales": rescales,
                       "migrations": migrations,
                       "straggler_migrations": straggler_migrations,
                       "log": self.log}

    def release(self) -> None:
        """Return the gang's chips to the shared fabric."""
        self.handle.release()
