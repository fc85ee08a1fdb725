"""Paper Fig 14 (Granule migration at runtime).

Two halves:
  * REAL migration mechanics on the host fabric (subprocess, 8 devices):
    snapshot -> restore wall time, full vs delta bytes moved, bit-exact
    verification — the actual cost side of Fig 14.
  * The speedup side (migrating a fragmented gang at 20/40/60/80% of the
    run) reproduced in the discrete-event simulator with the paper's
    calibration: network-bound jobs gain up to ~3.5x when migrated early;
    compute-bound jobs see single-digit gains and a slight loss at 80%.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from repro.core import simulator as S
from repro.core.scheduler import Allocation

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PROG = """
import json, time
import jax, jax.numpy as jnp
from repro.core import migration, snapshot as snap_mod
from repro.core.elastic import make_dp_mesh, replicated_shardings
from repro.configs.registry import reduced_config
from repro.models import model as M
from repro.optim.adamw import AdamWConfig

cfg = reduced_config("llama3.2-1b")
ocfg = AdamWConfig()
state = jax.jit(lambda k: M.init_train_state(k, cfg, ocfg))(
    jax.random.PRNGKey(0))
devs = jax.devices()
src = make_dp_mesh(devs[:4]); dst = make_dp_mesh(devs[4:])
state = jax.device_put(state, replicated_shardings(state, src))

out = {}
t0 = time.perf_counter()
moved, stats = migration.migrate_via_snapshot(
    "j", 0, state, replicated_shardings(state, dst))
out["full_migration_s"] = round(time.perf_counter() - t0, 3)
out["full_bytes_mb"] = round(stats["full_bytes"] / 2**20, 1)
assert migration.verify_migration(state, moved)

prior = snap_mod.take("j", 0, state)
state2 = {"params": jax.tree.map(lambda x: x, state["params"]),
          "opt": state["opt"]}
state2["params"]["final_norm"] = state2["params"]["final_norm"] * 1.001
t0 = time.perf_counter()
moved2, stats2 = migration.migrate_via_snapshot(
    "j", 1, state2, replicated_shardings(state, dst), prior=prior)
out["delta_migration_s"] = round(time.perf_counter() - t0, 3)
out["delta_bytes_mb"] = round(stats2["moved_bytes"] / 2**20, 3)
assert migration.verify_migration(state2, moved2)

# delta-chain checkpointing of the same live model state: one full
# base then per-step diffs (CheckpointManager delta_chain), restored
# bit-exactly through the chain
import tempfile
from repro.checkpoint.manager import CheckpointManager
with tempfile.TemporaryDirectory() as td:
    mgr = CheckpointManager(td, "mig", delta_chain=True,
                            rebase_every=4)
    st = state2
    t0 = time.perf_counter()
    for s in range(3):
        st = {"params": dict(st["params"]), "opt": st["opt"]}
        st["params"]["final_norm"] = st["params"]["final_norm"] * 1.001
        mgr.save(s, st)
    out["delta_chain_save_s"] = round(time.perf_counter() - t0, 3)
    deltas = [x["bytes"] for x in mgr.stats if x["kind"] == "delta"]
    out["delta_chain_link_mb"] = round(sum(deltas) / len(deltas)
                                       / 2**20, 3)
    out["delta_chain_full_mb"] = round(mgr.stats[0]["full_bytes"]
                                       / 2**20, 1)
    restored, step = mgr.restore(2)
    assert step == 2
print(json.dumps(out))
"""


def _single_job_speedup(kind: str, migrate_at: float) -> float:
    """One 8-rank job forced to fragment 4+4 over two hosts, optionally
    consolidated at ``migrate_at`` fraction of its work (paper Fig 14)."""
    job = S.Job("j", kind, 8, 400.0)
    frag = Allocation("j", [(0, 4), (1, 4)])
    whole = Allocation("j", [(0, 8)])

    def runtime(alloc_before, alloc_after, frac):
        rj = S.RunningJob(job, alloc_before, 0.0,
                          eff_parallelism=job.parallelism)
        t1 = frac / rj.rate()
        rj2 = S.RunningJob(job, alloc_after, 0.0,
                           eff_parallelism=job.parallelism)
        t2 = (1 - frac) / rj2.rate() + (S.MIGRATION_COST_S
                                        if frac < 1.0 else 0.0)
        return t1 + t2

    t_frag = runtime(frag, frag, 1.0)
    t_mig = runtime(frag, whole, migrate_at)
    return t_frag / t_mig


def run(report, tiny=False):
    if not tiny:
        # real snapshot/restore mechanics need the 8-device subprocess;
        # the smoke run keeps the (fast, pure) simulator half only
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"   # a CPU benchmark: keep off the chip
        env["PYTHONPATH"] = SRC
        res = subprocess.run([sys.executable, "-c",
                              textwrap.dedent(_PROG)],
                             capture_output=True, text=True, env=env,
                             timeout=1200)
        assert res.returncode == 0, res.stderr[-3000:]
        data = json.loads(res.stdout.strip().splitlines()[-1])
        for k, v in data.items():
            report(k, v, "", "Fig14 migration mechanics (real)")

    for kind, label in (("mpi-network", "all-to-all"),
                        ("mpi-compute", "LAMMPS")):
        coloc = _single_job_speedup(kind, 0.0)
        report(f"speedup/{label}/colocated", round(coloc, 2), "x",
               "Fig14 (1 VM reference)")
        for frac in (0.2, 0.4, 0.6, 0.8):
            sp = _single_job_speedup(kind, frac)
            report(f"speedup/{label}/migrate_at_{int(frac*100)}pct",
                   round(sp, 2), "x", "Fig14")
