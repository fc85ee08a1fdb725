"""Paper Fig 13 (MPI / ParRes kernels) + Fig 9 (two-level schedules).

Runs the ParRes-analogue kernels on a 2x4 (pod, data) host mesh in a
subprocess (so the main process keeps 1 device):

  p2p      ring exchange via collective-permute (paper: p2p kernel)
  nstream  axpy over sharded arrays + barrier  (paper: nstream)
  reduce   all-reduce size sweep: flat vs hierarchical vs ring vs
           compressed (threshold-select codec)
  stencil  halo exchange via ppermute          (paper: stencil)

Slow-link byte counts per schedule are *measured* from the compiled HLO
(``collectives.slowlink_bytes_from_hlo``), not assumed.  The forced-host
CPU mesh has no real slow link, so each schedule's headline time is its
``effective_s``: wall time plus measured slow bytes over the modeled
cross-pod bandwidth — the quantity Faabric's VM-leader schedule
minimises (Fig 9).  The sweep also locates the compressed-vs-flat
crossover size and A/Bs the vectorized chunk-select codec against the
old global top-k.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

FLEET = {"hosts": 2, "chips_per_host": 4, "mesh": "2x4 (pod, data)",
         "slow_bps": 0.025e9, "backend": "cpu-forced-host"}

_PROG = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as C
from repro.core import comms
from jax import make_mesh, shard_map
from repro.kernels.collective_codec import ops as codec_ops

mesh = make_mesh((2, 4), ("pod", "data"))
# bench link: a congested cross-VM link (200 Mbit/s) — the Fig 9 regime
# where schedule choice matters; chip-local walls on the forced-host CPU
# mesh are large relative to a datacenter slow link, so the emulated
# cross-pod term must dominate for the schedule gap to be visible
link = comms.LinkProfile(slow_bps=0.025e9)
out = {}
REPS = __REPS__
LOGS = __LOGS__          # sweep: log2(elements); bytes = 4 << log
TOP = LOGS[-1]

def timeit(f, *args, reps=REPS):
    r = jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / reps

n = 1 << TOP
vec = jnp.arange(8 * n, dtype=jnp.float32).reshape(8, n)

# --- p2p ring (collective-permute) ---
def p2p(x):
    def body(v):
        perm = [(i, (i + 1) % 4) for i in range(4)]
        return jax.lax.ppermute(v, "data", perm)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=P(("pod","data")),
                                 out_specs=P(("pod","data")),
                                 check_vma=False))(x)
p2p_s = timeit(p2p, vec)
out["p2p_ring_us"] = p2p_s * 1e6
# every chip forwards its n-element shard once per step
out["fastlink_gbps_measured"] = (n * 4 / p2p_s) / 1e9

# --- nstream: axpy + allreduce barrier ---
def nstream(x):
    def body(v):
        v = v * 2.0 + 1.0
        s = jax.lax.psum(jnp.sum(v), ("pod", "data"))
        return v + 0.0 * s
    return jax.jit(shard_map(body, mesh=mesh, in_specs=P(("pod","data")),
                                 out_specs=P(("pod","data")),
                                 check_vma=False))(x)
out["nstream_us"] = timeit(nstream, vec) * 1e6

# --- reduce: size sweep, all four schedules ---
# measure_schedule times the jitted all-reduce AND reads its slow-link
# bytes off the compiled HLO; effective_s adds the modeled cross-pod
# transfer (no real slow link on a forced-host mesh).
sweep = {}
for log in LOGS:
    nbytes = 4 << log
    for mode in comms.MODES:
        m = C.measure_schedule(mesh, mode, nbytes, compress_frac=0.05,
                               reps=REPS, link=link, emulate_slow=True)
        sweep[(log, mode)] = m
for mode in comms.MODES:
    m = sweep[(TOP, mode)]
    out[f"allreduce_{mode}_us"] = m["wall_s"] * 1e6
    out[f"allreduce_{mode}_effective_us"] = m["effective_s"] * 1e6
    out[f"slowlink_bytes_{mode}"] = m["slowlink_bytes"]

out["hierarchical_vs_flat_speedup"] = (
    sweep[(TOP, "flat")]["effective_s"]
    / sweep[(TOP, "hierarchical")]["effective_s"])
out["compressed_vs_flat_speedup"] = (
    sweep[(TOP, "flat")]["effective_s"]
    / sweep[(TOP, "compressed")]["effective_s"])

# smallest swept size where the compressed schedule beats flat; -1 when
# it never does (check_results asserts it exists at full tier)
cross = -1
for log in LOGS:
    if (sweep[(log, "compressed")]["effective_s"]
            < sweep[(log, "flat")]["effective_s"]):
        cross = 4 << log
        break
out["compressed_crossover_bytes"] = cross
topo = comms.Topology(hosts=2, chips=8, min_fast=4)
out["compressed_crossover_bytes_analytic"] = comms.crossover_bytes(
    topo, "flat", "compressed", link)

# --- codec A/B: chunk-select kernel vs old global top-k ---
shard = jnp.asarray(np.random.default_rng(0).standard_normal(n),
                    jnp.float32)
t_new = timeit(lambda v: codec_ops.select_codec(v, frac=0.05)[0], shard)
t_old = timeit(lambda v: C.reference_topk_select(v, 0.05)[0], shard)
out["codec_select_us"] = t_new * 1e6
out["codec_topk_us"] = t_old * 1e6
out["codec_select_speedup"] = t_old / t_new

# --- stencil: halo exchange ---
def stencil(x):
    def body(v):
        perm_f = [(i, (i + 1) % 4) for i in range(4)]
        perm_b = [((i + 1) % 4, i) for i in range(4)]
        left = jax.lax.ppermute(v[:, -128:], "data", perm_f)
        right = jax.lax.ppermute(v[:, :128], "data", perm_b)
        mid = v.at[:, :128].add(left).at[:, -128:].add(right)
        return mid * 0.25
    return jax.jit(shard_map(body, mesh=mesh, in_specs=P(("pod","data"), None),
                                 out_specs=P(("pod","data"), None),
                                 check_vma=False))(x)
grid = jnp.ones((8, 4096), jnp.float32)
out["stencil_us"] = timeit(stencil, grid) * 1e6

print(json.dumps(out))
"""


def run(report, tiny=False):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # a CPU benchmark: keep off the chip
    env["PYTHONPATH"] = SRC
    logs = "[12, 14]" if tiny else "[12, 14, 16, 18, 20]"
    prog = textwrap.dedent(_PROG) \
        .replace("__REPS__", "2" if tiny else "10") \
        .replace("__LOGS__", logs)
    res = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, env=env,
                         timeout=1800)
    assert res.returncode == 0, res.stderr[-3000:]
    data = json.loads(res.stdout.strip().splitlines()[-1])
    for k, v in data.items():
        if k.endswith("_us"):
            unit = "us"
        elif k.endswith("_bytes") or k.startswith("slowlink_bytes"):
            unit = "bytes"
        elif k.endswith("_speedup"):
            unit = "x"
        elif k.endswith("_gbps_measured"):
            unit = "GB/s"
        else:
            unit = ""
        note = "Fig9 two-level schedule" if "speedup" in k else "Fig13/Fig9"
        report(k, round(float(v), 2), unit, note)
