"""Placement-aware collective dispatch (DESIGN.md §11): the analytic
cost model in ``core.comms``, the ``CollectiveTuner`` dispatch table and
its Fabric/GangHandle re-derivation hooks, HLO slow-link accounting, the
threshold-select codec inside the compressed schedule, and the
``CostModel.collective_time`` pricing that feeds placement scoring.

Pure pieces run in-process; anything needing a (pod, data) mesh runs in
an 8-device subprocess (same pattern as test_dist)."""
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.core import comms
from repro.core.collectives import CollectiveTuner
from repro.core.placement import (ClusterView, CostModel,
                                  LocalityScoredPolicy,
                                  placement_cross_host_fraction)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout: int = 1200) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# comms: analytic cost model (pure)
# ---------------------------------------------------------------------------
def test_topology_from_placement():
    t = comms.Topology.from_placement([(0, 4), (1, 4)])
    assert (t.hosts, t.chips, t.min_fast) == (2, 8, 4)
    t = comms.Topology.from_placement([(3, 6), (0, 1), (5, 1)])
    assert (t.hosts, t.chips, t.min_fast) == (3, 8, 1)


def test_size_bucket_clamped_log2():
    assert comms.size_bucket(1) == comms.MIN_BUCKET
    assert comms.size_bucket(1 << 20) == 20
    assert comms.size_bucket((1 << 20) + 1) == 21
    assert comms.size_bucket(1 << 40) == comms.MAX_BUCKET
    assert comms.size_bucket(None) == comms.size_bucket(comms.DEFAULT_NBYTES)


def test_schedule_cost_orderings():
    topo = comms.Topology(hosts=2, chips=8, min_fast=4)
    link = comms.LinkProfile()
    big = 16 << 20
    # two-level beats flat on any multi-host topology at large sizes:
    # the slow hop ships bytes/min_fast instead of the whole vector
    assert comms.schedule_cost(topo, big, "hierarchical", link) \
        < comms.schedule_cost(topo, big, "flat", link)
    # compressed beats hierarchical at large sizes (2*frac of the shard)
    assert comms.schedule_cost(topo, big, "compressed", link, frac=0.05) \
        < comms.schedule_cost(topo, big, "hierarchical", link)
    # at tiny sizes per-step latency dominates: flat wins
    assert comms.schedule_cost(topo, 256, "flat", link) \
        < comms.schedule_cost(topo, 256, "compressed", link, frac=0.05)
    # compressed needs a pod axis
    assert comms.schedule_cost(comms.Topology(1, 8, 8), big, "compressed",
                               link, frac=0.05) == float("inf")
    # a ragged split prices worse than a balanced one (smaller min_fast)
    ragged = comms.Topology(2, 8, 1)
    assert comms.schedule_cost(topo, big, "hierarchical", link) \
        < comms.schedule_cost(ragged, big, "hierarchical", link)


def test_best_schedule_and_crossover():
    topo = comms.Topology(2, 8, 4)
    link = comms.LinkProfile()
    mode_small, _ = comms.best_schedule(topo, 256, link, 0.05)
    mode_big, _ = comms.best_schedule(topo, 64 << 20, link, 0.05)
    assert mode_small == "flat" and mode_big == "compressed"
    cross = comms.crossover_bytes(topo, "flat", "compressed", link, 0.05)
    assert cross > 0
    assert comms.schedule_cost(topo, 2 * cross, "compressed", link, 0.05) \
        < comms.schedule_cost(topo, 2 * cross, "flat", link)
    # measured overrides beat the analytic estimate
    mode, t = comms.best_schedule(topo, 64 << 20, link, 0.05,
                                  measured={"compressed": 1e3})
    assert mode != "compressed"


# ---------------------------------------------------------------------------
# CollectiveTuner dispatch (pure)
# ---------------------------------------------------------------------------
def test_tuner_dispatch_by_size_and_topology():
    tuner = CollectiveTuner()
    two_host = [(0, 4), (1, 4)]
    assert tuner.mode_for(two_host, 1 << 10) == "flat"
    assert tuner.mode_for(two_host, 64 << 20) == "compressed"
    # single host: no slow link, flat always wins
    for nbytes in (1 << 10, 64 << 20):
        assert tuner.mode_for([(0, 8)], nbytes) == "flat"
    # allowed restricts the choice (single-axis mesh: no pod schedules)
    assert tuner.mode_for(two_host, 64 << 20,
                          allowed=("flat", "ring")) in ("flat", "ring")


def test_tuner_placement_change_rederives_all_buckets():
    tuner = CollectiveTuner()
    topo = tuner.on_placement_change("j0", [(0, 4), (1, 4)])
    assert tuner.gangs["j0"] == topo and tuner.rederivations == 1
    n_buckets = comms.MAX_BUCKET - comms.MIN_BUCKET + 1
    assert sum(1 for (key, _) in tuner.table if key == topo.key) \
        == n_buckets
    # dispatch by job id follows the gang's recorded topology
    assert tuner.mode_for("j0", 64 << 20) == "compressed"
    # migration to a single host flips every bucket to flat
    tuner.on_placement_change("j0", [(2, 8)])
    assert tuner.rederivations == 2
    assert tuner.mode_for("j0", 64 << 20) == "flat"
    tuner.forget("j0")
    assert "j0" not in tuner.gangs


def test_tuner_probe_overrides_analytic():
    tuner = CollectiveTuner()
    pl = [(0, 4), (1, 4)]
    nbytes = 64 << 20
    assert tuner.mode_for(pl, nbytes) == "compressed"
    # a probe that measures compressed as catastrophically slow (say the
    # fleet's codec offload is broken) re-derives the dispatch entry
    tuner.record_probe(pl, nbytes, "compressed", 1e3)
    assert tuner.mode_for(pl, nbytes) == "hierarchical"
    assert tuner.predicted_time(pl, nbytes) \
        == comms.schedule_cost(comms.Topology.from_placement(pl),
                               comms.bucket_nbytes(comms.size_bucket(nbytes)),
                               "hierarchical", tuner.link)


# ---------------------------------------------------------------------------
# CostModel.collective_time pricing (pure)
# ---------------------------------------------------------------------------
def test_collective_time_prefers_balanced_splits():
    cm = CostModel(collective_bytes=64 << 20, step_compute_s=0.05)
    single = cm.collective_time([(0, 8)])
    balanced = cm.collective_time([(0, 4), (1, 4)])
    ragged = cm.collective_time([(0, 6), (1, 1), (2, 1)])
    assert single < balanced < ragged
    assert cm.slowdown([(0, 8)]) < cm.slowdown([(0, 4), (1, 4)])


def test_collective_pricing_off_is_bit_identical():
    # default CostModel keeps the exact pre-PR scalar-beta slowdown
    cm = CostModel()
    assert not cm.collective_pricing
    for pl in ([(0, 8)], [(0, 4), (1, 4)], [(0, 6), (1, 2)]):
        for kind in (None, "mpi-network", "omp"):
            assert cm.slowdown(pl, kind) == 1.0 + cm.beta(kind) \
                * placement_cross_host_fraction(pl)


def test_collective_priced_policy_picks_balanced_split():
    cm = CostModel(collective_bytes={"mpi-network": 64 << 20},
                   step_compute_s=0.01)
    pol = LocalityScoredPolicy(cost_model=cm)
    scalar = LocalityScoredPolicy(beta=13.0)
    free = np.array([7, 7, 7, 0], dtype=np.int64)
    a = pol.place(ClusterView(free.copy(), 8), 15, kind="mpi-network")
    b = scalar.place(ClusterView(free.copy(), 8), 15, kind="mpi-network")
    # greedy most-free gives the ragged {7,7,1}; only the collective
    # score can rank the balanced {5,5,5} candidate above it
    assert sorted(c for _, c in a) == [5, 5, 5]
    assert min(c for _, c in b) == 1
    # either way the gang is whole
    assert sum(c for _, c in a) == sum(c for _, c in b) == 15


def test_balanced_split_respects_caps():
    pol = LocalityScoredPolicy()
    free = np.array([7, 3, 3, 2], dtype=np.int64)
    pl = pol._balanced_split(free, 12)
    assert sum(c for _, c in pl) == 12
    assert all(c <= free[h] for h, c in pl)
    assert len(pl) == 3                    # fewest hosts that fit
    assert pol._balanced_split(free, 16) is None


def test_hlo_accounting_tuple_shapes_and_operand_mentions():
    from repro.core import collectives as C
    hlo = """
    ENTRY %main {
      %p0 = f32[256]{0} parameter(0)
      %cp = (f32[256]{0:T(256)}, f32[128]{0}) collective-permute(%p0), source_target_pairs={{0,1},{1,2}}
      %fusion = f32[256]{0} fusion(%collective-permute.1), kind=kLoop
      %ar = f32[64]{0} all-reduce(%p0), replica_groups={{0,1,2,3}}
    }
    """
    got = C.collective_bytes_from_hlo(hlo)
    # tuple-shaped permute results count every element (256+128 f32);
    # the fusion line *mentioning* a collective-permute operand doesn't
    assert got["collective-permute"] == (256 + 128) * 4
    assert got["all-reduce"] == 64 * 4
    assert got["total"] == (256 + 128 + 64) * 4
    # slow-link view: pods [0,0,1,1] -> the 1->2 hop crosses but 0->1
    # doesn't (half the pairs), and the all-reduce group spans pods
    slow = C.slowlink_bytes_from_hlo(hlo, [0, 0, 1, 1])
    assert slow == (256 + 128) * 4 // 2 + 64 * 4
    # a single-pod fleet has no slow link at all
    assert C.slowlink_bytes_from_hlo(hlo, [0, 0, 0, 0]) == 0


# ---------------------------------------------------------------------------
# mesh-level: schedules, codec bit-exactness, HLO accounting, hooks
# ---------------------------------------------------------------------------
def test_all_modes_agree_and_frac1_bit_exact():
    print(run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import collectives as C
        from jax import make_mesh
        mesh = make_mesh((2, 4), ("pod", "data"))
        tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (8, 4, 33)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (8, 257))}
        outs = {}
        for mode in ("flat", "ring", "hierarchical"):
            f = jax.jit(C.build_tree_allreduce(mesh, mode=mode))
            outs[mode] = jax.tree.leaves(f(tree, None)[0])
        for mode in ("ring", "hierarchical"):
            for o, e in zip(outs[mode], outs["flat"]):
                np.testing.assert_allclose(np.asarray(o), np.asarray(e),
                                           atol=1e-5)
        # frac=1.0: every element selected, m=1 chunks — the compressed
        # schedule reduces to hierarchical bit-for-bit
        f = jax.jit(C.build_tree_allreduce(mesh, mode="compressed",
                                           compress_frac=1.0))
        resid = C.init_residual_buffer(mesh, jax.tree.map(lambda x: x[0],
                                                          tree))
        out, resid = f(tree, resid)
        for o, e in zip(jax.tree.leaves(out), outs["hierarchical"]):
            np.testing.assert_array_equal(np.asarray(o), np.asarray(e))
        for r in jax.tree.leaves(resid):
            assert not np.asarray(r).any()
        print("modes-ok")
    """))


def test_slowlink_bytes_measured_from_hlo():
    print(run_sub("""
        import jax
        from repro.core import collectives as C
        from jax import make_mesh
        mesh = make_mesh((2, 4), ("pod", "data"))
        nbytes = 4096
        slow = {m: C.measure_schedule(mesh, m, nbytes, reps=1)
                     ["slowlink_bytes"] for m in
                ("flat", "ring", "hierarchical", "compressed")}
        # flat ships every chip's full shard across the pod boundary;
        # the two-level schedule ships 1/min_fast of it
        assert slow["flat"] == 4 * slow["hierarchical"], slow
        # ring's p2p hops cross the boundary for a fraction of steps but
        # still move the whole vector through the slow link overall
        assert slow["ring"] == slow["flat"], slow
        # the codec ships 2*frac of the shard (values + indices)
        assert 0 < slow["compressed"] < slow["hierarchical"], slow
        print("slowlink-ok", slow)
    """))


def test_ppermute_slowlink_counts_crossing_fraction():
    print(run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import collectives as C
        from jax import make_mesh, shard_map
        mesh = make_mesh((2, 4), ("pod", "data"))
        # ring over ALL 8 devices: 2 of 8 hops cross the pod boundary
        def body(v):
            perm = [(i, (i + 1) % 8) for i in range(8)]
            return jax.lax.ppermute(v, ("pod", "data"), perm)
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(("pod","data")),
                              out_specs=P(("pod","data")),
                              check_vma=False))
        x = jnp.ones((8, 256), jnp.float32)
        hlo = f.lower(x).compile().as_text()
        got = C.slowlink_bytes_from_hlo(hlo, C.mesh_pod_of(mesh))
        # per-chip shard is 256 f32 = 1024 B; 2/8 of the hops cross
        assert got == int(1024 * 2 / 8), (got, 256)
        print("ppermute-ok", got)
    """))


def test_fabric_hooks_rederive_tuner():
    print(run_sub("""
        import jax, jax.numpy as jnp
        from repro.core.fabric import Fabric
        mesh_state = {"w": jnp.zeros((4, 4))}
        fab = Fabric(chips_per_host=2)
        h = fab.bind("j0", fab.devices[:4], pods=2)
        tuner = fab.tuner
        assert "j0" in tuner.gangs
        base = tuner.rederivations
        assert base >= 1
        # a rescale re-derives the gang's dispatch entries
        state = jax.device_put(mesh_state)
        state = h.rescale(state, 8)
        assert tuner.rederivations > base
        assert "j0" in tuner.gangs
        # best_sync_mode consults the tuner for the gang's placement;
        # a two-pod gang may use any schedule, and a huge message routes
        # to a slow-link-avoiding one
        m = h.best_sync_mode(64 << 20)
        assert m in ("flat", "ring", "hierarchical", "compressed")
        assert m != "flat"
        h.release()
        assert "j0" not in tuner.gangs
        print("hooks-ok", m)
    """))


def test_compressed_error_feedback_converges_frac01():
    print(run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import collectives as C
        from jax import make_mesh
        mesh = make_mesh((2, 4), ("pod", "data"))
        tree = {"g": jax.random.normal(jax.random.PRNGKey(2), (8, 96))}
        f = jax.jit(C.build_tree_allreduce(mesh, mode="compressed",
                                           compress_frac=0.1))
        resid = C.init_residual_buffer(mesh, jax.tree.map(lambda x: x[0],
                                                          tree))
        expect = jnp.broadcast_to(tree["g"].mean(0), tree["g"].shape)
        total = jnp.zeros_like(tree["g"])
        errs = {}
        for step in range(1, 25):
            out, resid = f(tree, resid)
            total = total + out["g"]
            if step in (6, 24):
                errs[step] = float(jnp.abs(total / step - expect).max()
                                   / jnp.abs(expect).max())
        # error feedback: the residual is bounded, so the running mean
        # converges to the true mean ~ 1/steps
        assert errs[24] < errs[6] / 2, errs
        assert errs[24] < 0.25, errs
        print("ef-ok", errs)
    """))


def test_flatten_spec_cache_and_single_split_unflatten():
    import jax
    import jax.numpy as jnp
    from repro.core import collectives as C
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones((5,))}
    C._SPEC_CACHE.clear()
    vec, spec = C.flatten_tree(tree)
    assert len(C._SPEC_CACHE) == 1
    vec2, spec2 = C.flatten_tree(jax.tree.map(lambda x: x * 2, tree))
    assert len(C._SPEC_CACHE) == 1 and spec2 is spec   # cache hit
    out = C.unflatten_tree(vec, spec)
    for o, e in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(e))
    # a different structure misses and adds one entry
    C.flatten_tree({"c": jnp.ones((3, 3))})
    assert len(C._SPEC_CACHE) == 2
    # padded flatten roundtrips too
    vec, spec = C.flatten_tree(tree, pad_to=8)
    assert vec.size % 8 == 0
    out = C.unflatten_tree(vec, spec)
    for o, e in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(e))
