"""Distributed-runtime tests on an 8-device host fabric.

Each test runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 so the main pytest process keeps its 1-device view (the
dry-run instructions require the flag NOT be set globally)."""
import os
import subprocess
import sys
import textwrap


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


def test_collective_modes_agree():
    print(run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import collectives as C
        from jax import make_mesh
        mesh = make_mesh((2, 4), ("pod", "data"))
        tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (8, 4, 3)),
                "b": jax.random.normal(jax.random.PRNGKey(1), (8, 7))}
        expect = jax.tree.map(lambda x: jnp.broadcast_to(x.mean(0), x.shape),
                              tree)
        for mode in ("flat", "hierarchical", "ring"):
            f = C.build_tree_allreduce(mesh, mode=mode)
            out, _ = jax.jit(f)(tree)
            for o, e in zip(jax.tree.leaves(out), jax.tree.leaves(expect)):
                np.testing.assert_allclose(np.asarray(o), np.asarray(e),
                                           atol=1e-5)
        print("modes-ok")
    """))


def test_compressed_allreduce_error_feedback_converges():
    print(run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import collectives as C
        from jax import make_mesh
        mesh = make_mesh((2, 4), ("pod", "data"))
        tree = {"g": jax.random.normal(jax.random.PRNGKey(0), (8, 64))}
        f = jax.jit(C.build_tree_allreduce(mesh, mode="compressed",
                                           compress_frac=0.25))
        resid = C.init_residual_buffer(mesh, jax.tree.map(lambda x: x[0],
                                                          tree))
        total = jnp.zeros((8, 64))
        # repeated sync of the SAME grads: EF must deliver the full mean
        for _ in range(8):
            out, resid = f(tree, resid)
            total = total + out["g"]
        mean = jnp.broadcast_to(tree["g"].mean(0), (8, 64))
        err = float(jnp.abs(total / 8 - mean).max())
        assert err < 0.2, err
        print("ef-ok", err)
    """))


def test_runtime_failure_recovery_bit_exact():
    print(run_sub("""
        import shutil, numpy as np
        shutil.rmtree("/tmp/repro-t-rec", ignore_errors=True)
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.runtime.train_loop import (FaabricTrainRuntime,
                                              RuntimeConfig)
        cfg = reduced_config("llama3.2-1b").with_(n_layers=2, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=16, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        base = FaabricTrainRuntime(cfg, ocfg, dcfg, RuntimeConfig(
            total_steps=10, checkpoint_every=4,
            ckpt_dir="/tmp/repro-t-rec/a")).run(seed=0)[1]
        failed = FaabricTrainRuntime(cfg, ocfg, dcfg, RuntimeConfig(
            total_steps=10, checkpoint_every=4,
            ckpt_dir="/tmp/repro-t-rec/b",
            inject_failures={6: "x"})).run(seed=0)[1]
        assert failed["recoveries"] == 1
        np.testing.assert_allclose(base["losses"], failed["losses"],
                                   atol=1e-6)
        print("recovery-ok")
    """))


def test_runtime_elastic_rescale_loss_invariant():
    print(run_sub("""
        import shutil, numpy as np
        shutil.rmtree("/tmp/repro-t-el", ignore_errors=True)
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.runtime.train_loop import (FaabricTrainRuntime,
                                              RuntimeConfig)
        cfg = reduced_config("llama3.2-1b").with_(n_layers=2, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=16, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        base = FaabricTrainRuntime(cfg, ocfg, dcfg, RuntimeConfig(
            total_steps=8, checkpoint_every=100,
            ckpt_dir="/tmp/repro-t-el/a")).run(seed=0)[1]
        el = FaabricTrainRuntime(cfg, ocfg, dcfg, RuntimeConfig(
            total_steps=8, checkpoint_every=100,
            ckpt_dir="/tmp/repro-t-el/b",
            rescale_at={4: 4})).run(seed=0)[1]
        assert el["rescales"] == 1
        np.testing.assert_allclose(base["losses"], el["losses"], atol=1e-5)
        print("elastic-ok")
    """))


def test_migration_between_device_sets_bit_exact():
    print(run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import migration, snapshot as snap_mod
        from repro.core.elastic import make_dp_mesh, replicated_shardings
        devs = jax.devices()
        state = {"w": jnp.arange(100000, dtype=jnp.float32),
                 "m": {"v": jnp.ones((13, 7))}}
        src = make_dp_mesh(devs[:4])
        state = jax.device_put(state, replicated_shardings(state, src))
        dst = make_dp_mesh(devs[4:])
        moved, stats = migration.migrate_via_snapshot(
            "j", 3, state, replicated_shardings(state, dst))
        assert migration.verify_migration(state, moved)
        # delta migration against a prior snapshot moves fewer bytes
        prior = snap_mod.take("j", 3, state)
        state2 = {"w": state["w"].at[5].add(1.0), "m": state["m"]}
        moved2, stats2 = migration.migrate_via_snapshot(
            "j", 4, state2, replicated_shardings(state, dst), prior=prior)
        assert stats2["moved_bytes"] < stats2["full_bytes"] / 2
        assert migration.verify_migration(state2, moved2)
        print("migration-ok", stats2["moved_bytes"], stats2["full_bytes"])
    """))


def test_two_pod_hierarchical_matches_flat_training():
    print(run_sub("""
        import shutil, numpy as np
        shutil.rmtree("/tmp/repro-t-pod", ignore_errors=True)
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.runtime.train_loop import (FaabricTrainRuntime,
                                              RuntimeConfig)
        cfg = reduced_config("llama3.2-1b").with_(n_layers=2, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=16, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        ref = FaabricTrainRuntime(cfg, ocfg, dcfg, RuntimeConfig(
            total_steps=5, checkpoint_every=100, sync_mode="flat",
            ckpt_dir="/tmp/repro-t-pod/a")).run(seed=0)[1]
        hier = FaabricTrainRuntime(cfg, ocfg, dcfg, RuntimeConfig(
            total_steps=5, checkpoint_every=100, pods=2,
            sync_mode="hierarchical",
            ckpt_dir="/tmp/repro-t-pod/b")).run(seed=0)[1]
        np.testing.assert_allclose(ref["losses"], hier["losses"], atol=1e-5)
        print("pod-ok")
    """))


def test_straggler_triggers_live_migration():
    print(run_sub("""
        import shutil, numpy as np
        shutil.rmtree("/tmp/repro-t-strag", ignore_errors=True)
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.runtime.train_loop import (FaabricTrainRuntime,
                                              RuntimeConfig)
        cfg = reduced_config("llama3.2-1b").with_(n_layers=2, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=16, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        base = FaabricTrainRuntime(cfg, ocfg, dcfg, RuntimeConfig(
            total_steps=8, checkpoint_every=100,
            ckpt_dir="/tmp/repro-t-strag-b")).run(seed=0)[1]
        # straggler path: EWMA detector fires -> _migrate_gang reshards the
        # gang onto a rotated placement mid-run; losses must be unchanged
        rt = FaabricTrainRuntime(cfg, ocfg, dcfg, RuntimeConfig(
            total_steps=8, checkpoint_every=100,
            ckpt_dir="/tmp/repro-t-strag"))
        # deterministic detector firing: feed synthetic step times
        det = rt.control.straggler
        for t in (1.0, 1.0, 1.0):
            assert not det.observe(t)
        fired = [det.observe(5.0) for _ in range(det.patience)]
        assert fired[-1], "EWMA straggler detector must fire"
        # exercise the live-migration machinery at a control point
        state = rt.init_state(seed=0)
        rt._build()
        before = [d.id for d in rt.devices]
        state = rt._migrate_gang(state)
        after = [d.id for d in rt.devices]
        assert before != after and sorted(before) == sorted(after)
        out = rt.run(seed=0, state=state)[1]
        np.testing.assert_allclose(base["losses"], out["losses"],
                                   atol=1e-5)
        print("straggler-migration-ok", before, "->", after)
    """))
