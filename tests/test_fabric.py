"""Multi-tenant Fabric tests: gang lifecycle, priority preemption with
bit-exact resume, concurrent gangs, and trace-driven live execution
matching the simulator's prediction.

Fast tests exercise the pure pieces (PreemptPolicy, GranuleGroup queue
survival, device-pool accounting); the heavy end-to-end paths run in
subprocesses with an 8-device CPU fabric (same pattern as test_dist)."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.granule import GranuleGroup
from repro.core.placement import PlacementEngine, PreemptPolicy

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
# PreemptPolicy (pure)
# ---------------------------------------------------------------------------
def test_preemption_plan_evicts_lowest_priority_first():
    eng = PlacementEngine(2, 8)
    eng.allocate("low-big", 8)
    eng.allocate("mid", 4)
    eng.allocate("low-small", 4)
    pri = {"low-big": 0, "mid": 3, "low-small": 0}
    # 8 chips at priority 5: evicting the big low-priority gang suffices
    plan = eng.preemption_plan(8, 5, pri)
    assert plan == ["low-big"]
    # 14 chips: both low-priority gangs go before the mid one
    plan = eng.preemption_plan(14, 5, pri)
    assert plan is not None and "mid" not in plan[:2] \
        and set(plan) >= {"low-big", "low-small"}
    # nothing outranked: a priority-0 arrival cannot evict anyone
    assert eng.preemption_plan(4, 0, pri) is None
    # already placeable -> empty plan
    eng.release(eng.allocations["low-small"])
    assert eng.preemption_plan(2, 5, pri) == []


def test_preemption_plan_respects_max_victims():
    eng = PlacementEngine(2, 4)
    for i in range(4):
        eng.allocate(f"j{i}", 2)
    pri = {f"j{i}": 0 for i in range(4)}
    assert eng.preemption_plan(8, 1, pri, preempt=PreemptPolicy(
        max_victims=1)) is None
    plan = eng.preemption_plan(8, 1, pri)
    assert plan is not None and len(plan) == 4


def test_engine_ragged_capacities():
    eng = PlacementEngine(3, 4, capacities=[4, 4, 2])
    assert eng.total_chips == 10
    a = eng.allocate("j", 10)
    assert a is not None and a.n == 10
    eng.release(a)
    assert eng.idle_chips() == 10


def test_infer_host_speeds_uniform_pool_is_homogeneous():
    from repro.core.fabric import infer_host_speeds

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    # uniform pool (whatever the generation): no speeds, homogeneous path
    assert infer_host_speeds([Dev("TPU v4")] * 6, 2) is None
    # mixed generations: per-host means over the shared host map
    devs = [Dev("TPU v4")] * 2 + [Dev("TPU v2")] * 2 + [Dev("TPU v4")]
    speeds = infer_host_speeds(devs, 2)
    assert speeds == [0.75, 0.25, 0.75]     # ragged last host included


def test_infer_host_speeds_unknown_kind_in_mixed_pool_raises():
    from repro.core.fabric import Fabric, infer_host_speeds

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    # a uniform pool of a kind the table lacks is still homogeneous
    assert infer_host_speeds([Dev("TPU v5 lite")] * 4, 4) is None
    # mixed with an unknown kind: no silent 1.0 for the stranger
    with pytest.raises(ValueError, match="unknown device kinds"):
        infer_host_speeds([Dev("TPU v4")] * 2 + [Dev("TPU v5 lite")] * 2, 2)
    fab = Fabric(devices=[Dev("TPU v4")] * 2, chips_per_host=2)
    with pytest.raises(ValueError, match="unknown device kinds"):
        fab.join_hosts([Dev("TPU v5 lite")] * 2)


def test_join_hosts_infers_generation_speeds():
    from repro.core.fabric import Fabric

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    # an older-generation host joining a uniform fleet re-opens the
    # heterogeneous path at its relative speed
    fab = Fabric(devices=[Dev("TPU v5")] * 4, chips_per_host=2)
    assert fab.engine.speeds is None
    new = fab.join_hosts([Dev("TPU v2")] * 2)
    assert new == [2]
    assert list(fab.engine.speeds) == [1.0, 1.0, 0.25]
    assert fab.engine.heterogeneous
    # same-generation joiners keep the uniform fast path (relative 1.0
    # even when the shared generation is not the newest)
    fab2 = Fabric(devices=[Dev("TPU v4")] * 4, chips_per_host=2)
    fab2.join_hosts([Dev("TPU v4")] * 2)
    assert fab2.engine.speeds is None and fab2.engine.hosts == 3
    # joining an already-heterogeneous fleet uses absolute factors
    fab3 = Fabric(devices=[Dev("TPU v5")] * 2 + [Dev("TPU v3")] * 2,
                  chips_per_host=2)
    fab3.join_hosts([Dev("TPU v4")] * 2)
    assert list(fab3.engine.speeds) == [1.0, 0.45, 0.75]


def test_fabric_pool_churn_drops_doomed_devices():
    from repro.core.fabric import Fabric

    class Dev:
        def __init__(self, i):
            self.i = i

    devs = [Dev(i) for i in range(6)]
    fab = Fabric(devices=devs, chips_per_host=2)
    taken = fab.claim([(0, 2), (1, 1)])
    fab.mark_draining([1])
    assert fab._free[1] == []            # free chips surrendered
    fab.reclaim(taken)
    assert fab._free[0] == devs[0:2]     # host-0 devices return
    assert fab._free[1] == []            # draining-host device dropped
    fab.fail_hosts_pool([2])
    assert fab._free[2] == [] and 2 in fab._retired_hosts


# ---------------------------------------------------------------------------
# GranuleGroup: in-place re-address keeps queues + epoch (paper Fig 8)
# ---------------------------------------------------------------------------
def test_readdress_preserves_group_identity_and_epoch():
    g = GranuleGroup("j", 4, [(i // 2, None) for i in range(4)])
    g.send(0, 3, {"tok": 1})
    # barrier precondition (paper §5.2): the message plane must be empty
    with pytest.raises(RuntimeError):
        g.readdress([(1, None)] * 4)
    assert g.recv(3, 0) == {"tok": 1}
    e0 = g.epoch
    granules_before = g.granules
    g.readdress([((i + 1) % 2, None) for i in range(4)])
    # in-place: granule identity survives (the old rebuild-from-scratch
    # path silently discarded queues and reset the epoch to 0)
    assert g.granules is granules_before
    assert g.epoch == e0 + 1
    assert g.address_table() == {0: 1, 1: 0, 2: 1, 3: 0}
    # messaging still works across the move, addressed by rank
    g.send(1, 2, "post-move")
    assert g.recv(2, 1) == "post-move"
    # no-op readdress does not burn an epoch
    g.readdress([((i + 1) % 2, None) for i in range(4)])
    assert g.epoch == e0 + 1


def test_resize_keeps_surviving_rank_queues():
    g = GranuleGroup("j", 4, [(0, None)] * 4)
    g.send(0, 1, "in-flight")
    with pytest.raises(RuntimeError):           # resize is a barrier too
        g.resize([(0, None)] * 2)
    assert g.recv(1, 0) == "in-flight"
    e0 = g.epoch
    g.resize([(0, None), (1, None)])            # shrink 4 -> 2
    assert g.size == 2 and g.epoch == e0 + 1
    g.send(1, 0, "post")
    assert g.recv(0, 1) == "post"
    e1 = g.epoch
    g.resize([(h, None) for h in (0, 0, 1, 1, 2, 2)])   # grow 2 -> 6
    assert g.size == 6 and g.epoch == e1 + 1
    assert g.granules[5].index == 5 and g.pending(5) == 0
    assert g.leader_of(2) == 4


# ---------------------------------------------------------------------------
# Live fabric (subprocess, 8 devices)
# ---------------------------------------------------------------------------
def test_preemption_evicts_checkpoints_and_resumes_bit_exact():
    print(run_sub("""
        import numpy as np
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.core.fabric import Fabric
        from repro.runtime.gang_workloads import TrainWorkload

        cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=8, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)

        def steps(wl, handle, k):
            for _ in range(k):
                wl.run_step(handle)

        # reference: uninterrupted 6-step run on a whole-fabric gang
        fab = Fabric(chips_per_host=2)
        h = fab.allocate("ref", 8)
        ref = TrainWorkload(cfg, ocfg, dcfg, total_steps=6)
        ref.bind(h); ref.init_state(h); steps(ref, h, 6)
        h.release()
        assert fab.idle_chips() == 8

        # interrupted: 3 steps, then a high-priority arrival forces
        # preempt (checkpoint + release); the victim resumes bit-exactly
        low = fab.allocate("low", 8, priority=0)
        wl = TrainWorkload(cfg, ocfg, dcfg, total_steps=6)
        wl.bind(low); wl.init_state(low); steps(wl, low, 3)
        victims = fab.preemption_plan(4, priority=5)
        assert victims == ["low"], victims
        snap = low.preempt(wl.state, wl.steps_done)
        assert fab.idle_chips() == 8 and low.status == "preempted"
        hi = fab.allocate("hi", 4, priority=5)
        hiwl = TrainWorkload(cfg, ocfg, dcfg, total_steps=2)
        hiwl.bind(hi); hiwl.init_state(hi); steps(hiwl, hi, 2)
        hi.release()
        state, step = low.resume()          # fingerprint-verified restore
        assert step == 3 and low.status == "running"
        wl.state = state; wl.bind(low)
        steps(wl, low, 3)
        np.testing.assert_allclose(ref.losses, wl.losses, atol=1e-6)
        low.release()
        assert fab.idle_chips() == 8 and not fab.gangs
        print("preempt-resume-ok", wl.losses)
    """))


def test_concurrent_train_and_serve_gangs_share_fabric():
    print(run_sub("""
        import numpy as np, jax
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.core.fabric import Fabric
        from repro.runtime.gang_workloads import ServeWorkload, TrainWorkload

        cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=8, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)

        fab = Fabric(chips_per_host=2)
        a = fab.allocate("train0", 4, priority=0)
        b = fab.allocate("serve0", 2, priority=1)
        assert a is not None and b is not None
        assert not (set(a.devices) & set(b.devices))
        assert fab.idle_chips() == 2
        ta = TrainWorkload(cfg, ocfg, dcfg, total_steps=3)
        ta.bind(a); ta.init_state(a)
        sb = ServeWorkload(cfg, prompt_len=8, new_tokens=3, batch=2,
                           max_len=16)
        sb.bind(b); sb.init_state(b)
        # interleave the two gangs step by step on one fabric
        while not (ta.done and sb.done):
            if not ta.done: ta.run_step(a)
            if not sb.done: sb.run_step(b)
        outs = [r.out for r in sb.requests]
        assert all(len(o) == 3 for o in outs), outs
        assert len(ta.losses) == 3 and np.isfinite(ta.losses).all()
        a.release(); b.release()
        assert fab.idle_chips() == 8 and not fab.gangs
        print("concurrent-ok", ta.losses, outs)
    """))


def test_shared_fabric_rescale_caps_and_serve_resume_fresh_loop():
    print(run_sub("""
        import numpy as np, jax
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.core.fabric import Fabric
        from repro.core.placement import LocalityScoredPolicy
        from repro.core.simulator import Job
        from repro.models import transformer as tf
        from repro.runtime.gang_workloads import workload_factory
        from repro.runtime.serve_loop import Request, ServeLoop
        from repro.runtime.train_loop import (FaabricTrainRuntime,
                                              RuntimeConfig)

        cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)

        # a scheduled rescale beyond shared-fabric capacity is skipped
        # (other tenants' chips are not ours to take), not a crash
        dcfg = DataConfig(vocab=128, seq_len=8, global_batch=12)
        fab = Fabric(chips_per_host=2)
        rt = FaabricTrainRuntime(cfg, ocfg, dcfg, RuntimeConfig(
            total_steps=4, checkpoint_every=100,
            ckpt_dir="/tmp/repro-t-shresc/a", rescale_at={2: 8}),
            devices=fab.devices[2:8], fabric=fab, job_id="t0")
        other = fab.allocate("tenant", 2, priority=1)
        out = rt.run(seed=0)[1]
        assert out["rescales"] == 0 and len(rt.devices) == 6
        rt.release(); other.release()
        # ...but a placeable partial grow (4 -> world+idle = 6) fires
        fab = Fabric(chips_per_host=2)
        rt = FaabricTrainRuntime(cfg, ocfg, dcfg, RuntimeConfig(
            total_steps=4, checkpoint_every=100,
            ckpt_dir="/tmp/repro-t-shresc/b", rescale_at={2: 8}),
            devices=fab.devices[:4], fabric=fab, job_id="t1")
        other = fab.allocate("tenant", 2)
        out = rt.run(seed=0)[1]
        assert out["rescales"] == 1 and len(rt.devices) == 6
        rt.release(); other.release()
        assert fab.idle_chips() == 8
        print("shared-rescale-ok")

        # run_trace with an explicit policy must not overwrite the
        # fabric engine's configured default
        fab2 = Fabric(chips_per_host=2, policy="locality")
        before = fab2.engine.default_policy
        fab2.run_trace([Job("a", "mpi-compute", 2, 50.0,
                            workload="train")],
                       workload_factory(cfg, ocfg, dcfg, train_steps=1),
                       policy="binpack")
        assert fab2.engine.default_policy is before
        assert isinstance(before, LocalityScoredPolicy)
        print("policy-unmutated-ok")

        # a serving snapshot restores into a FRESH ServeLoop (new driver
        # process): host-side request bookkeeping rides in the snapshot
        params = jax.jit(lambda k: tf.init_params(k, cfg))(
            jax.random.PRNGKey(0))
        mk = lambda: [Request(rid=i,
                              prompt=np.asarray([1,2,3,4,5,6,7,8],
                                                np.int32),
                              max_new_tokens=6) for i in range(2)]
        ref = [r.out for r in ServeLoop(cfg, params, max_len=32).run(mk())]
        l1 = ServeLoop(cfg, params, max_len=32)
        l1.start(mk()); l1.decode_step(); l1.decode_step()
        snap = l1.serve_state()
        l2 = ServeLoop(cfg, params, max_len=32)
        l2.load_serve_state(snap)
        rebuilt = l2._reqs                  # drained to None on finish
        assert rebuilt is not None and not l2.done
        while l2.decode_step():
            pass
        assert [r.out for r in rebuilt] == ref
        print("fresh-serve-resume-ok")
    """))


def test_hetero_fabric_run_trace_matches_prediction():
    # mixed-generation fleet acceptance: a Fabric with per-host speeds
    # (half the hosts at s=0.5) runs a real train/serve trace whose
    # completion order matches predict_trace under the same
    # heterogeneous capacities/speeds — and placements favour the fast
    # generation for the compute-bound gang
    print(run_sub("""
        import numpy as np
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.core.fabric import Fabric
        from repro.core.simulator import Job, hetero_speeds
        from repro.runtime.gang_workloads import workload_factory

        cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=8, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)

        # 8 devices, 2 chips/host -> 4 hosts; hosts 0-1 old generation
        speeds = hetero_speeds(4, slow_fraction=0.5, slow=0.5)
        fab = Fabric(chips_per_host=2, policy="locality",
                     speeds=list(speeds))
        assert fab.engine.heterogeneous
        jobs = [
            Job("train-net", "mpi-network", 4, 120.0, arrival=0.0,
                priority=0, workload="train"),
            Job("train-cmp", "mpi-compute", 4, 120.0, arrival=0.0,
                priority=0, workload="train"),
            Job("serve-0", "omp", 2, 60.0, arrival=1.0, priority=1,
                workload="serve"),
        ]
        pred = fab.predict_trace(jobs, preempt=True)
        starts = {a.payload["job"]: a.payload["placement"]
                  for a in pred.actions if a.kind == "start"}
        # first-placed network gang takes the fast hosts whole; the
        # compute gang then splits across the slow generation
        fast = {h for h, s in enumerate(speeds) if s == 1.0}
        assert {h for h, _ in starts["train-net"]} <= fast, starts
        ex = fab.run_trace(jobs, workload_factory(cfg, ocfg, dcfg,
                                                  train_steps=3,
                                                  serve_tokens=3),
                           preempt=True)
        assert ex.result.finish_order == pred.finish_order, (
            ex.result.finish_order, pred.finish_order)
        live_starts = {a.payload["job"]: a.payload["placement"]
                       for a in ex.result.actions if a.kind == "start"}
        assert live_starts == starts      # placement-for-placement
        assert fab.idle_chips() == fab.engine.total_chips
        print("hetero-trace-ok", ex.result.finish_order)
    """))


def test_sharded_fabric_run_trace_matches_prediction():
    # acceptance: a Fabric built over a ShardedPlacementEngine executes
    # a real trace whose completion order matches predict_trace (the
    # clone keeps the sharded architecture), and a single-shard fabric
    # is placement-for-placement identical to the centralised one
    print(run_sub("""
        from repro.core.fabric import Fabric
        from repro.core.placement import ShardedPlacementEngine
        from repro.core.simulator import Job
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.runtime.gang_workloads import workload_factory

        cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=8, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        jobs = [
            Job("train-low", "mpi-compute", 6, 300.0, arrival=0.0,
                priority=0, workload="train"),
            Job("serve-0", "omp", 2, 120.0, arrival=0.0, priority=1,
                workload="serve"),
            Job("train-hi", "mpi-compute", 6, 150.0, arrival=3.0,
                priority=5, workload="train"),
        ]
        # 8 devices, 2 chips/host -> 4 hosts in 2 shards of 2
        fab = Fabric(chips_per_host=2, shard_hosts=2)
        assert isinstance(fab.engine, ShardedPlacementEngine)
        assert fab.engine.n_shards == 2
        pred = fab.predict_trace(jobs, preempt=True)
        assert pred.preemptions >= 1
        ex = fab.run_trace(jobs, workload_factory(cfg, ocfg, dcfg,
                                                  train_steps=3,
                                                  serve_tokens=3),
                           preempt=True)
        assert ex.result.finish_order == pred.finish_order, (
            ex.result.finish_order, pred.finish_order)
        assert ex.result.preemptions == pred.preemptions
        assert fab.idle_chips() == fab.engine.total_chips
        print("sharded-trace-ok", ex.result.finish_order)

        # single shard covering the fleet == centralised, live
        one = Fabric(chips_per_host=2, shard_hosts=4)
        central = Fabric(chips_per_host=2)
        p1 = one.predict_trace(jobs, preempt=True)
        p2 = central.predict_trace(jobs, preempt=True)
        assert p1.actions == p2.actions
        print("single-shard-parity-ok")
    """))


def test_fleet_churn_hard_fail_resumes_bit_exact_live():
    # fleet-churn acceptance: a running gang's host hard-fails mid-run;
    # live execution rolls it back to its last real snapshot and resumes
    # bit-exactly (fingerprint-verified), the trace Action log matches
    # predict_trace event-for-event (central AND sharded), a reclaim
    # drains gracefully through the evacuation planner, and a join pulls
    # staged spare devices into the pool
    print(run_sub("""
        import jax
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.core.fabric import Fabric
        from repro.core.fleet import FleetEvent
        from repro.core.simulator import Job
        from repro.runtime.gang_workloads import workload_factory

        cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=8, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        jobs = [
            Job("train-a", "mpi-compute", 4, 200.0, arrival=0.0,
                workload="train"),
            Job("serve-0", "omp", 2, 120.0, arrival=0.0, priority=1,
                workload="serve"),
        ]
        devs = jax.devices()
        # 6 devices in the fabric (3 hosts of 2), 2 staged as spares
        events = [FleetEvent(6.0, "fail", hosts=[0]),
                  FleetEvent(10.0, "join", capacities=[2])]
        for shard_hosts in (None, 2):
            fab = Fabric(devices=devs[:6], chips_per_host=2,
                         shard_hosts=shard_hosts, spares=devs[6:])
            pred = fab.predict_trace(jobs, preempt=True,
                                     fleet_events=events,
                                     checkpoint_interval=4.0)
            assert pred.recoveries >= 1, pred.recoveries
            ex = fab.run_trace(
                jobs, workload_factory(cfg, ocfg, dcfg, train_steps=3,
                                       serve_tokens=3),
                preempt=True, fleet_events=events,
                checkpoint_interval=4.0)
            res = ex.result
            # live Action log == simulated Action log, event for event
            assert res.actions == pred.actions
            assert res.recoveries == pred.recoveries >= 1
            assert res.finish_order == pred.finish_order
            # the failed gang took real checkpoints, lost its host, and
            # resumed bit-exactly (resume() fingerprint-verifies)
            victim = next(a.payload["job"] for a in res.actions
                          if a.kind == "recover")
            rec = ex.live[victim]
            assert rec["failures"] >= 1
            assert rec["checkpoints"] >= 1
            assert rec["resumes_verified"] >= 1
            assert ex.live[victim]["steps"] >= 3
            # every job still finished on the churned fleet
            assert set(res.finish_order) == {j.job_id for j in jobs}
            label = "central" if shard_hosts is None else "sharded"
            print(f"churn-fail-{label}-ok", res.finish_order)

        # graceful reclaim: with free capacity elsewhere, the drained
        # gang evacuates through the planner (live reshard, no rollback)
        small = [Job("train-a", "mpi-compute", 2, 150.0, arrival=0.0,
                     workload="train"),
                 Job("serve-0", "omp", 2, 120.0, arrival=0.0,
                     priority=1, workload="serve")]
        fab = Fabric(devices=devs[:6], chips_per_host=2,
                     spares=devs[6:])
        events = [FleetEvent(5.0, "reclaim", hosts=[2], drain_s=30.0)]
        pred = fab.predict_trace(small, preempt=True,
                                 fleet_events=events)
        ex = fab.run_trace(
            small, workload_factory(cfg, ocfg, dcfg, train_steps=3,
                                    serve_tokens=3),
            preempt=True, fleet_events=events)
        assert ex.result.actions == pred.actions
        assert ex.result.evacuations == pred.evacuations >= 1
        assert ex.result.recoveries == 0
        assert set(ex.result.finish_order) == {j.job_id for j in small}
        print("churn-drain-ok", ex.result.evacuations)
    """))


def test_run_trace_preempts_and_matches_simulator_prediction():
    # the acceptance trace: >=2 priority classes, a preemption with
    # bit-exact resume, a concurrent train+serve pair, and live per-job
    # completion order == the simulator's prediction under one policy
    print(run_sub("""
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.core.fabric import Fabric
        from repro.core.simulator import Job
        from repro.runtime.gang_workloads import workload_factory

        cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=8, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        jobs = [
            Job("train-low", "mpi-compute", 6, 300.0, arrival=0.0,
                priority=0, workload="train"),
            Job("serve-0", "omp", 2, 120.0, arrival=0.0, priority=1,
                workload="serve"),
            Job("train-hi", "mpi-compute", 6, 150.0, arrival=3.0,
                priority=5, workload="train"),
        ]
        fab = Fabric(chips_per_host=2)
        pred = fab.predict_trace(jobs, preempt=True)
        assert pred.preemptions >= 1
        ex = fab.run_trace(jobs, workload_factory(cfg, ocfg, dcfg,
                                                  train_steps=3,
                                                  serve_tokens=3),
                           preempt=True)
        res = ex.result
        assert res.finish_order == pred.finish_order, (
            res.finish_order, pred.finish_order)
        assert res.preemptions == pred.preemptions >= 1
        assert ex.live["train-low"]["preemptions"] >= 1
        assert ex.live["train-low"]["resumes_verified"] >= 1
        kinds = {j: r["workload"] for j, r in ex.live.items()}
        assert kinds["serve-0"] == "ServeWorkload"
        assert kinds["train-hi"] == "TrainWorkload"
        ms = ex.job_makespans(jobs)
        assert set(ms) == {j.job_id for j in jobs}
        assert all(v > 0 for v in ms.values())
        # the preemptor finished first despite arriving last
        assert res.finish_order[0] == "train-hi"
        assert fab.idle_chips() == fab.engine.total_chips
        assert not fab.gangs
        print("trace-acceptance-ok", res.finish_order, ms)
    """))


def test_run_trace_delta_checkpoints_match_prediction():
    # delta-everything data plane (ISSUE 6): with a configured delta
    # fraction the simulator charges cheaper non-rebase checkpoints,
    # the live gang ships diffsync chains, a hard failure replays
    # base+deltas bit-exactly, and live Action logs still match the
    # prediction event for event
    print(run_sub("""
        import jax
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.core.fabric import Fabric
        from repro.core.fleet import FleetEvent
        from repro.core.placement import CostModel
        from repro.core.simulator import Job
        from repro.runtime.gang_workloads import workload_factory

        cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=8, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        jobs = [Job("train-a", "mpi-compute", 4, 40.0, arrival=0.0,
                    workload="train")]
        devs = jax.devices()
        events = [FleetEvent(6.0, "fail", hosts=[1]),
                  FleetEvent(10.0, "join", capacities=[2])]
        fab = Fabric(devices=devs[:6], chips_per_host=2, spares=devs[6:])
        cm = fab.engine.cost_model
        cm.ckpt_delta_fraction = 0.1
        cm.ckpt_rebase_every = 4
        pred = fab.predict_trace(jobs, preempt=True, fleet_events=events,
                                 checkpoint_interval=2.0)
        assert pred.recoveries >= 1
        ex = fab.run_trace(
            jobs, workload_factory(cfg, ocfg, dcfg, train_steps=3,
                                   serve_tokens=3),
            preempt=True, fleet_events=events, checkpoint_interval=2.0)
        res = ex.result
        assert res.actions == pred.actions
        assert res.recoveries == pred.recoveries >= 1
        rec = ex.live["train-a"]
        # the gang shipped real deltas and recovered through the chain
        assert rec.get("delta_checkpoints", 0) >= 1, rec
        assert rec["ckpt_bytes"] < rec["ckpt_full_bytes"], rec
        assert rec["resumes_verified"] >= 1
        frac = cm.observed_delta_fraction()
        assert frac is not None and 0 < frac < 1.0
        print("delta-live-ok", rec["checkpoints"],
              rec["delta_checkpoints"], round(frac, 4))
    """))


def test_shrink_before_rollback_live_matches_prediction():
    # risk-aware recovery, live: a rack fail strands the training gang;
    # instead of rolling back it reshards onto surviving chips (live
    # reshard from a replica, no snapshot restore), then regrows to its
    # submitted width when the replacement host joins — Action log
    # bit-identical to predict_trace throughout
    print(run_sub("""
        import jax
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.core.fabric import Fabric
        from repro.core.fleet import FleetEvent
        from repro.core.placement import CostModel
        from repro.core.simulator import Job
        from repro.runtime.gang_workloads import workload_factory

        cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=8, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
        jobs = [
            Job("train-a", "mpi-compute", 4, 200.0, arrival=0.0,
                workload="train"),
            Job("serve-0", "omp", 2, 120.0, arrival=0.0, priority=1,
                workload="serve"),
        ]
        devs = jax.devices()
        events = [FleetEvent(6.0, "fail", hosts=[0]),
                  FleetEvent(10.0, "join", capacities=[2])]
        fab = Fabric(devices=devs[:6], chips_per_host=2,
                     spares=devs[6:],
                     cost_model=CostModel(risk_tau_s=4.0))
        pred = fab.predict_trace(jobs, fleet_events=events,
                                 checkpoint_interval=4.0,
                                 shrink_recovery=True)
        assert pred.shrinks >= 1 and pred.regrows >= 1, \\
            (pred.shrinks, pred.regrows)
        assert pred.recoveries == 0
        ex = fab.run_trace(
            jobs, workload_factory(cfg, ocfg, dcfg, train_steps=3,
                                   serve_tokens=3),
            fleet_events=events, checkpoint_interval=4.0,
            shrink_recovery=True)
        res = ex.result
        assert res.actions == pred.actions
        assert res.shrinks == pred.shrinks
        assert res.regrows == pred.regrows
        assert res.recoveries == 0 and res.lost_work_s == 0.0
        assert res.finish_order == pred.finish_order
        rec = ex.live["train-a"]
        assert rec.get("shrinks", 0) >= 1
        assert rec.get("regrows", 0) >= 1
        assert rec["steps"] >= 3          # training completed resharded
        assert set(res.finish_order) == {j.job_id for j in jobs}
        print("shrink-live-ok", res.shrinks, res.regrows)
    """))


def test_adaptive_cadence_rederives_interval_from_observed_deltas():
    # satellite: the live runner folds the observed delta fraction into
    # the Young/Daly cadence after each rebase window — tau tightens by
    # sqrt(eff_observed / eff_configured) when deltas run cheap
    print(run_sub("""
        from repro.configs.registry import reduced_config
        from repro.data.pipeline import DataConfig
        from repro.optim.adamw import AdamWConfig
        from repro.core.fabric import Fabric
        from repro.core.placement import CostModel
        from repro.core.simulator import Job
        from repro.runtime.gang_workloads import workload_factory

        cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
        dcfg = DataConfig(vocab=128, seq_len=8, global_batch=8)
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=40)
        jobs = [Job("train-a", "mpi-compute", 4, 400.0, arrival=0.0,
                    workload="train")]
        cm = CostModel(ckpt_rebase_every=3)
        fab = Fabric(chips_per_host=2, cost_model=cm)
        ex = fab.run_trace(
            jobs, workload_factory(cfg, ocfg, dcfg, train_steps=10),
            checkpoint_interval=8.0, adapt_cadence=True)
        rec = ex.live["train-a"]
        assert rec["checkpoints"] >= 3
        frac = cm.observed_delta_fraction()
        assert frac is not None and 0.0 < frac < 1.0
        # the interval was re-derived and recorded, and it tightened
        # (observed deltas are cheaper than the configured full cost);
        # tau = tau0 * sqrt(eff/eff0) with the fraction observed at the
        # rebase window, so the implied effective cost sits between the
        # all-delta floor and the configured full cost
        assert "adapted_interval_s" in rec, sorted(rec)
        tau = rec["adapted_interval_s"]
        assert 0.0 < tau < 8.0
        eff0 = cm.effective_checkpoint_cost_s()
        implied = eff0 * (tau / 8.0) ** 2
        assert cm.effective_checkpoint_cost_s(fraction=0.0) \\
            <= implied <= eff0
        print("adapt-cadence-ok", round(tau, 3))
    """))
