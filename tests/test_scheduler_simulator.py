"""Scheduler allocation invariants (property-based) and simulator
reproduction of the paper's qualitative results (Fig 10/11/14)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import simulator as S
from repro.core.scheduler import ClusterState


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(1, 20), min_size=1, max_size=30),
       st.integers(2, 8), st.integers(4, 16))
def test_granular_alloc_conserves_chips(sizes, chips, hosts):
    cs = ClusterState(hosts, chips)
    allocs = []
    for i, n in enumerate(sizes):
        a = cs.alloc_granular(f"j{i}", n)
        if a is not None:
            assert a.n == n
            allocs.append(a)
        assert cs.idle_chips() == cs.total_chips - sum(x.n for x in allocs)
        assert (cs.free >= 0).all()
    for a in allocs:
        cs.release(a)
    assert cs.idle_chips() == cs.total_chips


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 64), st.integers(1, 8))
def test_slice_alloc_wastes_fragmentation(n, k):
    """Slice allocation rounds up to whole slices — the paper's
    fragmentation waste."""
    cs = ClusterState(8, 8)
    slice_size = 8 // k if 8 % k == 0 else 1
    a = cs.alloc_slices("j", n, slice_size)
    if a is not None:
        assert a.n >= n                      # over-allocation = waste
        assert a.n % slice_size == 0


def test_migration_plan_defragments():
    cs = ClusterState(4, 8)
    fillers = [cs.alloc_granular(f"f{i}", 6) for i in range(4)]
    frag = cs.alloc_granular("frag", 8)      # forced to span hosts
    assert frag.fragmentation() > 1
    for f in fillers[:2]:
        cs.release(f)
    plans = cs.migration_plan([frag])
    assert plans and plans[0][0] == "frag"
    new = cs.apply_migration(frag, plans[0][1])
    assert new.fragmentation() < frag.fragmentation()
    assert new.n == 8


def test_cross_host_fraction():
    cs = ClusterState(2, 8)
    a = cs.alloc_granular("a", 8)            # fits one host
    assert a.cross_host_fraction() == 0.0
    b = cs.alloc_granular("b", 8)
    cs.release(a)
    cs.release(b)


# ---------------------------------------------------------------------------
# simulator: the paper's headline results, qualitatively
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mpi_results():
    jobs = S.generate_trace(100, "mpi-compute", seed=0)
    return S.run_baselines(jobs, hosts=32)


def test_fig10_mpi_faabric_beats_coarse_baselines(mpi_results):
    fa = mpi_results["faabric"].makespan
    # paper: 13-23% lower makespan vs coarse slices; on par with 8-ctr
    assert fa < mpi_results["1-ctr-per-vm"].makespan * 0.9
    assert fa < mpi_results["2-ctr-per-vm"].makespan
    assert abs(fa - mpi_results["8-ctr-per-vm"].makespan) \
        / mpi_results["8-ctr-per-vm"].makespan < 0.1


def test_fig10_idle_chips_lower_for_faabric(mpi_results):
    fa = np.median(mpi_results["faabric"].idle_cdf())
    coarse = np.median(mpi_results["1-ctr-per-vm"].idle_cdf())
    assert fa <= coarse + 0.05


def test_fig10_omp_overcommit_baseline_worst(mpi_results):
    jobs = S.generate_trace(100, "omp", seed=0)
    res = S.run_baselines(jobs, hosts=32)
    fa = res["faabric"].makespan
    # paper: Faabric 38% lower than 8-ctr-per-vm; higher than mid slices
    assert fa < res["8-ctr-per-vm"].makespan * 0.8
    assert fa > res["4-ctr-per-vm"].makespan


def test_fig11_scaling_constant_per_host_throughput():
    makespans = {}
    for hosts, njobs in ((16, 50), (32, 100), (64, 200)):
        jobs = S.generate_trace(njobs, "mpi-compute", seed=1)
        makespans[hosts] = S.Simulator(hosts, 8, "granular").run(jobs).makespan
    ms = list(makespans.values())
    assert max(ms) / min(ms) < 1.6   # roughly flat (paper: within 5-10%)


def test_fig14_migration_helps_network_bound():
    jobs = S.generate_trace(60, "mpi-network", seed=2)
    with_mig = S.Simulator(16, 8, "granular", migrate=True).run(jobs)
    without = S.Simulator(16, 8, "granular", migrate=False).run(jobs)
    assert with_mig.migrations > 0
    assert with_mig.makespan <= without.makespan * 1.02


# ---------------------------------------------------------------------------
# priority preemption (rFaaS-style lease reclamation)
# ---------------------------------------------------------------------------
def _blocked_high_priority_trace():
    return [
        S.Job("low-0", "mpi-compute", 8, 400.0, arrival=0.0, priority=0),
        S.Job("low-1", "mpi-compute", 8, 400.0, arrival=0.0, priority=0),
        S.Job("hi-0", "mpi-compute", 12, 200.0, arrival=5.0, priority=5),
    ]


def test_preemption_lets_high_priority_jump_the_cluster():
    res = S.Simulator(2, 8, "granular", preempt=True).run(
        _blocked_high_priority_trace())
    assert res.preemptions >= 1
    assert res.finish_order[0] == "hi-0"
    # victims resume from their checkpoint and still finish
    assert set(res.finish_order) == {"hi-0", "low-0", "low-1"}
    kinds = [a.kind for a in res.actions]
    assert "preempt" in kinds and "resume" in kinds
    # without preemption the high-priority job waits for the hogs
    base = S.Simulator(2, 8, "granular", preempt=False).run(
        _blocked_high_priority_trace())
    assert base.preemptions == 0 and base.finish_order[-1] == "hi-0"
    hi = next(j for j in _blocked_high_priority_trace()
              if j.job_id == "hi-0")
    assert res.makespans([hi])["hi-0"] < base.makespans([hi])["hi-0"]


def test_preemption_conserves_chips_and_work():
    jobs = S.mixed_trace(40, seed=3, arrival_rate=0.2,
                         priority_classes=[(0, 0.8), (5, 0.2)])
    sim = S.Simulator(8, 8, "granular", preempt=True)
    res = sim.run(jobs)
    assert sim.engine.idle_chips() == sim.engine.total_chips
    assert len(res.finish_order) == len(jobs)     # every job completes
    # preempted progress is preserved: makespan stays sane vs no-preempt
    base = S.Simulator(8, 8, "granular", preempt=False).run(
        S.mixed_trace(40, seed=3, arrival_rate=0.2,
                      priority_classes=[(0, 0.8), (5, 0.2)]))
    assert res.makespan < base.makespan * 1.5


def test_idle_cdf_backlogged_only_both_ways():
    # samples: backlog era up to drain at t=10, then a long idle tail
    res = S.TraceResult(
        makespan=100.0, exec_times=[], migrations=0, waited=[],
        idle_samples=[(0.0, 0.2), (5.0, 0.4), (10.0, 0.3),
                      (50.0, 0.9), (100.0, 1.0)],
        queue_drain_time=10.0)
    backlog = res.idle_cdf(backlogged_only=True)
    full = res.idle_cdf(backlogged_only=False)
    # the backlog-era CDF only sees fragmentation-waste samples
    assert backlog.max() <= 0.4 and set(np.unique(backlog)) \
        <= {0.2, 0.3, 0.4}
    # the full CDF is dominated by the drain-down tail
    assert full.max() == 1.0
    assert np.median(full) > np.median(backlog)
    # degenerate shapes: no drain recorded -> backlogged == full;
    # a single sample collapses to that value; empty -> [0.0]
    res.queue_drain_time = 0.0
    assert np.array_equal(res.idle_cdf(True), res.idle_cdf(False))
    one = S.TraceResult(makespan=1.0, exec_times=[], migrations=0,
                        waited=[], idle_samples=[(0.0, 0.7)])
    assert list(one.idle_cdf()) == [0.7]
    empty = S.TraceResult(makespan=0.0, exec_times=[], migrations=0,
                          waited=[], idle_samples=[])
    assert list(empty.idle_cdf()) == [0.0]
    # drain before every sample: the guard falls back to the first
    # sample instead of an empty CDF
    late = S.TraceResult(makespan=9.0, exec_times=[], migrations=0,
                         waited=[],
                         idle_samples=[(5.0, 0.5), (9.0, 0.8)],
                         queue_drain_time=1.0)
    assert list(late.idle_cdf(True)) == [0.5]


def test_queue_order_deterministic_under_equal_priority_and_arrival():
    """Equal priority + equal arrival time must resolve by submission
    order — on a one-host cluster the start order IS the job order, and
    repeated runs are identical."""
    jobs = [S.Job(f"j{i}", "mpi-compute", 8, 80.0, arrival=0.0,
                  priority=3) for i in range(6)]
    r1 = S.Simulator(1, 8, "granular").run(list(jobs))
    starts = [a.payload["job"] for a in r1.actions if a.kind == "start"]
    assert starts == [f"j{i}" for i in range(6)]
    assert r1.finish_order == starts
    r2 = S.Simulator(1, 8, "granular").run(list(jobs))
    assert r1.finish_order == r2.finish_order \
        and r1.makespan == r2.makespan
    # same ties arriving *late* (one arrival event carrying equal
    # priority/arrival) also resolve by submission order
    late = [S.Job(f"k{i}", "mpi-compute", 8, 80.0, arrival=2.0,
                  priority=3) for i in range(4)]
    r3 = S.Simulator(1, 8, "granular").run(list(late))
    starts = [a.payload["job"] for a in r3.actions if a.kind == "start"]
    assert starts == [f"k{i}" for i in range(4)]


def test_preemption_deterministic_and_actions_shared_vocabulary():
    jobs = lambda: S.mixed_trace(30, seed=5, arrival_rate=0.3,
                                 priority_classes=[(0, 0.7), (3, 0.3)])
    r1 = S.Simulator(4, 8, "granular", preempt=True).run(jobs())
    r2 = S.Simulator(4, 8, "granular", preempt=True).run(jobs())
    assert r1.finish_order == r2.finish_order
    assert r1.makespan == r2.makespan
    from repro.core.control import Action
    assert all(isinstance(a, Action) for a in r1.actions)
    assert {a.kind for a in r1.actions} <= {
        "start", "resume", "preempt", "migrate", "finish"}
