"""Telemetry plane: no-op bit-identity, recorder semantics, Action
round-trips, Chrome-trace export, and predicted-vs-live diffing
(DESIGN.md §14).

The load-bearing contract is the first block: with the default no-op
recorder, every instrumented path — simulator, placement engines
(central AND sharded), straggler control — produces output bit-identical
to a run with telemetry enabled, because recording only ever *observes*
(the ``risk_tau_s=None`` opt-in pattern).
"""
import json

import numpy as np
import pytest

from repro.core import fleet as F
from repro.core import simulator as S
from repro.core import telemetry
from repro.core.control import Action, ControlPointRunner, \
    EwmaStragglerDetector
from repro.core.placement import CostModel, PlacementEngine, \
    ShardedPlacementEngine


@pytest.fixture(autouse=True)
def _noop_default():
    """Every test starts and ends on the module-level no-op recorder."""
    telemetry.disable()
    yield
    telemetry.disable()


def _churn_sim(sched="central", shrink=False):
    return S.Simulator(8, 4, "granular", migrate=True, policy="binpack",
                      sched=sched, shard_hosts=4,
                      checkpoint_interval=6.0, shrink_recovery=shrink)


def _churn_run(sched="central", shrink=False, seed=3):
    jobs = S.mixed_trace(14, seed=seed, chips_per_host=4,
                         arrival_rate=0.5)
    events = F.churn_schedule("spot-heavy", 8, 4, 60.0, seed=seed,
                              rate=0.05)
    return _churn_sim(sched, shrink).run(jobs, fleet_events=events)


# ---- no-op fast path: bit-identity ------------------------------------------

@pytest.mark.parametrize("sched", ["central", "sharded"])
def test_noop_recorder_is_bit_identical_on_pinned_trace(sched):
    # telemetry off vs on over the same pinned churn trace: Action
    # streams, makespan and every TraceResult counter must match
    # exactly — recording never perturbs the scheduler
    off = _churn_run(sched)
    with telemetry.recording() as tel:
        on = _churn_run(sched)
    assert off.actions == on.actions
    assert off.makespan == on.makespan
    assert off.finish_order == on.finish_order
    assert off.lost_work_s == on.lost_work_s
    assert off.straggler_migrations == on.straggler_migrations
    # and the enabled run actually recorded the timeline
    assert tel.summary()["spans_total"] > 0
    assert tel.counters["sim.runs"] == 1


def test_disabled_recorder_records_nothing():
    tel = telemetry.get()
    assert not tel.enabled
    with tel.span("x", track="t", a=1):
        pass
    tel.span_at("y", 0.0, 1.0)
    tel.instant("z", t=0.5)
    tel.count("c")
    tel.gauge("g", 2.0)
    tel.observe("h", 0.1)
    tel.step_time("cpu", "train", 0.2)
    tel.record_actions([Action("start", {"job": "a", "t": 0.0})])
    assert tel.spans == [] and tel.instants == []
    assert tel.counters == {} and tel.gauges == {}
    assert tel.histograms == {} and tel.step_times == {}


def test_recording_scope_restores_previous_recorder():
    assert telemetry.get() is not telemetry.enable()  # installs live
    live = telemetry.get()
    with telemetry.recording() as inner:
        assert telemetry.get() is inner
    assert telemetry.get() is live
    telemetry.disable()
    assert not telemetry.get().enabled


# ---- Action round-trip ------------------------------------------------------

def test_every_simulated_action_kind_round_trips_through_json():
    # churn + shrink-recovery + straggler-free mixed trace covers the
    # full Action vocabulary the simulator emits
    res = _churn_run("central", shrink=True)
    kinds = {a.kind for a in res.actions}
    assert {"start", "finish", "checkpoint"} <= kinds
    for a in res.actions:
        wire = json.loads(json.dumps(a.to_dict()))
        back = Action.from_dict(wire)
        assert back.kind == a.kind
        assert back.payload == telemetry._plain(a.payload)


def test_action_to_dict_coerces_numpy_payloads():
    a = Action("migrate", {"t": np.float64(1.5), "job": "j",
                           "placement": [(np.int64(0), np.int32(4))],
                           "hosts": np.array([1, 2])})
    wire = json.loads(json.dumps(a.to_dict()))
    assert wire == {"kind": "migrate",
                    "payload": {"t": 1.5, "job": "j",
                                "placement": [[0, 4]], "hosts": [1, 2]}}
    assert Action.from_dict(wire).payload["t"] == 1.5


# ---- recorder basics + Chrome export ----------------------------------------

def test_recorder_spans_counters_histograms_and_chrome_trace():
    with telemetry.recording() as tel:
        with tel.span("placement.reserve", track="sched", n=3):
            pass
        tel.span_at("run", 1.0, 5.0, track="gang:a", clock="virtual")
        tel.instant("action.start", t=1.0, track="gang:a",
                    clock="virtual", job="a")
        tel.instant("fleet.join", t=2.0, track="host:1", clock="virtual")
        tel.count("sim.actions", 7)
        tel.gauge("serve.queue_depth", 4, t=0.5)
        for v in (1e-5, 1e-3, 0.1):
            tel.observe("placement.decision_latency_s", v)
    s = tel.summary()
    assert s["spans_total"] == 2 and s["instants_total"] == 2
    assert s["counters"]["sim.actions"] == 7
    hist = s["histograms"]["placement.decision_latency_s"]
    assert hist["count"] == 3
    assert hist["min"] == 1e-5 and hist["max"] == 0.1

    trace = tel.to_chrome_trace()
    events = trace["traceEvents"]
    json.dumps(trace)                       # Perfetto-loadable JSON
    by_ph = {}
    for e in events:
        by_ph.setdefault(e["ph"], []).append(e)
    # virtual gang span in pid 1, host instant in pid 2, wall span in 10
    run = next(e for e in by_ph["X"] if e["name"] == "run")
    assert run["pid"] == 1 and run["dur"] == 4e6
    join = next(e for e in by_ph["i"] if e["name"] == "fleet.join")
    assert join["pid"] == 2
    wall = next(e for e in by_ph["X"] if e["name"] == "placement.reserve")
    assert wall["pid"] == 10 and wall["cat"] == "placement"
    # gauges AND counter totals render as 'C' samples with a layer cat
    assert any(e["name"] == "serve.queue_depth" and e["cat"] == "serve"
               for e in by_ph["C"])
    assert any(e["name"] == "sim.actions" and e["args"]["sim.actions"] == 7
               for e in by_ph["C"])
    # track names registered as thread metadata
    names = {e["args"]["name"] for e in by_ph["M"] if
             e["name"] == "thread_name"}
    assert {"gang:a", "host:1", "sched"} <= names


def test_spans_from_actions_builds_run_segments():
    actions = [
        Action("start", {"job": "a", "t": 0.0}),
        Action("preempt", {"job": "a", "t": 2.0}),
        Action("resume", {"job": "a", "t": 3.0}),
        Action("finish", {"job": "a", "t": 7.0}),
        Action("join", {"hosts": [4], "t": 1.0}),
        Action("start", {"job": "b", "t": 5.0}),   # left open
    ]
    spans, instants = telemetry.spans_from_actions(actions)
    segs = [(s["t0"], s["t1"], s["attrs"]["closed_by"]) for s in spans
            if s["track"] == "gang:a"]
    assert segs == [(0.0, 2.0, "preempt"), (3.0, 7.0, "finish")]
    b = next(s for s in spans if s["track"] == "gang:b")
    assert b["attrs"]["closed_by"] == "end-of-trace" and b["t1"] == 7.0
    assert any(i["track"] == "host:4" and i["name"] == "fleet.join"
               for i in instants)
    assert all(i["clock"] == "virtual" for i in instants)


# ---- diff_traces ------------------------------------------------------------

def test_diff_traces_zero_divergence_on_identical_streams():
    res = _churn_run()
    diff = telemetry.diff_traces(res, res)
    assert diff["divergences"] == 0
    assert diff["first_divergence"] is None
    assert diff["aligned"] == len(res.actions)
    for ph in diff["phase_error"].values():
        assert ph["max_abs_dt_s"] == 0.0
        assert ph["span_rel_error"] == 0.0


def test_diff_traces_reports_first_divergence_with_context():
    pred = [Action("start", {"job": "a", "t": 0.0}),
            Action("checkpoint", {"job": "a", "t": 2.0}),
            Action("finish", {"job": "a", "t": 5.0})]
    live = [pred[0],
            Action("migrate", {"job": "a", "t": 2.5}),   # extra event
            pred[1],
            Action("finish", {"job": "a", "t": 5.5})]
    diff = telemetry.diff_traces(pred, live)
    assert diff["divergences"] == 1
    first = diff["first_divergence"]
    assert first["op"] == "insert"
    assert first["live"][0]["kind"] == "migrate"
    assert first["context_before"][-1]["kind"] == "start"
    # aligned finish pair still contributes phase timing error
    assert diff["phase_error"]["finish"]["max_abs_dt_s"] == \
        pytest.approx(0.5)


def test_diff_traces_phase_error_measures_time_skew():
    pred = [Action("start", {"job": j, "t": float(i)})
            for i, j in enumerate("abc")]
    live = [Action("start", {"job": j, "t": float(i) * 1.1})
            for i, j in enumerate("abc")]
    diff = telemetry.diff_traces(pred, live)
    assert diff["divergences"] == 0
    ph = diff["phase_error"]["start"]
    assert ph["count"] == 3
    assert ph["max_abs_dt_s"] == pytest.approx(0.2)
    assert ph["span_rel_error"] == pytest.approx(0.1)


# ---- placement + calibration ------------------------------------------------

@pytest.mark.parametrize("engine_fn", [
    lambda: PlacementEngine(8, 4),
    lambda: ShardedPlacementEngine(8, 4, hosts_per_shard=4)],
    ids=["central", "sharded"])
def test_placement_decisions_record_latency_and_attrs(engine_fn):
    with telemetry.recording() as tel:
        eng = engine_fn()
        alloc = eng.reserve(6)
        assert alloc is not None
    hist = tel.histograms["placement.decision_latency_s"]
    assert hist.n == 1
    span = next(s for s in tel.spans
                if s["name"] == "placement.reserve")
    assert span["track"] == "sched"
    assert span["attrs"]["placed"] is True
    assert span["attrs"]["n"] == 6
    assert tel.counters["placement.reserve"] == 1


def test_step_time_aggregates_feed_cost_model():
    model = CostModel()
    with telemetry.recording() as tel:
        for s in (0.1, 0.2, 0.3):
            tel.step_time("cpu", "train", s)
        tel.step_time("tpu", "serve", 0.05)
        assert tel.feed_cost_model(model) == 2
    assert model.observed_step_time("cpu", "train") == \
        pytest.approx(0.2)
    agg = model.observed_step_times()
    assert agg[("cpu", "train")][0] == 3
    assert agg[("tpu", "serve")] == (1, pytest.approx(0.05))
    # blind to objects without the hook
    assert telemetry.Telemetry().feed_cost_model(object()) == 0


# ---- straggler surfacing ----------------------------------------------------

def test_straggler_detector_counts_flags_and_runner_migrations():
    with telemetry.recording() as tel:
        det = EwmaStragglerDetector(alpha=0.5, factor=1.5, patience=2)
        runner = ControlPointRunner(straggler=det)
        for step in range(6):
            runner.on_step(step, 0.1, 4)
        acts = []
        for step in range(6, 10):
            acts += runner.on_step(step, 10.0, 4)
    migrations = [a for a in acts if a.kind == "migrate"
                  and a.payload.get("reason") == "straggler"]
    assert migrations and runner.straggler_migrations == len(migrations)
    assert det.flagged >= 1
    assert tel.counters["straggler.flagged"] == det.flagged
    assert tel.counters["straggler.migrations"] == \
        runner.straggler_migrations
    assert tel.gauges["straggler.ewma_s"] > 0
    assert any(i["name"] == "straggler.flag" for i in tel.instants)


def test_trace_result_straggler_migrations_defaults_to_zero():
    res = _churn_run()
    # pure-simulator gangs have no stragglers: field exists, stays 0
    assert res.straggler_migrations == 0


# ---- one span API, two sinks: the recorder and the profiler's trace ---------

def _profiled_spans(trace_dir, prefixes):
    """(name, attrs) of the host events under ``prefixes`` in the newest
    profiler trace under ``trace_dir``, in start order."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    evs = [(e.start_ns, e.name, dict(e.stats))
           for p in ProfileData.from_file(path).planes
           if p.name.startswith("/host:") for line in p.lines
           for e in line.events if e.name.startswith(prefixes)]
    return [(n, a) for _, n, a in sorted(evs, key=lambda e: e[0])]


def _small_serve_loop():
    import jax

    from repro.configs.registry import reduced_config
    from repro.models import transformer as tf
    from repro.runtime.serve_loop import ContinuousServeLoop

    cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=64)
    params = jax.jit(lambda k: tf.init_params(k, cfg))(
        jax.random.PRNGKey(0))
    return ContinuousServeLoop(cfg, params, slots=4, max_len=32)


def _serve_a_little(loop):
    from repro.runtime.serve_loop import Request
    for rid, n in ((0, 5), (1, 11)):
        loop.admit(Request(rid=rid, prompt=np.arange(n, dtype=np.int32),
                           max_new_tokens=4))
    for _ in range(3):
        loop.decode_step()


SERVE_SPANS = {"serve.admit", "serve.admit.prepare", "serve.admit.dispatch",
               "serve.decode_step", "serve.decode.sync",
               "serve.decode.dispatch", "serve.decode.select"}


def test_serve_path_builds_no_annotation_when_nothing_records(monkeypatch,
                                                               tmp_path):
    import jax

    class Counting(jax.profiler.TraceAnnotation):
        made = 0

        def __init__(self, name, **attrs):
            Counting.made += 1
            super().__init__(name, **attrs)

    loop = _small_serve_loop()
    monkeypatch.setattr(telemetry, "_Annotation", Counting)
    assert not telemetry.active()
    _serve_a_little(loop)
    assert Counting.made == 0
    assert telemetry.get().spans == []
    # the control: with a profiler session the same path builds them
    with jax.profiler.trace(str(tmp_path)):
        assert telemetry.profiling() and telemetry.active()
        loop.decode_step()
    assert Counting.made == 4


def test_live_recorder_records_serve_spans_in_memory():
    loop = _small_serve_loop()
    with telemetry.recording() as tel:
        _serve_a_little(loop)
    assert {s["name"] for s in tel.spans} == SERVE_SPANS
    assert all(s["track"] == "serve" and s["clock"] == "wall"
               for s in tel.spans)
    steps = [s["attrs"] for s in tel.spans
             if s["name"] == "serve.decode_step"]
    assert [(a["lanes"], a["ctx_tokens"], a["kv_positions"])
            for a in steps] == [(2, 18, 128), (2, 20, 128), (2, 22, 128)]
    admit = next(s["attrs"] for s in tel.spans if s["name"] == "serve.admit")
    # admitted without passing through a queue: no queue wait
    assert admit == {"rid": 0, "plen": 5, "bucket": 8, "slot": 0}
    assert tel.counters["serve.decoded_tokens"] == 6
    # the virtual-clock histograms and the occupancy gauge are gone
    assert not any(k.startswith("serve.") for k in tel.histograms)
    assert "serve.slot_occupancy" not in tel.gauges
    cats = {e.get("cat") for e in tel.to_chrome_trace()["traceEvents"]}
    assert "serve" in cats


@pytest.mark.parametrize("live", [False, True], ids=["noop", "recorder"])
def test_spans_reach_the_profiler_trace_with_late_attributes(tmp_path, live):
    import jax

    from repro.checkpoint.manager import CheckpointManager

    mgr = CheckpointManager(str(tmp_path / "ckpt"), job_id="j")
    state = {"w": np.arange(8, dtype=np.float32)}
    tel = telemetry.enable() if live else telemetry.get()
    with jax.profiler.trace(str(tmp_path / "trace")):
        with tel.span("x.outer", track="t", a=1):
            with tel.span("x.inner", track="t") as sp:
                sp.set(b=2.5)
        mgr.save(3, state)
        mgr.restore(3)
    got = _profiled_spans(str(tmp_path / "trace"), ("x.", "ckpt."))
    assert got == [("x.outer", {"a": 1}), ("x.inner", {"b": 2.5}),
                   ("ckpt.save", {"step": 3, "kind": "full", "bytes": 32,
                                  "full_bytes": 32}),
                   ("ckpt.restore", {"step": 3, "kind": "full"})]
    # the recorder keeps the same spans and attributes in memory
    assert [(s["name"], s["attrs"]) for s in tel.spans] == (
        [("x.inner", {"b": 2.5}), ("x.outer", {"a": 1}),
         got[2], got[3]] if live else [])
