"""Telemetry plane acceptance: predicted-vs-live divergence + Perfetto.

One pinned mixed train+serve trace with fleet churn, executed twice in
an 8-device subprocess (real jax gangs on the host fabric):

* ``Fabric.predict_trace`` — the discrete-event simulator's Action log.
* ``Fabric.run_trace`` — the live event loop driving real gangs, with
  a ``core.telemetry`` recorder enabled end to end.

``telemetry.diff_traces`` aligns the two Action streams; the gate is
**zero divergence** — the live fabric must replay the simulator's
decision sequence event for event even while recording (the recorder's
no-perturbation contract, measured rather than asserted).  The per-
phase predicted-vs-measured time-error report lands at
``results/<prefix>_bench_telemetry_diff.json`` and the recorded
timeline — placement decisions, gang lifecycle, checkpoints,
collective dispatch, serve admission — as a Perfetto-loadable Chrome
trace at ``results/<prefix>_bench_telemetry_perfetto.json``.

Reported metrics (gated in check_results.py at both tiers):

* ``diff/zero_divergence`` — 1.0 iff the aligned streams diverge
  nowhere (gate > 0).
* ``trace/layers_present`` — how many of the five instrumented layers
  (placement, gang/fabric, ckpt, collective, serve) emitted events
  into the exported trace (gate > 4: all five).
* ``telemetry/spans_total`` / ``telemetry/decision_latency_count`` —
  the recorder saw real spans and the placement engine's decision-
  latency histogram is populated (gates > 0).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

RESULTS_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "results"))
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# the five layers the exported timeline must cover (event ``cat`` =
# name prefix, see telemetry.to_chrome_trace)
REQUIRED_LAYERS = ("placement", "gang", "ckpt", "collective", "serve")

# fleet config stamped into the results/ artifact by run.py
FLEET = {"hosts": 3, "chips_per_host": 2, "spare_hosts": 1,
         "sched": "central", "policy": "binpack",
         "churn": "pinned fail@6s + join@10s",
         "checkpoint_interval_s": 4.0}

_PROG = """
import json, sys
import jax
from repro.configs.registry import reduced_config
from repro.core import telemetry
from repro.core.fabric import Fabric
from repro.core.fleet import FleetEvent
from repro.core.simulator import Job
from repro.data.pipeline import DataConfig
from repro.optim.adamw import AdamWConfig
from repro.runtime.gang_workloads import workload_factory

trace_path, diff_path = sys.argv[1], sys.argv[2]
cfg = reduced_config("llama3.2-1b").with_(n_layers=1, vocab=128)
dcfg = DataConfig(vocab=128, seq_len=8, global_batch=8)
ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)
# pinned mixed train+serve trace + churn schedule: one hard host
# failure mid-run (checkpoint rollback + recover) and a like-for-like
# join from the staged spares
jobs = [
    Job("train-a", "mpi-compute", 4, 200.0, arrival=0.0,
        workload="train"),
    Job("serve-0", "omp", 2, 120.0, arrival=0.0, priority=1,
        workload="serve"),
]
events = [FleetEvent(6.0, "fail", hosts=[0]),
          FleetEvent(10.0, "join", capacities=[2])]
devs = jax.devices()
fab = Fabric(devices=devs[:6], chips_per_host=2, spares=devs[6:])

tel = telemetry.enable()
predicted = fab.predict_trace(jobs, preempt=True, fleet_events=events,
                              checkpoint_interval=4.0)


def factory(job):
    wl = workload_factory(cfg, ocfg, dcfg, train_steps=3,
                          serve_tokens=3)(job)
    # "auto" routes the gradient-sync schedule through the fabric's
    # CollectiveTuner on every (re)bind — the collectives layer's
    # dispatch counters
    if hasattr(wl, "sync_mode"):
        wl.sync_mode = "auto"
    return wl


ex = fab.run_trace(jobs, factory, preempt=True, fleet_events=events,
                   checkpoint_interval=4.0)
live = ex.result
diff = telemetry.diff_traces(predicted, live)

tel.write_chrome_trace(trace_path)
with open(diff_path, "w") as f:
    json.dump(telemetry._plain(diff), f, indent=1, sort_keys=True)

summary = tel.summary()
dec = summary["histograms"].get("placement.decision_latency_s", {})
with open(trace_path) as f:
    cats = {e.get("cat") for e in json.load(f)["traceEvents"]}
out = {
    "divergences": diff["divergences"],
    "aligned": diff["aligned"],
    "n_predicted": diff["n_predicted"],
    "n_live": diff["n_live"],
    "phase_kinds": len(diff["phase_error"]),
    "max_phase_dt_s": max(
        [p["max_abs_dt_s"] for p in diff["phase_error"].values()],
        default=0.0),
    "spans_total": summary["spans_total"],
    "decision_latency_count": dec.get("count", 0),
    "layers": sorted(c for c in cats if c),
    "recoveries": live.recoveries,
    "checkpoints": sum(r.get("checkpoints", 0)
                       for r in ex.live.values()),
}
print(json.dumps(out))
"""


def run(report, tiny=False):
    prefix = "SMOKE" if tiny else "BENCH"
    trace_path = os.path.join(RESULTS_DIR,
                              f"{prefix}_bench_telemetry_perfetto.json")
    diff_path = os.path.join(RESULTS_DIR,
                             f"{prefix}_bench_telemetry_diff.json")
    os.makedirs(RESULTS_DIR, exist_ok=True)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # a CPU benchmark: keep off the chip
    env["PYTHONPATH"] = SRC
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_PROG),
         trace_path, diff_path],
        capture_output=True, text=True, env=env, timeout=1800)
    assert res.returncode == 0, res.stderr[-3000:]
    data = json.loads(res.stdout.strip().splitlines()[-1])

    layers = [l for l in REQUIRED_LAYERS if l in data["layers"]]
    report("diff/divergences", data["divergences"], "",
           "predicted vs live Action streams (pinned churn trace)")
    report("diff/zero_divergence",
           1.0 if data["divergences"] == 0 else 0.0, "",
           "1.0 iff live replays the prediction event for event")
    report("diff/aligned_actions", data["aligned"], "",
           f"of {data['n_predicted']} predicted / {data['n_live']} live")
    report("diff/phase_kinds", data["phase_kinds"], "",
           "Action kinds with a per-phase time-error entry")
    report("diff/max_phase_dt_s", round(data["max_phase_dt_s"], 6), "s",
           "worst aligned |t_live - t_predicted| (virtual clock)")
    report("trace/layers_present", len(layers), "",
           f"of {len(REQUIRED_LAYERS)}: {'+'.join(layers)}")
    report("telemetry/spans_total", data["spans_total"], "",
           "recorder spans (wall + virtual)")
    report("telemetry/decision_latency_count",
           data["decision_latency_count"], "",
           "placement.decision_latency_s histogram samples")
    report("run/recoveries", data["recoveries"], "",
           "checkpoint rollbacks on the pinned host failure")
    report("run/checkpoints", data["checkpoints"], "",
           "real snapshots taken by live gangs")
