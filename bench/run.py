#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json``; the configuration's ``runner`` runs it.  With ``--trace 0`` the result carries the cell's end-to-end
metrics; with ``--trace 1`` a profiler trace of the window's last
seconds is reduced to its per-layer metrics and a ``breakdown``.  Every
run checks what the timed path produced against the configuration's
plain reference.  The last line of standard output is the result
object; the numbers compared, each beside its limit, are also the last
lines of standard error.  Without a TPU, with fewer chips than the cell
asks for, or on a device kind the peak table lacks, it exits non-zero
and prints no result.  One process holds the chips; it starts none.

``--control 1`` judges the control in the program's place (the
reference computed with float8 inputs) and ``--fault <name>`` plants a
fault in the timed path; both exist to set and prove the limits, and
both must come out not correct.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime's logs go under this run's temporary directory
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from harness import common  # noqa: E402
from harness.common import say  # noqa: E402

RUNNERS = ("serve",)
#: seconds of the window's end that a --trace 1 run records
TRACE_S = 25.0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="")
    ap.add_argument("--manifest", default=str(common.ROOT / "BENCHMARK.json"))
    return ap.parse_args(argv)


def record(args, manifest) -> types.SimpleNamespace:
    """Everything a runner and a metric reader need about this run."""
    cell = manifest.workload(args.workload)
    conf = manifest.config(cell["config"])
    if conf["runner"] not in RUNNERS:
        raise SystemExit(f"bench: unknown runner {conf['runner']!r}")
    return types.SimpleNamespace(
        cell=cell, conf=conf, mix=manifest.mix(cell["traffic"]),
        ref=manifest.reference(conf), work=manifest.work(conf),
        seed=args.seed, seconds=args.seconds, t_process=T_PROCESS,
        trace_s=min(TRACE_S, args.seconds / 2), control=bool(args.control),
        fault=args.fault, trace_dir=None, trace=None)


def per_layer(rec, manifest) -> dict:
    out = {}
    for m in manifest.per_layer(rec.cell["name"]):
        value = manifest.reader(m["name"]).read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(args, devices, manifest, watch) -> str:
    """One run of the cell on ``devices``; returns the result line."""
    rec = record(args, manifest)
    rec.peaks = common.peaks_for(devices[0].device_kind)
    rec.watch = watch
    if args.trace:
        rec.trace_dir = str(common.ROOT / ".bench_trace")
        shutil.rmtree(rec.trace_dir, ignore_errors=True)
    runner = __import__(f"harness.{rec.conf['runner']}",
                        fromlist=["run"])
    out = runner.run(rec, devices)
    setup_s = rec.t_open_abs - rec.t_process
    say(f"set-up {setup_s:.3f} s; {watch.line()}")
    metrics = {}
    if args.trace:
        from harness import trace
        rec.trace = trace.load(rec.trace_dir, len(devices))
        metrics = per_layer(rec, manifest)
        rec.device["busy_s"] = trace.busy_s(rec.trace)
        rec.device["window_s"] = rec.trace.window_s
        breakdown = trace.breakdown(rec.trace)
        shutil.rmtree(rec.trace_dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"]
                 for m in manifest.end_to_end(rec.cell["name"])}
        values = {**out["metrics"], "setup_s": setup_s}
        metrics = {k: {"value": float(values[k]), "unit": u}
                   for k, u in units.items()}
        breakdown = None
    for name, c in out["checks"].items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return common.result_line(out["correct"], rec.attempted, out["failed"],
                              metrics, rec.device, out["checks"], breakdown)


def main(argv=None) -> None:
    args = parse(argv)
    manifest = common.Manifest(args.manifest)
    chips = manifest.workload(args.workload)["chips"]
    devices = common.require_chips(chips)
    cache = common.enable_cache()
    watch = common.CompileWatch()
    say(f"cell {args.workload}, seed {args.seed}, {args.seconds} s, "
        f"trace {args.trace}, on {chips} x {devices[0].device_kind}; "
        f"compile cache {cache}")
    line = run(args, devices, manifest, watch)
    print(line, flush=True)


if __name__ == "__main__":
    main()
