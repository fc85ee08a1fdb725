"""What every cell shares: the manifest and its files, the peak table,
the device check, compile counting, kernel names, percentiles, and the
result line."""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a Python file by path (configuration references and metric
    readers are named after the manifest, not as Python identifiers)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """BENCHMARK.json and the files it names, found by name."""

    def __init__(self, path: Path = ROOT / "BENCHMARK.json"):
        self.path = Path(path)
        self.data = load_json(self.path)
        self.root = self.path.parent

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"bench: no workload {name!r} in {self.path}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return {**load_json(self.root / c["file"]),
                        "_file": str(self.root / c["file"])}
        raise SystemExit(f"bench: no config {name!r} in {self.path}")

    def mix(self, name: str) -> dict:
        return load_json(self.root / "bench" / "mixes" / f"{name}.json")

    def reference(self, config: dict):
        path = Path(config["_file"]).parent / config["reference"]
        return load_module(path, "bench_ref_" + re.sub(r"\W", "_",
                                                       config["name"]))

    def work(self, config: dict):
        path = Path(config["_file"]).parent / config["work"]
        return load_module(path, "bench_work_" + re.sub(r"\W", "_",
                                                        config["name"]))

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.data["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        """Per-layer metrics this cell reads: those that list it, or,
        without a list, those whose end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str):
        return load_module(BENCH / "metrics" / f"{metric}.py",
                           "bench_metric_" + re.sub(r"\W", "_", metric))


# ---- peaks ----------------------------------------------------------------
def peaks_for(kind: str) -> dict:
    """The chip's published peaks; an unknown device kind is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; the table "
                       f"holds {sorted(table)}")
    return table[kind]


def require_chips(n: int):
    """The first ``n`` TPU devices, or exit without a result."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {dev.platform!r} "
                         f"({dev.device_kind})")
    if len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX found "
                         f"{len(devices)}")
    try:
        peaks_for(dev.device_kind)
    except KeyError as e:
        raise SystemExit(f"bench: {e}")
    return devices[:n]


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    if set, else a fixed directory inside the checkout; every program is
    kept, however fast it compiled, so that set-up is the same work on
    every run after the first."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT
                                                             / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileWatch:
    """Backend compiles (with their end times on the host clock) and
    persistent-cache hits and misses, through ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring
        self.compiles = []          # (end time, seconds)
        self.hits = self.misses = 0

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles.append((time.perf_counter(), secs))
        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    @property
    def compile_s(self) -> float:
        return sum(s for _, s in self.compiles)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.compiles if t0 <= t <= t1)

    def line(self) -> str:
        return (f"backend compile {self.compile_s:.3f} s over "
                f"{len(self.compiles)} programs, persistent cache hits "
                f"{self.hits} misses {self.misses}")


def kernels_called(lowered) -> set:
    """Names of the Pallas TPU kernels a lowered program calls."""
    return set(re.findall(r'tpu_custom_call.*?kernel_name = "([^"]+)"',
                          lowered.as_text()))


def percentile(values, q: float):
    """The q-th percentile with linear interpolation (numpy's default);
    None for no values."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    """The result line; ``checks`` (each number compared beside
    its limit) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
