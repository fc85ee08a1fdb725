import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # 512 host devices; never the chip

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the step function the shape dictates
(train_step / prefill_step / serve_step), assigns production shardings
(models.shardings), lowers and compiles it against ShapeDtypeStruct inputs
on the production mesh (single-pod 16x16 = 256 chips, multi-pod 2x16x16 =
512 chips), and extracts:

  * memory_analysis()   -> per-device bytes (proves the cell fits HBM)
  * cost_analysis()     -> per-device HLO FLOPs + bytes accessed
  * compiled.as_text()  -> per-collective byte counts (roofline's third term)

Results go to ``results/dryrun/<cell>.json``; ``--all`` fans cells out to
subprocesses (one compile per process keeps XLA state isolated).

NOTE: the environment lines above must run before ANY jax import — jax locks
the device count at first init.  Do not move it.
"""
import argparse
import json
import subprocess
import sys
import time
from typing import Any, Dict, Optional

import jax

from repro.configs.base import SHAPES, ArchConfig, ShapeConfig, cell_applicable
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch import hloanalysis
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_mod
from repro.models import shardings as sh
from repro.optim.adamw import AdamWConfig

# TPU v5e per-chip constants (roofline denominators)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link (slow-link bound for collectives)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# Gradient accumulation per arch for the train_4k shape: keeps per-device
# activation checkpoints within v5e HBM (napkin math in EXPERIMENTS.md).
GRAD_ACCUM = {
    "phi3.5-moe-42b-a6.6b": 4, "glm4-9b": 4, "llama-3.2-vision-11b": 4,
    "minitron-4b": 2, "llama3.2-3b": 2, "zamba2-2.7b": 4, "xlstm-1.3b": 4,
    "llama3.2-1b": 2, "granite-moe-1b-a400m": 2, "whisper-small": 2,
}


def dryrun_config(arch: str, deploy: bool = False) -> ArchConfig:
    """Dry-run overrides.

    analysis build (deploy=False): unrolled layers + python inner loops —
    the HLO contains every FLOP and collective exactly once per execution.
    deploy build (deploy=True): lax.scan layers + inner loops — the
    deployable artifact whose buffer reuse gives the real memory footprint.
    FSDP turns on when TP-only optimizer state would exceed ~2 GB/chip.
    """
    cfg = get_config(arch)
    # FSDP only when TP-only optimizer state exceeds ~2 GB/chip: blanket
    # FSDP regressed memory badly (XLA hoists loop-invariant all-gathers
    # out of the layer scan, materialising the whole gathered model).
    big = model_mod.count_params(cfg) * 16 / 256 > 2e9
    return cfg.with_(scan_layers=deploy, remat=True, fsdp=big,
                     deploy=deploy)


def build_cell(cfg: ArchConfig, shape: ShapeConfig, mesh):
    """Returns (fn, args, in_shardings) for this cell."""
    ocfg = AdamWConfig()
    if shape.kind == "train":
        # grad accumulation exists purely to bound activation memory: the
        # deploy build uses it; the analysis build lowers the full batch in
        # one pass (identical total FLOPs, 4x smaller unrolled HLO)
        accum = (GRAD_ACCUM.get(cfg.name, 1)
                 if (shape.name == "train_4k" and cfg.deploy) else 1)
        gspecs = sh.param_pspecs(cfg, model_mod.param_specs(cfg), mesh)
        state = model_mod.train_state_specs(cfg, ocfg)
        batch = model_mod.batch_specs(cfg, shape)
        fn = model_mod.make_train_step(
            cfg, ocfg, grad_accum=accum, grad_pspecs=gspecs,
            batch_pspecs=sh.batch_pspecs(cfg, batch, mesh))
        in_sh = (sh.named(mesh, sh.state_pspecs(cfg, state, mesh)),
                 sh.named(mesh, sh.batch_pspecs(cfg, batch, mesh)))
        return fn, (state, batch), in_sh
    if shape.kind == "prefill":
        fn = model_mod.make_prefill_step(cfg)
        params = model_mod.param_specs(cfg)
        batch = model_mod.batch_specs(cfg, shape, with_labels=False)
        in_sh = (sh.named(mesh, sh.param_pspecs(cfg, params, mesh)),
                 sh.named(mesh, sh.batch_pspecs(cfg, batch, mesh)))
        return fn, (params, batch), in_sh
    # decode
    window = model_mod.decode_window(cfg, shape)
    fn = model_mod.make_serve_step(cfg, window=window)
    params = model_mod.param_specs(cfg)
    states = model_mod.decode_state_specs(cfg, shape)
    inputs = model_mod.decode_input_specs(cfg, shape)
    in_sh = (sh.named(mesh, sh.param_pspecs(cfg, params, mesh)),
             sh.named(mesh, sh.decode_state_pspecs(cfg, states, mesh)),
             sh.named(mesh, sh.batch_pspecs(cfg, inputs, mesh))["tokens"],
             sh.named(mesh, sh.batch_pspecs(cfg, inputs, mesh))["positions"])
    return fn, (params, states, inputs["tokens"], inputs["positions"]), in_sh


def roofline(cost: Dict[str, float], coll: Dict[str, int],
             cfg: ArchConfig, shape: ShapeConfig, n_chips: int
             ) -> Dict[str, Any]:
    """Three-term roofline from the per-device compiled module."""
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(coll.get("hbm_bytes", cost.get("bytes accessed", 0.0)))
    coll_dev = float(coll.get("collective_bytes", 0))
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll = coll_dev / ICI_BW
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    n_active = model_mod.count_params(cfg, active_only=True)
    passes = 6 if shape.kind == "train" else 2
    model_flops = passes * n_active * tokens
    hlo_total = flops_dev * n_chips
    return {
        "per_device": {"flops": flops_dev, "hbm_bytes": bytes_dev,
                       "collective_bytes": coll_dev},
        "terms_s": terms,
        "bottleneck": bottleneck,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops / hlo_total) if hlo_total else 0,
        "roofline_fraction": (model_flops / n_chips / PEAK_FLOPS)
        / max(max(terms.values()), 1e-12),
        "step_time_bound_s": max(terms.values()),
    }


def _compile(cfg, shape, mesh):
    fn, args, in_sh = build_cell(cfg, shape, mesh)
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
        lower_s = round(time.time() - t0, 1)
        t0 = time.time()
        compiled = lowered.compile()
    return compiled, lower_s, round(time.time() - t0, 1)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_path: Optional[str] = None) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    ok, reason = cell_applicable(get_config(arch), shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "applicable": ok}
    if not ok:
        rec["skip_reason"] = reason
        return _emit(rec, out_path)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size

    # --- deploy build: the runnable artifact; memory truth ---
    cfg_d = dryrun_config(arch, deploy=True)
    compiled_d, rec["deploy_lower_s"], rec["deploy_compile_s"] = _compile(
        cfg_d, shape, mesh)
    mem = compiled_d.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "peak_per_device_gb": round(
            (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + max(0, mem.output_size_in_bytes
                   - mem.alias_size_in_bytes)) / 2 ** 30, 3),
    }
    rec["fits_hbm_16gb"] = rec["memory"]["peak_per_device_gb"] < 16.0
    del compiled_d
    if multi_pod:
        # multi-pod pass proves the "pod" axis shards (deploy compile +
        # memory); the roofline table is single-pod only (instructions).
        return _emit(rec, out_path)

    # --- analysis builds: unrolled; FLOP/collective truth ---
    # Difference method (single-core budget): compile 1-period and 2-period
    # unrolled models; per-period cost is exact for homogeneous periods, so
    #   total = cost(1p) + (n_periods - 1) * (cost(2p) - cost(1p)).
    # Embedding/loss/optimizer-fixed parts live in cost(1p) and cancel in
    # the delta.  Documented in EXPERIMENTS.md §Roofline.
    cfg_a = dryrun_config(arch, deploy=False)
    period_len = len(cfg_a.period())
    n_per = cfg_a.n_periods()
    measures = []
    for k in (1, 2):
        cfg_k = cfg_a.with_(n_layers=period_len * k)
        compiled_k, lo_s, co_s = _compile(cfg_k, shape, mesh)
        cost = compiled_k.cost_analysis()
        hlo = compiled_k.as_text()
        ana = hloanalysis.analyze(hlo)
        measures.append({
            "flops": float(cost.get("flops", 0.0)),
            "hbm_bytes": float(ana["hbm_bytes"]),
            "collectives": ana,
            "lower_s": lo_s, "compile_s": co_s, "hlo_bytes": len(hlo)})
        del compiled_k
    m1, m2 = measures
    extrap = lambda a, b: a + (n_per - 1) * (b - a)
    cost_full = {"flops": extrap(m1["flops"], m2["flops"])}
    ana_full = {
        k: max(0, int(extrap(m1["collectives"][k], m2["collectives"][k])))
        for k in m2["collectives"]}
    rec["analysis"] = {"one_period": m1, "two_periods": m2,
                       "n_periods": n_per, "period_len": period_len}
    rec["collectives"] = ana_full
    rec["cost"] = cost_full
    rec["compile_s"] = m1["compile_s"] + m2["compile_s"]
    rec["roofline"] = roofline(cost_full, ana_full, cfg_a, shape, n_chips)
    return _emit(rec, out_path)


def _emit(rec, out_path):
    if out_path:
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape x mesh) cell in "
                         "subprocesses")
    ap.add_argument("--jobs", type=int, default=2)
    ap.add_argument("--out-dir", default=os.path.abspath(RESULTS_DIR))
    args = ap.parse_args()

    if not args.all:
        assert args.arch and args.shape
        out = os.path.join(
            args.out_dir, f"{args.arch}__{args.shape}__"
            f"{'2x16x16' if args.multi_pod else '16x16'}.json")
        rec = run_cell(args.arch, args.shape, args.multi_pod, out)
        print(json.dumps(rec, indent=1))
        return

    # fan out cells to subprocesses (isolated XLA state, bounded RAM)
    cells = []
    for mp in (False, True):   # single-pod first: the roofline table
        for arch in ARCH_IDS:
            for shape_name in SHAPES:
                cells.append((arch, shape_name, mp))
    procs: Dict[Any, Any] = {}
    failures = []
    while cells or procs:
        while cells and len(procs) < args.jobs:
            arch, shape_name, mp = cells.pop(0)
            out = os.path.join(
                args.out_dir, f"{arch}__{shape_name}__"
                f"{'2x16x16' if mp else '16x16'}.json")
            if os.path.exists(out):
                print(f"skip (cached): {out}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name,
                   "--out-dir", args.out_dir]
            if mp:
                cmd.append("--multi-pod")
            procs[subprocess.Popen(
                cmd, stdout=subprocess.DEVNULL,
                stderr=subprocess.PIPE)] = (arch, shape_name, mp)
        done = [p for p in procs if p.poll() is not None]
        for p in done:
            cell = procs.pop(p)
            if p.returncode != 0:
                err = p.stderr.read().decode()[-2000:]
                failures.append((cell, err))
                print(f"FAIL {cell}:\n{err}")
            else:
                print(f"ok   {cell}")
        time.sleep(2)
    print(f"\n{len(failures)} failures")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
