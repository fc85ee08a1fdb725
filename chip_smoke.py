#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU.

One chip (the default): granite-moe-1b-a400m at its published width
(24 layers, d_model 1024, 32 experts, vocab 49155, bf16, random weights
from ``--seed``) serves an open-loop request stream.  The model, the
stream and the driver are ``repro.launch.serve``'s own
(``load_model``/``make_stream``, ``run_open_loop``), run in this process
on a ``ContinuousServeLoop`` bound to a serve gang of a ``Fabric`` over
the chip, with the Pallas kernels on.  Each check is fatal:

* the lowered prefill and decode programs call the expected kernels
  (``tpu_custom_call`` with the kernel's name);
* every request is answered in full, and a second pass over the same
  stream gives the same tokens;
* the prefill logits of a few prompts, from the served model code with
  the kernels on, agree with the float32 jnp reference of the same
  weights to a relative L2 error of at most ``LOGITS_RTOL``, both run in
  float32 at ``highest`` matmul precision (the served bf16 logits are
  printed beside those of the jnp path in bf16);
* each kernel alone, at served shapes and in bf16, agrees with its
  float32 reference to ``KERNEL_RTOL``;
* the gang preempted mid-generation (snapshot to host, chips released)
  and resumed yields exactly the tokens of the uninterrupted pass.

``--four-chips``: whisper-small at its published width trains as a
data-parallel ``FaabricTrainRuntime`` gang over four chips, once with the
hierarchical gradient sync over two pods and once flat, each with a 4->2
rescale at a control point; the two loss curves agree to ``LOSS_RTOL``.

Compile, time-to-first-token and decode-rate lines are information, not
a benchmark.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a
TPU the script exits non-zero and prints no result.  It starts no
process: one process holds the chip(s).

    python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "granite-moe-1b-a400m"
#: kernels the serve path must call: prefill runs attention and the MoE
#: FFN through Pallas; decode attends with the jnp path (one query row)
PREFILL_KERNELS = ("flash_attention", "moe_expert_ffn")
DECODE_KERNELS = ("moe_expert_ffn",)
#: the kernel path at float32 against the float32 jnp reference, same
#: weights, both at `highest` precision: one function summed in another
#: order (interpreted on a CPU the gap is ~1e-6)
LOGITS_RTOL = 1e-3
#: one bf16 kernel against its float32 reference: inputs, outputs and
#: the attention probabilities round at 2^-8 relative
KERNEL_RTOL = 2e-2
#: per-step loss agreement of the hierarchical and flat gradient syncs:
#: the same mean gradient summed in another order
LOSS_RTOL = 1e-2
SERVE_ARGV = ["--arch", ARCH, "--requests", "12",
              "--slots", "4", "--prompt-len", "40", "--new-tokens", "32",
              "--max-len", "128", "--offered-load", "4.0"]
TRAIN_ARCH = "whisper-small"
TRAIN_STEPS = 6
RESCALE_AT = 3          # control point of the 4 -> 2 shrink
TRAIN_SEQ = 448         # whisper's decoder context
#: one sequence per chip at world 4; at world 2 the step needs ~9.5 GiB
#: per chip (2.6 state + 6.9 temporaries, compiled for a described v5e),
#: where batch 8 would need ~15.9 GiB of the 16 GiB HBM
TRAIN_BATCH = 4


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


class CompileWatch:
    """Counts backend compiles and persistent-cache hits/misses through
    ``jax.monitoring``; registered once per process."""

    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.hits = self.misses = 0

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs
        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    def line(self) -> str:
        return (f"backend compile {self.compile_s:.3f} s, persistent "
                f"cache hits {self.hits} misses {self.misses}")


class Driven:
    """Stands in for the serve loop under ``run_open_loop``: each admit
    and decode step is timed to ``block_until_ready``.  With
    ``preempt_at`` the loop's gang is preempted (serve state snapshot to
    the host, chips released) before that decode step and resumed, and
    the run continues from the restored snapshot."""

    def __init__(self, loop, requests, preempt_at=None):
        self.loop = loop
        self.requests = requests
        self.preempt_at = preempt_at
        self.prefill_s, self.decode_s, self.lanes = [], [], []
        self.steps = 0
        self.resumed_fingerprint = None

    def __getattr__(self, name):
        return getattr(self.loop, name)

    def _wait(self):
        import jax
        jax.block_until_ready(self.loop.serve_state()["cur"])

    def admit(self, req, now=None, extras=None):
        t0 = time.perf_counter()
        slot = self.loop.admit(req, now=now, extras=extras)
        self._wait()
        self.prefill_s.append(time.perf_counter() - t0)
        return slot

    def decode_step(self, now=None):
        if self.steps == self.preempt_at:
            gang = self.loop.handle
            snap = gang.preempt(self.loop.serve_state(), step=self.steps)
            state, _ = gang.resume()        # raises unless bit-exact
            self.loop.attach(gang, state=state)
            self.loop.adopt_requests(self.requests)
            self.resumed_fingerprint = snap.fingerprint
        t0 = time.perf_counter()
        lanes = self.loop.decode_step(now=now)
        self._wait()
        self.decode_s.append(time.perf_counter() - t0)
        self.lanes.append(lanes)
        self.steps += 1
        return lanes


def kernels_called(lowered) -> set:
    """Names of the Pallas TPU kernels a lowered program calls."""
    import re
    return set(re.findall(r'tpu_custom_call.*?kernel_name = "([^"]+)"',
                          lowered.as_text()))


def serve_pass(loop, args, cfg, say, name, preempt_at=None):
    """One open-loop pass of ``args``' stream from an empty slot array;
    returns ({rid: tokens}, Driven)."""
    from repro.launch import serve
    from repro.runtime.admission import run_open_loop
    from repro.runtime.serve_loop import ServeStats
    loop.load_serve_state({"params": loop.params})    # empty slot array
    loop.stats = ServeStats()
    reqs = serve.make_stream(args, cfg)
    driven = Driven(loop, reqs, preempt_at=preempt_at)
    t0 = time.perf_counter()
    rep = run_open_loop(driven, reqs, step_s=args.step_ms / 1e3)
    wall = time.perf_counter() - t0
    short = [r.rid for r in reqs if len(r.out) != r.max_new_tokens]
    if rep.finished != len(reqs) or short:
        fail(f"{name}: {rep.finished}/{len(reqs)} requests answered, "
             f"short outputs for rids {short}")
    say(f"{name}: {len(reqs)} requests answered, {sum(driven.lanes)} "
        f"tokens in {driven.steps} decode steps, {wall:.3f} s wall")
    return {r.rid: list(r.out) for r in reqs}, driven


def serve_phases(serve_argv, devices, say, *, interpret=False):
    """The one-chip serve checks (see the module docstring).  With
    ``interpret`` the kernels run in the Pallas interpreter, and the
    kernel check is skipped (an interpreted kernel lowers to plain ops):
    the CPU rehearsal of the same phases."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.fabric import Fabric
    from repro.launch import serve
    from repro.runtime.serve_loop import (ContinuousServeLoop,
                                          make_ragged_prefill)

    args = serve.parse_args(serve_argv)
    t0 = time.perf_counter()
    cfg, params = serve.load_model(
        args, **({"use_pallas_kernels": True, "interpret_kernels": True}
                 if interpret else {}))
    jax.block_until_ready(params)
    if not cfg.use_pallas_kernels:
        fail("the serve launcher left the Pallas kernels off")
    say(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_experts} experts top-{cfg.top_k}, vocab {cfg.vocab}, "
        f"{cfg.dtype}, {cfg.n_params()} params, pallas="
        f"{cfg.use_pallas_kernels}; init {time.perf_counter() - t0:.3f} s")

    fabric = Fabric(devices=list(devices))
    gang = fabric.bind("serve0", list(devices))
    loop = ContinuousServeLoop(cfg, params, slots=args.slots,
                               max_len=args.max_len, handle=gang)
    del params

    # -- kernels on the path ------------------------------------------------
    buckets = sorted({loop.bucket(len(r.prompt))
                      for r in serve.make_stream(args, cfg)})
    say(f"prefill buckets {buckets}")
    if not interpret:
        for name, lowered, want in (
                (f"prefill[{buckets[0]}]", loop.lower(buckets[0]),
                 PREFILL_KERNELS),
                ("decode", loop.lower(), DECODE_KERNELS)):
            got = kernels_called(lowered)
            if not set(want) <= got:
                fail(f"{name} program calls kernels {sorted(got)}, "
                     f"expected {list(want)}")
            say(f"{name} program calls tpu_custom_call kernels "
                f"{sorted(got)}")

    # -- serve: cold pass compiles, warm pass is timed ------------------------
    cold, _ = serve_pass(loop, args, cfg, say, "cold pass (compiles)")
    warm, timed = serve_pass(loop, args, cfg, say, "warm pass")
    if warm != cold:
        fail("the warm pass decoded other tokens than the cold pass")
    ttft = np.asarray(timed.prefill_s)
    say(f"TTFT (admit: prefill + first token) p50 "
        f"{np.median(ttft) * 1e3:.3f} ms, max {ttft.max() * 1e3:.3f} ms "
        f"over {ttft.size} requests")
    say(f"decode {sum(timed.lanes) / sum(timed.decode_s):.3f} tokens/s "
        f"({np.median(timed.decode_s) * 1e3:.3f} ms per step p50, "
        f"{args.slots} slots)")

    # -- preempt mid-generation, resume, same tokens --------------------------
    mid = timed.steps // 2
    resumed, driven = serve_pass(loop, args, cfg, say,
                                 f"preempt at decode step {mid} + resume",
                                 preempt_at=mid)
    if driven.resumed_fingerprint is None:
        fail("the gang was never preempted")
    if resumed != warm:
        diff = [rid for rid in warm if resumed.get(rid) != warm[rid]]
        fail(f"tokens after preempt/resume differ for rids {diff}")
    say(f"preempt/resume: snapshot {driven.resumed_fingerprint} restored "
        f"bit-exact; tokens identical to the uninterrupted pass for all "
        f"{len(warm)} requests")

    # -- prefill logits against the float32 jnp reference ---------------------
    # The kernel path (the served model code, Pallas kernels on) runs at
    # float32 against the jnp reference, same weights, both at `highest`
    # precision.  The served bf16 logits are printed beside those of the
    # jnp path in bf16: on random weights the 24 top-8 routing layers
    # turn bf16 rounding into flipped expert choices, so that distance
    # measures bf16, not the kernels (the bf16 kernels are checked alone).
    params = loop.params
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    cfg32 = cfg.with_(dtype="float32")
    paths = {"kernel path in float32": (cfg32, params32, "highest"),
             f"served, {cfg.dtype}": (cfg, params, None),
             f"jnp path in {cfg.dtype}": (cfg.with_(use_pallas_kernels=False),
                                          params, None),
             "reference": (cfg32.with_(use_pallas_kernels=False), params32,
                           "highest")}
    logits = {name: [] for name in paths}
    prompts = serve.make_stream(args, cfg)[:3]
    for name, (c, p, precision) in paths.items():
        prefill = jax.jit(make_ragged_prefill(c))
        for req in prompts:
            # one padded width for every prompt: one compile per path
            tokens = np.zeros((1, buckets[-1]), np.int32)
            tokens[0, :len(req.prompt)] = req.prompt
            with jax.default_matmul_precision(precision):
                out, _ = prefill(p, {"tokens": jnp.asarray(tokens)},
                                 jnp.int32(len(req.prompt)))
            logits[name].append(np.asarray(out, np.float64))
    want = logits.pop("reference")
    for name, got in logits.items():
        if not all(np.isfinite(g).all() for g in got):
            fail(f"{name}: non-finite prefill logits")
        rel = [rel_l2(g, w) for g, w in zip(got, want)]
        say(f"prefill logits, {name} vs float32 reference: relative L2 "
            f"error {', '.join(f'{r:.3e}' for r in rel)} over prompts of "
            f"{[len(r.prompt) for r in prompts]} tokens")
        if name == "kernel path in float32" and max(rel) > LOGITS_RTOL:
            fail(f"kernel-path prefill logits off the float32 reference: "
                 f"relative L2 error {max(rel):.3e} > {LOGITS_RTOL}")
    say(f"kernel-path prefill logits within relative L2 {LOGITS_RTOL} of "
        f"the float32 reference")
    kernel_checks(cfg, params, say, args.seed, interpret=interpret)
    gang.release()


def rel_l2(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def kernel_checks(cfg, params, say, seed, *, interpret=False):
    """The two kernels of the serve path alone, at served shapes and the
    served dtype, against their float32 references: flash attention over
    a 64-token prefill, and the expert FFN on layer 0's expert weights
    at prefill bucket 64's capacity."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import ops as fa, ref as fa_ref
    from repro.kernels.moe_gmm import ops as gmm, ref as gmm_ref
    from repro.models.moe import expert_capacity
    dt, f32, seq = cfg.param_dtype(), jnp.float32, 64
    kq, kk, kv, kx = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (1, seq, cfg.n_heads, cfg.hd()), dt)
    k = jax.random.normal(kk, (1, seq, cfg.n_kv_heads, cfg.hd()), dt)
    v = jax.random.normal(kv, (1, seq, cfg.n_kv_heads, cfg.hd()), dt)
    got = fa.flash_attention(q, k, v, interpret=interpret)
    heads = lambda x: jnp.swapaxes(x.astype(f32), 1, 2)
    with jax.default_matmul_precision("highest"):
        want = jnp.swapaxes(fa_ref.attention_ref(heads(q), heads(k),
                                                 heads(v)), 1, 2)
    checks = {"flash_attention": rel_l2(got, want)}
    moe = params["blocks"][0]["moe"]
    w1, w2, w3 = (moe[w][0] for w in ("w1", "w2", "w3"))
    x = jax.random.normal(kx, (1, cfg.n_experts, expert_capacity(cfg, seq),
                               cfg.d_model), dt)
    got = gmm.expert_ffn(x, w1, w2, w3, act=cfg.act, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = gmm_ref.expert_ffn_ref(x[0].astype(f32), w1, w2, w3,
                                      act=cfg.act)
    checks["moe_expert_ffn"] = rel_l2(got[0], want)
    for name, rel in checks.items():
        say(f"{name} kernel in {cfg.dtype} vs its float32 reference: "
            f"relative L2 error {rel:.3e}")
        if rel > KERNEL_RTOL:
            fail(f"{name} kernel off its float32 reference: relative L2 "
                 f"error {rel:.3e} > {KERNEL_RTOL}")


def train_phases(devices, say, *, reduced=False, seed=0):
    """The four-chip train checks (see the module docstring): the
    hierarchical (two pods) and flat gradient syncs, each with a 4->2
    rescale at a control point, must give the same losses."""
    import numpy as np
    from repro.configs.registry import get_config, reduced_config
    from repro.data.pipeline import DataConfig
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.train_loop import FaabricTrainRuntime, RuntimeConfig

    cfg = reduced_config(TRAIN_ARCH) if reduced else get_config(TRAIN_ARCH)
    seq = 32 if reduced else TRAIN_SEQ
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq,
                      global_batch=TRAIN_BATCH, seed=seed)
    ocfg = AdamWConfig(lr=1e-4, warmup_steps=1, total_steps=TRAIN_STEPS)
    say(f"model {cfg.name}: {cfg.n_enc_layers}+{cfg.n_layers} layers, "
        f"d_model {cfg.d_model}, vocab {cfg.vocab}, {cfg.dtype}, "
        f"{cfg.n_params()} params; batch {TRAIN_BATCH} x {seq} tokens")
    ckpt_dir = ROOT / ".smoke_ckpt"
    losses = {}
    try:
        for mode in ("hierarchical", "flat"):
            rt = RuntimeConfig(
                total_steps=TRAIN_STEPS, sync_mode=mode, pods=2,
                checkpoint_every=TRAIN_STEPS + 1,
                ckpt_dir=str(ckpt_dir / mode),
                rescale_at={RESCALE_AT: 2})
            runtime = FaabricTrainRuntime(cfg, ocfg, dcfg, rt,
                                          devices=list(devices),
                                          job_id=f"train-{mode}")
            t0 = time.perf_counter()
            _, out = runtime.run(seed=seed)
            wall = time.perf_counter() - t0
            worlds = [e["world"] for e in out["log"]]
            runtime.release()
            if out["rescales"] != 1 or worlds[-1] != 2:
                fail(f"{mode}: rescale 4->2 did not complete "
                     f"(rescales {out['rescales']}, worlds {worlds})")
            losses[mode] = np.asarray(out["losses"])
            if not np.isfinite(losses[mode]).all():
                fail(f"{mode}: non-finite losses {losses[mode]}")
            say(f"{mode} sync (pods=2): losses "
                f"{[round(float(x), 6) for x in losses[mode]]}, worlds "
                f"{worlds}, {wall:.3f} s wall incl. compile")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    rel = np.abs(losses["hierarchical"] - losses["flat"]) \
        / np.abs(losses["flat"])
    if rel.max() > LOSS_RTOL:
        fail(f"hierarchical and flat losses differ: max relative "
             f"{rel.max():.6f} > {LOSS_RTOL}")
    say(f"hierarchical vs flat losses agree: max relative difference "
        f"{rel.max():.3e} <= {LOSS_RTOL}; rescale 4->2 completed in both")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip train-gang checks")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        raise SystemExit(f"chip_smoke: needs {need} TPU chips, JAX found "
                         f"{len(devices)}")
    devices = devices[:need]
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    watch = CompileWatch()
    label = f"[{dev.platform} {dev.device_kind} x{need}]"

    def say(msg: str) -> None:
        print(label, msg, flush=True)

    say(f"jax {jax.__version__}, compile cache {cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        train_phases(devices, say, seed=args.seed)
    else:
        serve_phases(SERVE_ARGV + ["--seed", str(args.seed)], devices, say)
    say(f"{watch.line()}; total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
