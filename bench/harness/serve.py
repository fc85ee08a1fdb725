"""The serve runner: one configuration served on one chip under an
open-loop traffic mix, on the wall clock.

Set-up builds the weights on the device from the seed (one jitted call
of the configuration's generator), binds a serve gang of a ``Fabric``
over the chip, builds the program's ``ContinuousServeLoop``, checks the
kernels on its programs, and warms every prefill bucket that the mix's
length range reaches plus the decode program.  The requests in flight
at the opening are admitted just before the window opens, so that the
slots hold steady occupancy from its start.

The window: requests become due on the schedule, wait in the program's
``AdmissionQueue``, and are admitted into free slots between decode
steps.  A token's time is the host time at which it appears in the
request's ``out``; time to first token counts from the request's due
time, and a request due in the window but not yet answered at its close
enters the tail with its wait so far.

After the window the program's state is freed and the reference scores
a sample of the finished requests: for each served token, how far its
reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import gc
import time
import types

import numpy as np

from harness import common, traffic
from harness.common import say

#: served tokens the correctness sample gathers at least (the longest
#: finished request always among them)
SAMPLE_TOKENS = 600
SAMPLE_REQUESTS = 8
#: the reference runs on sequences padded to a multiple of this
REF_PAD = 1024


def _annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def setup(rec, devices):
    """Everything before the window that does not depend on the clock."""
    import jax
    from repro.core.fabric import Fabric
    from repro.runtime.serve_loop import ContinuousServeLoop, Request
    from harness import program

    conf, mix = rec.conf, rec.mix
    cfg = program.program_config(conf)
    key = program.seed_key(rec.seed)
    to_program = program.weights_map(conf)
    params = jax.jit(lambda k: to_program(conf, rec.ref.init_weights(
        conf, k, cfg.param_dtype())))(key)
    program.check_tree(params, cfg)
    jax.block_until_ready(params)
    rec.gang = Fabric(devices=list(devices)).bind("serve0", list(devices))
    serve = conf["serve"]
    loop = ContinuousServeLoop(cfg, params, slots=serve["slots"],
                               max_len=serve["max_len"], handle=rec.gang)
    del params
    lo, hi = traffic.length_range(mix["prompt_tokens"])
    buckets = sorted({loop.bucket(n) for n in range(lo, hi + 1)})
    rec.kernels = {
        "prefill": sorted(common.kernels_called(loop.lower(buckets[0]))),
        "decode": sorted(common.kernels_called(loop.lower()))}
    say(f"kernels on the path: {rec.kernels}; prefill buckets {buckets}")
    for b in buckets:                       # every program the mix uses
        n = min(b, serve["max_len"] - 1)
        loop.admit(Request(rid=-b, prompt=np.zeros(n, np.int32),
                           max_new_tokens=2))
        loop.decode_step()
        jax.block_until_ready(loop.serve_state()["cur"])
    loop.load_serve_state({"params": loop.params})   # empty slot array
    plant(rec.fault, loop, conf["vocab_size"])
    rec.loop, rec.cfg = loop, cfg
    rec.schedule = traffic.serve_schedule(mix, rec.seed, rec.seconds,
                                          conf["vocab_size"])
    rec.in_flight = traffic.in_flight(mix, rec.seed, conf["vocab_size"])


def plant(fault: str, loop, vocab: int) -> None:
    """Break the timed path on purpose (the check must then fail):
    ``token`` alters every seventh token where the loop produces it;
    ``stale_cache`` makes each decode step hand back its cache
    unchanged."""
    if fault == "token":
        step = loop.decode_step

        def altered(now=None):
            n = step(now)
            for r in loop._reqs:
                if r is not None and len(r.out) % 7 == 3:
                    r.out[-1] = (r.out[-1] + 1) % vocab
            return n
        loop.decode_step = altered
    elif fault == "stale_cache":
        serve_fn = loop._serve
        loop._serve = lambda p, st, tok, pos: (serve_fn(p, st, tok, pos)[0],
                                               st)
    elif fault:
        raise SystemExit(f"bench: no serve fault {fault!r}")


def window(rec, trace_dir=None):
    """Admit the requests in flight at the opening, then the measured
    window; fills ``rec.serve``."""
    import jax
    from repro.runtime.admission import AdmissionQueue
    from repro.runtime.serve_loop import Request

    loop, seconds = rec.loop, rec.seconds
    made = lambda p: Request(rid=p.rid, prompt=p.prompt,
                             max_new_tokens=p.max_new_tokens, arrival=p.due)
    opened = [made(p) for p in rec.in_flight]
    for r in opened:
        if loop.admit(r) is None:
            raise SystemExit("bench: more requests in flight at the opening "
                             f"than the {loop.slots} slots")
    live = list(opened)                     # admitted, not finished
    jax.block_until_ready(loop.serve_state()["cur"])
    reqs = [made(p) for p in rec.schedule]
    queue = AdmissionQueue()
    tok_t = {r.rid: [] for r in live + reqs}  # host time of every token
    admits, steps, late = [], [], []
    dispatch = []                           # (host time, kind, work)
    nxt = 0
    trace_at = seconds - rec.trace_s if trace_dir else None
    traced = None
    clock = time.perf_counter
    origin = clock()                        # the window opens here
    rec.t_open_abs = origin
    while True:
        now = clock() - origin
        if now >= seconds:
            break
        if trace_at is not None and traced is None and now >= trace_at:
            jax.profiler.start_trace(trace_dir)
            traced = _annotate("bench.traced")
            traced.__enter__()
            rec.trace_host = (clock(), None)
        while nxt < len(reqs) and reqs[nxt].arrival <= now:
            late.append(now - reqs[nxt].arrival)
            queue.push(reqs[nxt])
            nxt += 1
        while len(queue) and loop.free_slots:
            r = queue.pop()
            t0 = clock()
            with _annotate("bench.admit"):
                loop.admit(r)
            admits.append((r.rid, t0 - origin, len(r.prompt)))
            dispatch.append((t0, "admit", len(r.prompt)))
            live.append(r)
        if live:
            ctx = [int(loop._plen[i] + loop._t[i] + 1)
                   for i in range(loop.slots) if loop._reqs[i] is not None]
            t0 = clock()
            with _annotate("bench.decode_step"):
                loop.decode_step()
            dispatch.append((t0, "decode", ctx))
            t1 = clock() - origin
            steps.append((t0 - origin, t1, ctx))
            still = []
            for r in live:
                seen = tok_t[r.rid]
                seen.extend([t1] * (len(r.out) - len(seen)))
                if len(r.out) < r.max_new_tokens:
                    still.append(r)
            live = still
        else:
            wait = (reqs[nxt].arrival if nxt < len(reqs) else seconds) - now
            with _annotate("bench.idle"):
                time.sleep(max(0.0, min(wait, seconds - now)))
    close = clock() - origin
    if traced is not None:
        traced.__exit__(None, None, None)
        rec.trace_host = (rec.trace_host[0], clock())
        jax.profiler.stop_trace()
    rec.serve = types.SimpleNamespace(
        reqs=opened + reqs, tok_t=tok_t, admits=admits, steps=steps,
        late=late, dispatch=dispatch, close=close)


def end_to_end(rec) -> dict:
    s = rec.serve
    close = s.close
    due_in = [r for r in s.reqs if 0.0 <= r.arrival < close]
    ttft, itl, tokens = [], [], 0
    for r in s.reqs:
        ts = [t for t in s.tok_t[r.rid] if 0.0 <= t <= close]
        tokens += len(ts)
        itl += list(np.diff(ts))
    for r in due_in:
        ts = s.tok_t[r.rid]
        first = ts[0] if ts and ts[0] <= close else close
        ttft.append(first - r.arrival)
    rec.attempted = len(due_in)
    return {"ttft_p90_ms": common.percentile(ttft, 90) * 1e3,
            "itl_p99_ms": common.percentile(itl, 99) * 1e3,
            "serve_tokens_per_s": tokens / close}


def info(rec) -> None:
    s = rec.serve
    done = sum(1 for r in s.reqs if len(r.out) == r.max_new_tokens)
    say(f"window {s.close:.3f} s: {len(s.steps)} decode steps, "
        f"{len(s.admits)} admits, {done} requests finished, generator "
        f"late p99 {common.percentile(s.late, 99) * 1e3:.3f} ms max "
        f"{max(s.late) * 1e3:.3f} ms (polled between steps)")


def release(rec) -> None:
    """Free the program's weights and cache before the reference runs."""
    rec.loop = None
    rec.gang.release()
    gc.collect()


def sample(rec) -> list:
    """Finished requests for the check, drawn from the seed: the longest,
    then others until ``SAMPLE_TOKENS`` served tokens."""
    done = [r for r in rec.serve.reqs if r.out
            and len(r.out) == r.max_new_tokens]
    if not done:
        return []
    rng = np.random.default_rng(traffic.seed_words(rec.seed, 11))
    done.sort(key=lambda r: (-len(r.out), r.rid))
    pick, rest = [done[0]], [done[i] for i in rng.permutation(
        np.arange(1, len(done)))]
    while rest and len(pick) < SAMPLE_REQUESTS and \
            sum(len(r.out) for r in pick) < SAMPLE_TOKENS:
        pick.append(rest.pop(0))
    return pick


def served_gaps(rec, reqs, control=False) -> dict:
    """Widest gap (in units of the row's standard deviation) by which a
    served token's reference logit lies below the reference's best, over
    every served token of ``reqs``; with ``control`` also the same gap
    of the token that the reference in the control's lower precision
    puts first."""
    import jax
    import jax.numpy as jnp
    from harness import precision, program

    conf, ref = rec.conf, rec.ref
    key = program.seed_key(rec.seed)
    w = jax.jit(lambda k: ref.init_weights(conf, k,
                                           jnp.dtype(conf["torch_dtype"])))(key)

    def gaps_of(logits, tok):
        best = jnp.max(logits, -1)
        got = jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]
        return (best - got) / jnp.std(logits, -1)

    @jax.jit
    def score(w, inp, tgt, mask):
        ref_l = ref.logits(conf, w, inp)
        out = {"served": jnp.max(jnp.where(mask, gaps_of(ref_l, tgt), 0.0))}
        if control:
            mm = precision.CONTROLS[conf["control_precision"]]
            low = ref.logits(conf, w, inp, mm=mm)
            pick = jnp.argmax(low, -1).astype(jnp.int32)
            out["control"] = jnp.max(jnp.where(mask, gaps_of(ref_l, pick),
                                               0.0))
        return out

    worst = {"served": 0.0, "control": 0.0}
    n_tok = 0
    for r in reqs:
        full = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.out, np.int32)])
        n = len(full) - 1
        pad = -(-n // REF_PAD) * REF_PAD
        inp = np.zeros(pad, np.int32)
        tgt = np.zeros(pad, np.int32)
        inp[:n], tgt[:n] = full[:-1], full[1:]
        mask = np.zeros(pad, bool)
        mask[len(r.prompt) - 1:n] = True
        got = score(w, jnp.asarray(inp), jnp.asarray(tgt), jnp.asarray(mask))
        for k, v in got.items():
            worst[k] = max(worst[k], float(v))
        n_tok += int(mask.sum())
    return {**worst, "tokens": n_tok}


def run(rec, devices) -> dict:
    """Set-up, window, check; returns the parts of the result line."""
    setup(rec, devices)
    window(rec, trace_dir=rec.trace_dir)
    info(rec)
    metrics = end_to_end(rec)
    rec.device = common.device_info(devices)
    want = rec.conf["serve"]["kernels"]
    kernels_ok = all(set(want[k]) <= set(rec.kernels[k]) for k in want)
    release(rec)
    t0 = time.perf_counter()
    picked = sample(rec)
    gaps = served_gaps(rec, picked, control=rec.control)
    say(f"reference over {len(picked)} requests, {gaps['tokens']} served "
        f"tokens, {time.perf_counter() - t0:.3f} s; served gap "
        f"{gaps['served']!r}")
    # the control's tokens stand in the program's place
    judged = "control" if rec.control else "served"
    limits = rec.conf["limits"]
    checks = {
        "kernels_on_path": {"value": int(kernels_ok), "limit": 1},
        "sampled_tokens": {"value": gaps["tokens"],
                           "limit": limits["min_sampled_tokens"]},
        f"{judged}_gap_z_max": {"value": gaps[judged],
                                "limit": limits["served_gap_z_max"]}}
    correct = (kernels_ok
               and gaps["tokens"] >= limits["min_sampled_tokens"]
               and gaps[judged] <= limits["served_gap_z_max"])
    return {"correct": correct, "metrics": metrics, "checks": checks,
            "failed": 0}


# ---- reading the trace: the serve programs' runs, paired with their work --
#: the programs' names in the trace (the jitted functions' names)
PROGRAMS = {"admit": "jit_admit", "decode": "jit_serve_step"}


def traced_runs(rec) -> list:
    """The admit and decode programs that ran on the chip wholly inside
    the traced window, each paired with the host dispatch it ran for: the
    chip runs them in dispatch order, so the kinds of the runs match a
    stretch of the dispatch sequence, found near where the host clock
    puts it.  Each run: (kind, work, start_ns, duration_ns)."""
    from harness import trace as tr
    if getattr(rec, "_runs", None) is not None:
        return rec._runs
    t = rec.trace
    runs = []
    for name, start, dur in t.programs.get(0, []):
        if not (t.window[0] <= start and start + dur <= t.window[1]):
            continue
        for kind, prog in PROGRAMS.items():
            if tr.matches(name, prog):
                runs.append((kind, start, dur))
    host = rec.serve.dispatch
    offset = t.window[0] - rec.trace_host[0] * 1e9
    kinds = [k for k, _, _ in runs]
    best = None
    for j in range(len(host) - len(runs) + 1):
        if [h[1] for h in host[j:j + len(runs)]] != kinds:
            continue
        lead = runs[0][1] - (host[j][0] * 1e9 + offset) if runs else 0.0
        if lead >= 0 and (best is None or lead < best[0]):
            best = (lead, j)
    if best is None:
        rec._runs = []
    else:
        j = best[1]
        rec._runs = [(k, host[j + i][2], s, d)
                     for i, (k, s, d) in enumerate(runs)]
    return rec._runs


def kernel_in_runs(rec, key: str, kind: str) -> float:
    """Device seconds of the ops named with ``key`` inside the runs of
    ``kind``."""
    import bisect
    ops = sorted((s, d) for n, _, s, d in rec.trace.ops.get(0, [])
                 if key in n)
    starts = [s for s, _ in ops]
    total = 0.0
    for k, _, s, d in traced_runs(rec):
        if k != kind:
            continue
        i = bisect.bisect_left(starts, s)
        while i < len(ops) and ops[i][0] < s + d:
            total += ops[i][1]
            i += 1
    return total / 1e9
