#!/usr/bin/env python3
"""Find a serve cell's knee once, on the chip, in one process: set the
cell up once, then run its traffic at each offered rate for the window
and report, per rate, the share of requests due in the window that met
both limits (time to first token, and time per output token after the
first), the tails, and whether the backlog grew.  The knee is the
highest rate without a growing backlog at which at least 90% met both
(DistServe's definition).  The requests in flight at the opening scale
with the rate, up to the slots.  One JSON line per rate.

    python3 bench/sweep.py --workload <serve cell> --rates 1,2,3 \\
        --seconds 30 --ttft-ms 1000 --tpot-ms 100
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402

from harness import common, serve, traffic  # noqa: E402


def rate_report(rec, ttft_ms: float, tpot_ms: float) -> dict:
    s = rec.serve
    admitted = {rid: t for rid, t, _ in s.admits}
    due = [r for r in s.reqs if 0.0 <= r.arrival < s.close]
    met, ttfts, tpots = 0, [], []
    for r in due:
        ts = [t for t in s.tok_t[r.rid] if t <= s.close]
        ttft = (ts[0] if ts else s.close) - r.arrival
        tpot = (ts[-1] - ts[0]) / (len(ts) - 1) if len(ts) > 1 else 0.0
        ttfts.append(ttft)
        tpots.append(tpot)
        met += ttft * 1e3 <= ttft_ms and tpot * 1e3 <= tpot_ms

    def backlog(t):
        return sum(1 for r in s.reqs if 0.0 <= r.arrival <= t
                   and admitted.get(r.rid, 1e9) > t)
    return {"due": len(due), "met_share": met / max(len(due), 1),
            "ttft_p50_ms": common.percentile(ttfts, 50) * 1e3,
            "ttft_p90_ms": common.percentile(ttfts, 90) * 1e3,
            "tpot_p90_ms": common.percentile(tpots, 90) * 1e3,
            "backlog_mid": backlog(s.close / 2),
            "backlog_end": backlog(s.close),
            "requests_ttft_tpot_ms": [[round(a * 1e3, 3), round(b * 1e3, 3)]
                                      for a, b in zip(ttfts, tpots)],
            **serve.end_to_end(rec)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--ttft-ms", type=float, required=True)
    ap.add_argument("--tpot-ms", type=float, required=True)
    ap.add_argument("--manifest", default=str(common.ROOT / "BENCHMARK.json"))
    args = ap.parse_args(argv)
    m = common.Manifest(args.manifest)
    cell = m.workload(args.workload)
    devices = common.require_chips(cell["chips"])
    common.enable_cache()
    conf = m.config(cell["config"])
    rec = types.SimpleNamespace(
        cell=cell, conf=conf, mix=m.mix(cell["traffic"]),
        ref=m.reference(conf), seed=args.seed, seconds=args.seconds,
        trace_s=0.0, fault="")
    t0 = time.perf_counter()
    serve.setup(rec, devices)
    common.say(f"set-up {time.perf_counter() - t0:.3f} s")
    for rate in (float(r) for r in args.rates.split(",")):
        mix = m.mix(cell["traffic"])
        base = mix["arrivals"]["rate_per_s"]
        rec.mix = {**mix, "arrivals": {**mix["arrivals"], "rate_per_s": rate},
                   "in_flight_at_open": min(conf["serve"]["slots"], round(
                       mix.get("in_flight_at_open", 0) * rate / base))}
        rec.schedule = traffic.serve_schedule(rec.mix, rec.seed,
                                              rec.seconds,
                                              conf["vocab_size"])
        rec.in_flight = traffic.in_flight(rec.mix, rec.seed,
                                          conf["vocab_size"])
        rec.loop.load_serve_state({"params": rec.loop.params})
        serve.window(rec)
        out = {"rate_per_s": rate, **rate_report(rec, args.ttft_ms,
                                                 args.tpot_ms)}
        out["decode_step_ms_p50"] = float(np.median(
            [b - a for a, b, _ in rec.serve.steps])) * 1e3
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
