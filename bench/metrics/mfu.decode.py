"""Model FLOP utilization of the decode programs in the traced window:
the steps' model operations over their device time times the chip's
bf16 peak."""
from harness import serve


def read(rec):
    if rec.trace is None or getattr(rec, "serve", None) is None:
        return None
    flops = t = 0.0
    for k, ctx, _, dur in serve.traced_runs(rec):
        if k == "decode":
            flops += rec.work.decode(rec.conf, ctx)["flops"]
            t += dur / 1e9
    return None if t <= 0 else 100.0 * flops / (
        t * rec.peaks["bf16_flops_per_s"])
