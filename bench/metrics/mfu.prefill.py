"""Model FLOP utilization of the admit programs in the traced window:
the prompts' model operations over their device time times the chip's
bf16 peak."""
from harness import serve


def read(rec):
    if rec.trace is None or getattr(rec, "serve", None) is None:
        return None
    flops = t = 0.0
    for k, n, _, dur in serve.traced_runs(rec):
        if k == "admit":
            flops += rec.work.prefill(rec.conf, n)["flops"]
            t += dur / 1e9
    return None if t <= 0 else 100.0 * flops / (
        t * rec.peaks["bf16_flops_per_s"])
