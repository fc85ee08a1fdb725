"""Median device time of one admit program (prefill of one prompt and
its splice into a slot) in the traced window."""
from harness import common, serve


def read(rec):
    if rec.trace is None or getattr(rec, "serve", None) is None:
        return None
    d = [dur for k, _, _, dur in serve.traced_runs(rec) if k == "admit"]
    return None if not d else common.percentile(d, 50) / 1e6
