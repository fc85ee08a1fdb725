"""The traced window over the decode steps run in it: the time per
token of a lane, admits and host time included."""
from harness import serve


def read(rec):
    if rec.trace is None or getattr(rec, "serve", None) is None:
        return None
    n = sum(1 for k, *_ in serve.traced_runs(rec) if k == "decode")
    return None if not n else rec.trace.window_s * 1e3 / n
