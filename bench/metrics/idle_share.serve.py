"""Share of the traced window in which no op ran on the serving chip."""
from harness import trace


def read(rec):
    if rec.trace is None or getattr(rec, "serve", None) is None:
        return None
    share = trace.idle_share(rec.trace)
    return None if share is None else 100.0 * share
