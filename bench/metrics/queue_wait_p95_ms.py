"""95th percentile, over the requests due in the window and admitted,
of the wait from due time to the start of their admit (host clock)."""
from harness import common


def read(rec):
    s = getattr(rec, "serve", None)
    if s is None:
        return None
    due = {r.rid: r.arrival for r in s.reqs}
    waits = [t - due[rid] for rid, t, _ in s.admits if due[rid] >= 0.0]
    return None if not waits else common.percentile(waits, 95) * 1e3
