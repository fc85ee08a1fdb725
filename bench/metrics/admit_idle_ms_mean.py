"""Device idle time inside the program's ``serve.admit`` spans of the
traced window (no op running on chip 0), over the number of those
spans: what an admit leaves the chip waiting for."""
import bisect

from harness import spans, trace


def read(rec):
    adm = spans.within(rec, "serve.admit")
    if not adm or not rec.trace.ops.get(0):
        return None
    idle = trace.gaps(rec.trace, 0)
    starts = [lo for lo, _ in idle]
    total = 0.0
    for _, s, d, _ in adm:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(idle) and idle[i][0] < s + d:
            total += max(0.0, min(s + d, idle[i][1]) - max(s, idle[i][0]))
            i += 1
    return total / len(adm) / 1e6
