"""The program's own host spans in a run's profiler trace: the ``serve.``
spans that ``repro.core.telemetry`` writes into the trace while a
profiler session is active, on the clock of the device ops.

A span is (name, start_ns, duration_ns, attrs).  ``of(rec)`` reads them
once from the ``.xplane.pb`` under ``rec.trace_dir`` and keeps them on
``rec``; the tests build them with ``from_json`` from the form
[[name, start_ns, duration_ns, {attrs}], ...].  A trace of a program
that writes no such span yields none, and the readers then return None.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, List, Optional, Tuple

Span = Tuple[str, float, float, Dict[str, Any]]
PREFIX = "serve."


def load(trace_dir: str) -> List[Span]:
    """The ``serve.`` host spans of the newest ``.xplane.pb`` under
    ``trace_dir``, by start."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        return []
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                    for e in line.events if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda s: s[1])


def from_json(items) -> List[Span]:
    return sorted(((n, float(s), float(d), dict(a)) for n, s, d, a in items),
                  key=lambda s: s[1])


def of(rec) -> List[Span]:
    """The run's spans, read once and kept on ``rec``."""
    got = getattr(rec, "_spans", None)
    if got is None:
        got = load(rec.trace_dir) if rec.trace_dir else []
        rec._spans = got
    return got


def within(rec, name: str) -> List[Span]:
    """Spans called ``name`` that lie wholly inside the traced window."""
    if rec.trace is None:
        return []
    lo, hi = rec.trace.window
    return [s for s in of(rec)
            if s[0] == name and lo <= s[1] and s[1] + s[2] <= hi]


def children(rec, parents: List[Span], name: str) -> List[Optional[Span]]:
    """For each of ``parents``, the first span called ``name`` that
    starts inside it (None where there is none)."""
    spans = of(rec)
    starts = [s[1] for s in spans]
    out = []
    for p in parents:
        i = bisect.bisect_left(starts, p[1])
        while i < len(spans) and spans[i][1] <= end(p) \
                and spans[i][0] != name:
            i += 1
        out.append(spans[i] if i < len(spans) and spans[i][1] <= end(p)
                   else None)
    return out


def end(span: Span) -> float:
    return span[1] + span[2]
