"""From a ``jax.profiler`` trace to numbers: device busy time as a union
of op intervals, idle share, a kernel's device time found by name,
program time, and the ``breakdown`` of the
result line (the device ops that took most time, and the idle gaps
labelled by the host span that was open during them).

``load`` reduces an ``.xplane.pb`` to a small plain structure, ``Trace``,
which the functions below read and the tests build by hand:

* ``ops[device]``: (name, program, start_ns, duration_ns) of every op on
  the device's "XLA Ops" line; ``program`` is the XLA module it ran in;
* ``programs[device]``: (name, start_ns, duration_ns) of the "XLA
  Modules" line;
* ``host``: (name, start_ns, duration_ns) of the benchmark's own
  ``TraceAnnotation`` spans (names starting ``bench.``);
* ``window``: (start_ns, end_ns) of the ``bench.traced`` span that
  brackets the traced stretch, on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]
#: idle stretches shorter than this sit between the ops of one program;
#: longer ones are put down to what the host was doing
SHORT_GAP_NS = 20_000


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Tuple[str, str, float, float]]]
    programs: Dict[int, List[Event]]
    host: List[Event]
    window: Tuple[float, float]

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops={int(k): [tuple(e) for e in v]
                        for k, v in d["ops"].items()},
                   programs={int(k): [tuple(e) for e in v]
                             for k, v in d["programs"].items()},
                   host=[tuple(e) for e in d["host"]],
                   window=tuple(d["window"]))

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def load(trace_dir: str, devices: int) -> Trace:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    ops, programs, host = {}, {}, []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[12:].isdigit():
            dev = int(name[12:])
            if dev >= devices:
                continue
            for line in plane.lines:
                if line.name == "XLA Modules":
                    programs[dev] = [(e.name, e.start_ns, e.duration_ns)
                                     for e in line.events]
                elif line.name == "XLA Ops":
                    ops[dev] = [(op_name(e.name),
                                 str(_stat(e, "hlo_module") or ""),
                                 e.start_ns, e.duration_ns)
                                for e in line.events]
        elif name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.name.startswith("bench.")]
    spans = [h for h in host if h[0] == "bench.traced"]
    if not spans:
        raise RuntimeError("the trace holds no bench.traced span")
    window = (spans[0][1], spans[0][1] + spans[0][2])
    for dev, evs in ops.items():
        ops[dev] = [e if e[1] else (e[0], _program_at(programs.get(dev, []),
                                                      e[2]), e[2], e[3])
                    for e in evs]
    return Trace(ops=ops, programs=programs, host=host, window=window)


def op_name(text: str) -> str:
    """An op event's HLO instruction name (``%moe_expert_ffn.6``): the
    trace names each op by its whole HLO text, operands included."""
    return text.split(" = ", 1)[0]


#: ops that only contain others (a scanned layer loop is one ``while``);
#: their time is their children's, so the breakdown does not rank them
CONTAINERS = ("%while", "%conditional", "%call")


def op_kind(name: str) -> str:
    """``%copy.171`` -> ``copy``: the instruction without its number."""
    base = name.lstrip("%")
    head, _, tail = base.rpartition(".")
    return head if head and tail.isdigit() else base


def _program_at(programs: List[Event], t: float) -> str:
    for name, start, dur in programs:
        if start <= t < start + dur:
            return name
    return ""


def clip(start: float, dur: float, window) -> Tuple[float, float]:
    lo, hi = max(start, window[0]), min(start + dur, window[1])
    return (lo, hi) if hi > lo else (lo, lo)


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def covered(intervals) -> float:
    return sum(hi - lo for lo, hi in union(intervals))


def busy_ns(trace: Trace, dev: int) -> float:
    return covered(clip(s, d, trace.window) for _, _, s, d in
                   trace.ops.get(dev, []))


def busy_s(trace: Trace) -> float:
    """Device busy seconds in the window, averaged over the devices."""
    devs = sorted(trace.ops)
    return sum(busy_ns(trace, d) for d in devs) / max(len(devs), 1) / 1e9


def idle_share(trace: Trace) -> Optional[float]:
    if not trace.ops or trace.window_s <= 0:
        return None
    return 1.0 - busy_s(trace) / trace.window_s


def matches(name: str, key: str) -> bool:
    return key in name


def program_runs(trace: Trace, key: str, dev: int = 0) -> List[Event]:
    """The runs of programs whose name holds ``key`` that ran wholly
    inside the window (the last one dispatched is still running when
    the window closes, and the trace cuts it short)."""
    lo, hi = trace.window
    return [e for e in trace.programs.get(dev, [])
            if matches(e[0], key) and lo <= e[1] and e[1] + e[2] <= hi]


def gaps(trace: Trace, dev: int) -> List[Tuple[float, float]]:
    """Idle (start, end) stretches of ``dev`` inside the window."""
    busy = union(clip(s, d, trace.window) for _, _, s, d in
                 trace.ops.get(dev, []))
    out, t = [], trace.window[0]
    for lo, hi in busy:
        if lo > t:
            out.append((t, lo))
        t = max(t, hi)
    if t < trace.window[1]:
        out.append((t, trace.window[1]))
    return out


def host_label(trace: Trace, lo: float, hi: float) -> str:
    """The innermost ``bench.`` host span that covers most of [lo, hi)."""
    best, best_cover, best_len = "host: no span", 0.0, float("inf")
    for name, s, d in trace.host:
        if name == "bench.traced":
            continue
        cover = min(hi, s + d) - max(lo, s)
        if cover <= 0:
            continue
        if cover > best_cover or (cover == best_cover and d < best_len):
            best, best_cover, best_len = name, cover, d
    return best


def breakdown(trace: Trace, top: int = 10) -> dict:
    """Top device ops by seconds (mean over devices) and idle seconds by
    host activity (mean over devices), each at most ``top`` entries."""
    n = max(len(trace.ops), 1)
    by_op: Dict[str, float] = {}
    by_gap: Dict[str, float] = {}
    for dev, evs in trace.ops.items():
        for name, prog, s, d in evs:
            if name.startswith(CONTAINERS):
                continue
            lo, hi = clip(s, d, trace.window)
            key = f"{prog.split('(')[0]}:{op_kind(name)}"
            by_op[key] = by_op.get(key, 0.0) + (hi - lo) / 1e9 / n
        for lo, hi in gaps(trace, dev):
            label = (host_label(trace, lo, hi) if hi - lo >= SHORT_GAP_NS
                     else "device: between ops")
            by_gap[label] = by_gap.get(label, 0.0) + (hi - lo) / 1e9 / n
    rank = lambda d: sorted(([k, v] for k, v in d.items() if v > 0),
                            key=lambda kv: -kv[1])[:top]
    return {"device_ops": rank(by_op), "idle_gaps": rank(by_gap)}
