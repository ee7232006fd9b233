"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
idle gaps and program device time.

Device planes are named ``/device:TPU:<i>``. On each, the line ``XLA Ops``
holds one event per operation that ran, and ``XLA Modules`` one event per
execution of a compiled program, named after the jitted function (``jit_
<name>(<id>)``). The benchmark's own host spans are ``TraceAnnotation``
events whose names start with ``bench.``; ``bench.window`` spans the
measured window.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
UNLABELLED = "loop"

Interval = Tuple[int, int]


@dataclasses.dataclass
class Event:
    name: str
    start_ns: int
    end_ns: int

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class Trace:
    """The parts of one trace the metrics read."""
    ops: Dict[str, List[Event]]        # device plane -> operations
    modules: Dict[str, List[Event]]    # device plane -> program executions
    spans: List[Event]                 # benchmark host spans


def _events(line) -> List[Event]:
    return [Event(e.name, int(e.start_ns), int(e.end_ns))
            for e in line.events]


def from_profile(pd) -> Trace:
    """Collect device and host events from a ``jax.profiler.ProfileData``."""
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        else:
            for line in plane.lines:
                spans.extend(Event(e.name, int(e.start_ns), int(e.end_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=spans)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)))


def window(tr: Trace) -> Interval:
    """Bounds of the measured window, from its host span."""
    w = [s for s in tr.spans if s.name == WINDOW_SPAN]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
    return w[0].start_ns, w[0].end_ns


def union(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """Merged, sorted intervals clipped to [lo, hi]."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of merged ``busy`` intervals inside [lo, hi]."""
    out: List[Interval] = []
    t = lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_intervals(tr: Trace, plane: str, lo: int, hi: int
                   ) -> List[Interval]:
    """Merged intervals of [lo, hi] in which the device ran an operation
    or a program (a program's span also covers the short gaps between its
    operations)."""
    evs = tr.ops.get(plane, []) + tr.modules.get(plane, [])
    return union([(e.start_ns, e.end_ns) for e in evs], lo, hi)


def busy_seconds(tr: Trace, lo: int, hi: int) -> Optional[float]:
    """Seconds in [lo, hi] in which the device was busy, averaged over the
    device planes; None where the trace has no device operation."""
    planes = sorted(set(tr.ops) | set(tr.modules))
    if not planes:
        return None
    per = [sum(e - s for s, e in busy_intervals(tr, p, lo, hi)) * 1e-9
           for p in planes]
    return sum(per) / len(per)


def _overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def label_gaps(idle: Sequence[Interval], spans: Sequence[Event]
               ) -> Dict[str, float]:
    """Idle seconds by what the host was doing: the part of each gap that a
    benchmark span covers goes to that span's name (without ``bench.``),
    the rest to ``loop``. The driver's spans do not nest, so they are
    taken as disjoint."""
    inner = sorted((s for s in spans if s.name != WINDOW_SPAN),
                   key=lambda s: s.start_ns)
    ends = [s.end_ns for s in inner]
    out: Dict[str, float] = collections.defaultdict(float)
    for g in idle:
        rest = g[1] - g[0]
        i = bisect.bisect_right(ends, g[0])
        while i < len(inner) and inner[i].start_ns < g[1]:
            ov = _overlap(g, (inner[i].start_ns, inner[i].end_ns))
            out[inner[i].name[len(SPAN_PREFIX):]] += ov * 1e-9
            rest -= ov
            i += 1
        out[UNLABELLED] += max(rest, 0) * 1e-9
    return dict(out)


def program_times(tr: Trace, pattern: str, lo: int, hi: int
                  ) -> List[float]:
    """Device seconds of each execution, inside [lo, hi], of the programs
    whose name matches ``pattern`` (a regular expression), over all device
    planes."""
    rx = re.compile(pattern)
    return [m.seconds for evs in tr.modules.values() for m in evs
            if rx.search(m.name) and m.start_ns >= lo and m.end_ns <= hi]


_OP_HEAD = re.compile(r"^(%\S+ = [^\s{(]+)")


def op_name(name: str) -> str:
    """An operation's HLO text cut to its name and result shape
    (``%fusion.3 = f32[22313,4,7]``)."""
    m = _OP_HEAD.match(name)
    return m.group(1) if m else name[:80]


def top_ops(tr: Trace, lo: int, hi: int, k: int = 10
            ) -> List[List[object]]:
    """The ``k`` device operations that took most time in [lo, hi],
    averaged over device planes: ``[[name, seconds], ...]``."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for evs in tr.ops.values():
        for o in evs:
            if o.start_ns >= lo and o.end_ns <= hi:
                tot[op_name(o.name)] += o.seconds
    n = max(len(tr.ops), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, secs / n] for name, secs in best]
