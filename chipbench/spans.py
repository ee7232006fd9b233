"""The serving program's own spans in a profiler trace, and what the device
did under each.

The program opens a ``jax.profiler.TraceAnnotation`` named ``serve.<name>``
around each step of its work (``repro.serve.trace.SPANS``), on the same
clock as the device's operations. This module reads them beside the
benchmark's ``bench.*`` spans (:mod:`chipbench.xplane`), and, as a tool,
runs one cell traced, exactly as ``run.py --trace 1`` does, and prints
what they show:

    python3 chipbench/spans.py --workload saint-flickr.fresh --seed 7 \\
        --seconds 40

Before ``run.py``'s own lines and result line it prints

* ``program_spans``: per ``serve.*`` name, ``n``, ``total_ms`` and
  ``max_ms`` over the window;
* ``idle_by_span``: the window's idle device seconds, each put down to the
  innermost ``serve.*`` span covering it, else the ``bench.*`` span, else
  ``loop``;
* ``spans_over_400ms``: every ``serve.*`` span of the window longer than
  400 ms, with its parent, longest first (where a host stall sits);
* ``layer_readings``: the per-layer numbers these spans and the program's
  upload counters give (``sync_ms``, ``sync_host_ms``, ``upload_mb``,
  ``complete_ms``, ``khop_ms``, ``frdc_build_ms``, ``extract_stage_ms``,
  ``launch_ms``), those the run holds.

A program without the spans or counters gives empty lines, not an error.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import xplane  # noqa: E402

PREFIX = "serve."
STALL_NS = 400_000_000


@dataclasses.dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    thread: str                  # host plane and line (one per thread)

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns


def from_profile(pd) -> List[Span]:
    """The ``serve.*`` host events of a ``jax.profiler.ProfileData``."""
    out: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}#{i}"
            out.extend(Span(e.name, int(e.start_ns), int(e.end_ns), thread)
                       for e in line.events if e.name.startswith(PREFIX))
    return out


def in_window(spans: Sequence[Span], lo: int, hi: int) -> List[Span]:
    return [s for s in spans if s.start_ns >= lo and s.end_ns <= hi]


def parents(spans: Sequence[Span]) -> List[Optional[int]]:
    """Index of each span's parent: the innermost span of the same thread
    that holds it, or None."""
    out: List[Optional[int]] = [None] * len(spans)
    by_thread: Dict[str, List[int]] = collections.defaultdict(list)
    for i, s in enumerate(spans):
        by_thread[s.thread].append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i].start_ns, -spans[i].end_ns))
        stack: List[int] = []
        for i in idx:
            while stack and spans[stack[-1]].end_ns < spans[i].end_ns:
                stack.pop()
            out[i] = stack[-1] if stack else None
            stack.append(i)
    return out


def summary(spans: Sequence[Span]) -> Dict[str, dict]:
    """``n``, ``total_ms`` and ``max_ms`` per span name."""
    acc: Dict[str, List[int]] = collections.defaultdict(list)
    for s in spans:
        acc[s.name].append(s.ns)
    return {k: dict(n=len(v), total_ms=sum(v) * 1e-6, max_ms=max(v) * 1e-6)
            for k, v in sorted(acc.items())}


def mean_ms(spans: Sequence[Span], name: str) -> Optional[float]:
    d = [s.ns for s in spans if s.name == name]
    return sum(d) / len(d) * 1e-6 if d else None


def idle_ns(span: Span, busy: Sequence[xplane.Interval],
            starts: Optional[List[int]] = None) -> int:
    """The part of ``span`` in which the device ran nothing, against the
    merged, sorted ``busy`` intervals (:func:`xplane.busy_intervals`);
    ``starts``, their start times, may be passed in once for many spans."""
    starts = [b[0] for b in busy] if starts is None else starts
    i = max(bisect.bisect_right(starts, span.start_ns) - 1, 0)
    covered = 0
    while i < len(busy) and busy[i][0] < span.end_ns:
        covered += max(0, min(busy[i][1], span.end_ns)
                       - max(busy[i][0], span.start_ns))
        i += 1
    return span.ns - covered


def mean_idle_ms(spans: Sequence[Span], name: str,
                 busy: Sequence[xplane.Interval]) -> Optional[float]:
    starts = [b[0] for b in busy]
    d = [idle_ns(s, busy, starts) for s in spans if s.name == name]
    return sum(d) / len(d) * 1e-6 if d else None


def idle_by_span(idle: Sequence[xplane.Interval], spans: Sequence[Span],
                 bench: Sequence[xplane.Event]) -> Dict[str, float]:
    """Idle device seconds by label: the innermost ``serve.*`` span
    covering each idle instant, else the ``bench.*`` span (other than the
    window), else ``loop``."""
    par = parents(spans)
    depth = [0] * len(spans)
    for i in sorted(range(len(spans)),
                    key=lambda i: (spans[i].start_ns, -spans[i].end_ns)):
        depth[i] = 0 if par[i] is None else depth[par[i]] + 1
    # labelled intervals, higher rank wins: serve spans by depth over the
    # benchmark's spans
    lab = [(s.start_ns, s.end_ns, s.name, (2, depth[i], s.start_ns))
           for i, s in enumerate(spans)]
    lab += [(e.start_ns, e.end_ns, e.name, (1, 0, e.start_ns))
            for e in bench if e.name != xplane.WINDOW_SPAN]
    ev = [(s, 1, k) for k, (s, _, _, _) in enumerate(lab)]
    ev += [(e, -1, k) for k, (_, e, _, _) in enumerate(lab)]
    ev += [(s, 1, -1) for s, _ in idle] + [(e, -1, -1) for _, e in idle]
    ev.sort(key=lambda x: x[0])
    active: Dict[int, tuple] = {}
    idle_depth = 0
    out: Dict[str, float] = collections.defaultdict(float)
    for j, (t, d, k) in enumerate(ev):
        if k < 0:
            idle_depth += d
        elif d > 0:
            active[k] = lab[k][3]
        else:
            active.pop(k, None)
        nxt = ev[j + 1][0] if j + 1 < len(ev) else t
        if nxt > t and idle_depth > 0:
            name = (lab[max(active, key=active.get)][2] if active
                    else xplane.UNLABELLED)
            out[name] += (nxt - t) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def stalls(spans: Sequence[Span], over_ns: int = STALL_NS) -> List[list]:
    """``[name, ms, parent name]`` of every span longer than ``over_ns``,
    longest first."""
    par = parents(spans)
    out = [[s.name, s.ns * 1e-6,
            None if par[i] is None else spans[par[i]].name]
           for i, s in enumerate(spans) if s.ns > over_ns]
    return sorted(out, key=lambda r: -r[1])[:20]


def counters(engine, session) -> Dict[str, int]:
    """The program's upload counters and what they are counted per; a
    program without a counter leaves it out."""
    out = dict(invalidations=session.invalidations,
               batches=engine.metrics.batches)
    for key, obj in (("sync_bytes", session),
                     ("launch_bytes", getattr(session, "core", None))):
        v = getattr(obj, "bytes_uploaded", None)
        if v is not None:
            out[key] = v
    return out


def readings(spans: Sequence[Span], busy: Sequence[xplane.Interval],
             delta: Dict[str, int], full_cache: bool) -> Dict[str, float]:
    """The per-layer numbers of one traced window, those it holds. Upload
    is per refresh on the full-cache path, per batch on the subgraph
    path."""
    out = dict(
        sync_ms=mean_ms(spans, "serve.sync"),
        sync_host_ms=mean_idle_ms(spans, "serve.sync", busy),
        complete_ms=mean_ms(spans, "serve.complete"),
        khop_ms=mean_ms(spans, "serve.extract.khop"),
        frdc_build_ms=mean_ms(spans, "serve.extract.frdc"),
        extract_stage_ms=mean_ms(spans, "serve.extract.stage"),
        launch_ms=mean_ms(spans, "serve.launch"))
    num, per = (("sync_bytes", "invalidations") if full_cache
                else ("launch_bytes", "batches"))
    if delta.get(per) and num in delta:
        out["upload_mb"] = delta[num] / delta[per] / 1e6
    return {k: v for k, v in out.items() if v is not None}


def run_traced(workload: str, seed: int, seconds: float, **kw) -> dict:
    """One traced run of a cell through ``run.run_cell``, with the
    program's spans and the window's counter deltas kept beside it; the
    result gains ``"_spans"``: the lines this tool prints."""
    from jax.profiler import ProfileData

    from chipbench import run

    kept: dict = {}
    real_load, real_drive = xplane.load, run.drive

    def load(path):
        pd = ProfileData.from_file(str(path))
        kept["spans"] = from_profile(pd)
        kept["trace"] = xplane.from_profile(pd)
        return kept["trace"]

    def drive(engine, session, *args):
        c0 = counters(engine, session)
        loop = real_drive(engine, session, *args)
        c1 = counters(engine, session)
        kept["delta"] = {k: c1[k] - c0[k] for k in c1}
        return loop

    xplane.load, run.drive = load, drive
    try:
        res = run.run_cell(workload, seed, seconds, True, **kw)
    finally:
        xplane.load, run.drive = real_load, real_drive
    tr = kept.get("trace")
    lines: dict = {}
    if tr is not None:
        lo, hi = xplane.window(tr)
        spans = in_window(kept["spans"], lo, hi)
        plane = sorted(tr.ops)[0] if tr.ops else ""
        busy = xplane.busy_intervals(tr, plane, lo, hi)
        lines = dict(
            program_spans=summary(spans),
            idle_by_span=idle_by_span(xplane.gaps(busy, lo, hi), spans,
                                      tr.spans),
            spans_over_400ms=stalls(spans),
            layer_readings=readings(spans, busy, kept.get("delta", {}),
                                    res["_info"]["plan"]["full_cache"]),
            counters=kept.get("delta", {}))
    res["_spans"] = lines
    return res


def main(argv=None) -> int:
    import argparse

    from chipbench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    run.configure_jax()
    try:
        res = run_traced(args.workload, args.seed, args.seconds)
    except run.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for key, val in res.pop("_spans").items():
        run.log(f"{key}: {json.dumps(val, default=float)}")
    run.print_result(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
