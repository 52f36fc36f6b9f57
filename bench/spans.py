"""The program's own host spans in a traced window.

The served path marks its host work with profiler spans
(``repro.utils.spans``; names and stats in ``docs/observability.md``):
``session.*`` for the session's event loop, ``backend.*`` for building
a batch and sampling, ``engine.*`` for the engine's step.  The profiler
records them on the chip's clock, so no offset estimate is needed to lay
them over the device trace.

- ``load(trace_dir)``: the program spans of the session's thread and the
  union of the first chip's op intervals, read from the window's
  ``.xplane.pb`` (once per path);
- ``window(ctx)``: those spans and the device busy intervals with the
  measured window placed on the trace clock.  The session spans carry
  the session clock (``now_us``); the median of their starts less that
  clock places the window, which the harness gives on the session clock.
  Nothing here reads the harness's ``bench_step`` / ``bench_collect``;
- ``idle_by_span(win)``: the window's device idle time put down to the
  innermost program span open on the host at that moment (its self
  time), and the idle time under no program span.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import pathlib
import statistics
from typing import Dict, Iterable, List, Optional, Tuple

from bench.trace import DEVICE_PREFIX, Event, _union

PROGRAM = ("session.", "backend.", "engine.")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float              # seconds, trace clock
    end: float
    stats: dict


@dataclasses.dataclass
class Loaded:
    spans: List[Span]         # the session thread's, by start
    busy: List[Tuple[float, float]]


@dataclasses.dataclass
class Window:
    t0: float                 # the measured window, trace clock
    t1: float
    spans: List[Span]
    busy: List[Tuple[float, float]]   # clipped to [t0, t1]

    def named(self, name: str, inside: bool = True) -> List[Span]:
        """Spans called ``name``; with ``inside``, those starting in the
        window."""
        return [s for s in self.spans if s.name == name and (
            not inside or self.t0 <= s.start <= self.t1)]


_CACHE: Dict[str, Loaded] = {}


def from_events(events: Iterable[Event]) -> Loaded:
    """Program spans and the chip's busy intervals from trace events
    (``bench.trace.Event``).  Spans of several host threads keep only
    the thread holding the most: the session runs on one."""
    by_line: Dict[Tuple[str, str], List[Span]] = {}
    ops = []
    for e in events:
        a, b = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
        if e.plane.startswith(DEVICE_PREFIX):
            if e.line == "XLA Ops":
                ops.append((a, b))
        elif e.name.startswith(PROGRAM):
            by_line.setdefault((e.plane, e.line), []).append(
                Span(e.name, a, b, dict(e.stats)))
    spans = max(by_line.values(), key=len, default=[])
    spans.sort(key=lambda s: (s.start, -s.end))
    return Loaded(spans, _union(ops))


def load(trace_dir: str) -> Loaded:
    if trace_dir not in _CACHE:
        from jax.profiler import ProfileData
        files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        pd = ProfileData.from_file(str(files[-1]))
        devs = sorted(p.name for p in pd.planes
                      if p.name.startswith(DEVICE_PREFIX))
        events = []
        for p in pd.planes:
            if p.name.startswith(DEVICE_PREFIX):
                if p.name != devs[0]:
                    continue
                events += [Event(p.name, l.name, "", e.start_ns,
                                 e.duration_ns)
                           for l in p.lines if l.name == "XLA Ops"
                           for e in l.events]
                continue
            events += [Event(p.name, l.name, e.name, e.start_ns,
                             e.duration_ns, tuple(e.stats))
                       for l in p.lines for e in l.events
                       if e.name.startswith(PROGRAM)]
        _CACHE[trace_dir] = from_events(events)
    return _CACHE[trace_dir]


def place(w, loaded: Loaded) -> Optional[Window]:
    """The window of ``w`` (a ``harness.WindowResult``) on the trace
    clock of ``loaded``; None when no session span is there to place
    it."""
    zero = [s.start - s.stats["now_us"] * 1e-6 for s in loaded.spans
            if "now_us" in s.stats]
    if not zero:
        return None
    z = statistics.median(zero)          # session clock 0, trace clock
    t0, t1 = z + w.trace_t0 - w.epoch, z + w.trace_t1 - w.epoch
    busy = [(max(a, t0), min(b, t1)) for a, b in loaded.busy
            if a < t1 and b > t0]
    return Window(t0, t1, loaded.spans, busy)


def window(ctx) -> Optional[Window]:
    """The traced window of a metric reader's context, or None."""
    if ctx.tr is None or not ctx.w.trace_dir:
        return None
    return place(ctx.w, load(ctx.w.trace_dir))


def host_gaps(win: Window) -> List[float]:
    """Seconds from the end of step k's ``engine.collect`` to the end of
    step k+1's ``engine.launch``, for consecutive steps both dispatched
    in the window with k+1 dispatched after k was collected: the host's
    critical path from one step's logits to the next step enqueued."""
    disp = {s.stats["seq"]: s for s in win.named("engine.dispatch")}
    coll = {s.stats["seq"]: s for s in win.named("engine.collect", False)}
    launches = win.named("engine.launch", False)
    starts = [s.start for s in launches]
    gaps = []
    for k, d in disp.items():
        nxt, c = disp.get(k + 1), coll.get(k)
        if nxt is None or c is None or nxt.start < c.end:
            continue
        i = bisect.bisect_left(starts, nxt.start)
        if i < len(launches) and launches[i].end <= nxt.end:
            gaps.append(launches[i].end - c.end)
    return gaps


def _self_time(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """(start, end, name): the stretches in which each span is the
    innermost one open (the spans of one thread nest)."""
    out: List[Tuple[float, float, str]] = []
    stack: List[list] = []      # [span, where its self time resumes]

    def pop():
        s, resume = stack.pop()
        if s.end > resume:
            out.append((resume, s.end, s.name))
        if stack:
            stack[-1][1] = max(stack[-1][1], s.end)

    for s in spans:
        while stack and stack[-1][0].end <= s.start:
            pop()
        if stack and s.start > stack[-1][1]:
            out.append((stack[-1][1], s.start, stack[-1][0].name))
        stack.append([s, s.start])
    while stack:
        pop()
    out.sort()
    return out


def idle_by_span(win: Window) -> dict:
    """Device idle seconds in the window by the innermost program span on
    the host then; ``uncovered_s`` is the idle time under no span."""
    idle, prev = [], win.t0
    for a, b in win.busy + [(win.t1, win.t1)]:
        if a > prev:
            idle.append((prev, a))
        prev = max(prev, b)
    total = sum(b - a for a, b in idle)
    by: Dict[str, float] = {}
    segs = _self_time(win.spans)
    j = 0
    for a, b in idle:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            ov = min(b, segs[k][1]) - max(a, segs[k][0])
            if ov > 0:
                by[segs[k][2]] = by.get(segs[k][2], 0.0) + ov
            k += 1
    covered = sum(by.values())
    steps = len(win.named("engine.dispatch"))
    return {"window_s": win.t1 - win.t0, "idle_s": total,
            "covered_pct": 100.0 * covered / total if total > 0 else None,
            "uncovered_s": total - covered, "steps": steps,
            "by_span_s": dict(sorted(by.items(), key=lambda kv: -kv[1]))}


def idle_line(win: Window) -> str:
    """The printed, unjudged line of ``idle_by_span``."""
    return "idle_by_program_span " + json.dumps(idle_by_span(win))


def queue_wait_ms(ctx) -> Optional[float]:
    """Median wait from a request's due time to its first batch
    (``session.start``'s ``wait_us``), over every request due in the
    window; as for TTFT, one that has not started by the close (shed,
    or still queued) enters at the close less its due time."""
    win = window(ctx)
    if win is None:
        return None
    w = ctx.w
    due = {a.rid: a.due for a in w.arrivals if w.open_t <= a.due < w.close_t}
    if not due:
        return None
    waits = {rid: w.close_t - d for rid, d in due.items()}
    for s in win.named("session.start", False):
        rid = s.stats["rid"]
        if rid in waits:
            waits[rid] = min(waits[rid], s.stats["wait_us"] * 1e-6)
    return 1e3 * statistics.median(waits.values())
