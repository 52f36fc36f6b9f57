"""Reduction of a traced window to device time, step time and kernel time.

Reads the profiler's ``.xplane.pb`` with ``jax.profiler.ProfileData``:

- device busy time: the union of the op intervals on the chip's "XLA Ops"
  line, clipped to the traced window;
- step programs: the executions on the chip's "XLA Modules" line whose
  name holds ``step_body``, matched in order to the harness's host
  annotations ``bench_step`` (one per call into the engine's
  ``dispatch_batch``, carrying its sequence number), so each device step
  knows its spans;
- kernel time: the Mosaic custom calls (the Pallas kernels) on the ops
  line inside each step; in a decode step (every row one token) that is
  the paged-decode kernel, in a mixed step the chunked-prefill kernel;
- idle gaps: the stretches of the window with no op on the chip, each
  named by what the host was doing then (``bench_step``: building and
  dispatching a step; ``bench_collect``: waiting for logits and
  sampling; neither: the session's own loop).
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
# An op event's name is its HLO instruction ("%name.N = type op(...)").
# The Pallas kernels are Mosaic custom calls; the program's wrappers
# name them paged_decode_attention_op / chunked_prefill_attention_op.
KERNEL = re.compile(r'custom_call_target="tpu_custom_call"'
                    r"|^%\w*attention_op[.\s]")
# control flow that holds other ops (the scan over layers)
CONTAINER = re.compile(r"^%(while|conditional|call)[.\s]")


@dataclasses.dataclass
class Step:
    seq: int
    start: float              # seconds, trace clock
    end: float
    decode: bool              # every row one token
    spans: list
    kernel_s: float = 0.0


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    steps: List[Step]
    breakdown: dict


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    w: object                 # harness.WindowResult
    tr: Optional[Reduced]
    peak: dict
    chips: int


@dataclasses.dataclass(frozen=True)
class Event:
    """One trace event, plane and line by name, times in nanoseconds."""
    plane: str
    line: str
    name: str
    start_ns: float
    duration_ns: float
    stats: Tuple[Tuple[str, object], ...] = ()


def _stats(ev) -> Dict[str, object]:
    return dict(ev.stats)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _op_group(ev) -> str:
    """``%fusion.166 = ...`` -> ``fusion``."""
    m = re.match(r"%?([\w-]+?)(\.\d+)?(\s|=|$)", ev.name)
    return m.group(1) if m else ev.name[:64]


def load(trace_dir: str) -> List[Event]:
    """The events of the profiler's trace under ``trace_dir``: every event
    of the first chip's plane, and the harness's host annotations."""
    from jax.profiler import ProfileData
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    devs = sorted(p.name for p in pd.planes
                  if p.name.startswith(DEVICE_PREFIX))
    out = []
    for p in pd.planes:
        dev = devs and p.name == devs[0]
        for l in p.lines:
            for e in l.events:
                if dev or e.name in ("bench_step", "bench_collect"):
                    out.append(Event(p.name, l.name, e.name, e.start_ns,
                                     e.duration_ns, tuple(e.stats)))
    return out


def reduce(w, events: Optional[List[Event]] = None) -> Reduced:
    """``events`` defaults to ``load(w.trace_dir)``."""
    if events is None:
        events = load(w.trace_dir)
    dev = [e for e in events if e.plane.startswith(DEVICE_PREFIX)]
    if not dev:
        raise ValueError("the trace holds no TPU plane")
    ops = [e for e in dev if e.line == "XLA Ops"]
    mods = [e for e in dev if e.line == "XLA Modules"]
    host = {"bench_step": [], "bench_collect": []}
    for e in events:
        if not e.plane.startswith(DEVICE_PREFIX) and e.name in host:
            host[e.name].append(e)
    # host clock (time.monotonic) -> trace clock, anchored on the steps
    anchors = [(e.start_ns * 1e-9, _stats(e).get("seq")) for e in
               host["bench_step"]]
    offs = [t - w.steps[int(k)].t for t, k in anchors if k is not None]
    off = float(np.median(offs)) if offs else 0.0
    t0, t1 = w.trace_t0 + off, w.trace_t1 + off

    busy_iv = _union([(max(e.start_ns * 1e-9, t0),
                       min((e.start_ns + e.duration_ns) * 1e-9, t1))
                      for e in ops if e.start_ns * 1e-9 < t1
                      and (e.start_ns + e.duration_ns) * 1e-9 > t0])
    busy = sum(b - a for a, b in busy_iv)

    dev_steps = sorted((e for e in mods if "step_body" in e.name),
                       key=lambda e: e.start_ns)
    starts = [e.start_ns * 1e-9 for e in dev_steps]
    steps: List[Step] = []
    used = 0
    for t, k in sorted(anchors, key=lambda a: a[0]):
        i = max(used, bisect.bisect_left(starts, t))
        if k is None or i >= len(dev_steps):
            continue
        e = dev_steps[i]
        used = i + 1
        rec = w.steps[int(k)]
        s = e.start_ns * 1e-9
        if s >= t0 and s + e.duration_ns * 1e-9 <= t1:
            steps.append(Step(int(k), s, s + e.duration_ns * 1e-9,
                              rec.T == 1, rec.spans))
    k_starts = sorted((e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in ops
                      if KERNEL.search(e.name))
    ks = [a for a, _ in k_starts]
    for st in steps:
        i = bisect.bisect_left(ks, st.start)
        while i < len(ks) and ks[i] < st.end:
            st.kernel_s += k_starts[i][1]
            i += 1

    groups: Dict[str, float] = {}
    for e in ops:
        if CONTAINER.match(e.name):
            continue
        a = max(e.start_ns * 1e-9, t0)
        b = min((e.start_ns + e.duration_ns) * 1e-9, t1)
        if b > a:
            g = _op_group(e)
            groups[g] = groups.get(g, 0.0) + (b - a)
    device_ops = sorted(groups.items(), key=lambda kv: -kv[1])[:10]

    spans = {n: sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                        * 1e-9) for e in evs) for n, evs in host.items()}
    gaps = []
    prev = t0
    for a, b in busy_iv + [(t1, t1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)

    def doing(a, b):
        """The host annotation covering most of [a, b], if it covers at
        least half of it; else the session's own loop."""
        best, name = 0.5 * (b - a), "host:session_loop"
        for n, label in (("bench_step", "host:build_and_dispatch_step"),
                         ("bench_collect", "host:wait_logits_and_sample")):
            ov = sum(max(0.0, min(b, y) - max(a, x)) for x, y in spans[n]
                     if x < b and y > a)
            if ov >= best:
                best, name = ov, label
        return name

    idle = sorted(((doing(a, b), b - a) for a, b in gaps),
                  key=lambda g: -g[1])[:10]
    return Reduced(window_s=t1 - t0, busy_s=busy, steps=steps,
                   breakdown={"device_ops": [[n, s] for n, s in device_ops],
                              "idle_gaps": [[n, s] for n, s in idle]})
