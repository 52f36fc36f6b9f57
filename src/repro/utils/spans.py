"""Profiler-clock spans of the served path.

The serving stack's other observability (``serving.tracing.Tracer``,
``serving.flightrecorder.FlightRecorder``, ``serving.metrics``) runs on
the session clock.  The spans here are host ``TraceMe`` events: the
profiler records them beside the accelerator's own planes on one clock,
so a captured profile shows what the host thread was doing during every
device gap.  They cost nothing but one cheap call unless a profiler
trace is being collected, e.g. inside ``jax.profiler.trace(dir)``:
there is no flag of their own.

Sites attach their stats only while tracing::

    with spans.span("engine.dispatch") as sp:
        ...
        if sp is not None:
            sp.set_metadata(seq=seq, rows=len(items))

and zero-length markers build theirs under ``if spans.active():``.
The span names and their stats are listed in ``docs/observability.md``.
"""
from __future__ import annotations

from contextlib import nullcontext

from jax.profiler import TraceAnnotation

_enabled = TraceAnnotation.is_enabled
_OFF = nullcontext()            # entered as ``None``


def active() -> bool:
    """True while a profiler trace is being collected."""
    return _enabled()


def span(name: str, **stats):
    """A context manager timing the enclosed host work as ``name``.

    Not tracing, it is one shared no-op context whose ``as`` target is
    ``None``; tracing, a ``TraceAnnotation`` carrying ``stats``, to which
    the site may add more with ``set_metadata``."""
    if not _enabled():
        return _OFF
    return TraceAnnotation(name, **stats)
