"""Program spans: named, nested intervals of host time on the served path.

    from repro.spans import span
    with span("executor.run_batch", bucket=4, n=3):
        ...

A span is recorded only while a JAX profiler session is active
(``jax.profiler.start_trace``, or a capture through the profiler server;
``jax.profiler.TraceAnnotation.is_enabled()`` says which).  Otherwise
``span()`` makes that one check and returns a shared no-op context, so
the spans cost nothing measurable on a path that runs unprofiled.  While
recording, each span is appended to an in-memory timeline and opens a
``jax.profiler.TraceAnnotation`` named ``archipelago.<name>``, which puts it
in the profiler's own trace beside the device operations; the profiler's
trace is the export.  The timeline holds one session's spans: the first
span of a new session clears what the last one left.

A session can only have been started through ``jax.profiler``, so this
module looks for it among the loaded modules and never imports JAX itself:
a process that runs the scheduler alone pays neither the import nor the
memory of JAX's runtime.

Spans decide nothing: they draw no random numbers, schedule no events and
feed no clock reading back into the program.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, List, Optional

PREFIX = "archipelago."

_modules = sys.modules
_forced = False
_session = False            # the last span() saw a profiler session
_timeline: List["Span"] = []


class _Open(threading.local):
    top: Optional["Span"] = None        # the innermost open span


_open = _Open()


class Span:
    """One recorded span: ``start`` and ``end`` in ``time.perf_counter_ns``,
    ``parent`` the span open on the same thread when it began (None at the
    top), and the ``attrs`` it was given."""

    __slots__ = ("name", "start", "end", "parent", "attrs", "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any], start: int = 0,
                 end: int = 0, parent: Optional["Span"] = None,
                 annotation: Any = None):
        self.name = name
        self.attrs = attrs
        self.start = start
        self.end = end
        self.parent = parent
        self._ann = annotation      # its twin in the profiler's trace

    def __enter__(self) -> "Span":
        self.parent = _open.top
        _open.top = self
        _timeline.append(self)
        if self._ann is not None:
            self._ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        _open.top = self.parent


class _NoSpan:
    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> None:
        return None


NO_SPAN = _NoSpan()


def span(name: str, **attrs: Any):
    """A context manager that records the span ``name`` while the profiler
    is on, and the shared ``NO_SPAN`` otherwise."""
    global _session
    prof = _modules.get("jax.profiler")
    if prof is not None and prof.TraceAnnotation.is_enabled():
        if not _session:
            _session = True
            _timeline.clear()
        return Span(name, attrs, annotation=prof.TraceAnnotation(
            PREFIX + name, **attrs))
    _session = False
    if _forced:
        return Span(name, attrs)
    return NO_SPAN


def force(on: bool) -> None:
    """Record with no profiler session (tests); ``force(False)`` returns to
    recording only while the profiler is on."""
    global _forced
    _forced = on


def timeline() -> List[Span]:
    """The recorded spans, in the order they began: those of the current
    or last profiler session, and any recorded while forced since."""
    return list(_timeline)


def reset() -> None:
    """Forget every recorded span."""
    _timeline.clear()
