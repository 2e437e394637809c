"""Spans and counters of the program, on the profiler's clock.

``span(name, **attrs)`` marks one piece of work at a layer boundary:

* it opens ``jax.profiler.TraceAnnotation(name)``, so under a profiler
  session the span lands in the trace beside the device's ops, on their
  clock;
* on exit it appends itself, now a record ``(name, t0, t1, parent,
  attrs, id)``, to a bounded in-memory buffer, timed by
  ``time.perf_counter()``.  ``parent`` is the ``id`` of the enclosing span
  on the same thread.  ``attrs`` are the counters of the work done there;
  those known only at the end are set on the span before it closes::

      with tracing.span("serving.decode", active=3) as sp:
          ...
          sp.attrs["tokens_held"] = held

Compile time is attributed too: every JAX compile event (tracing to a
jaxpr, lowering, backend compile, a persistent-cache read) adds its
seconds to the innermost open span of the thread that compiled, as the
attribute ``compile_s``.  Events nested inside one another (a jit traced
inside another's trace, a cache read inside a backend compile) count once.

Recording is always on: outside a profiler session a span costs the
annotation and one append, a few microseconds.  :func:`records` returns
what the buffer holds, filtered by time.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Optional

import jax

CAPACITY = 1 << 16  # records: minutes of serving at ~10 spans a step

_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
))


class Records(list):
    """What :meth:`Recorder.records` returns: closed :class:`Span` objects.
    ``dropped`` is 0 when the buffer cannot have lost a record of the
    range; otherwise it is how many records the buffer has lost to its
    bound in all."""

    dropped: int = 0


class Span:
    """One span, and once closed its record: ``name``, ``t0`` and ``t1``
    (``time.perf_counter()`` at entry and exit), ``parent`` (the ``id`` of
    the enclosing span on the same thread, or None), ``attrs`` (the
    counters, which may be updated until the span closes) and ``id``."""

    __slots__ = ("name", "attrs", "id", "parent", "t0", "t1", "_recorder",
                 "_ann", "_stack", "_seq")

    def __init__(self, name: str, attrs: dict, recorder: "Recorder"):
        self.name, self.attrs, self._recorder = name, attrs, recorder

    def __enter__(self) -> "Span":
        self._stack = stack = _stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(_ids)
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._stack.pop()
        if not self._stack:  # no compile in flight can outlast it
            _local.compiles = []
        self._ann = self._stack = None
        self._recorder._append(self)
        return False


class Recorder:
    """A bounded buffer of closed spans (the oldest go first)."""

    def __init__(self, capacity: int = CAPACITY):
        self._buf: deque[Span] = deque(maxlen=capacity)
        self._closed = itertools.count()  # next() is atomic under the GIL

    def span(self, name: str, **attrs) -> Span:
        return Span(name, attrs, self)

    def _append(self, span: Span) -> None:
        span._seq = next(self._closed)  # how many closed before it
        self._buf.append(span)

    def records(self, since: Optional[float] = None,
                until: Optional[float] = None) -> Records:
        """The closed spans that started at or after ``since`` and ended at
        or before ``until`` (``time.perf_counter()`` values), in the order
        they closed."""
        buf = list(self._buf)
        out = Records(r for r in buf
                      if (since is None or r.t0 >= since)
                      and (until is None or r.t1 <= until))
        # records leave in the order they closed: every lost one ended
        # before the oldest kept one did
        if buf and buf[0]._seq and (since is None or since <= buf[0].t1):
            out.dropped = buf[0]._seq
        return out


_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list[Span]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _on_duration(event: str, duration: float, **_) -> None:
    """Add a compile event's seconds to the innermost open span, less the
    part of it that earlier events on this thread already covered."""
    if event not in _COMPILE_EVENTS:
        return
    stack = _stack()
    if not stack:
        return
    end = time.perf_counter()
    start = end - duration
    try:
        done = _local.compiles
    except AttributeError:
        done = _local.compiles = []
    # ``done``: disjoint intervals of earlier events, in the order they
    # ended, all before this one; those ending after it began lie inside it
    covered = 0.0
    while done and done[-1][1] > start:
        s, e = done.pop()
        covered += e - max(s, start)
        start = min(start, s)
    done.append((start, end))
    attrs = stack[-1].attrs
    attrs["compile_s"] = attrs.get("compile_s", 0.0) + max(
        duration - covered, 0.0)


jax.monitoring.register_event_duration_secs_listener(_on_duration)

RECORDER = Recorder()
span = RECORDER.span
records = RECORDER.records
