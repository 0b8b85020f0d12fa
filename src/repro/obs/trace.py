"""Span tracing: the request-lifecycle half of ``repro.obs``.

A ``Tracer`` records SPANS — named time intervals with attributes — from
any number of threads at once and exports them as a plain event list or
as Chrome-trace JSON (the format Perfetto / ``chrome://tracing`` load
directly).  The serving stack emits one span chain per ticket::

    submit -> queue-wait -> solve -> cache/put -> resolve

with the batch-level ``bucket/pad`` (holding ``batch/stage``) and
``device-solve`` (holding ``solve/dispatch``, ``solve/crop`` and
``solve/wait``) spans beside it, plus ``refill-admission`` spans at
continuous-batching cycle boundaries; every per-ticket span is tagged with
``ticket`` / ``kind`` so a trace reconstructs each request's full
lifecycle (tests/test_obs.py, docs/observability.md).

``span(name, **attrs)`` is the one call instrumented code makes.  It has
two halves, each live only when its consumer is:

* the TRACER half records a ``Span`` into the ambient tracer
  (``use_tracer`` / ``current_tracer``): code that holds a tracer runs its
  stages under ``use_tracer(tracer)``, and everything they call sees it;
* the PROFILER half opens a ``jax.profiler.TraceAnnotation`` while a
  profiler capture is running, so the span lands on the capture's host
  plane, on the device trace's own clock (``step_annotation``).

Design constraints ("lock-free in the hot path"):

* RECORDING takes no lock: finished spans are appended to a
  ``collections.deque`` (append is atomic under the GIL) and span nesting
  lives in per-thread stacks (``threading.local``), so submit paths, the
  scheduler thread, and lane threads never contend.
* DISABLED tracing is cheap: with no tracer and no capture, ``span`` costs
  one contextvar read and one C call (the profiler's ``is_enabled``) and
  returns a shared no-op context — no clock reads, no span records; an
  engine's ``use_tracer(None)`` around a stage adds one contextvar read.
* Timestamps come from ``time.monotonic()`` — the same clock the
  scheduler's deadlines and latency metrics use, so retroactive spans
  (``record``) built from scheduler timestamps land on one axis.

Nothing here imports jax at module import: the module stays importable
(and the tracer testable) without touching device state.  The profiler
half and the compile listener (``watch_compiles``) import jax lazily.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import json
import os
import threading
import time
from typing import Any, NamedTuple


class Span(NamedTuple):
    """One finished span: a named ``[t0, t1]`` interval with attributes.

    ``tid`` is the recording thread's ident; ``parent_id`` is the span id
    of the span that was OPEN on that thread when this one was recorded
    (``None`` at top level) — nesting is per thread, matching how the
    scheduler's threads each own a stage of a request's lifecycle.
    """

    name: str
    t0: float                  # time.monotonic() seconds
    t1: float
    tid: int
    attrs: dict
    span_id: int
    parent_id: int | None


class Tracer:
    """Thread-safe span recorder; export via ``spans()`` / ``to_chrome()``.

    Use ``span(name, **attrs)`` as a context manager for spans that open
    and close on one thread (nesting is tracked automatically), and
    ``record(name, t0, t1, **attrs)`` for RETROACTIVE spans whose
    endpoints were measured elsewhere — e.g. queue-wait, whose start is
    the submit timestamp taken on the caller's thread and whose end is
    the scheduler thread's pop.  ``instant(name, **attrs)`` records a
    zero-length mark.
    """

    def __init__(self):
        self._events: collections.deque[Span] = collections.deque()
        self._ids = itertools.count(1)
        self._local = threading.local()

    # ---- recording (lock-free) ------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Record a span around the ``with`` body (per-thread nesting)."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        sid = next(self._ids)
        stack.append(sid)
        t0 = time.monotonic()
        try:
            yield
        finally:
            t1 = time.monotonic()
            stack.pop()
            self._events.append(Span(name, t0, t1, threading.get_ident(),
                                     attrs, sid, parent))

    def record(self, name: str, t0: float, t1: float, **attrs) -> int:
        """Record a retroactive span from externally-measured endpoints.

        The parent is whatever span is open on the CALLING thread (usually
        none — cross-thread stages are stitched by their shared ``ticket``
        attribute, not by parent ids).  Returns the span id.
        """
        stack = self._stack()
        sid = next(self._ids)
        self._events.append(Span(name, t0, t1, threading.get_ident(), attrs,
                                 sid, stack[-1] if stack else None))
        return sid

    def instant(self, name: str, **attrs) -> int:
        """Record a zero-length mark at the current time."""
        now = time.monotonic()
        return self.record(name, now, now, **attrs)

    # ---- export ----------------------------------------------------------

    def spans(self) -> list[Span]:
        """Finished spans in completion order (a plain event list)."""
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()

    def to_chrome(self) -> dict:
        """The trace as a Chrome-trace / Perfetto JSON object.

        Every span becomes one ``"X"`` (complete) event; ``ts``/``dur``
        are microseconds on the ``time.monotonic`` axis, ``args`` carries
        the span attributes plus ``span_id``/``parent_id``.
        """
        pid = os.getpid()
        events = [{
            "name": s.name, "ph": "X", "pid": pid, "tid": s.tid,
            "ts": s.t0 * 1e6, "dur": max(s.t1 - s.t0, 0.0) * 1e6,
            "args": {**s.attrs, "span_id": s.span_id,
                     "parent_id": s.parent_id},
        } for s in self._events]
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save(self, path) -> None:
        """Write the Chrome-trace JSON to ``path`` (open it in Perfetto)."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


def load_trace(path) -> list[dict]:
    """Load a saved trace; returns its ``traceEvents`` list.

    Accepts both the object form ``Tracer.save`` writes and the bare
    event-array form of the Chrome-trace spec.
    """
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        raise ValueError(f"{path} is not a Chrome-trace file")
    return events


# ---- ambient tracer ------------------------------------------------------

_tracer_var: contextvars.ContextVar[Tracer | None] = \
    contextvars.ContextVar("repro_obs_tracer", default=None)
_NOOP = contextlib.nullcontext()


def current_tracer() -> Tracer | None:
    """The ambient tracer installed by ``use_tracer``, or ``None``.

    A ``ContextVar``, so it does NOT cross thread starts: long-lived
    engines capture it ONCE at construction (``tracer=`` falls back to
    this) and hand it to their worker threads explicitly.
    """
    return _tracer_var.get()


def use_tracer(tracer: Tracer | None):
    """Install ``tracer`` as the ambient tracer for the ``with`` body.

    Installing ``None`` where none is installed changes nothing, so it
    returns the shared no-op context after one contextvar read: an engine
    without a tracer pays that per stage, not a set and a reset.
    """
    if tracer is None and _tracer_var.get() is None:
        return _NOOP
    return _installed(tracer)


@contextlib.contextmanager
def _installed(tracer: Tracer | None):
    token = _tracer_var.set(tracer)
    try:
        yield tracer
    finally:
        _tracer_var.reset(token)


# ---- one span call, two halves -------------------------------------------
_capture_on = None          # the profiler's ``is_enabled``, bound on first use


def _profiling() -> bool:
    """Is a ``jax.profiler`` capture running? One C call once bound."""
    global _capture_on
    if _capture_on is None:
        try:
            from jax._src.lib import _profiler
            _capture_on = _profiler.TraceMe.is_enabled
        except Exception:                              # pragma: no cover
            _capture_on = lambda: False                # noqa: E731
    return _capture_on()


def span(name: str, **attrs: Any):
    """Context manager: the span ``name`` around the ``with`` body.

    The TRACER half records into the ambient tracer (``use_tracer`` /
    ``current_tracer()``); the PROFILER half opens a ``TraceAnnotation``
    while a ``jax.profiler`` capture runs, putting the span on the
    capture's host plane on the device trace's clock.  With neither live
    it returns a shared no-op context after one contextvar read and one C
    call.  ``attrs`` go to the tracer half only.
    """
    tracer = _tracer_var.get()
    capture = _profiling()
    if tracer is None and not capture:
        return _NOOP
    return _both_halves(name, tracer, capture, attrs)


@contextlib.contextmanager
def _both_halves(name, tracer, capture, attrs):
    with (tracer.span(name, **attrs) if tracer is not None else _NOOP), \
            (step_annotation(name) if capture else _NOOP):
        yield


@contextlib.contextmanager
def step_annotation(name: str, **attrs: Any):
    """The profiler half of ``span``: annotate a running capture.

    While a ``jax.profiler`` capture runs, the ``with`` body shows up
    under ``name`` on the capture's host plane, on the same clock as the
    device ops.  A no-op without a capture (and without jax).
    """
    try:
        from jax.profiler import TraceAnnotation
    except Exception:                                  # pragma: no cover
        yield
        return
    with TraceAnnotation(name, **attrs):
        yield


# ---- compile spans ---------------------------------------------------------

COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile/cache",
}


def watch_compiles(tracer: Tracer):
    """Record a span into ``tracer`` for every program jax compiles
    (``compile``) or loads from its persistent cache (``compile/cache``).

    Registers a ``jax.monitoring`` duration listener: each span ends when
    jax reports the event, starts its reported duration earlier, and
    lands on the compiling thread.  Returns ``unwatch()``, which removes
    the listener; call it once.
    """
    import jax.monitoring

    def listen(event: str, duration: float, **_: Any) -> None:
        name = COMPILE_EVENTS.get(event)
        if name is not None:
            t1 = time.monotonic()
            tracer.record(name, t1 - duration, t1, event=event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: jax.monitoring.unregister_event_duration_listener(listen)
