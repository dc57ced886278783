"""Thread-safe span tracer with nesting, for Chrome-trace-event export.

A :class:`Tracer` records *spans* — named wall-clock intervals with
key/value attributes — via the ``with tracer.span(name, **attrs):`` context
manager. Spans nest per thread (each records its parent's name and depth),
and the recorded events serialize straight into the Chrome trace event
format (``obs.export.chrome_trace``) that Perfetto / chrome://tracing load.

Each live span also enters a ``jax.profiler.TraceAnnotation`` of the same
name, so under a ``jax.profiler`` trace it sits on the profiler's host
plane, on the same clock as the device ops. A ``dispatch/...`` span times
the host side of a jit dispatch and does not wait for the device: the
device time of that call is read from the device trace, where the
program's stage scopes (``repro.obs.stages``) name every op.

Disabled (the default), ``tracer.span(name)`` returns a module-level no-op
singleton — no event, no allocation, no lock, no jax import.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

from repro.obs import _state


def _now_us() -> float:
    return time.perf_counter() * 1e6


class _NullSpan:
    """The shared disabled-path span: enter/exit do nothing, allocate
    nothing. ``set_attr`` is accepted and dropped so call sites need no
    enabled-check of their own around attribute updates."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_attr(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()


class Span:
    """One live span (context manager). Created only when tracing is on."""

    __slots__ = ("tracer", "name", "attrs", "t0_us", "tid", "parent", "depth",
                 "annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.t0_us = 0.0
        self.tid = 0
        self.parent = ""
        self.depth = 0
        self.annotation = None

    def set_attr(self, **attrs) -> None:
        """Attach/overwrite attributes while the span is open (e.g. a
        result computed inside the ``with`` block)."""
        self.attrs.update(attrs)

    def __enter__(self):
        stack = self.tracer._stack()
        self.parent = stack[-1].name if stack else ""
        self.depth = len(stack)
        self.tid = threading.get_ident()
        stack.append(self)
        self.annotation = _profiler_annotation(self.name)
        if self.annotation is not None:
            self.annotation.__enter__()
        self.t0_us = _now_us()
        return self

    def __exit__(self, *exc):
        dur = _now_us() - self.t0_us
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
            self.annotation = None
        stack = self.tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self.tracer._record({
            "name": self.name, "ts": self.t0_us, "dur": dur,
            "tid": self.tid, "parent": self.parent, "depth": self.depth,
            "args": self.attrs,
        })
        return False


def _profiler_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``name``, or None where jax
    is not installed (``repro.obs`` itself never needs it)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return None
    return TraceAnnotation(name)


class Tracer:
    """Collects span events; thread-safe; cheap when disabled."""

    def __init__(self):
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._local = threading.local()

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, event: dict) -> None:
        with self._lock:
            self._events.append(event)

    def span(self, name: str, /, **attrs):
        """Context manager timing one named interval. Returns the shared
        no-op singleton when tracing is disabled (zero allocation as long
        as the caller passes no ``**attrs`` — guard attribute construction
        behind ``obs.is_enabled()`` on hot paths)."""
        if not _state.is_enabled():
            return NULL_SPAN
        return Span(self, name, attrs)

    def events(self) -> list[dict]:
        """Snapshot of the recorded span events (closed spans only)."""
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()


class TracedCallable:
    """A callable wrapped in a ``dispatch/...`` span. The span times the
    dispatch: it does not wait for the result, so the caller's pipeline of
    asynchronous dispatches runs as it would untraced, and the device time
    of the call is read from the device trace. Disabled, the wrapper is one
    branch and a tail call.

    Attribute access forwards to the wrapped function, so jit surfaces
    (``.lower``, ``.trace``, ...) keep working on the wrapped object.
    """

    def __init__(self, fn: Callable, name: str, tracer: "Tracer",
                 attrs: dict | None = None):
        self._fn = fn
        self._name = name
        self._tracer = tracer
        self._attrs = dict(attrs or {})

    def __call__(self, *args, **kwargs) -> Any:
        if not _state.is_enabled():
            return self._fn(*args, **kwargs)
        with self._tracer.span(self._name, **self._attrs):
            return self._fn(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def __repr__(self):
        return f"TracedCallable({self._name!r}, {self._fn!r})"
