"""``repro.obs`` — zero-overhead-when-disabled tracing and metrics.

The observability layer of the stack: a thread-safe span :class:`Tracer`
(nested ``with obs.span(name, **attrs)`` contexts, Chrome-trace-event
export for Perfetto), a counters/gauges :class:`Metrics` registry (wire
bytes, ppermute/all-to-all dispatches, per-axis exchange rounds, plan-cache
hits/misses, retraces), and the :func:`traced_call` dispatch-boundary
wrapper, and the stage vocabulary (:mod:`repro.obs.stages`) the program
puts on its compiled work with ``jax.named_scope``. Instrumentation lives
in ``core.transpose``/``core.comm`` (wire metrics at trace time),
``core.fft3d`` (retrace counters), ``solvers.base`` (step/observable
spans) and ``repro.tuning`` (sweep spans, cache counters).

Disabled — the default — every entry point returns before allocating:
``span()`` hands back a shared no-op singleton, ``metrics.inc`` is one
branch, ``traced_call`` wrappers tail-call straight through. Enable with
:func:`enable` (the CLIs' ``--trace PATH`` flags do), export with
:func:`write_chrome_trace` / :func:`summary_table`.

Import of this package is jax-free; jax is only imported by an enabled
span, which also enters a ``jax.profiler.TraceAnnotation`` of its name so
that it lands on a profiler trace's host plane beside the device ops.

What jit lets us see: **spans cannot live inside jitted shard_map
bodies** — a ``with`` block there times Python *tracing*, which runs once
per compilation. The span layer therefore wraps dispatch boundaries
(``dispatch/...`` spans, which time the dispatch and do not block), while
the work inside the compiled step is named by the stage scopes and timed
from a device trace (``bench/stagetime.py``); structure that only exists
at trace time is counted by the metrics registry.
"""

from __future__ import annotations

from repro.obs import _state
from repro.obs.export import (chrome_trace, summary_table,
                              validate_chrome_trace, write_chrome_trace)
from repro.obs.metrics import Metrics
from repro.obs.tracer import NULL_SPAN, Span, TracedCallable, Tracer

__all__ = [
    "Tracer", "Span", "TracedCallable", "Metrics", "NULL_SPAN",
    "tracer", "metrics", "span", "traced_call",
    "enable", "disable", "is_enabled", "clear", "capture",
    "chrome_trace", "write_chrome_trace", "validate_chrome_trace",
    "summary_table",
]

#: process-wide default instances every instrumented module shares
tracer = Tracer()
metrics = Metrics()

is_enabled = _state.is_enabled


def enable() -> None:
    """Turn span/metric collection on (process-wide)."""
    _state.set_enabled(True)


def disable() -> None:
    """Turn collection off; recorded spans/counters stay readable."""
    _state.set_enabled(False)


def clear() -> None:
    """Drop all recorded spans and counters."""
    tracer.clear()
    metrics.clear()


def span(name: str, /, **attrs):
    """``with obs.span("dispatch/fft3d.fwd", engine="torus"):`` on the
    default tracer. Returns the shared no-op singleton while disabled —
    guard ``**attrs`` construction behind :func:`is_enabled` on hot paths,
    since keyword packing allocates before the call."""
    return tracer.span(name, **attrs)


def traced_call(fn, name: str, attrs: dict | None = None) -> TracedCallable:
    """Wrap ``fn`` so every call is a ``dispatch/...`` span around its
    dispatch (it does not wait for the result). Attributes are fixed at
    wrap time; jit surfaces (``.lower`` etc.) forward through."""
    return TracedCallable(fn, name, tracer, attrs)


class capture:
    """``with obs.capture() as (tracer, metrics):`` — enable + clear on
    entry, disable on exit (events stay readable). Test/tooling helper."""

    def __enter__(self):
        clear()
        enable()
        return tracer, metrics

    def __exit__(self, *exc):
        disable()
        return False
