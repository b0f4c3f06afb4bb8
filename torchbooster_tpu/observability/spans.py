"""Nested host-side spans unified with the XLA profiler timeline.

``span("decode_step")`` measures host wall time (perf_counter), emits
one event into the JSONL event log (via any subscribed sink, see
:mod:`torchbooster_tpu.observability.export`), records a latency
histogram in the registry, AND wraps the body in
``jax.profiler.TraceAnnotation`` — so the same name shows up in the
Perfetto/TensorBoard trace when one is being captured. One context
manager, both timelines.

This module also absorbs (and is the canonical home of) the profiler
helpers that previously lived in ``utils``: :class:`trace` (the
start/stop_trace capture window) and :func:`annotate` (a bare
TraceAnnotation). ``utils.trace``/``utils.annotate`` remain importable
aliases.

Host spans are *wall-time* measurements: with async dispatch they time
the host-side critical path (dispatch + any blocking read the body
does), not device execution — device truth comes from the captured
trace. That is exactly the split the two outputs are for.

Overhead discipline: when the registry is disabled, ``span(...)``
returns a shared no-op context manager — no allocation, no clock read,
no annotation (measured ~100 ns/call; numbers in
docs/observability.md).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable

import jax

from torchbooster_tpu.observability.registry import Registry, get_registry

__all__ = ["annotate", "span", "span_events_subscribe", "trace"]


_tls = threading.local()

# stamped once (re-reading os.getpid() per span close would cost a
# syscall on a path serving pumps hit thousands of times a second);
# forked workers restamp via the at-fork hook so their span events
# land on their OWN Chrome-trace process track, not the parent's
_PID = os.getpid()


def _restamp_pid() -> None:
    global _PID
    _PID = os.getpid()


if hasattr(os, "register_at_fork"):       # absent on non-posix
    os.register_at_fork(after_in_child=_restamp_pid)

# event sinks: callables receiving one dict per closed span
_sinks: list[Callable[[dict], None]] = []
_sinks_lock = threading.Lock()


def span_events_subscribe(sink: Callable[[dict], None]) -> Callable[[], None]:
    """Register a span-event sink (the JSONL exporter does); returns an
    unsubscribe callable."""
    with _sinks_lock:
        _sinks.append(sink)

    def unsubscribe() -> None:
        with _sinks_lock:
            if sink in _sinks:
                _sinks.remove(sink)

    return unsubscribe


def _emit(event: dict) -> None:
    with _sinks_lock:
        sinks = list(_sinks)
    for sink in sinks:
        try:
            sink(event)
        except Exception:  # noqa: BLE001 — telemetry must never kill work
            pass


class _NoopSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NOOP = _NoopSpan()


class _Span:
    """One live span: wall clock + TraceAnnotation + nesting depth."""

    __slots__ = ("name", "registry", "_t0", "_annotation", "_depth")

    def __init__(self, name: str, registry: Registry):
        self.name = name
        self.registry = registry

    def __enter__(self) -> "_Span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._depth = len(stack)
        stack.append(self.name)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dur = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        _tls.stack.pop()
        self.registry.histogram(
            "span_seconds", "host wall time per span").observe(
                dur, name=self.name)
        # skip event construction entirely when nothing subscribed
        # (Prometheus-only / LogCallback-only sessions): the benign
        # unlocked truthiness read keeps sink-less span close cheap.
        # The event is a VALID Chrome trace event (ph/pid/tid +
        # microsecond ts/dur on the perf_counter timebase — the same
        # timebase tracing.py's request events use, so one
        # write_chrome_trace call merges both onto one timeline);
        # dur_s/ok/path/depth ride along for the JSONL readers.
        if _sinks:
            emit_slice(self.name, self._t0, dur, event="span", cat="span",
                       path="/".join((*_tls.stack, self.name)),
                       depth=self._depth, dur_s=round(dur, 6),
                       ok=exc[0] is None)


def span(name: str, registry: Registry | None = None):
    """Context manager: time ``name`` on the host AND annotate it on
    the device timeline. No-op (shared singleton) when telemetry is
    disabled."""
    registry = registry if registry is not None else get_registry()
    if not registry.enabled:
        return _NOOP
    return _Span(name, registry)


def current_span_path() -> str:
    """The '/'-joined open-span stack of this thread ('' outside)."""
    return "/".join(getattr(_tls, "stack", ()))


def current_span() -> str | None:
    """The innermost open span of this thread (None outside any)."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def emit_slice(name: str, t0: float, dur: float, **fields: Any) -> None:
    """One closed slice of this thread's time to the span sinks, as a
    Chrome trace ``X`` event on the spans' ``perf_counter`` timebase
    (``t0`` in its seconds): a closed span, or a compile JAX reports
    (:mod:`recompile`). Nothing is built when nothing subscribed."""
    if _sinks:
        _emit({"name": name, "ph": "X", "pid": _PID,
               "tid": threading.get_ident(), "ts": int(t0 * 1e6),
               "dur": int(dur * 1e6), **fields})


class trace:
    """Profiler trace context (SURVEY §5.1: the reference constructs
    torch profiler objects without entering them, ref utils.py:42-45 —
    its NVTX story; here the real one): captures an XLA/TPU trace
    viewable in TensorBoard or Perfetto.

    >>> with trace("/tmp/profile"):
    ...     state, metrics = step(state, batch)

    ``trace(path, annotate="step")`` also wraps the body in a named
    TraceAnnotation so device ops group under one label. Body
    exceptions propagate — but only after ``stop_trace`` has run, so a
    failed region still leaves a finished, viewable trace and the
    profiler is reusable afterwards."""

    def __init__(self, path: str = "profile", annotate: str | None = None):
        self.path = str(path)
        self.annotate = annotate
        self._annotation = None

    def __enter__(self) -> "trace":
        jax.profiler.start_trace(self.path)
        if self.annotate:
            self._annotation = jax.profiler.TraceAnnotation(self.annotate)
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        jax.profiler.stop_trace()


def annotate(name: str) -> Any:
    """Named trace region for host-side code (NVTX-range analogue)."""
    return jax.profiler.TraceAnnotation(name)
