"""Recompile sentinel: turn "this region must not compile" into a
runtime guard.

The serving engine's zero-recompile contract (engine.py: the decode
step's signature depends only on pool geometry) and the train loop's
one-compile steady state were, until now, test-only asserts over
``jitted_fn._cache_size()``. This module watches those cache sizes
around any region and counts / warns / raises when the region
compiled more than expected — so a shape leak (a stray python float
turning into a fresh abstract value, a batch remainder, a new prompt
length) surfaces in production telemetry instead of as a silent
latency cliff.

>>> with RecompileSentinel([step], on_recompile="raise"):
...     state, metrics = step(state, batch)     # steady state: 0 compiles

``expected=`` budgets legitimate compiles (the very first call);
``watch(...)`` is the decorator-style convenience.

What the compiles that DO happen cost is booked here too:
:func:`install_compile_listener` (run by the first
``set_enabled(True)``) turns JAX's own compile events into the
``compile_seconds{stage, program, span}`` histogram — ``stage`` one of
``trace`` / ``lower`` / ``load`` (a backend event the persistent cache
answered) / ``compile``, ``program`` JAX's function name, ``span`` the
innermost open :func:`~torchbooster_tpu.observability.spans.span` of
the compiling thread — and lays each on the span sinks' timeline as a
``compile/<stage>`` slice. It books nothing while the default registry
is off.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Iterable

from torchbooster_tpu.observability import spans
from torchbooster_tpu.observability.registry import Registry, get_registry

__all__ = ["POLICIES", "RecompileError", "RecompileSentinel",
           "cache_size", "install_compile_listener"]

# the accepted on_recompile policy set — THE single source of truth
# (batcher/config build-time validation imports this; re-inlined
# literals would drift when a policy is added)
POLICIES = ("ignore", "warn", "raise")
_POLICIES = POLICIES


class RecompileError(RuntimeError):
    """Raised under ``on_recompile="raise"`` when a watched region
    compiled more than its budget."""


def cache_size(fn: Any) -> int:
    """Compiled-executable count backing a jitted callable: its jit
    cache size (``_cache_size``), or 0 for things jax gives no handle
    for. Also accepts a zero-arg int callable (e.g. a lambda over
    ``PagedEngine.decode_compiles``)."""
    sizer = getattr(fn, "_cache_size", None)
    if sizer is not None:
        return int(sizer())
    if callable(fn):
        try:
            value = fn()
        except TypeError:
            return 0
        if isinstance(value, int):
            return value
    return 0


class RecompileSentinel:
    """Watch jit cache sizes around a region.

    ``fns``: jitted callables (anything with ``_cache_size()``) or
    zero-arg int callables returning a compile count. ``expected``
    budgets compiles that are *supposed* to happen inside the region
    (pass 1 around a first call). On exit, compiles beyond the budget
    increment the ``recompiles_total`` counter (labeled by region
    name) and apply the policy: ``ignore`` | ``warn`` | ``raise``.

    Re-enterable and reusable; ``extra`` holds the last region's
    over-budget compile count for callers that branch on it.
    """

    def __init__(self, fns: Iterable[Any] | Any,
                 on_recompile: str = "warn", expected: int = 0,
                 name: str = "region",
                 registry: Registry | None = None):
        if on_recompile not in _POLICIES:
            raise ValueError(
                f"on_recompile={on_recompile!r}: expected one of "
                f"{_POLICIES}")
        self.fns = list(fns) if isinstance(fns, (list, tuple)) else [fns]
        self.on_recompile = on_recompile
        self.expected = expected
        self.name = name
        self.registry = registry if registry is not None else get_registry()
        self.extra = 0
        self._base = 0

    def _size(self) -> int:
        return sum(cache_size(fn) for fn in self.fns)

    def __enter__(self) -> "RecompileSentinel":
        self._base = self._size()
        return self

    def __exit__(self, exc_type, *exc) -> bool:
        compiled = self._size() - self._base
        self.extra = max(0, compiled - self.expected)
        if self.extra and exc_type is None:
            # the counter honors the registry's master switch, but the
            # policy below fires regardless — an explicitly-constructed
            # sentinel is a correctness guard, not telemetry
            self.registry.counter(
                "recompiles_total",
                "unexpected XLA compiles inside watched regions").inc(
                    self.extra, region=self.name)
            message = (f"recompile sentinel [{self.name}]: {compiled} "
                       f"compile(s) in a region budgeted for "
                       f"{self.expected}")
            if self.on_recompile == "warn":
                logging.warning(message)
            elif self.on_recompile == "raise":
                raise RecompileError(message)
        return False


# ---------------------------------------------------------------------
# compile accounting: JAX's compile events -> compile_seconds
# ---------------------------------------------------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

# per compiling thread: ``pending`` traces by function name, waiting
# for a lowering of that name; ``hit``, set by a persistent-cache hit
# inside the backend event that is still open
_compile_tls = threading.local()
_install_lock = threading.Lock()
_installed = False
# perf_counter - time.time, once: JAX stamps its events with
# time.time(), the span sinks' timeline is perf_counter's
_clock_offset = 0.0


def install_compile_listener() -> None:
    """Register the ONE process-wide listener pair on
    ``jax.monitoring`` (idempotent). Each listener returns at its first
    branch while the default registry is off, so a registry switched
    off again books nothing and pays one branch per compile event."""
    global _installed, _clock_offset
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        _clock_offset = time.perf_counter() - time.time()
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_time_span_listener(_on_time_span)
        _installed = True


def _on_event(event: str, **kwargs: Any) -> None:
    if event == _CACHE_HIT and get_registry().enabled:
        _compile_tls.hit = True


def _on_time_span(event: str, start: float, end: float,
                  **kwargs: Any) -> None:
    registry = get_registry()
    if not registry.enabled:
        return
    name = str(kwargs.get("fun_name", ""))
    pending = getattr(_compile_tls, "pending", None)
    if pending is None:
        pending = _compile_tls.pending = {}
    if event == _TRACE:
        # booked only if this thread then lowers jit(<name>): the jitted
        # functions a program calls are traced inside its trace and
        # never lowered on their own, so they are not counted twice
        pending[name] = (start, end)
    elif event == _LOWER:
        program = _program(name)
        traced = pending.get(program)
        pending.clear()
        if traced is not None:
            _book(registry, "trace", program, *traced)
        _book(registry, "lower", program, start, end)
    elif event == _BACKEND:
        hit = getattr(_compile_tls, "hit", False)
        _compile_tls.hit = False
        _book(registry, "load" if hit else "compile", _program(name),
              start, end)


def _program(fun_name: str) -> str:
    """``jit(f)`` -> ``f``: the name the trace event gave."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _book(registry: Registry, stage: str, program: str, start: float,
          end: float) -> None:
    span = spans.current_span() or "none"
    registry.histogram(
        "compile_seconds",
        "JAX compile time by stage (trace | lower | load | compile)").observe(
            end - start, stage=stage, program=program, span=span)
    spans.emit_slice(f"compile/{stage}", start + _clock_offset,
                     end - start, event="compile", cat="compile",
                     dur_s=round(end - start, 6),
                     args={"program": program, "span": span})
