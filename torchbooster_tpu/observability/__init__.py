"""Unified telemetry subsystem: metrics, spans, recompile guard,
device stats, exporters.

The reference TorchBooster never had a working profiling story
(SURVEY §5.1: it constructs torch profiler objects without entering
them); this package is the coherent replacement the production story
needs — one registry every layer instruments into, one span primitive
that lands on both the host event log and the XLA trace, a runtime
guard for the "this region must not compile" contracts, and exporters
that ship it all on a cadence thread.

- :mod:`registry`  — Counter/Gauge/Histogram, thread-safe, labeled,
  device-scalar-friendly (no per-step host sync), near-zero when off;
- :mod:`spans`     — ``span("decode_step")`` → wall-time histogram +
  JSONL event + ``jax.profiler.TraceAnnotation``; also the canonical
  home of :class:`~torchbooster_tpu.observability.spans.trace` /
  :func:`~torchbooster_tpu.observability.spans.annotate`;
- :mod:`recompile` — :class:`RecompileSentinel` over jit cache sizes
  (``on_recompile: ignore | warn | raise``);
- :mod:`device`    — HBM gauges from ``memory_stats()``, XLA
  ``cost_analysis`` FLOP cross-checks for utilization denominators;
- :mod:`export`    — JSONL event log + Prometheus text snapshots on a
  background cadence thread;
- :mod:`slo`       — :class:`SLOBurnEngine`, multi-window burn rates
  over the serving deadline/goodput counters with a firing/resolved
  alert FSM, ticked by the exporter on the same cadence;
- :mod:`tracing`   — per-request lifecycle events on a bounded sink,
  exported as JSONL / Chrome trace-event JSON (one Perfetto track per
  request, one per engine step kind);
- :mod:`flight`    — always-on fixed-size ring of per-step engine
  records (provably bounded memory) + a stall/recompile watchdog,
  dumped by the front door when the pump dies and on demand.

Everything is OFF by default: importing this package (or the modules
it instruments) configures nothing, starts no threads, and adds one
predictable branch per instrumented call site. Flip it on via
``ObservabilityConfig`` (YAML ``observability:`` block) or
:func:`enable`.
"""
from __future__ import annotations

from torchbooster_tpu.observability.device import (
    cost_analysis,
    flop_check,
    record_memory_gauges,
    xla_flops,
)
from torchbooster_tpu.observability.export import (
    JsonlExporter,
    MetricsExporter,
    prometheus_text,
)
from torchbooster_tpu.observability.flight import (
    FlightRecorder,
)
from torchbooster_tpu.observability.recompile import (
    RecompileError,
    RecompileSentinel,
)
from torchbooster_tpu.observability.registry import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    get_registry,
    set_enabled,
)
from torchbooster_tpu.observability.slo import (
    SLOBurnEngine,
)
from torchbooster_tpu.observability.spans import (
    annotate,
    span,
    span_events_subscribe,
    trace,
)
from torchbooster_tpu.observability.tracing import (
    RequestTracer,
    write_chrome_trace,
)

__all__ = [
    "Counter", "FlightRecorder", "Gauge", "Histogram", "JsonlExporter",
    "MetricsExporter", "Observability", "RecompileError",
    "RecompileSentinel", "Registry", "RequestTracer", "SLOBurnEngine",
    "annotate",
    "cost_analysis", "enable", "flop_check", "get_registry",
    "prometheus_text", "record_memory_gauges", "set_enabled", "span",
    "span_events_subscribe", "trace", "write_chrome_trace", "xla_flops",
]


class Observability:
    """A running telemetry session: the enabled default registry plus
    (optionally) a started cadence exporter. Built by
    ``ObservabilityConfig.make``; usable as a context manager so CLI
    entry points get flush-on-exit for free."""

    def __init__(self, registry: Registry,
                 exporter: MetricsExporter | None = None,
                 on_recompile: str = "warn"):
        self.registry = registry
        self.exporter = exporter
        self.on_recompile = on_recompile

    def sentinel(self, fns, name: str = "region",
                 expected: int = 0) -> RecompileSentinel:
        """A RecompileSentinel pre-wired with this session's policy."""
        return RecompileSentinel(fns, on_recompile=self.on_recompile,
                                 expected=expected, name=name,
                                 registry=self.registry)

    def close(self) -> None:
        global _default_exporter
        if self.exporter is not None:
            self.exporter.stop()
            if _default_exporter is self.exporter:
                _default_exporter = None

    def __enter__(self) -> "Observability":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# the exporter attached to the process-default registry by enable():
# tracked so repeated enable() calls (two entry points in one process)
# replace it instead of stacking threads + duplicate span sinks
_default_exporter: MetricsExporter | None = None


def enable(jsonl_path: str | None = None, prom_path: str | None = None,
           cadence_s: float = 10.0,
           on_recompile: str = "warn",
           slo: SLOBurnEngine | None = None) -> Observability:
    """Programmatic switch-on: enable the default registry and (when
    any path is given) start the cadence exporter. Idempotent on the
    default session: a previously-started default exporter is flushed
    and stopped before the new one starts — calling this twice never
    double-writes span events or leaks a cadence thread. An optional
    :class:`SLOBurnEngine` rides the exporter cadence (its burn gauges
    land in the same snapshot; alert events go to the JSONL log)."""
    global _default_exporter

    registry = set_enabled(True)
    if _default_exporter is not None:
        _default_exporter.stop()
        _default_exporter = None
    exporter = None
    if jsonl_path or prom_path:
        exporter = MetricsExporter(
            registry, jsonl_path=jsonl_path, prom_path=prom_path,
            cadence_s=cadence_s, slo=slo).start()
        _default_exporter = exporter
    return Observability(registry, exporter, on_recompile=on_recompile)
