"""Device-side telemetry: HBM gauges + XLA cost-analysis capture.

Two read paths into what the accelerator actually does:

- :func:`record_memory_gauges` — per-device allocator stats from
  ``device.memory_stats()`` into gauges (``device_bytes_in_use`` et
  al). TPU runtimes report these; CPU returns None and the call is a
  clean no-op, so instrumented code needs no backend branch.
- :func:`cost_analysis` / :func:`xla_flops` — the compiler's own
  FLOP/byte accounting from ``Compiled.cost_analysis()``.
  :func:`flop_check` compares a hand-derived FLOP count against this
  (``6·N·D`` formulas drift when architectures grow knobs; XLA's
  count is ground truth for the graph it actually compiled) and warns
  when they disagree by more than 10%. (The benchmark's utilization
  readings use ``benchmark/flops.py``; nothing in the package calls
  these two: ``tests/test_observability.py`` holds them.)
"""
from __future__ import annotations

import logging
from typing import Any, Callable

from torchbooster_tpu.observability.registry import Registry, get_registry

__all__ = ["cost_analysis", "flop_check", "record_memory_gauges",
           "xla_flops"]

# memory_stats keys worth exporting when present (plugin-dependent)
_MEM_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
             "largest_free_block_bytes", "pool_bytes", "num_allocs")


def record_memory_gauges(registry: Registry | None = None) -> dict:
    """Snapshot every local device's ``memory_stats()`` into gauges
    labeled by device id; returns ``{device_id: stats}`` for direct
    use. Devices that report nothing (CPU) contribute nothing."""
    import jax

    registry = registry if registry is not None else get_registry()
    out: dict[int, dict] = {}
    for device in jax.local_devices():
        stats = None
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — plugin-dependent surface
            pass
        if not stats:
            continue
        out[device.id] = stats
        for key in _MEM_KEYS:
            if key in stats:
                registry.gauge(
                    f"device_{key}",
                    "allocator stat from device.memory_stats()").set(
                        float(stats[key]), device=str(device.id))
    return out


def cost_analysis(compiled: Any) -> dict[str, float]:
    """``Compiled.cost_analysis()`` as a plain dict; {} when the
    backend offers nothing."""
    try:
        costs = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend-optional surface
        return {}
    return dict(costs or {})


def xla_flops(fn: Callable, *args: Any, **kwargs: Any) -> float | None:
    """The compiler's FLOP count for ``fn(*args)``: lower → compile →
    cost_analysis. This builds a second executable (AOT), so call it
    once per program, not per step. None when unavailable."""
    import jax

    try:
        lowered = jax.jit(fn).lower(*args, **kwargs) \
            if not hasattr(fn, "lower") else fn.lower(*args, **kwargs)
        flops = cost_analysis(lowered.compile()).get("flops")
    except Exception as exc:  # noqa: BLE001 — cross-check is best-effort
        logging.info("xla_flops unavailable: %s", exc)
        return None
    return float(flops) if flops else None


def flop_check(name: str, formula_flops: float, measured: float | None,
               tolerance: float = 0.10) -> float | None:
    """Compare a hand-derived FLOP count against XLA's; returns their
    ratio (measured/formula) and WARNS when they disagree beyond
    ``tolerance`` — a utilization's denominator must not silently
    drift from the graph it describes."""
    if not measured or not formula_flops:
        return None
    ratio = measured / formula_flops
    if abs(ratio - 1.0) > tolerance:
        logging.warning(
            "%s: hand FLOP formula (%.3g) and XLA cost analysis "
            "(%.3g) disagree by %.0f%% — the MFU denominator needs "
            "re-deriving", name, formula_flops, measured,
            abs(ratio - 1.0) * 100)
    return round(ratio, 4)
