"""Device-side telemetry: HBM gauges.

:func:`record_memory_gauges` — per-device allocator stats from
``device.memory_stats()`` into gauges (``device_bytes_in_use`` et al).
TPU runtimes report these; CPU returns None and the call is a clean
no-op, so instrumented code needs no backend branch.
"""
from __future__ import annotations

from torchbooster_tpu.observability.registry import Registry, get_registry

__all__ = ["record_memory_gauges"]

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
