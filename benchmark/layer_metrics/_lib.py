"""Shared by the readers: every reader is ``read(name, layers)`` and
returns a number, or None where it finds nothing to read."""
from __future__ import annotations

import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import flops  # noqa: E402,F401
import trace_reduce  # noqa: E402,F401
import xplane_scopes  # noqa: E402
from loadgen.plan import pooled_percentile  # noqa: E402,F401


def scoped_trace(layers: dict):
    """The run's trace with the ops' name stacks, from the path
    ``run.py`` puts into ``layers``; None where there is none."""
    return xplane_scopes.load(layers.get("trace_path"))


def median_ms(seconds: list[float]) -> float | None:
    return statistics.median(seconds) * 1e3 if seconds else None


def client_percentile_ms(layers: dict, series: str, q: float):
    """Percentile ``q`` of a client-side series of the whole window
    (``gaps``, ``ttfts``, ``late``), in milliseconds."""
    values = layers.get("window", {}).get(series)
    return pooled_percentile(values, q) * 1e3 if values else None


def registry_delta(layers: dict, key: str) -> float | None:
    """Growth of a registry series over the window."""
    a, b = layers.get("registry_open"), layers.get("registry_close")
    if a is None or b is None or key not in b:
        return None
    return b[key] - a.get(key, 0.0)

