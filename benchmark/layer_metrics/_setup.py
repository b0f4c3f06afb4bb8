"""Shared by the start-up readers (``setup_*.setup``): the program's
``compile_seconds{program, span, stage}`` histogram as it stood when
the window opened (``layers["registry_open"]``), which is every
compile since the job switched the registry on, before the build. The
series are JAX's own compile events (``observability/recompile.py``):
``trace`` and ``lower`` are paid on every start, cache or not;
``load`` is an executable the persistent cache gave back; ``compile``
one it did not. Every helper returns None where the program has no
such series at all."""
from __future__ import annotations

import re

SERIES = re.compile(
    r"^compile_seconds(?P<labels>(?:\{[^{}=]+=[^{}]*\})*)_(?P<part>sum|count)$")
LABEL = re.compile(r"\{([^{}=]+)=([^{}]*)\}")
BUILD = "span_seconds{name=serving_build}_sum"


def compiles(layers: dict) -> list[dict] | None:
    """One ``{"stage", "program", "span", "sum", "count"}`` a series,
    or None where the snapshot holds no ``compile_seconds``."""
    series: dict[tuple, dict] = {}
    for key, value in (layers.get("registry_open") or {}).items():
        match = SERIES.match(key)
        if match is None:
            continue
        labels = dict(LABEL.findall(match["labels"]))
        row = series.setdefault(tuple(sorted(labels.items())),
                                {**labels, "sum": 0.0, "count": 0.0})
        row[match["part"]] = float(value)
    return list(series.values()) or None


def stage_total(layers: dict, stages: tuple[str, ...],
                part: str = "sum") -> float | None:
    """Sum of ``part`` over the series of ``stages``: 0.0 where the
    program books compiles but none of these stages."""
    rows = compiles(layers)
    if rows is None:
        return None
    return float(sum(r[part] for r in rows if r.get("stage") in stages))


def build_self(layers: dict) -> float | None:
    """The ``serving_build`` span's seconds less the compiles booked
    under it: the build's own time."""
    rows = compiles(layers)
    whole = (layers.get("registry_open") or {}).get(BUILD)
    if rows is None or whole is None:
        return None
    return whole - sum(r["sum"] for r in rows
                       if r.get("span") == "serving_build")
