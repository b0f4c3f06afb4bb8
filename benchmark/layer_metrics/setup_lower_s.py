"""Seconds the program spent tracing and lowering its programs to
modules before the window opened: ``compile_seconds{stage=trace}``
and ``{stage=lower}``. Paid on every start, cache or not: the
persistent cache's key is computed from the lowered module."""
from _setup import stage_total


def read(name: str, layers: dict):
    return stage_total(layers, ("trace", "lower"))
