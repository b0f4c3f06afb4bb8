"""Sequences preempted in the window
(``serving_preemptions_total``); 0 is a reading, not an absence."""
from _lib import registry_delta


def read(name: str, layers: dict):
    if layers.get("registry_close") is None:
        return None
    return registry_delta(layers, "serving_preemptions_total") or 0.0
