"""99th percentile of all pooled token gaps ending inside the window (recorded, not judged: the thin two-chunk tail begins here)."""
from _lib import client_percentile_ms


def read(name: str, layers: dict):
    return client_percentile_ms(layers, "gaps", 99)
