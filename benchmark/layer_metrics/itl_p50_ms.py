"""Median of all gaps between consecutive streamed tokens, pooled over all requests, gaps ending inside the window."""
from _lib import client_percentile_ms


def read(name: str, layers: dict):
    return client_percentile_ms(layers, "gaps", 50)
