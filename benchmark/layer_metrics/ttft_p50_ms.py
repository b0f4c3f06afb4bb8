"""Median time from when a request was DUE to its first token, client clock, requests whose first token fell in the window."""
from _lib import client_percentile_ms


def read(name: str, layers: dict):
    return client_percentile_ms(layers, "ttfts", 50)
