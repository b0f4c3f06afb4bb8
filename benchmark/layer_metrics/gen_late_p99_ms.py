"""How late the load generator sent: p99 of sent - due on its own clock, over the requests due in the window. A health reading: high means the generator, not the server, was starved."""
from _lib import client_percentile_ms


def read(name: str, layers: dict):
    return client_percentile_ms(layers, "late", 99)
