"""Device time of one run of the decode program under ``moe_route``
(float32 sigmoid scores, biased top-k, renormalised weights), all
layers together: median over the traced runs."""
from _subscope import median_ms


def read(name: str, layers: dict):
    return median_ms(layers, "decode_fn", "moe_route")
