"""Device time of one run of the decode program under ``moe_experts``
(the dropless expert layers: sort by expert, the three grouped
products, the weighted combine), all layers together: median over the
traced runs."""
from _subscope import median_ms


def read(name: str, layers: dict):
    return median_ms(layers, "decode_fn", "moe_experts")
