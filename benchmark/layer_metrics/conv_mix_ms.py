"""Device time of one run of the decode program under ``conv_mix``
(the short-convolution mixers: gate, three-tap depthwise convolution,
the slot state's read and write), all layers together: median over the
traced runs."""
from _subscope import median_ms


def read(name: str, layers: dict):
    return median_ms(layers, "decode_fn", "conv_mix")
