"""The expert layers' share of their roofline in the decode program:
the least time for what a step's grouped products must do — read the
experts HIT in the step (``serving_moe_experts_hit``, the program's
count, not all of them by assumption), read each (token, expert) pair's
input row and write its output row, or three products of 2 x hidden x
width a pair, whichever is the larger — over ALL device time under
``moe_experts`` in one run (sort, gather and combine included), median
over the traced runs. Sequences a step are the window's mean."""
from _lib import flops, registry_delta
from _subscope import mean_of, median_ms
import flops_lfm2


def read(name: str, layers: dict):
    took_ms = median_ms(layers, "decode_fn", "moe_experts")
    hit = mean_of(layers, "serving_moe_experts_hit")
    tokens = registry_delta(layers, "serving_decode_tokens_total")
    steps = registry_delta(layers, "span_seconds{name=decode_step}_count")
    if not (took_ms and hit and tokens and steps):
        return None
    cfg = layers["cfg"]
    pairs = (tokens / steps) * cfg["num_experts_per_tok"] \
        * flops_lfm2.layer_counts(cfg)["moe"]
    least = flops.roofline_seconds(
        flops_lfm2.experts_flops(cfg, pairs),
        flops_lfm2.experts_bytes(cfg, hit, pairs), layers["peaks"])
    return 100.0 * least / (took_ms * 1e-3)
