"""The routed experts' share of their roofline in the PLAIN decode
program, for a Bailing hybrid model that holds a share of its experts:
the least time for what a step's grouped products must do — read the
held experts the step's pairs HIT at three matrices each (11.8 MB in
bfloat16; ``serving_moe_experts_hit``, the program's count, filed by
plain steps only), read each pair's input row and write its output row,
or three products of 2 x hidden x 768 a pair, whichever is the larger —
over ALL device time under ``mlp/moe_experts`` in one run of
``jit__decode_fn`` (sort, gather and combine included; the mean over
the traced runs: the counts are means too).

Counts and time are of the SAME steps, as in
``qwen3next_moe_experts_roofline``: the registry at the traced
stretch's edges, the pairs computed HERE
(``serving_moe_pairs_total{where=here}``) over the plain steps that
filed a count. None where the stretch held no plain step, or the run
has none of this to read."""
from _bailing_hybrid import edges, is_family
from _lib import flops, statistics
from _sarvam import PAIRS
from _subscope import seconds_by_run

import flops_bailing_hybrid as fl


def read(name: str, layers: dict):
    if not is_family(layers):
        return None
    at = edges(layers, stretch=True)
    a, b = at.get("registry_open"), at.get("registry_close")
    delta = lambda key: None if a is None or b is None or key not in b \
        else b[key] - a.get(key, 0.0)
    steps = delta("serving_moe_experts_hit_count")
    hit, here = delta("serving_moe_experts_hit_sum"), delta(PAIRS % "here")
    runs = [s for s in seconds_by_run(layers, "decode_fn", "moe_experts")
            if s > 0]
    if not (steps and hit and here and runs):
        return None
    cfg = layers["cfg"]
    least = flops.roofline_seconds(
        fl.experts_flops(cfg, here / steps),
        fl.experts_bytes(cfg, hit / steps, here / steps), layers["peaks"])
    return 100.0 * least / statistics.fmean(runs)
