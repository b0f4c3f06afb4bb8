"""The decode step's share of its roofline, for an LFM2-MoE model: the
least time for what a step NEEDS — every non-expert weight once, the
experts hit in the step (``serving_moe_experts_hit``), the live cached
K/V of the step's sequences in the attention layers, their conv state
— over the device time of one run of the decode program (median over
the traced stretch). Sequences a step and context a sequence are the
window's means. Bytes bound it; the operations' bound is taken too and
the larger wins."""
from _lib import flops, registry_delta, statistics, trace_reduce
from _subscope import mean_of
import flops_lfm2


def read(name: str, layers: dict):
    runs = trace_reduce.module_seconds(layers["trace"], "decode_fn")
    tokens = registry_delta(layers, "serving_decode_tokens_total")
    steps = registry_delta(layers, "span_seconds{name=decode_step}_count")
    hit = mean_of(layers, "serving_moe_experts_hit")
    win = layers.get("window")
    if not (runs and tokens and steps and hit and win
            and win["decode_tokens"]):
        return None
    seqs = tokens / steps
    live = seqs * win["context_read"] / win["decode_tokens"]
    cfg = layers["cfg"]
    least = flops.roofline_seconds(
        flops_lfm2.decode_step_flops(cfg, seqs, live),
        flops_lfm2.decode_step_bytes(cfg, seqs, live, hit),
        layers["peaks"])
    return 100.0 * least / statistics.median(runs)
