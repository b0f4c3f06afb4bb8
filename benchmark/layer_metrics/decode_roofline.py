"""The decode step's share of its roofline: the least time the chip
could take for what a step NEEDS — every weight once plus the live
cached tokens of the sequences in the step, not the pool's size — over
the device time of one run of the decode program (``XLA Modules``,
median over the traced stretch). Sequences a step and context a
sequence are the window's means (registry and client). Bytes bound it
(a decode step does ~2 operations a byte); the operations' bound is
taken too and the larger wins."""
from _lib import flops, registry_delta, statistics, trace_reduce


def read(name: str, layers: dict):
    runs = trace_reduce.module_seconds(layers["trace"], "decode_fn")
    tokens = registry_delta(layers, "serving_decode_tokens_total")
    steps = registry_delta(layers, "span_seconds{name=decode_step}_count")
    win = layers.get("window")
    if not (runs and tokens and steps and win and win["decode_tokens"]):
        return None
    seqs = tokens / steps
    live = seqs * win["context_read"] / win["decode_tokens"]
    cfg = layers["cfg"]
    least = flops.roofline_seconds(
        flops.decode_step_flops(cfg, seqs, live),
        flops.decode_step_bytes(cfg, live), layers["peaks"])
    return 100.0 * least / statistics.median(runs)
