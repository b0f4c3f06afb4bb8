"""A prefill chunk's KDA rule against its roofline, for a Bailing hybrid
model: the chunked form's operations for a mean chunk's real tokens
(``flops_bailing_hybrid.kda_chunk_flops``, every KDA layer) at 197
TFLOP/s, or its bytes (``kda_chunk_bytes``: ``S`` read and written, q,
k, g, v, o and beta in float32) at 819 GB/s, the larger, over the
device time of one run of ``jit__chunk_fn`` (median over the traced
runs) under ``attn_core/kda_chunk``."""
from _bailing_hybrid import is_family
from _lib import flops, registry_delta
from _sarvam import scope_ms

import flops_bailing_hybrid as fl


def read(name: str, layers: dict):
    win = layers.get("window")
    chunks = registry_delta(layers, "serving_prefill_chunks_total")
    ms = scope_ms(layers, "chunk_fn", "kda_chunk")
    if not (is_family(layers) and win and win["prefill_tokens"] and chunks
            and ms):
        return None
    cfg = layers["cfg"]
    tokens = win["prefill_tokens"] / chunks
    n = fl.layer_counts(cfg)["kda"]
    least = flops.roofline_seconds(n * fl.kda_chunk_flops(cfg, tokens),
                                   n * fl.kda_chunk_bytes(cfg, tokens),
                                   layers["peaks"])
    return 100.0 * least / (ms * 1e-3)
