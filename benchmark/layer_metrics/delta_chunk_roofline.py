"""A prefill chunk's delta rule against its roofline, for a Qwen3-Next
model: the chunked form's operations for a mean chunk's real tokens
(``flops_qwen3_next.delta_chunk_flops``, every linear layer) at 197
TFLOP/s, or its bytes (``delta_chunk_bytes``: ``S`` read and written,
q, k, v, o, g and beta in float32) at 819 GB/s, the larger, over the
device time of one run of ``jit__chunk_fn`` (median over the traced
runs) under ``attn_core/delta_chunk``."""
from _lib import flops, registry_delta
from _qwen3_next import is_family
from _sarvam import scope_ms

import flops_qwen3_next as fl


def read(name: str, layers: dict):
    win = layers.get("window")
    chunks = registry_delta(layers, "serving_prefill_chunks_total")
    ms = scope_ms(layers, "chunk_fn", "delta_chunk")
    if not (is_family(layers) and win and win["prefill_tokens"] and chunks
            and ms):
        return None
    cfg = layers["cfg"]
    tokens = win["prefill_tokens"] / chunks
    n = fl.layer_counts(cfg)["linear"]
    least = flops.roofline_seconds(n * fl.delta_chunk_flops(cfg, tokens),
                                   n * fl.delta_chunk_bytes(cfg, tokens),
                                   layers["peaks"])
    return 100.0 * least / (ms * 1e-3)
