"""``mla_chunk_attn_roofline``'s reading for a Bailing hybrid model
(``model_type: bailing_hybrid``; Ling-3.0-flash): a prefill chunk's
latent attention as a share of the chip's peak — the operations the
chunk's VISIBLE (query, key) pairs need in the expanded form, 32 heads
x (192 + 128) x 2 a pair in the one MLA layer held
(``flops_bailing_hybrid.attention_flops``), over the device time under
``mla_chunk`` (the chunk's tokens attending their slot's table through
the paged kernel, in the absorbed form) in one run of the chunk
program, alone or with the decode lanes riding; median over the traced
runs. Pairs a chunk are the window's mean: the prompts whose first
token fell in the window hold n (n + 1) / 2 pairs each, over the
chunks the window issued (``serving_prefill_chunks_total``). None for
a run of another family or one with nothing to read."""
from _bailing_hybrid import is_family
from _lib import registry_delta
from _sarvam import scope_ms

import flops_bailing_hybrid as fl


def read(name: str, layers: dict):
    if not is_family(layers):
        return None
    took_ms = scope_ms(layers, "chunk_fn", "mla_chunk")
    chunks = registry_delta(layers, "serving_prefill_chunks_total")
    pairs = layers.get("prefill_pairs")
    if not (took_ms and chunks and pairs):
        return None
    need = fl.attention_flops(layers["cfg"], pairs / chunks)
    return 100.0 * need / (layers["peaks"]["bf16_flops_per_s"]
                           * took_ms * 1e-3)
