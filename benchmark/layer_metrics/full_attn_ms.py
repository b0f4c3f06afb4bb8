"""Device time of one run of the MIXED program (``jit__chunk_fn``)
under ``attn_core/attn_full`` — the full layers' attention: the lanes'
sweep of the paged pool, the chunk's walk of its table up to its own
position, both K/V writes — median over the traced runs. Grows with
the prompts; ``win_attn_ms`` beside it does not."""
from _sarvam import scope_ms


def read(name: str, layers: dict):
    return scope_ms(layers, "chunk_fn", "attn_full")
