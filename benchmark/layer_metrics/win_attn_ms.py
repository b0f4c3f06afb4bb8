"""Device time of one run of the MIXED program (``jit__chunk_fn``)
under ``attn_core/attn_window`` — the sliding layers' attention, all
of them together: the lanes' sweep of the ring pool, the chunk's read
of its slot's ring, both K/V writes — median over the traced runs.
Bounded by the window whatever the sequences' lengths."""
from _sarvam import scope_ms


def read(name: str, layers: dict):
    return scope_ms(layers, "chunk_fn", "attn_window")
