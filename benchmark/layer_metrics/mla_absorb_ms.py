"""Device time of one run of the MIXED program (``jit__chunk_fn``: a
chunk's tokens and the decode lanes on one token axis) under
``mla_absorb`` (latent attention's two absorb products: ``q_nope @
W_uk^T`` before the attention, ``o' @ W_uv`` after it), all layers
together: median over the traced runs."""
from _sarvam import scope_ms


def read(name: str, layers: dict):
    return scope_ms(layers, "chunk_fn", "mla_absorb")
