"""Device time of one run of the MIXED program (``jit__chunk_fn``)
under ``moe_shared`` (the shared expert: a dense SwiGLU over every
token, added unweighted to the routed experts' sum), all expert layers
together: median over the traced runs."""
from _sarvam import scope_ms


def read(name: str, layers: dict):
    return scope_ms(layers, "chunk_fn", "moe_shared")
