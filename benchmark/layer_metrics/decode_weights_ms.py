"""Device time of one run of the decode program in the scopes that
stream the weights (``embed``, ``attn_qkv``, ``attn_out``, ``mlp``,
``head``): median over the traced runs. Its floor is every weight once
at the chip's memory bandwidth (gpt2-xl in bf16: 3.1 GB at 819 GB/s =
3.8 ms)."""
from _lib import scoped_trace     # puts benchmark/ on the path
import xplane_scopes

WEIGHTS = ("embed", "attn_qkv", "attn_out", "mlp", "head")


def read(name: str, layers: dict):
    return xplane_scopes.median_scope_ms(scoped_trace(layers),
                                         "decode_fn", WEIGHTS)
