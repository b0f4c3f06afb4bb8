"""Device time of one run of the decode program under the ``kv_write``
scope (the new token's K/V into its pool page): median over the traced
runs."""
from _lib import scoped_trace     # puts benchmark/ on the path
import xplane_scopes


def read(name: str, layers: dict):
    return xplane_scopes.median_scope_ms(scoped_trace(layers),
                                         "decode_fn", ("kv_write",))
