"""Device time of one run of the decode program under the
``attn_core`` scope (scores, softmax, values, with the read of the
pool; the K/V write nested in it is ``decode_kv_write_ms``): median
over the traced runs, from the ops' ``tf_op`` name stacks."""
from _lib import scoped_trace     # puts benchmark/ on the path
import xplane_scopes


def read(name: str, layers: dict):
    return xplane_scopes.median_scope_ms(scoped_trace(layers),
                                         "decode_fn", ("attn_core",))
