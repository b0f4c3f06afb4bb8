"""Device time of one run of the prefill-chunk program under the
``attn_core`` scope (the chunk against the slot's prior pages and
against itself): median over the traced runs."""
import _lib  # noqa: F401  (puts benchmark/ on the path)
import xplane_scopes


def read(name: str, layers: dict):
    return xplane_scopes.median_scope_ms(xplane_scopes.load(),
                                         "chunk_fn", ("attn_core",))
