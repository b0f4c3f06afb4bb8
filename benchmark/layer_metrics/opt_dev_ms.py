"""Device time of one train step under the ``optimizer`` scope (clip,
the optimizer's update, applying it, EMA): median over the traced
steps (first chip)."""
import _lib  # noqa: F401  (puts benchmark/ on the path)
import xplane_scopes


def read(name: str, layers: dict):
    return xplane_scopes.median_scope_ms(xplane_scopes.load(),
                                         "step_fn", ("optimizer",))
