"""Share of the traced device-op seconds (first chip) that lies under
one of the program's ten ``jax.named_scope`` names. A health reading:
it falls when a scope rots, or when the compile cache served a program
compiled before the scopes were there."""
import _lib  # noqa: F401  (puts benchmark/ on the path)
import xplane_scopes


def read(name: str, layers: dict):
    return xplane_scopes.scoped_share(xplane_scopes.load())
