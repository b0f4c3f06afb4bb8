"""The serving stack's build less its compiles: the ``serving_build``
span round ``ServingConfig.make`` (engine, pool, batcher) in
``span_seconds``, less the ``compile_seconds`` whose ``span`` is
``serving_build``."""
from _setup import build_self


def read(name: str, layers: dict):
    return build_self(layers)
