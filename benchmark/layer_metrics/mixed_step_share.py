"""Share of the window's prefill chunks that rode a decode step as ONE
program (``PagedEngine.mixed_step``: one pass over the weights where a
chunk program and a decode program would each make one): the window
delta of ``serving_mixed_steps_total`` over that of
``serving_prefill_chunks_total``, both from the program's registry.
None where the program has no such counter, or issued no chunk."""
from _lib import registry_delta


def read(name: str, layers: dict):
    mixed = registry_delta(layers, "serving_mixed_steps_total")
    chunks = registry_delta(layers, "serving_prefill_chunks_total")
    return 100.0 * mixed / chunks if mixed is not None and chunks else None
