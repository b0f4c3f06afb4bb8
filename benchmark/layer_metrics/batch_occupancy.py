"""Sequences per decode step, mean over the window: decoded tokens
(``serving_decode_tokens_total``) over decode steps (the count of the
``decode_step`` span), both from the program's registry."""
from _lib import registry_delta


def read(name: str, layers: dict):
    tokens = registry_delta(layers, "serving_decode_tokens_total")
    steps = registry_delta(layers, "span_seconds{name=decode_step}_count")
    return tokens / steps if tokens and steps else None
