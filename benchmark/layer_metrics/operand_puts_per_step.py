"""Host-to-device transfers the engine issued for step operands, per
decode step, mean over the window: the window delta of
``serving_operand_puts_total`` (counted where ``PagedEngine`` issues
them: the packed operand buffer, and a mode's large operand beside it)
over decode steps (the count of the ``decode_step`` span, the divisor
``batch_occupancy`` uses), both from the program's registry. 1.0 where
every step's operands cross as the one buffer; a lone prefill chunk
(no slot decoding beside it) adds a transfer and no step. None where
the program has no such counter."""
from _lib import registry_delta


def read(name: str, layers: dict):
    puts = registry_delta(layers, "serving_operand_puts_total")
    steps = registry_delta(layers, "span_seconds{name=decode_step}_count")
    return puts / steps if puts is not None and steps else None
