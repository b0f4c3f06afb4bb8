"""Share of the window's decode steps that were launched while the
step before them was still in flight, in percent: the window delta of
``serving_lookahead_steps_total`` (counted by ``ContinuousBatcher``
where it launches a step behind one that has not landed) over decode
steps (the count of the ``decode_step`` span, the divisor
``batch_occupancy`` uses), both from the program's registry. 100 where
the host's work of every iteration ran beside a program; what is
missing are the steps landed with nothing behind them
(``serving_sync_lands_total`` says why). None where the program has no
such counter."""
from _lib import registry_delta


def read(name: str, layers: dict):
    ahead = registry_delta(layers, "serving_lookahead_steps_total")
    steps = registry_delta(layers, "span_seconds{name=decode_step}_count")
    return 100.0 * ahead / steps if ahead is not None and steps else None
