"""The scheduler iteration's host time: the wall time of
``ContinuousBatcher.step()`` (the ``sched_step`` span) less the spans
that dispatch a program or wait for one (``decode_step``,
``serving_prefill_chunk``, ``spec_verify_step``, and
``prefill_finish``, which reads the last chunk's token back), a mean
over the iterations of the window; from the registry's
``span_seconds``."""
from _lib import registry_delta

DEVICE_SPANS = ("decode_step", "serving_prefill_chunk", "spec_verify_step",
                "prefill_finish")


def _sum(layers: dict, span: str):
    return registry_delta(layers, f"span_seconds{{name={span}}}_sum")


def read(name: str, layers: dict):
    whole = _sum(layers, "sched_step")
    steps = registry_delta(layers, "span_seconds{name=sched_step}_count")
    if whole is None or not steps:
        return None
    device = sum(_sum(layers, span) or 0.0 for span in DEVICE_SPANS)
    return 1e3 * (whole - device) / steps
