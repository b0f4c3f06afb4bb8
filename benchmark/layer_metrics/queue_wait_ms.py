"""Mean time a request waited for its first seat (arrival -> first
admission, which a re-admission does not reset), over the requests
retired in the window: the registry's ``serving_queue_wait_seconds``
histogram, sum over count, window deltas. With
``serving_prefill_seconds`` it makes up ``serving_ttft_seconds``."""
from _lib import registry_delta


def read(name: str, layers: dict):
    total = registry_delta(layers, "serving_queue_wait_seconds_sum")
    count = registry_delta(layers, "serving_queue_wait_seconds_count")
    return 1e3 * total / count if total is not None and count else None
