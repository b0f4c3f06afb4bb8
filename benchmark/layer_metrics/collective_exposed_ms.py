"""Per step, the time in all-gather / reduce-scatter / all-reduce
device ops during which no other op ran on that device (mean over the
chips), from the trace. Silent on one chip, where no collective runs."""
from _lib import trace_reduce


def read(name: str, layers: dict):
    steps = layers.get("traced_steps")
    if not steps or layers.get("n_devices", 1) < 2:
        return None
    return trace_reduce.exposed_collective_seconds(
        layers["trace"]) / steps * 1e3
