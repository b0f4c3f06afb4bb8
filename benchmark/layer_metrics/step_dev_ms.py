"""Device time of one train step's program: the median over the
traced runs of the largest program on the first chip's ``XLA Modules``
line; where the trace has no module line, busy time over traced
steps."""
from _lib import median_ms, trace_reduce


def read(name: str, layers: dict):
    trace, steps = layers["trace"], layers.get("traced_steps")
    if not steps:
        return None
    runs = sorted(trace_reduce.module_seconds(trace, "."), reverse=True)
    if len(runs) >= steps:
        return median_ms(runs[:steps])
    return layers["busy_s"] / steps * 1e3
