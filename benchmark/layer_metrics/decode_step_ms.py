"""Median duration of the program's ``decode_step`` span (the
engine's one compiled step over every live slot, result read back), on
the profiler's clock."""
from _lib import median_ms, trace_reduce


def read(name: str, layers: dict):
    return median_ms(trace_reduce.span_seconds(layers["trace"],
                                               "decode_step"))
