"""Median device time of one run of the prefill-chunk program (one
256-token chunk through the paged cache), from the first chip's
``XLA Modules`` line. The program's ``serving_prefill_chunk`` span
closes at dispatch (2 ms: the chunk's result is not read back), so the
span cannot time it; its count is checked against the runs found."""
from _lib import median_ms, trace_reduce


def read(name: str, layers: dict):
    return median_ms(trace_reduce.module_seconds(layers["trace"],
                                                 "chunk_fn"))
