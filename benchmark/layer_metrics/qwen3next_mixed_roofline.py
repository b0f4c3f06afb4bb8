"""The MIXED program's share of its roofline, for a Qwen3-Next model —
a chunk of a prompt with the decode lanes riding, one pass over the
weights (``trinity_mixed_roofline``'s definition with this family's
bytes): the least time for what a mean such step NEEDS — every
non-expert weight once, the held experts its pairs hit at 6.29 MB each,
the full layer's cached rows (the lanes' live rows and the chunk's
prior rows, 2,048 B a row) and the slot state's bytes the program
counted (the lanes' and the chunk's) — or its operations
(``forward_flops`` at the step's tokens), whichever is the larger, over
the device time of one run of ``jit__chunk_fn`` (median over the
traced runs). The step's contents are the window's means
(``_qwen3_next.step_means``)."""
from _lib import flops, statistics, trace_reduce
from _qwen3_next import step_means

import flops_qwen3_next as fl


def read(name: str, layers: dict):
    runs = trace_reduce.module_seconds(layers["trace"], "chunk_fn")
    step = step_means(layers)
    if not runs or step is None:
        return None
    cfg = layers["cfg"]
    least = flops.roofline_seconds(
        fl.forward_flops(cfg, step["seqs"] + step["chunk_tokens"],
                         step["live"] + step["chunk_pairs"],
                         step["routed"], step["seqs"] + 1),
        fl.step_bytes(cfg, step["live"] + step["chunk_rows"], step["hit"],
                      step["state"]),
        layers["peaks"])
    return 100.0 * least / statistics.median(runs)
