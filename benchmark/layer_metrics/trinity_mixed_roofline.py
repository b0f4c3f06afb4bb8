"""The MIXED program's share of its roofline, for an AFMoE model — the
step this cell's ``itl_p95_ms`` sits on (a chunk of a prompt with the
decode lanes riding, one pass over the weights): the least time for
what a mean such step NEEDS — every non-expert weight once, the held
experts its pairs hit at three matrices each, and the cached rows each
KIND's read needs (the lanes' live rows and the chunk's prior rows:
within the window for the sliding layers, all of them for the full
one; 4,096 B a row and layer, each once) — or its operations
(``forward_flops`` at the step's tokens, visible pairs by kind, routed
pairs here), whichever is the larger, over the device time of one run
of ``jit__chunk_fn`` (median over the traced runs). The step's
contents are the window's means (``_trinity.step_means``)."""
from _lib import flops, statistics, trace_reduce
import flops_afmoe as fl
from _trinity import step_means


def read(name: str, layers: dict):
    runs = trace_reduce.module_seconds(layers["trace"], "chunk_fn")
    step = step_means(layers)
    if not runs or step is None:
        return None
    cfg = layers["cfg"]
    both = lambda a, b, kind: step[a][kind] + step[b][kind]
    least = flops.roofline_seconds(
        fl.forward_flops(
            cfg, step["seqs"] + step["chunk_tokens"],
            both("live", "chunk_pairs", "window"),
            both("live", "chunk_pairs", "full"),
            step["routed"], step["seqs"] + 1),
        fl.step_bytes(cfg, both("live", "chunk_rows", "window"),
                      both("live", "chunk_rows", "full"), step["hit"]),
        layers["peaks"])
    return 100.0 * least / statistics.median(runs)
