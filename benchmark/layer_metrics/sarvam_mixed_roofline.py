"""The MIXED program's share of its roofline, for a latent-attention
(``sarvam_mla``) model — the step this cell's ``itl_p95_ms`` sits on
(a chunk of a prompt with the decode lanes riding, one pass over the
weights): the least time for what a mean such step NEEDS — every
non-expert weight once, the held experts its pairs hit at three
matrices each, the lanes' live latent rows and the prior rows the
chunk's tokens see (1,280 B a token and layer as stored, each once) —
or its operations (``forward_flops`` at the step's tokens, visible
pairs, routed pairs here), whichever is the larger, over the device
time of one run of ``jit__chunk_fn`` (median over the traced runs).
The step's contents are the window's means (``_sarvam.step_means``)."""
from _lib import flops, statistics, trace_reduce
import flops_sarvam_mla as fl
from _sarvam import step_means


def read(name: str, layers: dict):
    runs = trace_reduce.module_seconds(layers["trace"], "chunk_fn")
    step = step_means(layers)
    if not runs or step is None:
        return None
    cfg = layers["cfg"]
    tokens = step["seqs"] + step["chunk_tokens"]
    least = flops.roofline_seconds(
        fl.forward_flops(cfg, tokens, step["live"] + step["chunk_pairs"],
                         step["routed"], step["seqs"] + 1),
        fl.step_bytes(cfg, step["live"] + step["chunk_context"],
                      step["hit"]),
        layers["peaks"])
    return 100.0 * least / statistics.median(runs)
