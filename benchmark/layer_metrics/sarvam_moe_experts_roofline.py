"""The routed experts' share of their roofline in the MIXED program,
for a model that holds a SHARE of its experts: the least time for what
a mean step's grouped products must do — read the held experts its
pairs hit at three matrices of hidden x width each, read each computed
pair's input row and write its output row, or three products of 2 x
hidden x width a pair, whichever is the larger — over ALL device time
under ``moe_experts`` in one run of ``jit__chunk_fn`` (sort, gather
and combine included), median over the traced runs. The pairs are
those computed HERE: the step's tokens x top-k x expert layers x the
share of pairs the program counted on experts it holds (a pair routed
to an absent expert costs its place in the sort and no product). A
mixed step reports no per-expert counts, so the experts hit are by
arithmetic (``_sarvam.step_means``: with ~18 pairs an expert nearly
all 160): where a later PR makes the program count them, read that."""
from _lib import flops
import flops_sarvam_mla as fl
from _sarvam import scope_ms, step_means


def read(name: str, layers: dict):
    took_ms = scope_ms(layers, "chunk_fn", "moe_experts")
    step = step_means(layers)
    if not took_ms or step is None:
        return None
    cfg = layers["cfg"]
    least = flops.roofline_seconds(
        fl.experts_flops(cfg, step["routed"]),
        fl.experts_bytes(cfg, step["hit"], step["routed"]),
        layers["peaks"])
    return 100.0 * least / (took_ms * 1e-3)
