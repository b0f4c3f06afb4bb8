"""A prefill chunk's attention as a share of the chip's peak: the
operations the chunk's VISIBLE (query, key) pairs need in the expanded
form — 64 heads x (192 + 128) x 2 a pair and layer — over the device
time under ``mla_chunk`` (inside ``attn_core``: the chunk's tokens
attending their slot's table through the paged kernel, in the absorbed
form) in one run of the chunk program, alone or with the decode lanes
riding; median over the traced runs. Pairs a chunk are the window's
mean: the prompts whose first token fell in the window hold
n (n + 1) / 2 pairs each, over the chunks the window issued. What
keeps it low is the finding: the absorbed form does (576 + 512) /
(192 + 128) = 3.4 times the expanded form's operations a pair (29 %
here would be the matrix unit's peak), and a block of 8 tokens walks
whole 512-position steps of the table up to its own position."""
import flops_sarvam_mla as fl
from _sarvam import scope_ms, step_means


def read(name: str, layers: dict):
    took_ms = scope_ms(layers, "chunk_fn", "mla_chunk")
    step = step_means(layers)
    if not took_ms or step is None:
        return None
    need = fl.attention_flops(layers["cfg"], step["chunk_pairs"])
    return 100.0 * need / (layers["peaks"]["bf16_flops_per_s"]
                           * took_ms * 1e-3)
