"""Whole-step share of the chip's peak while serving an AFMoE model:
the forward operations the window's tokens require
(``flops_afmoe.forward_flops``) — per token the matrices every token
multiplies, the routed pairs computed HERE (tokens x top-k x expert
layers x the share of pairs the program counted on experts it holds),
the head on the rows that are sampled, and attention at the (query,
key) pairs a query really SEES, a kind at a time (at most the window
in a sliding layer: ``layers["attn_pairs"]``, the job's count) — over
window x chips x peak."""
import _lib  # noqa: F401  (puts benchmark/ on the path)
import flops_afmoe as fl
from _trinity import pairs_here_share


def read(name: str, layers: dict):
    win, pairs = layers.get("window"), layers.get("attn_pairs")
    share = pairs_here_share(layers)
    if share is None or not win or not pairs or not win["decode_tokens"]:
        return None
    cfg = layers["cfg"]
    tokens = win["decode_tokens"] + win["prefill_tokens"]
    routed = tokens * cfg["num_experts_per_tok"] \
        * fl.layer_counts(cfg)["moe"] * share
    need = fl.forward_flops(cfg, tokens, pairs["window"], pairs["full"],
                            routed, win["decode_tokens"] + len(win["ttfts"]))
    return 100.0 * need / (layers["seconds"] * layers["chips"]
                           * layers["peaks"]["bf16_flops_per_s"])
