"""Whole-step share of the chip's peak while serving a Qwen3-Next
model: the forward operations the window's tokens require
(``flops_qwen3_next.forward_flops``) — per token the matrices every
token multiplies (both mixers' projections, the shared expert and its
gate, the router), the routed pairs computed HERE (tokens x top-k x
layers x the share of pairs the program counted on experts it holds:
10 + 1 experts of width 512 a token at most), the delta rule's
recurrence in every linear layer, the head on the rows that are
sampled, and the full layer's attention at the (query, key) pairs the
window's tokens see — over window x chips x peak."""
import flops_qwen3_next as fl
from _qwen3_next import pairs_here_share


def read(name: str, layers: dict):
    win, prompts = layers.get("window"), layers.get("prefill_pairs")
    share = pairs_here_share(layers)
    if share is None or not win or prompts is None \
            or not win["decode_tokens"]:
        return None
    cfg = layers["cfg"]
    tokens = win["decode_tokens"] + win["prefill_tokens"]
    routed = tokens * cfg["num_experts_per_tok"] \
        * fl.layer_counts(cfg)["moe"] * share
    need = fl.forward_flops(cfg, tokens, win["context_read"] + prompts,
                            routed, win["decode_tokens"] + len(win["ttfts"]))
    return 100.0 * need / (layers["seconds"] * layers["chips"]
                           * layers["peaks"]["bf16_flops_per_s"])
