"""Whole-step share of the chip's peak while serving a latent-attention
(``sarvam_mla``) model: the forward operations the window's tokens
require (``flops_sarvam_mla.forward_flops``) — per token the matrices
every token multiplies (attention's, the shared expert, the router,
the dense layer), the routed pairs computed HERE (tokens x top-k x
expert layers x the share of pairs the program counted on experts it
holds), the head on the rows that are sampled, and attention in the
expanded form at the (query, key) pairs really visible: every prompt
whose first token fell in the window (n (n + 1) / 2 pairs), every
decoded token (its context) — over window x chips x peak."""
import _lib  # noqa: F401  (puts benchmark/ on the path)
import flops_sarvam_mla as fl
from _sarvam import pairs_here_share


def read(name: str, layers: dict):
    win = layers.get("window")
    share = pairs_here_share(layers)
    if share is None or not win or not win["decode_tokens"]:
        return None
    cfg = layers["cfg"]
    tokens = win["decode_tokens"] + win["prefill_tokens"]
    pairs = win["context_read"] + layers.get("prefill_pairs", 0.0)
    routed = tokens * cfg["num_experts_per_tok"] \
        * fl.layer_counts(cfg)["moe"] * share
    need = fl.forward_flops(cfg, tokens, pairs, routed,
                            win["decode_tokens"] + len(win["ttfts"]))
    return 100.0 * need / (layers["seconds"] * layers["chips"]
                           * layers["peaks"]["bf16_flops_per_s"])
