"""Whole-step share of the chip's peak while serving a Bailing hybrid
model: the forward operations the window's tokens require
(``flops_bailing_hybrid.forward_flops``) — per token the matrices every
token multiplies (both mixers' projections, the dense layer, the shared
expert, the router), the routed pairs computed HERE (tokens x top-k x
expert layers x the share of pairs the program counted on experts it
holds), the KDA recurrence in every KDA layer, the head on the rows
that are sampled, and latent attention in the expanded form at the
(query, key) pairs the window's tokens see — over window x chips x
peak."""
import flops_bailing_hybrid as fl
from _bailing_hybrid import pairs_here_share


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
