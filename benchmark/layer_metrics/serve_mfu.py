"""Whole-step share of the chip's peak while serving: the forward
operations the window's tokens require — every prompt whose first token
fell in the window, every decoded token at the context it read — over
window x chips x peak."""
from _lib import flops


def read(name: str, layers: dict):
    win = layers.get("window")
    if not win or not win["decode_tokens"]:
        return None
    cfg = layers["cfg"]
    need = flops.forward_flops(cfg, win["decode_tokens"],
                               win["context_read"] / win["decode_tokens"])
    if win["prefill_tokens"]:
        # a prompt of n tokens reads n/2 positions a token on average
        mean_ctx = win["prefill_tokens"] / max(len(win["ttfts"]), 1) / 2
        need += flops.forward_flops(cfg, win["prefill_tokens"], mean_ctx)
    return 100.0 * need / (layers["seconds"] * layers["chips"]
                           * layers["peaks"]["bf16_flops_per_s"])
