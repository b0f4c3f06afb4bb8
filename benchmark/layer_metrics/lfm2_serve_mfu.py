"""Whole-step share of the chip's peak while serving an LFM2-MoE model:
the forward operations the window's tokens require — per token the
ACTIVE matrices only (``num_experts_per_tok`` experts, not all),
attention at the context read in the attention layers, the head; every
prompt whose first token fell in the window, every decoded token —
over window x chips x peak."""
import _lib  # noqa: F401  (puts benchmark/ on the path)
import flops_lfm2


def read(name: str, layers: dict):
    win = layers.get("window")
    if not win or not win["decode_tokens"] \
            or "num_experts_per_tok" not in layers["cfg"]:
        return None
    cfg = layers["cfg"]
    need = flops_lfm2.forward_flops(
        cfg, win["decode_tokens"],
        win["context_read"] / win["decode_tokens"])
    if win["prefill_tokens"]:
        # a prompt of n tokens reads n/2 positions a token on average
        mean_ctx = win["prefill_tokens"] / max(len(win["ttfts"]), 1) / 2
        need += flops_lfm2.forward_flops(cfg, win["prefill_tokens"],
                                         mean_ctx)
    return 100.0 * need / (layers["seconds"] * layers["chips"]
                           * layers["peaks"]["bf16_flops_per_s"])
