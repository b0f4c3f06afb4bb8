"""Whole-step share of the chips' peak: the operations forward and
backward require for the tokens of the window (recomputation not
counted) over elapsed time x chips x peak."""
from _lib import flops


def read(name: str, layers: dict):
    if not layers.get("tokens"):
        return None
    need = layers["tokens"] * flops.train_flops_per_token(
        layers["cfg"], layers["seq"])
    return 100.0 * need / (layers["elapsed"] * layers["chips"]
                           * layers["peaks"]["bf16_flops_per_s"])
