"""How unevenly a decode step's tokens fall on the experts: per step
and expert layer, the tokens on the fullest expert over the mean per
expert; mean over the window's steps (the program's
``serving_moe_tokens_per_expert`` series)."""
from _subscope import mean_of


def read(name: str, layers: dict):
    return mean_of(layers, "serving_moe_tokens_per_expert")
