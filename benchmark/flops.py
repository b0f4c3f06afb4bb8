"""Operations and bytes a GPT-2 step needs, from shapes alone.

The yardstick's arithmetic: what the algorithm requires, whichever
kernel or backend carries it out. Recomputation (remat) is not counted,
a causal mask halves attention, an embedding lookup is no matrix
product. ``cfg`` is a configuration file's dict (``n_layer``,
``n_embd``, ``n_head``, ``vocab_size``, ``n_positions``).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks_of(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}: add a row with its source")
    return table[device_kind]


def matmul_params(cfg: dict) -> int:
    """Weights that sit in a matrix product once per token: the four
    block matrices (12 d^2 a layer) and the tied head (V x d)."""
    d = cfg["n_embd"]
    return cfg["n_layer"] * 12 * d * d + cfg["vocab_size"] * d


def n_params(cfg: dict) -> int:
    """Every stored parameter (tied head counted once)."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    block = 12 * d * d + 13 * d          # 4 matrices, 4 biases, 2 norms
    return (cfg["vocab_size"] * d + cfg["n_positions"] * d
            + layers * block + 2 * d)


def attention_flops(cfg: dict, context: float) -> float:
    """Forward QK^T and AV for ONE token attending ``context`` cached
    positions, all layers: 2 products x 2 ops x context x d."""
    return 4.0 * cfg["n_layer"] * cfg["n_embd"] * context


def forward_flops(cfg: dict, n_tokens: float, mean_context: float) -> float:
    """Forward pass over ``n_tokens`` tokens whose attention reads
    ``mean_context`` positions on average."""
    return n_tokens * (2.0 * matmul_params(cfg)
                       + attention_flops(cfg, mean_context))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward for one token of a causal sequence of
    ``seq_len``: 6 N for the matrices, and three times the forward
    attention at the causal mean context ``seq_len / 2``."""
    return (6.0 * matmul_params(cfg)
            + 3.0 * attention_flops(cfg, seq_len / 2.0))


def kv_bytes_per_token(cfg: dict, cache_bytes: int = 2) -> int:
    """K and V of one cached token over all layers."""
    return 2 * cfg["n_layer"] * cfg["n_embd"] * cache_bytes


def decode_step_bytes(cfg: dict, live_tokens: float,
                      weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step NEEDS to read: every weight once, plus the
    live cached tokens of the sequences in the step (not the pool's
    size) — so the same work reads the same whichever backend runs
    it."""
    return (n_params(cfg) * weight_bytes
            + live_tokens * kv_bytes_per_token(cfg, cache_bytes))


def decode_step_flops(cfg: dict, n_seqs: float, live_tokens: float) -> float:
    """Operations of one decode step over ``n_seqs`` sequences holding
    ``live_tokens`` cached tokens between them."""
    return (n_seqs * 2.0 * matmul_params(cfg)
            + attention_flops(cfg, live_tokens))


def roofline_seconds(flops: float, n_bytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of operations over
    peak rate and bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               n_bytes / peaks["hbm_bytes_per_s"])
