"""Operations and bytes an LFM2-MoE step needs, from shapes alone.

``flops.py``'s arithmetic is GPT-2's; this is the same yardstick for a
model whose layers differ in kind and whose experts are sparse: what
the algorithm requires, whichever kernel carries it out. Per token only
the ACTIVE matrices count (``num_experts_per_tok`` experts, not all),
attention exists in the ``full_attention`` layers only, an embedding
lookup is no matrix product, and a decode step reads the experts that
were HIT in it, not every expert. ``cfg`` is the configuration file's
dict under the published keys.
"""
from __future__ import annotations


def _kinds(cfg: dict) -> list[str]:
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def layer_counts(cfg: dict) -> dict[str, int]:
    kinds = _kinds(cfg)
    dense = min(cfg["num_dense_layers"], len(kinds))
    return {"conv": kinds.count("conv"),
            "attention": kinds.count("full_attention"),
            "dense": dense, "moe": len(kinds) - dense}


def kv_width(cfg: dict) -> int:
    """Lanes of one token's K (or V) in one attention layer."""
    return (cfg["num_key_value_heads"] * cfg["hidden_size"]
            // cfg["num_attention_heads"])


def expert_params(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_matmul_params(cfg: dict) -> int:
    """Matrix weights every token multiplies that are no expert's:
    conv mixers (W_in, W_out), attention mixers (q, k, v, o), dense
    MLPs, routers, and the tied head."""
    d, n = cfg["hidden_size"], layer_counts(cfg)
    conv = 4 * d * d
    attn = d * (d + 2 * kv_width(cfg)) + d * d
    dense = 3 * d * cfg["intermediate_size"]
    return (n["conv"] * conv + n["attention"] * attn + n["dense"] * dense
            + n["moe"] * d * cfg["num_experts"] + cfg["vocab_size"] * d)


def active_matmul_params(cfg: dict) -> int:
    """Weights in a matrix product once per token: the shared ones and
    ``num_experts_per_tok`` experts in every expert layer."""
    return (shared_matmul_params(cfg) + layer_counts(cfg)["moe"]
            * cfg["num_experts_per_tok"] * expert_params(cfg))


def n_params(cfg: dict) -> int:
    """Every stored parameter (tied head once): the matrices, every
    expert, the norms' gains, the conv taps, the selection biases."""
    d, n = cfg["hidden_size"], layer_counts(cfg)
    hd = d // cfg["num_attention_heads"]
    small = ((2 * len(_kinds(cfg)) + 1) * d + n["attention"] * 2 * hd
             + n["conv"] * cfg["conv_L_cache"] * d
             + n["moe"] * cfg["num_experts"])
    return (shared_matmul_params(cfg) + small
            + n["moe"] * cfg["num_experts"] * expert_params(cfg))


def attention_flops(cfg: dict, context: float) -> float:
    """Forward QK^T and AV for ONE token attending ``context`` cached
    positions, over the attention layers: 2 products x 2 ops x context
    x (query heads x head size = hidden)."""
    return 4.0 * layer_counts(cfg)["attention"] * cfg["hidden_size"] \
        * context


def forward_flops(cfg: dict, n_tokens: float, mean_context: float) -> float:
    return n_tokens * (2.0 * active_matmul_params(cfg)
                       + attention_flops(cfg, mean_context))


def decode_step_bytes(cfg: dict, n_seqs: float, live_tokens: float,
                      experts_hit: float, weight_bytes: int = 2,
                      cache_bytes: int = 2) -> float:
    """Bytes one decode step NEEDS to read: every non-expert weight
    once, the experts hit in the step (``experts_hit``: summed over the
    expert layers), the live cached K/V of the step's sequences, and
    their conv state."""
    n = layer_counts(cfg)
    shared = n_params(cfg) - n["moe"] * cfg["num_experts"] \
        * expert_params(cfg)
    kv = live_tokens * n["attention"] * 2 * kv_width(cfg) * cache_bytes
    conv = n_seqs * n["conv"] * (cfg["conv_L_cache"] - 1) \
        * cfg["hidden_size"] * cache_bytes
    return (shared + experts_hit * expert_params(cfg)) * weight_bytes \
        + kv + conv


def decode_step_flops(cfg: dict, n_seqs: float, live_tokens: float) -> float:
    return (n_seqs * 2.0 * active_matmul_params(cfg)
            + attention_flops(cfg, live_tokens))


def experts_bytes(cfg: dict, experts_hit: float, pairs: float,
                  weight_bytes: int = 2, act_bytes: int = 2) -> float:
    """What the grouped products of the expert layers must move for
    ``pairs`` (token, expert) pairs on ``experts_hit`` experts (both
    summed over the layers): the hit experts' three matrices, each
    pair's input row read and output row written."""
    return (experts_hit * expert_params(cfg) * weight_bytes
            + pairs * 2 * cfg["hidden_size"] * act_bytes)


def experts_flops(cfg: dict, pairs: float) -> float:
    """Three products of 2 x hidden x expert width per pair."""
    return 2.0 * expert_params(cfg) * pairs
