"""Operations and bytes a latent-attention (``sarvam_mla``) step needs,
from shapes alone.

``flops.py``'s yardstick for a model with a latent cache, a shared
expert and a SHARE of its routed experts: what the mathematics
requires, whichever kernel or form carries it out. Per token the
matrices every token multiplies (attention's, the shared expert, the
router, the dense layer) and the routed pairs computed HERE (the
program's count: a pair routed to an expert another chip holds is not
this chip's work); an embedding lookup is no matrix product, the head
runs on the rows that are sampled only. Attention pairs are counted in
the EXPANDED form, ``(q_head_dim + v_head_dim) x 2`` operations a
visible (query, key) pair and head — the fewest the mathematics needs;
the absorbed form the program decodes in does more arithmetic to read
fewer bytes, and a roofline of the absorbed sweep says so itself
(:func:`latent_sweep_flops`). ``cfg`` is the configuration file's dict
under the published keys.
"""
from __future__ import annotations


def layer_counts(cfg: dict) -> dict[str, int]:
    n = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], n)
    return {"attention": n, "dense": dense, "moe": n - dense}


def q_dim(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def row_dim(cfg: dict) -> int:
    """Values one token leaves in the cache a layer: latent + rotary."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """Bytes of one cached row as the pool stores it: padded to whole
    128-lane tiles (576 -> 640)."""
    return -(-row_dim(cfg) // 128) * 128 * cache_bytes


def attention_params(cfg: dict) -> int:
    """W_q, W_dkv, W_ukv, W_o of one layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (d * h * q_dim(cfg) + d * row_dim(cfg)
            + cfg["kv_lora_rank"] * h
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg: dict) -> int:
    return cfg["num_shared_experts"] * expert_params(cfg)


def router_width(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def token_matmul_params(cfg: dict) -> int:
    """Matrix weights EVERY token multiplies, the head apart:
    attention in every layer, the dense MLPs, and per expert layer the
    shared expert and the router."""
    d, n = cfg["hidden_size"], layer_counts(cfg)
    return (n["attention"] * attention_params(cfg)
            + n["dense"] * 3 * d * cfg["intermediate_size"]
            + n["moe"] * (shared_expert_params(cfg)
                          + d * router_width(cfg)))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    """Every stored parameter: the matrices, the experts HELD, the
    embedding and the untied head, the gains, the selection biases."""
    d, n = cfg["hidden_size"], layer_counts(cfg)
    small = ((2 * n["attention"] + 1) * d
             + n["attention"] * (q_dim(cfg) + cfg["kv_lora_rank"])
             + n["moe"] * router_width(cfg))
    return (token_matmul_params(cfg) + 2 * head_params(cfg) + small
            + n["moe"] * cfg["num_experts"] * expert_params(cfg))


def attention_flops(cfg: dict, pairs: float) -> float:
    """Scores and values of ``pairs`` visible (query, key) pairs (per
    layer; all layers and heads counted here), expanded form."""
    return (2.0 * (q_dim(cfg) + cfg["v_head_dim"])
            * cfg["num_attention_heads"] * layer_counts(cfg)["attention"]
            * pairs)


def latent_sweep_flops(cfg: dict, pairs: float) -> float:
    """The same pairs in the ABSORBED form: a head scores against the
    whole row and sums the latent."""
    return (2.0 * (row_dim(cfg) + cfg["kv_lora_rank"])
            * cfg["num_attention_heads"] * layer_counts(cfg)["attention"]
            * pairs)


def forward_flops(cfg: dict, n_tokens: float, attn_pairs: float,
                  routed_pairs: float, sampled_rows: float) -> float:
    """Forward over ``n_tokens`` tokens that attend ``attn_pairs``
    (query, key) pairs a layer between them, with ``routed_pairs``
    (token, expert) pairs computed here (summed over the expert
    layers) and ``sampled_rows`` rows through the head."""
    return (2.0 * token_matmul_params(cfg) * n_tokens
            + attention_flops(cfg, attn_pairs)
            + 2.0 * expert_params(cfg) * routed_pairs
            + 2.0 * head_params(cfg) * sampled_rows)


def step_bytes(cfg: dict, rows: float, experts_hit: float,
               weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one step NEEDS to read: every non-expert weight once (the
    head with them; the embedding's rows are a lookup), the experts
    hit in the step (``experts_hit``: summed over the expert layers),
    and ``rows`` cached latent rows in every layer (the decoding
    sequences' live tokens; with a chunk riding, the prior positions
    its tokens see)."""
    n = layer_counts(cfg)
    fixed = n_params(cfg) - head_params(cfg) \
        - n["moe"] * cfg["num_experts"] * expert_params(cfg)
    return ((fixed + experts_hit * expert_params(cfg)) * weight_bytes
            + rows * n["attention"] * row_bytes(cfg, cache_bytes))


def latent_read_bytes(cfg: dict, live_tokens: float,
                      cache_bytes: int = 2) -> float:
    """The live rows of every layer, read once."""
    return live_tokens * layer_counts(cfg)["attention"] \
        * row_bytes(cfg, cache_bytes)


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
