"""Operations and bytes an AFMoE (``model_type: afmoe``) step needs,
from shapes alone.

``flops.py``'s yardstick for a model whose attention layers are of two
kinds, with a gate on the attended values, a shared expert and a SHARE
of its routed experts: what the mathematics requires, whichever kernel
or cache carries it out. Per token the matrices every token multiplies
(``W_q``, ``W_k``, ``W_v``, ``W_g``, ``W_o``, the shared expert, the
router, the dense layer) and the routed pairs computed HERE (the
program's count: a pair routed to an expert another chip holds is not
this chip's work); an embedding lookup is no matrix product, the head
runs on the rows that are sampled only. Attention is counted by the
(query, key) pairs a query really SEES, a kind at a time: at most
``sliding_window`` keys in a sliding layer, every earlier position in
a full one — ``2 x head_dim x 2`` operations a pair and head. Bytes
likewise: the rows a read NEEDS are the live rows within the window in
a sliding layer, every live row in a full one, 4,096 B each (K and V
of 8 x 128 lanes). ``cfg`` is the configuration file's dict under the
published keys.
"""
from __future__ import annotations

SLIDING = "sliding_attention"


def layer_counts(cfg: dict) -> dict[str, int]:
    n = cfg["num_hidden_layers"]
    kept = cfg.get("layers_held", range(n))
    window = sum(cfg["layer_types"][i] == SLIDING for i in kept)
    dense = min(cfg["num_dense_layers"], n)
    return {"attention": n, "window": window, "full": n - window,
            "dense": dense, "moe": n - dense}


def attention_params(cfg: dict) -> int:
    """W_q, W_k, W_v, W_g, W_o of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * hd * (3 * h + 2 * g)


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
    embedding and the untied head, the gains (four norms a layer, the
    q and k norms, the final norm), the selection biases."""
    d, n = cfg["hidden_size"], layer_counts(cfg)
    small = ((4 * n["attention"] + 1) * d
             + n["attention"] * 2 * cfg["head_dim"]
             + n["moe"] * router_width(cfg))
    return (token_matmul_params(cfg) + 2 * head_params(cfg) + small
            + n["moe"] * cfg["num_experts"] * expert_params(cfg))


def attention_flops(cfg: dict, window_pairs: float,
                    full_pairs: float) -> float:
    """Scores and values of the visible (query, key) pairs, a kind at
    a time: ``window_pairs`` in EACH sliding layer, ``full_pairs`` in
    each full one; all heads counted here."""
    n = layer_counts(cfg)
    return (4.0 * cfg["head_dim"] * cfg["num_attention_heads"]
            * (n["window"] * window_pairs + n["full"] * full_pairs))


def forward_flops(cfg: dict, n_tokens: float, window_pairs: float,
                  full_pairs: float, routed_pairs: float,
                  sampled_rows: float) -> float:
    """Forward over ``n_tokens`` tokens that attend ``window_pairs``
    (query, key) pairs in a sliding layer and ``full_pairs`` in a full
    one between them, with ``routed_pairs`` (token, expert) pairs
    computed here (summed over the expert layers) and ``sampled_rows``
    rows through the head."""
    return (2.0 * token_matmul_params(cfg) * n_tokens
            + attention_flops(cfg, window_pairs, full_pairs)
            + 2.0 * expert_params(cfg) * routed_pairs
            + 2.0 * head_params(cfg) * sampled_rows)


def row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """Bytes of one cached position in one layer as the pool stores
    it: a K row and a V row, each padded to whole 128-lane tiles."""
    lanes = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * -(-lanes // 128) * 128 * cache_bytes


def step_bytes(cfg: dict, window_rows: float, full_rows: float,
               experts_hit: float, weight_bytes: int = 2,
               cache_bytes: int = 2) -> float:
    """Bytes one step NEEDS to read: every non-expert weight once (the
    head with them; the embedding's rows are a lookup), the experts
    hit in the step (``experts_hit``: summed over the expert layers),
    and the cached rows its reads need: ``window_rows`` in every
    sliding layer (the decoding sequences' live rows within the
    window; with a chunk riding, the prior rows within the window its
    tokens see), ``full_rows`` in every full one."""
    n = layer_counts(cfg)
    fixed = n_params(cfg) - head_params(cfg) \
        - n["moe"] * cfg["num_experts"] * expert_params(cfg)
    return ((fixed + experts_hit * expert_params(cfg)) * weight_bytes
            + (n["window"] * window_rows + n["full"] * full_rows)
            * row_bytes(cfg, cache_bytes))
