"""Operations and bytes a Qwen3-Next (``model_type: qwen3_next``) step
needs, from shapes alone.

``flops.py``'s yardstick for a model whose mixers are of two kinds — a
gated delta-rule linear attention that keeps one float32 state a
sequence, and a gated full attention over paged K/V — with a gated
shared expert and a SHARE of its routed experts in every layer: what
the mathematics requires, whichever kernel or cache carries it out.
Per token the matrices every token multiplies (the in- and
out-projections of both mixers, the shared expert and its gate, the
router) and the routed pairs computed HERE (a pair routed to an expert
another chip holds is not this chip's work); an embedding lookup is no
matrix product, the head runs on the sampled rows only. Full attention
is counted by the (query, key) pairs a query sees, ``2 x head_dim x
2`` operations a pair and head; the delta rule by the recurrence's own
products, ``6 x d_k x d_v`` a token and value head (``S^T k``, ``S^T
q`` and the rank-one update), or in its chunked form by
:func:`delta_chunk_flops`. Bytes likewise: a full layer's cached row is
K and V of 2 x 256 lanes, 2,048 B; a linear layer's state is ``S``
(float32) and the convolution's rows (the compute dtype) a sequence.
``cfg`` is the configuration file's dict under the published keys.
"""
from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"
DELTA_BLOCK = 64        # models/qwen3_next.py's block of the chunked form


def layer_counts(cfg: dict) -> dict[str, int]:
    n = cfg["num_hidden_layers"]
    full = sum((i + 1) % cfg["full_attention_interval"] == 0
               for i in range(n))
    return {"layers": n, "linear": n - full, "full": full, "moe": n}


def widths(cfg: dict) -> dict[str, int]:
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    key = hk * cfg["linear_key_head_dim"]
    val = hv * cfg["linear_value_head_dim"]
    return {"key": key, "value": val, "conv": 2 * key + val, "hv": hv}


def linear_params(cfg: dict) -> int:
    """The in-projections ``[q | k | v | z]`` and ``[b | a]`` and the
    out-projection of one linear layer."""
    d, w = cfg["hidden_size"], widths(cfg)
    return d * (w["conv"] + w["value"]) + d * 2 * w["hv"] + w["value"] * d


def full_params(cfg: dict) -> int:
    """``W_q``, ``W_g``, ``W_k``, ``W_v``, ``W_o`` of one full layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * hd * (3 * h + 2 * g)


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg: dict) -> int:
    """The shared expert's three matrices and its gate's column."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["shared_expert_intermediate_size"] + d


def router_width(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def token_matmul_params(cfg: dict) -> int:
    """Matrix weights EVERY token multiplies, the head apart."""
    n, d = layer_counts(cfg), cfg["hidden_size"]
    return (n["linear"] * linear_params(cfg) + n["full"] * full_params(cfg)
            + n["moe"] * (shared_expert_params(cfg) + d * router_width(cfg)))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    """Every stored parameter: the matrices, the experts HELD, the
    embedding and the untied head, the convolution's taps, ``A_log``
    and ``dt_bias``, the gains (two norms a layer, the gated norm, the
    q and k norms, the final norm)."""
    n, d, w = layer_counts(cfg), cfg["hidden_size"], widths(cfg)
    small = ((2 * n["layers"] + 1) * d
             + n["linear"] * (cfg["linear_conv_kernel_dim"] * w["conv"]
                              + 2 * w["hv"] + cfg["linear_value_head_dim"])
             + n["full"] * 2 * cfg["head_dim"])
    return (token_matmul_params(cfg) + 2 * head_params(cfg) + small
            + n["moe"] * cfg["num_experts"] * expert_params(cfg))


def state_bytes(cfg: dict, conv_bytes: int = 2) -> dict[str, int]:
    """Bytes of ONE sequence's state in ONE linear layer, by kind:
    ``delta`` (``S`` of every value head, float32) and ``conv`` (the
    convolution's last ``taps - 1`` inputs)."""
    w = widths(cfg)
    return {"delta": 4 * w["hv"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"],
            "conv": conv_bytes * (cfg["linear_conv_kernel_dim"] - 1)
            * w["conv"]}


def experts_bytes(cfg: dict, experts_hit: float, pairs: float,
                  weight_bytes: int = 2, act_bytes: int = 2) -> float:
    """What the grouped products of the expert layers must move for
    ``pairs`` (token, expert) pairs on ``experts_hit`` experts (both
    summed over the layers): the hit experts' three matrices, each
    pair's input row read and output row written."""
    return (experts_hit * expert_params(cfg) * weight_bytes
            + pairs * 2 * cfg["hidden_size"] * act_bytes)


def experts_flops(cfg: dict, pairs: float) -> float:
    """Three products of 2 x hidden x expert width a pair."""
    return 2.0 * expert_params(cfg) * pairs


def delta_token_flops(cfg: dict) -> float:
    """The recurrence's operations for ONE token in ONE linear layer:
    ``S^T k``, ``S^T q`` and the rank-one update, every value head."""
    return 6.0 * widths(cfg)["hv"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def delta_chunk_flops(cfg: dict, tokens: float,
                      block: int = DELTA_BLOCK) -> float:
    """The chunked form's operations for ``tokens`` tokens of ONE
    sequence in ONE linear layer, in blocks of ``block``: per block and
    value head the five products with the state (``W S``, ``q S``, ``k^T
    v'``: ``6 T d_k d_v``), the four ``T x T`` ones (``(beta k) k^T``,
    ``q k^T``, ``T (beta v)``, ``T (beta k)``, ``tril(q k^T) v'``) and
    the inverse's squarings (two ``T^3`` products each)."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    blocks = -(-int(round(tokens)) // block)
    squarings = max(block - 1, 1).bit_length() - 1
    per = (6.0 * block * dk * dv + 2.0 * block ** 2 * (3 * dk + 2 * dv)
           + 4.0 * squarings * block ** 3)
    return blocks * widths(cfg)["hv"] * per


def delta_chunk_bytes(cfg: dict, tokens: float) -> float:
    """Bytes the chunked form must move for ``tokens`` tokens of ONE
    sequence in ONE linear layer: ``S`` read and written, ``q``, ``k``
    (key heads), ``v`` in and ``o`` out, ``g`` and ``beta``, all
    float32."""
    w = widths(cfg)
    per_token = 4 * (2 * w["key"] + 2 * w["value"] + 2 * w["hv"])
    return 2.0 * state_bytes(cfg)["delta"] + tokens * per_token


def attention_flops(cfg: dict, pairs: float) -> float:
    """Scores and values of ``pairs`` visible (query, key) pairs in
    EACH full layer; all heads and full layers counted here."""
    return (4.0 * cfg["head_dim"] * cfg["num_attention_heads"]
            * layer_counts(cfg)["full"] * pairs)


def forward_flops(cfg: dict, n_tokens: float, full_pairs: float,
                  routed_pairs: float, sampled_rows: float) -> float:
    """Forward over ``n_tokens`` tokens that attend ``full_pairs``
    (query, key) pairs in a full layer between them, with
    ``routed_pairs`` (token, expert) pairs computed here (summed over
    the layers) and ``sampled_rows`` rows through the head; the delta
    rule by its recurrence."""
    n = layer_counts(cfg)
    return (2.0 * token_matmul_params(cfg) * n_tokens
            + attention_flops(cfg, full_pairs)
            + n["linear"] * delta_token_flops(cfg) * n_tokens
            + 2.0 * expert_params(cfg) * routed_pairs
            + 2.0 * head_params(cfg) * sampled_rows)


def row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """Bytes of one cached position in one full layer as the pool
    stores it: a K row and a V row, each padded to whole 128-lane
    tiles."""
    lanes = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * -(-lanes // 128) * 128 * cache_bytes


def step_bytes(cfg: dict, full_rows: float, experts_hit: float,
               state: float, weight_bytes: int = 2,
               cache_bytes: int = 2) -> float:
    """Bytes one step NEEDS to move: every non-expert weight once (the
    head with them; the embedding's rows are a lookup), the experts hit
    (summed over the layers), ``full_rows`` cached rows in every full
    layer, and ``state`` bytes of slot state (read and written, summed
    over the linear layers)."""
    n = layer_counts(cfg)
    fixed = n_params(cfg) - head_params(cfg) \
        - n["moe"] * cfg["num_experts"] * expert_params(cfg)
    return ((fixed + experts_hit * expert_params(cfg)) * weight_bytes
            + n["full"] * full_rows * row_bytes(cfg, cache_bytes) + state)
