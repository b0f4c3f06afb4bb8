"""Operations and bytes a Bailing hybrid (``model_type: bailing_hybrid``;
Ling-3.0-flash) step needs, from shapes alone.

``flops.py``'s yardstick for a model whose mixers are of two kinds —
Kimi Delta Attention, whose float32 state a sequence decays each key
channel on its own, and head-gated latent attention over a paged
latent pool — with a dense leading layer, a shared expert and a SHARE
of the routed experts in every later layer: what the mathematics
requires, whichever kernel or cache carries it out. Per token the
matrices every token multiplies (both mixers' projections, the dense
layer, the shared expert, the router) and the routed pairs computed
HERE; an embedding lookup is no matrix product, the head runs on the
sampled rows only. Latent attention is counted in the EXPANDED form by
the (query, key) pairs a query sees (``flops_sarvam_mla``'s
convention); KDA by the recurrence's own products, ``6 x d_k x d_v`` a
token and head, or in its chunked form by :func:`kda_chunk_flops`.
Bytes likewise: the MLA layer's cached row is 576 lanes padded to 640,
1,280 B; a KDA layer's state is ``S`` (float32) and the convolution's
rows (the compute dtype) a sequence. ``cfg`` is the configuration
file's dict under the published keys.
"""
from __future__ import annotations

KDA_BLOCK = 64          # models/bailing_hybrid.py's block of the chunked form


def layers_held(cfg: dict) -> list[int]:
    return cfg.get("layers_held", list(range(cfg["num_hidden_layers"])))


def layer_counts(cfg: dict) -> dict[str, int]:
    at = layers_held(cfg)
    mla = sum((i + 1) % cfg["layer_group_size"] == 0 for i in at)
    dense = sum(i < cfg["first_k_dense_replace"] for i in at)
    return {"layers": len(at), "kda": len(at) - mla, "mla": mla,
            "dense": dense, "moe": len(at) - dense}


def key_dim(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def kda_params(cfg: dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_f``, the output gate, ``W_b`` and
    ``W_o`` of one KDA layer."""
    d, kd = cfg["hidden_size"], key_dim(cfg)
    return 6 * d * kd + d * cfg["num_attention_heads"]


def row_dim(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def mla_params(cfg: dict) -> int:
    """``W_q``, ``W_dkv``, ``W_ukv``, the head-wise gate and ``W_o`` of
    one MLA layer."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    n, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    return (d * h * (n + r) + d * row_dim(cfg)
            + cfg["kv_lora_rank"] * h * (n + v) + d * h + h * v * d)


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] \
        * cfg["moe_shared_expert_intermediate_size"] \
        * cfg["num_shared_experts"]


def router_width(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def token_matmul_params(cfg: dict) -> int:
    """Matrix weights EVERY token multiplies, the head apart."""
    n, d = layer_counts(cfg), cfg["hidden_size"]
    return (n["kda"] * kda_params(cfg) + n["mla"] * mla_params(cfg)
            + n["dense"] * 3 * d * cfg["intermediate_size"]
            + n["moe"] * (shared_expert_params(cfg) + d * router_width(cfg)))


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def n_params(cfg: dict) -> int:
    """Every stored parameter: the matrices, the experts HELD, the
    embedding and the untied head, the convolutions' taps, ``A_log``,
    ``dt_bias``, the selection biases and the gains (two norms a layer,
    the KDA output norm, the query and latent norms, the final norm)."""
    n, d, kd = layer_counts(cfg), cfg["hidden_size"], key_dim(cfg)
    small = ((2 * n["layers"] + 1) * d
             + n["kda"] * (3 * cfg["short_conv_kernel_size"] * kd
                           + cfg["num_attention_heads"] + kd
                           + cfg["head_dim"])
             + n["mla"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                           + cfg["kv_lora_rank"])
             + n["moe"] * router_width(cfg))
    return (token_matmul_params(cfg) + 2 * head_params(cfg) + small
            + n["moe"] * cfg["num_experts"] * expert_params(cfg))


def state_bytes(cfg: dict, conv_bytes: int = 2) -> dict[str, int]:
    """Bytes of ONE sequence's state in ONE KDA layer, by kind: ``kda``
    (``S`` of every head, float32) and ``conv`` (the three
    convolutions' last ``taps - 1`` inputs)."""
    hd = cfg["head_dim"]
    return {"kda": 4 * cfg["num_attention_heads"] * hd * hd,
            "conv": conv_bytes * (cfg["short_conv_kernel_size"] - 1)
            * 3 * key_dim(cfg)}


def row_bytes(cfg: dict, cache_bytes: int = 2) -> int:
    """Bytes of one cached position in one MLA layer as the pool stores
    it: the row padded to whole 128-lane tiles (576 -> 640)."""
    return -(-row_dim(cfg) // 128) * 128 * cache_bytes


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


def kda_token_flops(cfg: dict) -> float:
    """The recurrence's operations for ONE token in ONE KDA layer:
    ``S^T (a k)``, ``S^T (a q)`` and the decayed rank-one update, every
    head."""
    hd = cfg["head_dim"]
    return 6.0 * cfg["num_attention_heads"] * hd * hd


def kda_chunk_flops(cfg: dict, tokens: float,
                    block: int = KDA_BLOCK) -> float:
    """The chunked form's operations for ``tokens`` tokens of ONE
    sequence in ONE KDA layer, in blocks of ``block``: per block and
    head the products with the state (``W S``, ``q S``, ``k^T v'``: ``6
    T d_k d_v``), the ``T x T`` ones (the two pairwise products, taken
    row sub-block by row sub-block over every column, and ``tril(q
    k^T) v'``: ``2 T^2 (2 d_k + d_v)``) and the two unit-triangular
    solves for ``U`` and ``W`` (``T^2`` a right-hand column)."""
    dk = dv = cfg["head_dim"]
    blocks = -(-int(round(tokens)) // block)
    per = (6.0 * block * dk * dv + 2.0 * block ** 2 * (2 * dk + dv)
           + block ** 2 * (dk + dv))
    return blocks * cfg["num_attention_heads"] * per


def kda_chunk_bytes(cfg: dict, tokens: float) -> float:
    """Bytes the chunked form must move for ``tokens`` tokens of ONE
    sequence in ONE KDA layer: ``S`` read and written, ``q``, ``k``,
    ``g`` (a channel each) and ``v`` in, ``o`` out, ``beta``, all
    float32."""
    kd, h = key_dim(cfg), cfg["num_attention_heads"]
    return 2.0 * state_bytes(cfg)["kda"] + tokens * 4 * (5 * kd + h)


def attention_flops(cfg: dict, pairs: float) -> float:
    """Scores and values of ``pairs`` visible (query, key) pairs in
    EACH MLA layer, expanded form; all heads and MLA layers counted."""
    return (2.0 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                   + cfg["v_head_dim"])
            * cfg["num_attention_heads"] * layer_counts(cfg)["mla"] * pairs)


def latent_sweep_flops(cfg: dict, pairs: float) -> float:
    """The same pairs in the ABSORBED form, as the decode lanes' kernel
    computes them: a head scores against the whole cached row and sums
    the latent; all heads and MLA layers counted."""
    return (2.0 * (row_dim(cfg) + cfg["kv_lora_rank"])
            * cfg["num_attention_heads"] * layer_counts(cfg)["mla"] * pairs)


def latent_read_bytes(cfg: dict, live_tokens: float,
                      cache_bytes: int = 2) -> float:
    """The live rows of every MLA layer, read once as stored."""
    return live_tokens * layer_counts(cfg)["mla"] \
        * row_bytes(cfg, cache_bytes)


def forward_flops(cfg: dict, n_tokens: float, attn_pairs: float,
                  routed_pairs: float, sampled_rows: float) -> float:
    """Forward over ``n_tokens`` tokens that attend ``attn_pairs``
    (query, key) pairs in an MLA layer between them, with
    ``routed_pairs`` (token, expert) pairs computed here (summed over
    the layers) and ``sampled_rows`` rows through the head; KDA by its
    recurrence."""
    n = layer_counts(cfg)
    return (2.0 * token_matmul_params(cfg) * n_tokens
            + attention_flops(cfg, attn_pairs)
            + n["kda"] * kda_token_flops(cfg) * n_tokens
            + 2.0 * expert_params(cfg) * routed_pairs
            + 2.0 * head_params(cfg) * sampled_rows)
