"""Where the benchmark touches the program for the Qwen3-Next family
(``model_type: qwen3_next``): ``program.py``'s part for an architecture
that file does not know (it may not be edited; README-qwen3_next.md).
The model config, the benchmark's flat weights as
``models/qwen3_next.py``'s tree, and the serving stack built as a
user's YAML builds it."""
from __future__ import annotations

import jax.numpy as jnp

import program  # noqa: F401  (puts the checkout on the path)
import weights_qwen3_next as weights

# at import, so that a checkout without the model fails before any
# weight is made
from torchbooster_tpu.models.qwen3_next import Qwen3NextConfig  # noqa: E402


def model_config(cfg: dict, seq_len: int | None = None):
    if not (cfg["norm_topk_prob"] and cfg.get("rope_scaling") is None
            and not cfg["mlp_only_layers"] and cfg["decoder_sparse_step"] == 1
            and not cfg["tie_word_embeddings"]):
        raise ValueError("models/qwen3_next.py is the family as published: "
                         "experts in every layer, top-k renormalised, plain "
                         "partial RoPE, an untied head")
    return Qwen3NextConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        k_heads=cfg["linear_num_key_heads"],
        v_heads=cfg["linear_num_value_heads"],
        k_head_dim=cfg["linear_key_head_dim"],
        v_head_dim=cfg["linear_value_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["shared_expert_intermediate_size"],
        n_experts=weights.published(cfg, "num_experts"),
        experts_held=weights.held(cfg),
        top_k=cfg["num_experts_per_tok"],
        layer_types=tuple(weights.layer_kinds(cfg)),
        rope_base=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        seq_len=seq_len or cfg["max_position_embeddings"])


def arranger(cfg: dict):
    """``arrange(take)`` for ``weights_qwen3_next.generate``: the
    program's tree — every layer's leaves stacked over the periods by
    their place in the period; a linear layer's ``la_*`` rows and a
    full layer's ``fa_*`` rows by its index among its kind."""
    _, period, n_periods = model_config(cfg).plan
    kinds = weights.layer_kinds(cfg)
    among = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]

    def layer_tree(take, layer_ids):
        kind = kinds[layer_ids[0]]
        mat = lambda name, rows: {"kernel": take(name, rows)}
        gain = lambda name, rows: {"scale": take(name, rows)}
        at = layer_ids
        lp = {"in_norm": gain("ly_n1", at), "post_norm": gain("ly_n2", at),
              "moe_gate": mat("mo_gate", at), "moe_fc1": mat("mo_w1", at),
              "moe_fc3": mat("mo_w3", at), "moe_fc2": mat("mo_w2", at),
              "shared_fc1": mat("mo_s1", at), "shared_fc3": mat("mo_s3", at),
              "shared_fc2": mat("mo_s2", at),
              "shared_gate": mat("mo_sg", at)}
        mine = [among[i] for i in layer_ids]
        if kind == weights.LINEAR:
            lp.update(attn_qkvz=mat("la_qkvz", mine),
                      attn_ba=mat("la_ba", mine),
                      conv=mat("la_conv", mine),
                      a_log=take("la_alog", mine),
                      dt_bias=take("la_dt", mine),
                      out_norm=gain("la_norm", mine),
                      attn_out=mat("la_out", mine))
        else:
            lp.update(
                # [q | k | v | g]: one product in the program
                attn_qkvg={"kernel": jnp.concatenate(
                    [take(name, mine) for name in ("fa_q", "fa_k", "fa_v",
                                                   "fa_g")], axis=-1)},
                q_norm=gain("fa_qn", mine), k_norm=gain("fa_kn", mine),
                attn_out=mat("fa_out", mine))
        return lp

    def arrange(take):
        return {
            "wte": {"table": take("wte")},
            "head": {"kernel": take("head")},
            "periods": [layer_tree(take, [p * len(period) + j
                                          for p in range(n_periods)])
                        for j in range(len(period))],
            "norm_f": {"scale": take("norm_f")},
        }

    return arrange


def build_serve(cfg: dict, serving_block: dict, seed: int,
                seq_len: int | None = None):
    """``program.build_serve`` for this family: ``ServingConfig`` from
    the cell's ``serving:`` block -> ``.make(params, Qwen3NextConfig)``
    -> ``.frontend.make(batcher)``, on bfloat16 weights from the seed.
    ``seq_len``: the longest sequence the traffic sends (the full
    layers' block tables' width, not the model's 262,144 positions)."""
    from torchbooster_tpu.config import ServingConfig, resolve_types

    conf = ServingConfig(**resolve_types(ServingConfig, serving_block))
    program.enable_compile_cache()
    params = weights.generate(cfg, seed, jnp.bfloat16,
                              arrange=arranger(cfg))
    batcher = conf.make(params, model_config(cfg, seq_len))
    return batcher, conf.frontend.make(batcher), conf
