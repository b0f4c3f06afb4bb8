"""Where the benchmark touches the program for the AFMoE family
(``model_type: afmoe``): ``program.py``'s part for an architecture
that file does not know (it may not be edited; README-afmoe.md). The
model config, the benchmark's flat weights as ``models/afmoe.py``'s
tree, and the serving stack built as a user's YAML builds it."""
from __future__ import annotations

import jax.numpy as jnp

import program  # noqa: F401  (puts the checkout on the path)
import weights_afmoe as weights

# at import, so that a checkout without the model fails before any
# weight is made
from torchbooster_tpu.models.afmoe import AfmoeConfig  # noqa: E402


def model_config(cfg: dict, seq_len: int | None = None):
    if not (cfg["mup_enabled"] and cfg["route_norm"]
            and cfg["score_func"] == "sigmoid"
            and cfg.get("rope_scaling") is None):
        raise ValueError("models/afmoe.py is the family as published: "
                         "mup, sigmoid scores renormalised, plain RoPE")
    return AfmoeConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        n_experts=weights.published(cfg, "num_experts"),
        experts_held=weights.held(cfg),
        top_k=cfg["num_experts_per_tok"],
        n_dense_layers=cfg["num_dense_layers"],
        layer_types=tuple(weights.layer_kinds(cfg)),
        window=cfg["sliding_window"],
        rope_base=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        routed_scaling=float(cfg["route_scale"]),
        seq_len=seq_len or cfg["max_position_embeddings"])


def arranger(cfg: dict):
    """``arrange(take)`` for ``weights_afmoe.generate``: the program's
    tree — the leading dense layers one tree each, the expert layers'
    leaves stacked over the periods by their place in the period."""
    lead, period, n_periods = model_config(cfg).plan
    n_lead = len(lead)

    def layer_tree(take, layer_ids, stacked):
        pick = take if stacked else (
            lambda name, rows: take(name, rows)[0])
        mat = lambda name, rows: {"kernel": pick(name, rows)}
        gain = lambda name, rows: {"scale": pick(name, rows)}
        at = layer_ids
        lp = {"attn_norm": gain("at_n1", at),
              "attn_post_norm": gain("at_n2", at),
              # [q | k | v | g]: one product in the program
              "attn_qkvg": {"kernel": jnp.concatenate(
                  [pick(name, at) for name in ("at_q", "at_k", "at_v",
                                               "at_g")], axis=-1)},
              "q_norm": gain("at_qn", at), "k_norm": gain("at_kn", at),
              "attn_out": mat("at_out", at)}
        if layer_ids[0] < n_lead:
            lp.update(ffn_norm=gain("ff_n3", at),
                      ffn_post_norm=gain("ff_n4", at),
                      mlp_fc1=mat("ff_w1", at), mlp_fc3=mat("ff_w3", at),
                      mlp_fc2=mat("ff_w2", at))
        else:
            mo = [i - n_lead for i in layer_ids]
            lp.update(ffn_norm=gain("mo_n3", mo),
                      ffn_post_norm=gain("mo_n4", mo),
                      moe_gate=mat("mo_gate", mo),
                      moe_bias=pick("mo_bias", mo),
                      moe_fc1=mat("mo_w1", mo), moe_fc3=mat("mo_w3", mo),
                      moe_fc2=mat("mo_w2", mo),
                      shared_fc1=mat("mo_s1", mo),
                      shared_fc3=mat("mo_s3", mo),
                      shared_fc2=mat("mo_s2", mo))
        return lp

    def arrange(take):
        return {
            "wte": {"table": take("wte")},
            "head": {"kernel": take("head")},
            "lead": [layer_tree(take, [i], False) for i in range(n_lead)],
            "periods": [layer_tree(
                take, [n_lead + p * len(period) + j
                       for p in range(n_periods)], True)
                for j in range(len(period))],
            "norm_f": {"scale": take("norm_f")},
        }

    return arrange


def build_serve(cfg: dict, serving_block: dict, seed: int,
                seq_len: int | None = None):
    """``program.build_serve`` for this family: ``ServingConfig`` from
    the cell's ``serving:`` block -> ``.make(params, AfmoeConfig)`` ->
    ``.frontend.make(batcher)``, on bfloat16 weights from the seed.
    ``seq_len``: the longest sequence the traffic sends (the full
    layers' block tables' width, not the model's 262,144 positions;
    the window layers' pool follows from the slots and the window)."""
    from torchbooster_tpu.config import ServingConfig, resolve_types

    conf = ServingConfig(**resolve_types(ServingConfig, serving_block))
    program.enable_compile_cache()
    params = weights.generate(cfg, seed, jnp.bfloat16,
                              arrange=arranger(cfg))
    batcher = conf.make(params, model_config(cfg, seq_len))
    return batcher, conf.frontend.make(batcher), conf
