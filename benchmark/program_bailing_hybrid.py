"""Where the benchmark touches the program for the Bailing hybrid family
(``model_type: bailing_hybrid``): ``program.py``'s part for an
architecture that file does not know (it may not be edited;
README-bailing_hybrid.md). The model config, the benchmark's flat
weights as ``models/bailing_hybrid.py``'s tree — the rotary lanes
permuted from the published interleaved order to rotate-half's — and
the serving stack built as a user's YAML builds it."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import program  # noqa: F401  (puts the checkout on the path)
import weights_bailing_hybrid as weights

# at import, so that a checkout without the model fails before any
# weight is made
from torchbooster_tpu.models.bailing_hybrid import (  # noqa: E402
    KDA, BailingHybridConfig, rope_permutation)


def swiglu_limit(cfg: dict) -> float:
    """The largest SwiGLU clamp of the layers held (the routed and the
    shared experts'): the model refuses any but 0."""
    at = weights.layers_held(cfg)
    return float(max(max(cfg[key][i] for i in at) for key in (
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list")))


def model_config(cfg: dict, seq_len: int | None = None):
    if not (cfg["norm_topk_prob"] and cfg.get("rope_scaling") is None
            and cfg["rope_interleave"] and cfg["q_lora_rank"] is None
            and cfg["use_qk_norm"] and cfg["score_function"] == "sigmoid"
            and cfg["topk_method"] == "noaux_tc"
            and cfg["kda_safe_gate"] and not cfg["use_kda_lora"]
            and cfg["num_kv_heads_for_linear_attn"] in (
                0, cfg["num_attention_heads"])
            and cfg["gated_attention_proj_granularity_type"] == "head_wise"
            and not cfg["tie_word_embeddings"]):
        raise ValueError(
            "models/bailing_hybrid.py is the family as published: "
            "sigmoid scores selected by group, top-k renormalised, a "
            "direct query with its norm, interleaved plain RoPE, the "
            "bounded full-rank KDA gate, a head-wise MLA gate, an untied "
            "head")
    kinds = weights.layer_kinds(cfg)
    return BailingHybridConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        conv_kernel=cfg["short_conv_kernel_size"],
        gate_lower_bound=float(cfg["kda_lower_bound"]),
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        latent_dim=cfg["kv_lora_rank"], v_dim=cfg["v_head_dim"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_shared_expert_intermediate_size"]
        * cfg["num_shared_experts"],
        n_experts=weights.published(cfg, "num_experts"),
        experts_held=weights.held(cfg),
        top_k=cfg["num_experts_per_tok"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        layer_types=tuple(kinds),
        n_dense_layers=sum(weights.dense_layers(cfg)),
        rope_base=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        swiglu_limit=swiglu_limit(cfg),
        seq_len=seq_len or cfg["max_position_embeddings"])


def arranger(cfg: dict):
    """``arrange(take)`` for ``weights_bailing_hybrid.generate``: the
    program's tree — the leading dense layers one tree each, the rest
    stacked over the periods by their place in the period; a layer's
    ``kd_*`` / ``ml_*`` rows by its index among its kind, ``ff_*`` /
    ``mo_*`` among its feed-forward's. The KDA in-projections are
    concatenated (``[q | k | v | f | g]``, the three convs' taps); the
    MLA query and up-projection are stored output-major, as
    ``models/mla_moe.py`` keeps them, and the rotary lanes of the query
    (and of its norm's gain) and of ``k_r`` permuted by
    :func:`rope_permutation`."""
    mcfg = model_config(cfg)
    lead, period, n_periods = mcfg.plan
    kinds, dense = weights.layer_kinds(cfg), weights.dense_layers(cfg)
    among = lambda flags: [flags[:i].count(flags[i])
                           for i in range(len(flags))]
    in_kind, in_ff = among(kinds), among(dense)
    n, r, c, h = (mcfg.nope_dim, mcfg.rope_dim, mcfg.latent_dim,
                  mcfg.n_heads)
    q_lanes = np.concatenate([np.arange(n), n + rope_permutation(r)])
    row_lanes = np.concatenate([np.arange(c), c + rope_permutation(r)])

    def layer_tree(take, layer_ids, stacked):
        pick = take if stacked else (
            lambda name, rows: take(name, rows)[0])
        mat = lambda name, rows: {"kernel": pick(name, rows)}
        gain = lambda name, rows: {"scale": pick(name, rows)}
        mine = [in_kind[i] for i in layer_ids]
        ff = [in_ff[i] for i in layer_ids]
        lp = {"in_norm": gain("ly_n1", layer_ids),
              "post_norm": gain("ly_n2", layer_ids)}
        if kinds[layer_ids[0]] == KDA:
            cat = lambda names: {"kernel": jnp.concatenate(
                [pick(name, mine) for name in names], axis=-1)}
            lp.update(attn_qkvfg=cat(("kd_q", "kd_k", "kd_v", "kd_f",
                                      "kd_g")),
                      attn_b=mat("kd_b", mine),
                      conv=cat(("kd_cq", "kd_ck", "kd_cv")),
                      a_log=pick("kd_alog", mine), dt_bias=pick("kd_dt", mine),
                      out_norm=gain("kd_norm", mine),
                      attn_out=mat("kd_out", mine))
        else:
            q = pick("ml_q", mine)
            q = q.reshape(*q.shape[:-1], h, n + r)[..., q_lanes]
            lp.update(
                attn_q={"kernel": jnp.swapaxes(
                    q.reshape(*q.shape[:-2], -1), -1, -2)},
                q_norm={"scale": pick("ml_qn", mine)[..., q_lanes]},
                attn_dkv={"kernel": pick("ml_dkv", mine)[..., row_lanes]},
                kv_norm=gain("ml_kvn", mine),
                attn_ukv={"kernel": jnp.swapaxes(pick("ml_ukv", mine),
                                                 -1, -2)},
                attn_gate=mat("ml_gate", mine), attn_out=mat("ml_out", mine))
        if dense[layer_ids[0]]:
            lp.update(mlp_fc1=mat("ff_w1", ff), mlp_fc3=mat("ff_w3", ff),
                      mlp_fc2=mat("ff_w2", ff))
        else:
            lp.update(moe_gate=mat("mo_gate", ff),
                      moe_bias=pick("mo_bias", ff),
                      moe_fc1=mat("mo_w1", ff), moe_fc3=mat("mo_w3", ff),
                      moe_fc2=mat("mo_w2", ff),
                      shared_fc1=mat("mo_s1", ff),
                      shared_fc3=mat("mo_s3", ff),
                      shared_fc2=mat("mo_s2", ff))
        return lp

    def arrange(take):
        n_lead = len(lead)
        return {
            "wte": {"table": take("wte")},
            "head": {"kernel": take("head")},
            "lead": [layer_tree(take, [i], False) for i in range(n_lead)],
            "periods": [layer_tree(take, [n_lead + p * len(period) + j
                                          for p in range(n_periods)], True)
                        for j in range(len(period))],
            "norm_f": {"scale": take("norm_f")},
        }

    return arrange


def build_serve(cfg: dict, serving_block: dict, seed: int,
                seq_len: int | None = None):
    """``program.build_serve`` for this family: ``ServingConfig`` from
    the cell's ``serving:`` block -> ``.make(params,
    BailingHybridConfig)`` -> ``.frontend.make(batcher)``, on bfloat16
    weights from the seed. ``seq_len``: the longest sequence the
    traffic sends (the block tables' width, not the model's 262,144
    positions)."""
    from torchbooster_tpu.config import ServingConfig, resolve_types

    conf = ServingConfig(**resolve_types(ServingConfig, serving_block))
    program.enable_compile_cache()
    params = weights.generate(cfg, seed, jnp.bfloat16,
                              arrange=arranger(cfg))
    batcher = conf.make(params, model_config(cfg, seq_len))
    return batcher, conf.frontend.make(batcher), conf
