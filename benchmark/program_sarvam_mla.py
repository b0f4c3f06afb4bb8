"""Where the benchmark touches the program for the latent-attention
(``sarvam_mla``) family: ``program.py``'s part for an architecture
that file does not know (it may not be edited; README-sarvam_mla.md).
The model config, the benchmark's flat weights as
``models/mla_moe.py``'s tree, and the serving stack built as a user's
YAML builds it."""
from __future__ import annotations

import jax.numpy as jnp

import program  # noqa: F401  (puts the checkout on the path)
import weights_sarvam_mla as weights

# at import, so that a checkout without the model fails before any
# weight is made
from torchbooster_tpu.models.mla_moe import MLAMoEConfig  # noqa: E402


def model_config(cfg: dict, seq_len: int | None = None):
    yarn = cfg["rope_scaling"]
    return MLAMoEConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        latent_dim=cfg["kv_lora_rank"], v_dim=cfg["v_head_dim"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_intermediate_size"]
        * cfg["num_shared_experts"],
        n_experts=weights.published(cfg, "num_experts"),
        experts_held=weights.held(cfg),
        top_k=cfg["num_experts_per_tok"],
        n_layers=cfg["num_hidden_layers"],
        n_dense_layers=cfg["first_k_dense_replace"],
        rope_base=float(cfg["rope_theta"]),
        yarn_factor=float(yarn["factor"]),
        yarn_original=yarn["original_max_position_embeddings"],
        yarn_beta_fast=float(yarn["beta_fast"]),
        yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_mscale_all_dim=float(yarn["mscale_all_dim"]),
        norm_eps=cfg["rms_norm_eps"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        seq_len=seq_len or cfg["max_position_embeddings"])


def arranger(cfg: dict):
    """``arrange(take)`` for ``weights_sarvam_mla.generate``: the
    program's tree — the leading dense layers one tree each, the expert
    layers' leaves stacked."""
    n_of = weights.counts(cfg)
    n_lead = n_of["ff"]

    def layer_tree(take, layer_ids, stacked):
        pick = take if stacked else (
            lambda name, rows: take(name, rows)[0])
        mat = lambda name, rows: {"kernel": pick(name, rows)}
        # the program keeps W_q and W_ukv output-major (out, in)
        mat_t = lambda name, rows: {
            "kernel": jnp.swapaxes(pick(name, rows), -1, -2)}
        gain = lambda name, rows: {"scale": pick(name, rows)}
        at = layer_ids
        lp = {"attn_norm": gain("at_norm", at), "attn_q": mat_t("at_q", at),
              "q_norm": gain("at_qn", at), "attn_dkv": mat("at_dkv", at),
              "kv_norm": gain("at_kvn", at),
              "attn_ukv": mat_t("at_ukv", at),
              "attn_out": mat("at_out", at)}
        if layer_ids[0] < n_lead:
            lp.update(ffn_norm=gain("ff_norm", at),
                      mlp_fc1=mat("ff_w1", at), mlp_fc3=mat("ff_w3", at),
                      mlp_fc2=mat("ff_w2", at))
        else:
            mo = [i - n_lead for i in layer_ids]
            lp.update(ffn_norm=gain("mo_norm", mo),
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
            "stack": layer_tree(
                take, list(range(n_lead, n_of["at"])), True),
            "norm_f": {"scale": take("norm_f")},
        }

    return arrange


def build_serve(cfg: dict, serving_block: dict, seed: int,
                seq_len: int | None = None):
    """``program.build_serve`` for this family: ``ServingConfig`` from
    the cell's ``serving:`` block -> ``.make(params, MLAMoEConfig)`` ->
    ``.frontend.make(batcher)``, on bfloat16 weights from the seed.
    ``seq_len``: the longest sequence the traffic sends (the block
    tables' width, not the model's 131,072 positions)."""
    from torchbooster_tpu.config import ServingConfig, resolve_types

    conf = ServingConfig(**resolve_types(ServingConfig, serving_block))
    program.enable_compile_cache()
    params = weights.generate(cfg, seed, jnp.bfloat16,
                              arrange=arranger(cfg))
    batcher = conf.make(params, model_config(cfg, seq_len))
    return batcher, conf.frontend.make(batcher), conf
