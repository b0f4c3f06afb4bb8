"""Where the benchmark touches the program for the LFM2-MoE family:
``program.py``'s part for an architecture that file does not know (it
may not be edited; README-lfm2.md). The model config, the benchmark's
flat weights as ``models/lfm2.py``'s tree, and the serving stack built
as a user's YAML builds it."""
from __future__ import annotations

import program  # noqa: F401  (puts the checkout on the path)


def model_config(cfg: dict, seq_len: int | None = None):
    from torchbooster_tpu.models.lfm2 import LFM2Config

    return LFM2Config(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        dense_width=cfg["intermediate_size"],
        expert_width=cfg["moe_intermediate_size"],
        n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        n_dense_layers=cfg["num_dense_layers"],
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        conv_kernel=cfg["conv_L_cache"], rope_base=float(cfg["rope_theta"]),
        norm_eps=cfg["norm_eps"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        seq_len=seq_len or cfg["max_position_embeddings"])


def arranger(cfg: dict):
    """``arrange(take)`` for ``weights_lfm2.generate``: the program's
    tree, each stacked leaf drawn as the rows it holds (a sub-layer of
    the period holds every period's layer at that place)."""
    mcfg = model_config(cfg)
    lead, period, n_periods = mcfg.plan
    kinds = mcfg.layer_types

    def rows_of(layer_ids):
        """Per leaf family, the row among its kind of each layer."""
        at = lambda i, kind: kinds[:i].count(kind)
        first = kinds[layer_ids[0]]
        mixer = [at(i, first) for i in layer_ids]
        dense = layer_ids[0] < mcfg.n_dense_layers
        ff = [i if dense else i - mcfg.n_dense_layers for i in layer_ids]
        return first, mixer, dense, ff

    def layer_tree(take, layer_ids, stacked):
        kind, mixer, dense, ff = rows_of(layer_ids)
        pick = take if stacked else (
            lambda name, rows: take(name, rows)[0])
        mat = lambda name, rows: {"kernel": pick(name, rows)}
        gain = lambda name, rows: {"scale": pick(name, rows)}
        if kind == "conv":
            lp = {"op_norm": gain("cv_norm", mixer),
                  "conv_in": mat("cv_in", mixer),
                  "conv": mat("cv_w", mixer),
                  "conv_out": mat("cv_out", mixer)}
        else:
            lp = {"op_norm": gain("at_norm", mixer),
                  "attn_qkv": mat("at_qkv", mixer),
                  "q_norm": gain("at_qn", mixer),
                  "k_norm": gain("at_kn", mixer),
                  "attn_out": mat("at_out", mixer)}
        if dense:
            lp.update(ffn_norm=gain("ff_norm", ff),
                      mlp_fc1=mat("ff_w1", ff), mlp_fc3=mat("ff_w3", ff),
                      mlp_fc2=mat("ff_w2", ff))
        else:
            lp.update(ffn_norm=gain("mo_norm", ff),
                      moe_gate=mat("mo_gate", ff),
                      moe_bias=pick("mo_bias", ff),
                      moe_fc1=mat("mo_w1", ff), moe_fc3=mat("mo_w3", ff),
                      moe_fc2=mat("mo_w2", ff))
        return lp

    def arrange(take):
        n_lead = len(lead)
        return {
            "wte": {"table": take("wte")},
            "lead": [layer_tree(take, [i], False) for i in range(n_lead)],
            "periods": [
                layer_tree(take, [n_lead + p * len(period) + j
                                  for p in range(n_periods)], True)
                for j in range(len(period))],
            "norm_f": {"scale": take("norm_f")},
        }

    return arrange


def build_serve(cfg: dict, serving_block: dict, seed: int,
                seq_len: int | None = None):
    """``program.build_serve`` for this family: ``ServingConfig`` from
    the cell's ``serving:`` block -> ``.make(params, LFM2Config)`` ->
    ``.frontend.make(batcher)``, on bfloat16 weights from the seed.
    ``seq_len``: the longest sequence the traffic sends (the block
    tables' width; the model's 128k positions would make every slot's
    table 2,000 pages wide)."""
    import jax.numpy as jnp

    from torchbooster_tpu.config import ServingConfig, resolve_types

    import weights_lfm2

    conf = ServingConfig(**resolve_types(ServingConfig, serving_block))
    program.enable_compile_cache()
    params = weights_lfm2.generate(cfg, seed, jnp.bfloat16,
                                   arrange=arranger(cfg))
    batcher = conf.make(params, model_config(cfg, seq_len))
    return batcher, conf.frontend.make(batcher), conf
