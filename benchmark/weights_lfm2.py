"""LFM2-MoE weights from ``--seed``, in the benchmark's own flat layout.

As ``weights.py`` for GPT-2: one jitted call makes every leaf on the
device in the dtype asked for, ``program_lfm2.py`` rearranges them into
the program's tree and ``reference/lfm2.py`` reads them as they are.

Layers differ in kind, so a leaf is stacked over the layers OF ITS
KIND, in layer order: ``cv_*`` over the conv mixers, ``at_*`` over the
attention mixers, ``ff_*`` over the dense feed-forwards (the leading
``num_dense_layers`` layers), ``mo_*`` over the expert layers (the
rest). Row ``i`` of a leaf is drawn from a key of its own (seed, leaf,
``i``), whatever else is drawn with it: the program's tree stacks the
same rows in another order (``take(name, rows)``) and gets the same
numbers without a copy of the flat leaf.

``wte (V,d)  norm_f (d,)
cv_norm (d,)  cv_in (d,3d)  cv_w (K,d)  cv_out (d,d)
at_norm (d,)  at_qkv (d,(H+2G)hd)  at_qn at_kn (hd,)  at_out (H hd,d)
ff_norm (d,)  ff_w1 ff_w3 (d,F)  ff_w2 (F,d)
mo_norm (d,)  mo_gate (d,E)  mo_bias (E,) float32  mo_w1 mo_w3 (E,d,W)
mo_w2 (E,W,d)``
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import seed_key

SINGLE = ("wte", "norm_f")


def counts(cfg: dict) -> dict[str, int]:
    """Layers of each kind: conv mixers, attention mixers, dense and
    expert feed-forwards."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    dense = min(cfg["num_dense_layers"], len(kinds))
    return {"cv": kinds.count("conv"), "at": kinds.count("full_attention"),
            "ff": dense, "mo": len(kinds) - dense}


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Shape of ONE row of every leaf (the single leaves whole)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    f, w, e = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    return {
        "wte": (v, d), "norm_f": (d,),
        "cv_norm": (d,), "cv_in": (d, 3 * d),
        "cv_w": (cfg["conv_L_cache"], d), "cv_out": (d, d),
        "at_norm": (d,), "at_qkv": (d, (h + 2 * g) * hd),
        "at_qn": (hd,), "at_kn": (hd,), "at_out": (h * hd, d),
        "ff_norm": (d,), "ff_w1": (d, f), "ff_w3": (d, f), "ff_w2": (f, d),
        "mo_norm": (d,), "mo_gate": (d, e), "mo_bias": (e,),
        "mo_w1": (e, d, w), "mo_w3": (e, d, w), "mo_w2": (e, w, d),
    }


def _std_of(name: str, cfg: dict) -> tuple[float, float]:
    """(mean, std) of a leaf, the configuration file's ``assumed``:
    matrices N(0, 0.02), the residual branches' output projections
    scaled by 1/sqrt(2L), gains 1 + N(0, 0.02), conv taps N(0, 0.5)
    (a 3-tap filter with taps of 0.02 would pass nothing), the
    experts' selection bias N(0, 0.1)."""
    if name in ("cv_out", "at_out", "ff_w2", "mo_w2"):
        return 0.0, 0.02 / (2 * cfg["num_hidden_layers"]) ** 0.5
    if name.endswith("norm") or name in ("norm_f", "at_qn", "at_kn"):
        return 1.0, 0.02
    if name == "cv_w":
        return 0.0, 0.5
    if name == "mo_bias":
        return 0.0, 0.1
    return 0.0, 0.02


def taker(cfg: dict, key: jax.Array, dtype=jnp.float32):
    """Trace-time: ``take(name, rows=None)`` draws rows ``rows`` of
    leaf ``name`` stacked in that order (all of them, in layer order,
    by default; a single leaf whole). Call under ``jax.jit``."""
    all_shapes, n_of = shapes(cfg), counts(cfg)
    order = sorted(all_shapes)

    def take(name: str, rows=None):
        mean, std = _std_of(name, cfg)
        out_t = jnp.float32 if name == "mo_bias" else dtype
        leaf_key = jax.random.fold_in(key, order.index(name))
        draw = lambda k: (mean + std * jax.random.normal(
            k, all_shapes[name], jnp.float32)).astype(out_t)
        if name in SINGLE:
            return draw(leaf_key)
        if rows is None:
            rows = range(n_of[name[:2]])
        keys = jnp.stack([jax.random.fold_in(leaf_key, int(i))
                          for i in rows])
        return jax.vmap(draw)(keys)

    return take


def generate(cfg: dict, seed: int, dtype=jnp.float32, *, arrange=None):
    """All weights in one jitted call: the flat dict, or whatever tree
    ``arrange(take)`` builds from rows of the same leaves."""
    n_of = counts(cfg)

    def flat(take):
        return {name: take(name) for name in shapes(cfg)
                if name in SINGLE or n_of[name[:2]]}

    build = arrange or flat
    return jax.jit(lambda key: build(taker(cfg, key, dtype)))(
        seed_key(seed))
