"""Latent-attention (``sarvam_mla``) weights from ``--seed``, in the
benchmark's own flat layout.

As ``weights_lfm2.py``: one jitted call makes every leaf on the device
in the dtype asked for, ``program_sarvam_mla.py`` rearranges them into
the program's tree and ``reference/sarvam_mla.py`` reads them as they
are. A leaf is stacked over the layers OF ITS KIND, in layer order:
``at_*`` over every layer (all attend), ``ff_*`` over the dense
feed-forwards (the leading ``first_k_dense_replace`` layers), ``mo_*``
over the expert layers (the rest). Row ``i`` of a leaf is drawn from a
key of its own (seed, leaf, ``i``).

**The share.** A routed expert is drawn from a key of ITS own (seed,
leaf, layer, expert id among the published count), so the experts a
configuration HOLDS (``experts_held: {first, count}``; ``num_experts``
is that count) are the same numbers whichever share draws them: the
four shares of a layer are four slices of one uncut layer
(tests/test_sarvam_mla.py adds them up). The router (``mo_gate``,
``mo_bias``) is drawn at its published width whatever is held. The
embedding and the head are drawn at the sliced ``vocab_size``: a
smaller vocabulary, not rows of the larger one.

``wte (V,d)  head (d,V)  norm_f (d,)
at_norm (d,)  at_q (d,H*q)  at_qn (q,)  at_dkv (d,c+r)  at_kvn (c,)
at_ukv (c,H*(n+v))  at_out (H*v,d)
ff_norm (d,)  ff_w1 ff_w3 (d,F)  ff_w2 (F,d)
mo_norm (d,)  mo_gate (d,E)  mo_bias (E,) float32
mo_w1 mo_w3 (held,d,W)  mo_w2 (held,W,d)  mo_s1 mo_s3 (d,S)  mo_s2 (S,d)``
(q = qk_nope + qk_rope, c = kv_lora_rank, r = qk_rope, n = qk_nope,
v = v_head_dim, E = the published expert count.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import seed_key

SINGLE = ("wte", "head", "norm_f")
EXPERT = ("mo_w1", "mo_w3", "mo_w2")


def published(cfg: dict, key: str):
    """A ``reduced`` key's published value (the file keeps them under
    ``published``); the key itself where nothing was cut."""
    return cfg.get("published", {}).get(key, cfg[key])


def held(cfg: dict) -> tuple[int, int]:
    """``(first, count)`` of the routed experts this configuration
    holds of each layer's published count."""
    share = cfg.get("experts_held")
    if share is None:
        return 0, cfg["num_experts"]
    return share["first"], share["count"]


def counts(cfg: dict) -> dict[str, int]:
    """Layers of each kind: attention, dense and expert feed-forwards."""
    n = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], n)
    return {"at": n, "ff": dense, "mo": n - dense}


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Shape of ONE row of every leaf (the single leaves whole; an
    expert leaf's row is ONE expert's matrix)."""
    d, v, h = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["num_attention_heads"])
    n, r, c, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["kv_lora_rank"], cfg["v_head_dim"])
    f, w = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    s = w * cfg["num_shared_experts"]
    e = published(cfg, "num_experts")
    return {
        "wte": (v, d), "head": (d, v), "norm_f": (d,),
        "at_norm": (d,), "at_q": (d, h * (n + r)), "at_qn": (n + r,),
        "at_dkv": (d, c + r), "at_kvn": (c,),
        "at_ukv": (c, h * (n + vd)), "at_out": (h * vd, d),
        "ff_norm": (d,), "ff_w1": (d, f), "ff_w3": (d, f), "ff_w2": (f, d),
        "mo_norm": (d,), "mo_gate": (d, e), "mo_bias": (e,),
        "mo_w1": (d, w), "mo_w3": (d, w), "mo_w2": (w, d),
        "mo_s1": (d, s), "mo_s3": (d, s), "mo_s2": (s, d),
    }


def _std_of(name: str, cfg: dict) -> tuple[float, float]:
    """(mean, std) of a leaf, the configuration file's ``assumed``:
    matrices N(0, 0.02), the residual branches' output projections
    scaled by 1/sqrt(2L) with L the PUBLISHED depth, gains 1 + N(0,
    0.02), the experts' selection bias N(0, 0.1)."""
    if name in ("at_out", "ff_w2", "mo_w2", "mo_s2"):
        return 0.0, 0.02 / (2 * published(cfg, "num_hidden_layers")) ** 0.5
    if name.endswith("norm") or name in ("norm_f", "at_qn", "at_kvn"):
        return 1.0, 0.02
    if name == "mo_bias":
        return 0.0, 0.1
    return 0.0, 0.02


def taker(cfg: dict, key: jax.Array, dtype=jnp.float32):
    """Trace-time: ``take(name, rows=None)`` draws rows ``rows`` of
    leaf ``name`` stacked in that order (all of them, in layer order,
    by default; a single leaf whole; an expert leaf's row is the held
    experts of that layer, ``(count, ...)``). Call under ``jax.jit``."""
    all_shapes, n_of = shapes(cfg), counts(cfg)
    order = sorted(all_shapes)
    first, count = held(cfg)

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
        keys = [jax.random.fold_in(leaf_key, int(i)) for i in rows]
        if name in EXPERT:
            keys = [jnp.stack([jax.random.fold_in(k, e)
                               for e in range(first, first + count)])
                    for k in keys]
            return jax.vmap(jax.vmap(draw))(jnp.stack(keys))
        return jax.vmap(draw)(jnp.stack(keys))

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
