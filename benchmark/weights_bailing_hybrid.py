"""Bailing hybrid (``model_type: bailing_hybrid``; Ling-3.0-flash)
weights from ``--seed``, in the benchmark's own flat layout.

As ``weights_qwen3_next.py``: one jitted call makes every leaf on the
device in the dtype asked for, ``program_bailing_hybrid.py``
rearranges them into the program's tree and
``reference/bailing_hybrid.py`` reads them as they are. A leaf is
stacked over the HELD layers of its kind, in layer order: ``kd_*``
over the KDA layers, ``ml_*`` over the MLA ones, ``ly_*`` (the two
norms) over every layer, ``ff_*`` over the dense feed-forwards and
``mo_*`` over the expert ones. Row ``i`` of a leaf is drawn from a key
of its own (seed, leaf, ``i``); a routed expert from a key of ITS own
(seed, leaf, layer, expert id among the published count), so a
share's experts are the uncut layer's (tests/test_bailing_hybrid.py
adds the shares up). The router is drawn at its published width; the
embedding and the head at the sliced ``vocab_size``. The rotary lanes
are in the published INTERLEAVED order (the program permutes them).

``wte (V,d)  head (d,V)  norm_f (d,)  ly_n1 ly_n2 (d,)
kd_q kd_k kd_v kd_f kd_g (d,H*D)  kd_cq kd_ck kd_cv (C,H*D)  kd_b (d,H)
kd_alog (H,)  kd_dt (H*D,)  kd_norm (D,)  kd_out (H*D,d)
ml_q (d,H*(n+r))  ml_qn (n+r,)  ml_dkv (d,c+r)  ml_kvn (c,)
ml_ukv (c,H*(n+v))  ml_gate (d,H)  ml_out (H*v,d)
ff_w1 ff_w3 (d,F)  ff_w2 (F,d)
mo_gate (d,E)  mo_bias (E,)  mo_w1 mo_w3 (held,d,W)  mo_w2 (held,W,d)
mo_s1 mo_s3 (d,S)  mo_s2 (S,d)``
(D = head_dim, C = short_conv_kernel_size, n / r = qk_nope / qk_rope,
c = kv_lora_rank, v = v_head_dim, E = the published expert count;
``kd_alog``, ``kd_dt``, ``mo_bias`` float32.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import seed_key
from weights_sarvam_mla import held, published  # noqa: F401

SINGLE = ("wte", "head", "norm_f")
EXPERT = ("mo_w1", "mo_w3", "mo_w2")
GAINS = ("norm_f", "ly_n1", "ly_n2", "kd_norm", "ml_qn", "ml_kvn")
FLOAT32 = ("kd_alog", "kd_dt", "mo_bias")
KDA, MLA = "kda", "mla"
# the decays' spread (the configuration file's ``assumed``): A = e^A_log
# a head log-uniform over [A_LO, A_HI], dt_bias a channel uniform over
# [DT_LO, DT_HI]; the router's selection bias N(0, BIAS_STD)
A_LO, A_HI = 0.9, 1.1
DT_LO, DT_HI = -8.5, -4.5
BIAS_STD = 0.01


def layers_held(cfg: dict) -> list[int]:
    """The published indices of the layers the configuration runs."""
    return cfg.get("layers_held", list(range(cfg["num_hidden_layers"])))


def layer_kinds(cfg: dict) -> list[str]:
    """The mixer of every layer held: MLA where ``(i + 1) %
    layer_group_size == 0`` of its published index ``i``, KDA
    elsewhere."""
    every = cfg["layer_group_size"]
    return [MLA if (i + 1) % every == 0 else KDA for i in layers_held(cfg)]


def dense_layers(cfg: dict) -> list[bool]:
    """Whether each layer held has a dense feed-forward (published
    index below ``first_k_dense_replace``)."""
    return [i < cfg["first_k_dense_replace"] for i in layers_held(cfg)]


def counts(cfg: dict) -> dict[str, int]:
    kinds, dense = layer_kinds(cfg), dense_layers(cfg)
    return {"kd": kinds.count(KDA), "ml": kinds.count(MLA),
            "ly": len(kinds), "ff": sum(dense),
            "mo": len(dense) - sum(dense)}


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Shape of ONE row of every leaf (the single leaves whole; an
    expert leaf's row is ONE expert's matrix)."""
    d, v, h = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["num_attention_heads"])
    hd = cfg["head_dim"]
    key = h * hd
    n, r, c, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["kv_lora_rank"], cfg["v_head_dim"])
    f, w = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    s = cfg["moe_shared_expert_intermediate_size"] \
        * cfg["num_shared_experts"]
    e = published(cfg, "num_experts")
    taps = cfg["short_conv_kernel_size"]
    return {
        "wte": (v, d), "head": (d, v), "norm_f": (d,),
        "ly_n1": (d,), "ly_n2": (d,),
        **{name: (d, key) for name in ("kd_q", "kd_k", "kd_v", "kd_f",
                                       "kd_g")},
        **{name: (taps, key) for name in ("kd_cq", "kd_ck", "kd_cv")},
        "kd_b": (d, h), "kd_alog": (h,), "kd_dt": (key,), "kd_norm": (hd,),
        "kd_out": (key, d),
        "ml_q": (d, h * (n + r)), "ml_qn": (n + r,), "ml_dkv": (d, c + r),
        "ml_kvn": (c,), "ml_ukv": (c, h * (n + vd)), "ml_gate": (d, h),
        "ml_out": (h * vd, d),
        "ff_w1": (d, f), "ff_w3": (d, f), "ff_w2": (f, d),
        "mo_gate": (d, e), "mo_bias": (e,),
        "mo_w1": (d, w), "mo_w3": (d, w), "mo_w2": (w, d),
        "mo_s1": (d, s), "mo_s3": (d, s), "mo_s2": (s, d),
    }


def _draw(name: str, key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """One row of leaf ``name`` in float32, the configuration file's
    ``assumed``: matrices N(0, 0.02); gains 1 + N(0, 0.02); conv taps
    N(0, 0.5); ``A_log`` = log of a log-uniform draw over [0.9, 1.1] a
    head and ``dt_bias`` uniform over [-8.5, -4.5] a channel, so that
    ``g = -5 sigmoid(e^A_log (f + dt_bias))`` (``f`` about N(0, 1) at
    the published width) gives decays of half-lives about 10 to 1,000
    tokens; the selection bias N(0, 0.01)."""
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    if name in GAINS:
        return 1.0 + 0.02 * normal()
    if name in ("kd_cq", "kd_ck", "kd_cv"):
        return 0.5 * normal()
    if name == "kd_alog":
        return jax.random.uniform(key, shape, jnp.float32,
                                  jnp.log(A_LO), jnp.log(A_HI))
    if name == "kd_dt":
        return jax.random.uniform(key, shape, jnp.float32, DT_LO, DT_HI)
    if name == "mo_bias":
        return BIAS_STD * normal()
    return 0.02 * normal()


def taker(cfg: dict, key: jax.Array, dtype=jnp.float32):
    """Trace-time: ``take(name, rows=None)`` draws rows ``rows`` of
    leaf ``name`` stacked in that order (all of them, in layer order,
    by default; a single leaf whole; an expert leaf's row is the held
    experts of that layer, ``(count, ...)``). Call under ``jax.jit``."""
    all_shapes, n_of = shapes(cfg), counts(cfg)
    order = sorted(all_shapes)
    first, count = held(cfg)

    def take(name: str, rows=None):
        out_t = jnp.float32 if name in FLOAT32 else dtype
        leaf_key = jax.random.fold_in(key, order.index(name))
        draw = lambda k: _draw(name, k, all_shapes[name]).astype(out_t)
        if name in SINGLE:
            return draw(leaf_key)
        if rows is None:
            rows = range(n_of[name[:2]])
        keys = jnp.stack([jax.random.fold_in(leaf_key, int(i))
                          for i in rows])
        if name in EXPERT:
            ids = jnp.arange(first, first + count)
            keys = jax.vmap(lambda k: jax.vmap(
                lambda e: jax.random.fold_in(k, e))(ids))(keys)
            return jax.vmap(jax.vmap(draw))(keys)
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
