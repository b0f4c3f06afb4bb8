"""Qwen3-Next (``model_type: qwen3_next``) weights from ``--seed``, in
the benchmark's own flat layout.

As ``weights_afmoe.py``: one jitted call makes every leaf on the device
in the dtype asked for, ``program_qwen3_next.py`` rearranges them into
the program's tree and ``reference/qwen3_next.py`` reads them as they
are. A leaf is stacked over the layers OF ITS KIND, in layer order:
``la_*`` over the linear-attention layers, ``fa_*`` over the full
ones, ``ly_*`` (the two norms) and ``mo_*`` over every layer (each has
experts). Row ``i`` of a leaf is drawn from a key of its own (seed,
leaf, ``i``); a routed expert from a key of ITS own (seed, leaf,
layer, expert id among the published count), so a share's experts are
the uncut layer's (tests/test_qwen3_next.py adds the shares up). The
router is drawn at its published width; the embedding and the head at
the sliced ``vocab_size``.

``wte (V,d)  head (d,V)  norm_f (d,)  ly_n1 ly_n2 (d,)
la_qkvz (d, 2K+2W)  la_ba (d, 2Hv)  la_conv (C, 2K+W)  la_alog la_dt (Hv,)
la_norm (Dv,)  la_out (W,d)
fa_q fa_g (d, H*D)  fa_k fa_v (d, G*D)  fa_qn fa_kn (D,)  fa_out (H*D,d)
mo_gate (d,E)  mo_w1 mo_w3 (held,d,F)  mo_w2 (held,F,d)
mo_s1 mo_s3 (d,S)  mo_s2 (S,d)  mo_sg (d,1)``
(K = key heads x their width, W = value heads x theirs, C the conv's
taps, E = the published expert count; ``la_alog``, ``la_dt`` float32.)
The gains of the zero-centred norms (``ly_*``, ``fa_qn``, ``fa_kn``,
``norm_f``) are the ``w`` of ``1 + w``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import seed_key
from weights_sarvam_mla import held, published  # noqa: F401

SINGLE = ("wte", "head", "norm_f")
EXPERT = ("mo_w1", "mo_w3", "mo_w2")
ZERO_CENTRED = ("norm_f", "ly_n1", "ly_n2", "fa_qn", "fa_kn")
FLOAT32 = ("la_alog", "la_dt")
LINEAR, FULL = "linear_attention", "full_attention"
# the decay's spread (the configuration file's ``assumed``): A = e^A_log
# log-uniform over [A_LO, A_HI] a head, dt_bias N(DT_MEAN, DT_STD)
A_LO, A_HI = 0.1, 1.0
DT_MEAN, DT_STD = -4.0, 0.25


def layer_kinds(cfg: dict) -> list[str]:
    """The mixer of every layer the configuration runs: full attention
    where ``(i + 1) % full_attention_interval == 0``, linear attention
    elsewhere."""
    every = cfg["full_attention_interval"]
    return [FULL if (i + 1) % every == 0 else LINEAR
            for i in range(cfg["num_hidden_layers"])]


def counts(cfg: dict) -> dict[str, int]:
    kinds = layer_kinds(cfg)
    n = len(kinds)
    return {"la": kinds.count(LINEAR), "fa": kinds.count(FULL),
            "ly": n, "mo": n}


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Shape of ONE row of every leaf (the single leaves whole; an
    expert leaf's row is ONE expert's matrix)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hk = cfg["num_key_value_heads"] * cfg["head_dim"]
    key = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    hv = cfg["linear_num_value_heads"]
    val = hv * cfg["linear_value_head_dim"]
    f, s = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    e = published(cfg, "num_experts")
    return {
        "wte": (v, d), "head": (d, v), "norm_f": (d,),
        "ly_n1": (d,), "ly_n2": (d,),
        "la_qkvz": (d, 2 * key + 2 * val), "la_ba": (d, 2 * hv),
        "la_conv": (cfg["linear_conv_kernel_dim"], 2 * key + val),
        "la_alog": (hv,), "la_dt": (hv,),
        "la_norm": (cfg["linear_value_head_dim"],), "la_out": (val, d),
        "fa_q": (d, hq), "fa_g": (d, hq), "fa_k": (d, hk), "fa_v": (d, hk),
        "fa_qn": (cfg["head_dim"],), "fa_kn": (cfg["head_dim"],),
        "fa_out": (hq, d),
        "mo_gate": (d, e), "mo_w1": (d, f), "mo_w3": (d, f), "mo_w2": (f, d),
        "mo_s1": (d, s), "mo_s3": (d, s), "mo_s2": (s, d), "mo_sg": (d, 1),
    }


def _draw(name: str, key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """One row of leaf ``name`` in float32, the configuration file's
    ``assumed``: matrices N(0, 0.02); the zero-centred norms' ``w``
    N(0, 0.02) (a gain of ``1 + w``); the gated norm's gain 1 + N(0,
    0.02); conv taps N(0, 0.5); ``A_log`` = log of a log-uniform draw
    over [0.1, 1] and ``dt_bias`` N(-4, 0.25) a head, so that a token's
    decay ``e^g`` lies about 0.9-0.999 (``g = -A softplus(a +
    dt_bias)``, ``a`` about N(0, 0.9) at the published width)."""
    normal = lambda: jax.random.normal(key, shape, jnp.float32)
    if name == "la_norm":
        return 1.0 + 0.02 * normal()
    if name == "la_conv":
        return 0.5 * normal()
    if name == "la_alog":
        return jax.random.uniform(key, shape, jnp.float32,
                                  jnp.log(A_LO), jnp.log(A_HI))
    if name == "la_dt":
        return DT_MEAN + DT_STD * normal()
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
            # one vectorised fold-in a row over the expert ids (written
            # out expert by expert, the program took minutes to compile)
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
