"""AFMoE (``model_type: afmoe``) weights from ``--seed``, in the
benchmark's own flat layout.

As ``weights_sarvam_mla.py`` (whose ``published`` / ``held`` read this
family's configuration files too): one jitted call makes every leaf on
the device in the dtype asked for, ``program_afmoe.py`` rearranges
them into the program's tree and ``reference/afmoe.py`` reads them as
they are. A leaf is stacked over the layers OF ITS KIND, in layer
order: ``at_*`` over every layer (all attend, sliding or full: the
kind changes the mask and the rotation, not a shape), ``ff_*`` over
the dense feed-forwards (the leading ``num_dense_layers`` layers),
``mo_*`` over the expert layers (the rest). Row ``i`` of a leaf is
drawn from a key of its own (seed, leaf, ``i``).

**The share.** A routed expert is drawn from a key of ITS own (seed,
leaf, layer, expert id among the published count), so the experts a
configuration HOLDS (``experts_held: {first, count}``; ``num_experts``
is that count) are the same numbers whichever share draws them: the
eight shares of a layer are eight slices of one uncut layer
(tests/test_afmoe.py adds them up). The router (``mo_gate``,
``mo_bias``) is drawn at its published width whatever is held. The
embedding and the head are drawn at the sliced ``vocab_size``: a
smaller vocabulary, not rows of the larger one.

``wte (V,d)  head (d,V)  norm_f (d,)
at_n1 at_n2 (d,)  at_q at_g (d,H*D)  at_k at_v (d,G*D)  at_qn at_kn (D,)
at_out (H*D,d)
ff_n3 ff_n4 (d,)  ff_w1 ff_w3 (d,F)  ff_w2 (F,d)
mo_n3 mo_n4 (d,)  mo_gate (d,E)  mo_bias (E,) float32
mo_w1 mo_w3 (held,d,W)  mo_w2 (held,W,d)  mo_s1 mo_s3 (d,S)  mo_s2 (S,d)``
(``n1`` .. ``n4``: the sandwich's norms before and after attention and
before and after the feed-forward; E = the published expert count.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from weights import seed_key
from weights_sarvam_mla import held, published  # noqa: F401

SINGLE = ("wte", "head", "norm_f")
EXPERT = ("mo_w1", "mo_w3", "mo_w2")
GAINS = ("norm_f", "at_n1", "at_qn", "at_kn", "ff_n3", "mo_n3")
# the sandwich's norms AFTER a branch: their gains are depth-scaled
POST_GAINS = ("at_n2", "ff_n4", "mo_n4")


def layer_kinds(cfg: dict) -> list[str]:
    """The attention kind of every layer the configuration RUNS: the
    entries of ``layer_types`` at ``layers_held`` (indices into the
    published pattern, kept whole in the file) where a cut names
    them, all of ``layer_types`` otherwise."""
    kept = cfg.get("layers_held", range(cfg["num_hidden_layers"]))
    return [cfg["layer_types"][i] for i in kept]


def counts(cfg: dict) -> dict[str, int]:
    """Layers of each kind: attention, dense and expert feed-forwards."""
    n = cfg["num_hidden_layers"]
    dense = min(cfg["num_dense_layers"], n)
    return {"at": n, "ff": dense, "mo": n - dense}


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Shape of ONE row of every leaf (the single leaves whole; an
    expert leaf's row is ONE expert's matrix)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hk = cfg["num_key_value_heads"] * cfg["head_dim"]
    f, w = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    s = w * cfg["num_shared_experts"]
    e = published(cfg, "num_experts")
    return {
        "wte": (v, d), "head": (d, v), "norm_f": (d,),
        "at_n1": (d,), "at_n2": (d,), "at_q": (d, hq), "at_k": (d, hk),
        "at_v": (d, hk), "at_g": (d, hq), "at_qn": (cfg["head_dim"],),
        "at_kn": (cfg["head_dim"],), "at_out": (hq, d),
        "ff_n3": (d,), "ff_n4": (d,), "ff_w1": (d, f), "ff_w3": (d, f),
        "ff_w2": (f, d),
        "mo_n3": (d,), "mo_n4": (d,), "mo_gate": (d, e), "mo_bias": (e,),
        "mo_w1": (d, w), "mo_w3": (d, w), "mo_w2": (w, d),
        "mo_s1": (d, s), "mo_s3": (d, s), "mo_s2": (s, d),
    }


def _std_of(name: str, cfg: dict) -> tuple[float, float]:
    """(mean, std) of a leaf, the configuration file's ``assumed``:
    matrices N(0, 0.02) (a residual branch ends in a norm, so no
    output projection is scaled by depth), gains 1 + N(0, 0.02) —
    those of the norms that END a branch times 1 / sqrt(L), L the
    PUBLISHED depth (the "depth-scaled" sandwich: 2L branches then add
    up to about the embedding's size; at gain 1 every branch adds a
    unit-RMS vector whose largest part is the same for all tokens —
    near-uniform attention over thousands of random keys, normalised
    up — and every token routes to the same four experts: seen on the
    first chip run, 1-2 of 32 held experts hit a layer) — and the
    experts' selection bias N(0, 0.01): the four highest of 256
    sigmoid scores lie 0.01-0.02 apart, so a bias of the other expert
    configurations' spread (0.1) chooses the experts by itself, the
    same few for every token (second chip run: ~13 of 32 held experts
    hit by a mixed step's ~130 pairs here, where a balanced router —
    what the trained buffer is FOR — hits 31)."""
    if name in GAINS:
        return 1.0, 0.02
    if name in POST_GAINS:
        depth = published(cfg, "num_hidden_layers") ** -0.5
        return depth, 0.02 * depth
    if name == "mo_bias":
        return 0.0, 0.01
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
