"""AFMoE: window and full attention layers in one model, a gate on the
attended values, sandwich norms, and a shared expert beside
sigmoid-routed dropless experts (``Trinity-Large-Preview`` is the
published model the benchmark runs through it).

The fourth language-model family of the zoo (``models/gpt.py``,
``models/lfm2.py``, ``models/mla_moe.py``) and the first whose
attention layers do not all see the same keys. ``x0 = E[ids] *
sqrt(d_model)`` (``mup``); per layer, with four RMSNorms (the
"sandwich"): ``h = x + N2(Attn(N1(x)))``, ``y = h + N4(FF(N3(h)))``;
logits ``= RMSNorm(y_L) @ W_head`` (untied head).

**Attention.** ``[q | k | v | g] = u @ W_qkvg`` (no biases): ``q`` and
the gate ``g`` are ``n_heads`` heads of ``head_dim`` lanes, ``k`` and
``v`` ``n_kv_heads`` (query head ``h`` reads KV head ``h // rep``);
``q`` and ``k`` are RMS-normed over each head's lanes (one gain a
projection). By the config's ``layer_types``:

- ``sliding_attention`` — RoPE (rotate-half, all lanes) on ``q`` and
  ``k``; key ``j`` is visible to query ``i`` iff ``0 <= i - j <
  window``;
- ``full_attention`` — NO position encoding; plain causal visibility.

Then ``Attn = (o * sigmoid(g)) @ W_o``: the gate multiplies the
attended values lane by lane, before the output projection.

**Feed-forward.** A dense SwiGLU in the first ``n_dense_layers``
layers; after them a shared expert (added unweighted) plus the
dropless routed experts of ``models/moe.py`` — ``s = sigmoid(u @
W_r)`` in float32, the ``top_k`` chosen by ``s + b``, weights
``routed_scaling * s[sel] / (sum s[sel] + route_eps)``. The router is
``n_experts`` wide whatever this device holds: ``experts_held =
(first, n)`` is one expert-parallel rank's share (``moe_dropless``:
a pair routed elsewhere keeps its place in the top-k and in the
renormalisation and adds nothing here).

**Layout.** As ``models/lfm2.py``: the leading dense layers are
unrolled (``params["lead"]``, a list of layer trees), what follows is
a ``lax.scan`` over PERIODS of the layer pattern (``(sliding, sliding,
sliding, full)`` in the published model), the period's sub-layers
written out in the scan's body with their kinds STATIC — no
``lax.cond`` on a kind. ``params["periods"]`` is a list (one entry a
sub-layer of the period) of trees stacked over the periods; the routed
experts of all periods lie in one ``(periods * n, ...)`` stack the
body reads in place by ``first_group``.

**One layer stack, two callers.** :func:`layers` leaves to its caller
``attend(q, k, v, cache, li, kind=...) -> (o, cache)``: how layer
``li`` AMONG ITS KIND reads and writes the cache of that kind
(``kind``: ``"window"`` or ``"full"``). ``Afmoe.apply`` hands in plain
masked attention; the serving engine (serving/engine.py) hands in two
pools — a ring of ``window + chunk + page`` positions a slot for the
window layers, growing pages for the full ones
(``serving/kv_pages.py``). :func:`cache_spec` tells it what to
allocate.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from torchbooster_tpu.models import layers as L
from torchbooster_tpu.models.gpt import _rope
from torchbooster_tpu.models.lfm2 import _stack
from torchbooster_tpu.models.mla_moe import swiglu
from torchbooster_tpu.models.moe import moe_dropless
from torchbooster_tpu.ops.attention import attention, expand_kv_heads

SLIDING, FULL = "sliding_attention", "full_attention"
# the cache kinds of serving/kv_pages.py, by layer type
KIND = {SLIDING: "window", FULL: "full"}
# What the paged engine does not serve for this family, and why
# (serving/engine.py raises these at build): a window layer keeps a
# RING of its slot's last positions, so its pages are not the whole of
# a sequence and belong to no block table; the rest is GPT-shaped code.
_RING = "a window layer's cache is a ring of its slot's last " \
        "positions: "
UNSERVED = {
    "prefix_cache": _RING + "a prefix hit would skip the chunks that "
                            "fill it, and a ring page is shared with "
                            "no other slot",
    "speculative": _RING + "a rewind could land on a position the "
                           "ring has already recycled",
    "parallel_sampling (fork)": _RING + "a fork would need a copy of "
                                        "the parent's ring",
    "host_spill": _RING + "a spilled page carries none of it",
    "disagg (prefill_only)": _RING + "an exported page carries none "
                                     "of it",
    "tp": "the tp layout of attn_qkv and of the pool's rows is "
          "GPTConfig's",
    "cache_dtype: int8": "the int8 rows and their per-head scales are "
                         "wired for GPTConfig's attention core",
    "decode_backend: pallas": "the paged-attention kernel walks one "
                              "block table and has no window term",
    "structured": "the programs of a model with its own layer stack "
                  "carry no legality-mask operand",
    "adapters (lora)": "the adapter stacks are laid out for GPTConfig's "
                       "fused attn_qkv",
    "weights (int8/int4)": "the quantizer walks GPTConfig's block tree",
}
# the published model's layer pattern: three sliding, one full, x 15
LAYER_TYPES = (SLIDING, SLIDING, SLIDING, FULL) * 15


@dataclass(frozen=True)
class AfmoeConfig:
    vocab: int = 200_192
    d_model: int = 3072
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128             # NOT d_model // n_heads
    dense_width: int = 12_288       # SwiGLU width of the dense layers
    expert_width: int = 3072        # SwiGLU width of one routed expert
    shared_width: int = 3072        # ... of the shared expert
    n_experts: int = 256            # the ROUTER's width
    experts_held: tuple[int, int] = (0, 256)    # (first, n) held here
    top_k: int = 4
    n_dense_layers: int = 6         # leading layers with a dense MLP
    layer_types: tuple[str, ...] = LAYER_TYPES
    window: int = 4096              # keys a sliding layer's query sees
    rope_base: float = 10_000.0
    norm_eps: float = 1e-5
    routed_scaling: float = 2.448   # route_scale
    route_eps: float = 1e-20        # in the renormalisation's sum
    seq_len: int = 262_144

    def __post_init__(self):
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"layer_types: unknown kinds {sorted(unknown)}")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError("n_dense_layers exceeds the layer count")
        first, n = self.experts_held
        if not (0 <= first and n >= 1 and first + n <= self.n_experts):
            raise ValueError(
                f"experts_held {self.experts_held} is no slice of the "
                f"router's {self.n_experts} experts")
        if self.n_heads % self.n_kv_heads or self.window < 1:
            raise ValueError("n_heads must be a multiple of n_kv_heads "
                             "and window positive")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def plan(self) -> tuple[tuple[str, ...], tuple[str, ...], int]:
        """``(lead, period, n_periods)``: the attention kinds of the
        leading dense layers, of one period of the rest, and how often
        the period repeats (an irregular rest is one period)."""
        lead = self.layer_types[:self.n_dense_layers]
        rest = self.layer_types[self.n_dense_layers:]
        for p in range(1, len(rest) + 1):
            if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p):
                return lead, rest[:p], len(rest) // p
        return lead, (), 0

    def cache_spec(self):
        """What a serving engine allocates for this model: K/V rows of
        ``n_kv_heads * head_dim`` lanes for every layer, each layer's
        KIND beside them — the full layers' pages grow with a
        sequence, the window layers' are a ring of the last
        ``window`` positions a slot (serving/kv_pages.py)."""
        from torchbooster_tpu.serving.kv_pages import CacheSpec

        return CacheSpec(
            kv_layers=self.n_layers, kv_heads=self.n_kv_heads,
            head_dim=self.head_dim,
            kv_kinds=tuple(KIND[t] for t in self.layer_types),
            window=self.window)


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------

def _layer_init(rng: jax.Array, cfg: AfmoeConfig, moe: bool,
                dtype: Any) -> dict:
    """One layer: matrices N(0, 0.02), gains 1 — those of the two
    norms that END a branch ``1 / sqrt(n_layers)`` (the depth-scaled
    sandwich: the branches of all layers then add up to about the
    embedding's size) —, selection bias 0. A residual branch ends in
    a norm, so its output projection's spread is no scale of the
    residual stream's."""
    ks = iter(jax.random.split(rng, 12))
    d, hd, std = cfg.d_model, cfg.head_dim, 0.02
    mat = lambda shape: {
        "kernel": std * jax.random.normal(next(ks), shape, dtype)}
    gain = lambda n, g=1.0: {"scale": jnp.full((n,), g, dtype)}
    post = cfg.n_layers ** -0.5
    lp = {
        "attn_norm": gain(d), "attn_post_norm": gain(d, post),
        "ffn_norm": gain(d), "ffn_post_norm": gain(d, post),
        # [q | k | v | g]: one product over the normed input
        "attn_qkvg": mat((d, 2 * (cfg.n_heads + cfg.kv_heads) * hd)),
        "q_norm": gain(hd), "k_norm": gain(hd),
        "attn_out": mat((cfg.n_heads * hd, d)),
    }
    if moe:
        e, w, sw = cfg.experts_held[1], cfg.expert_width, cfg.shared_width
        lp["moe_gate"] = mat((d, cfg.n_experts))
        lp["moe_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
        lp["moe_fc1"], lp["moe_fc3"] = mat((e, d, w)), mat((e, d, w))
        lp["moe_fc2"] = mat((e, w, d))
        lp["shared_fc1"], lp["shared_fc3"] = mat((d, sw)), mat((d, sw))
        lp["shared_fc2"] = mat((sw, d))
    else:
        w = cfg.dense_width
        lp["mlp_fc1"], lp["mlp_fc3"] = mat((d, w)), mat((d, w))
        lp["mlp_fc2"] = mat((w, d))
    return lp


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------

EXPERT_KERNELS = ("moe_fc1", "moe_fc3", "moe_fc2")


def _layer(lp: dict, x: jax.Array, cfg: AfmoeConfig, kind: str, *,
           positions, attend: Callable, cache, li, valid, first_group=0):
    """One layer of either attention kind and either feed-forward kind
    (``"moe_gate" in lp``). Returns ``(x, cache, (tokens per held
    expert, pairs elsewhere) or None)``. The named scopes are
    docs/observability.md's: the new ones (``attn_window`` /
    ``attn_full``, ``attn_gate``) sit INSIDE the ones the benchmark's
    readers already know."""
    b, s, _ = x.shape
    hd, n_q, n_kv = cfg.head_dim, cfg.n_heads, cfg.kv_heads
    norm = lambda g, t: L.rms_norm_f32(g["scale"], t, cfg.norm_eps)
    with jax.named_scope("attn_qkv"):
        qkvg = L.dense(lp["attn_qkvg"], norm(lp["attn_norm"], x))
        cut = (n_q * hd, (n_q + n_kv) * hd, (n_q + 2 * n_kv) * hd)
        q = qkvg[..., :cut[0]].reshape(b, s, n_q, hd)
        k = qkvg[..., cut[0]:cut[1]].reshape(b, s, n_kv, hd)
        v = qkvg[..., cut[1]:cut[2]].reshape(b, s, n_kv, hd)
        gate = qkvg[..., cut[2]:]
        q, k = norm(lp["q_norm"], q), norm(lp["k_norm"], k)
        if kind == SLIDING:     # a full layer has no position encoding
            q = _rope(q, positions, cfg.rope_base)
            k = _rope(k, positions, cfg.rope_base)
    with jax.named_scope("attn_core"), \
            jax.named_scope("attn_" + KIND[kind]):
        o, cache = attend(q, k, v, cache, li, kind=KIND[kind])
    with jax.named_scope("attn_out"):
        with jax.named_scope("attn_gate"):
            o = (o.reshape(b, s, n_q * hd).astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))
                 ).astype(x.dtype)
        x = x + norm(lp["attn_post_norm"], L.dense(lp["attn_out"], o))
    counts = None
    with jax.named_scope("mlp"):
        if "moe_gate" in lp:
            # the router reads the float32 norm, the experts its
            # rounding to the compute dtype
            u32 = L.rms_norm_f32(lp["ffn_norm"]["scale"],
                                 x.astype(jnp.float32), cfg.norm_eps)
            u = u32.astype(x.dtype)
            m, held, elsewhere = moe_dropless(
                lp, u, cfg.top_k, cfg.routed_scaling, valid=valid,
                first_group=first_group, route_on=u32,
                held=cfg.experts_held, route_eps=cfg.route_eps)
            with jax.named_scope("moe_shared"):
                m = m + swiglu(lp, u, "shared")
            counts = (held, elsewhere)
        else:
            m = swiglu(lp, norm(lp["ffn_norm"], x), "mlp")
        x = x + norm(lp["ffn_post_norm"], m)
    return x, cache, counts


def layers(params: dict, x: jax.Array, cfg: AfmoeConfig, *, positions,
           attend: Callable, cache=None, valid=None, conv=None,
           state=None):
    """The whole layer stack over ``x (B, S, d)``: the leading dense
    layers unrolled, then the scan over periods. ``cache`` is the
    caller's (any pytree or None), carried through and handed to
    ``attend`` with the layer's kind and its index AMONG THAT KIND
    (``li``: the row of that kind's pool). ``valid (B, S)``: which
    tokens are real (expert routing skips the others). ``conv`` /
    ``state`` are the engine's for a model with slot state: this one
    has none and hands ``state`` back as it came. Returns ``(x, cache,
    state, {"held": tokens per held expert (n_moe_layers, n) int32,
    "elsewhere": pairs routed to experts not held (n_moe_layers,)
    int32})``."""
    lead, period, n_periods = cfg.plan
    n_held = cfg.experts_held[1]
    kw = dict(positions=positions, attend=attend, valid=valid)
    seen = {SLIDING: 0, FULL: 0}
    for kind, lp in zip(lead, params["lead"]):
        x, cache, _ = _layer(lp, x, cfg, kind, cache=cache,
                             li=seen[kind], **kw)
        seen[kind] += 1
    held = jnp.zeros((0, n_held), jnp.int32)
    elsewhere = jnp.zeros((0,), jnp.int32)
    if n_periods:
        per_period = {k: period.count(k) for k in (SLIDING, FULL)}
        # the experts are not scanned over: every period's lie in one
        # (n_periods * n, ...) stack the body reads in place, its own
        # period's by ``first_group`` (moe_dropless says why)
        experts = [{k: {"kernel": lp[k]["kernel"].reshape(
                       -1, *lp[k]["kernel"].shape[2:])}
                    for k in EXPERT_KERNELS} for lp in params["periods"]]
        scanned = [{k: v for k, v in lp.items() if k not in EXPERT_KERNELS}
                   for lp in params["periods"]]

        def body(carry, inputs):
            x, cache = carry
            subs, i = inputs
            at = dict(seen)
            cnts = []
            for kind, lp, ex in zip(period, subs, experts):
                li = at[kind] + i * per_period[kind]
                at[kind] += 1
                x, cache, cnt = _layer(
                    {**lp, **ex}, x, cfg, kind, cache=cache, li=li,
                    first_group=i * n_held, **kw)
                cnts.append(cnt)
            return (x, cache), (jnp.stack([c[0] for c in cnts]),
                                jnp.stack([c[1] for c in cnts]))

        (x, cache), (held, elsewhere) = jax.lax.scan(
            body, (x, cache), (scanned, jnp.arange(n_periods)))
        held = held.reshape(-1, n_held)
        elsewhere = elsewhere.reshape(-1)
    return x, cache, state, {"held": held, "elsewhere": elsewhere}


@jax.named_scope("embed")
def embed(params: dict, ids: jax.Array, dtype: Any = None) -> jax.Array:
    """``E[ids] * sqrt(d_model)``: the family is published with
    ``mup_enabled`` (the width is the table's own)."""
    x = L.embedding(params["wte"], ids, dtype=dtype)
    return (x.astype(jnp.float32) * x.shape[-1] ** 0.5).astype(x.dtype)


@jax.named_scope("head")
def head(params: dict, x: jax.Array, cfg: AfmoeConfig) -> jax.Array:
    """Final RMSNorm and the untied head; logits in float32."""
    x = L.rms_norm_f32(params["norm_f"]["scale"], x, cfg.norm_eps)
    return jnp.dot(x, params["head"]["kernel"].astype(x.dtype),
                   preferred_element_type=jnp.float32)


def window_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     window: int) -> jax.Array:
    """Plain attention over ``(B, S, H, D)`` whose query ``i`` sees the
    keys ``j`` with ``0 <= i - j < window``; softmax in float32. The
    flash kernel has no window term, so this is the one path a sliding
    layer's full-sequence forward has (counted as ``reference`` in
    ``attention_dispatch_total``)."""
    from torchbooster_tpu.observability import get_registry

    get_registry().counter(
        "attention_dispatch_total",
        "attention() calls traced, by the implementation chosen",
    ).inc(impl="reference")
    rep = q.shape[2] // k.shape[2]
    k, v = expand_kv_heads(k, rep), expand_kv_heads(v, rep)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / q.shape[-1] ** 0.5
    back = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])[None]
    scores = jnp.where((back >= 0) & (back < window), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class Afmoe:
    """Namespace: ``init`` / ``apply``, as the zoo's other models."""

    @staticmethod
    def init(rng: jax.Array, cfg: AfmoeConfig = AfmoeConfig(),
             dtype: Any = jnp.float32) -> dict:
        lead, period, n_periods = cfg.plan
        k_emb, k_head, k_lead, k_per = jax.random.split(rng, 4)
        lead_keys = jax.random.split(k_lead, max(len(lead), 1))
        per_keys = jax.random.split(
            k_per, max(n_periods * len(period), 1)
        ).reshape(max(n_periods, 1), max(len(period), 1), -1)
        return {
            "wte": L.embedding_init(k_emb, cfg.vocab, cfg.d_model,
                                    dtype=dtype),
            "head": {"kernel": 0.02 * jax.random.normal(
                k_head, (cfg.d_model, cfg.vocab), dtype)},
            "lead": [_layer_init(lead_keys[i], cfg, False, dtype)
                     for i in range(len(lead))],
            "periods": [_stack([_layer_init(per_keys[p, j], cfg, True,
                                            dtype)
                                for p in range(n_periods)])
                        for j in range(len(period))],
            "norm_f": {"scale": jnp.ones((cfg.d_model,), dtype)},
        }

    @staticmethod
    def apply(params: dict, ids: jax.Array, cfg: AfmoeConfig,
              compute_dtype: Any = None, return_counts: bool = False):
        """Full-sequence forward: ``ids (B, S)`` -> logits ``(B, S,
        vocab)`` float32 (and the routed pairs' counts with
        ``return_counts``). The window as a mask over the whole
        sequence: what a training step runs, and what the served path
        (two caches, a ring for the window layers) must equal."""
        x = embed(params, ids, dtype=compute_dtype)

        def attend(q, k, v, cache, li, kind):
            if kind == "window":
                return window_attention(q, k, v, cfg.window), cache
            return attention(q, k, v, causal=True), cache

        x, _, _, counts = layers(
            params, x, cfg, positions=jnp.arange(ids.shape[1]),
            attend=attend)
        logits = head(params, x, cfg)
        return (logits, counts) if return_counts else logits


__all__ = ["Afmoe", "AfmoeConfig", "FULL", "KIND", "LAYER_TYPES",
           "SLIDING", "UNSERVED", "embed", "head", "layers",
           "window_attention"]
