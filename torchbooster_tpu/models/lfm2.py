"""LFM2-MoE: gated short-convolution layers beside grouped-query
attention layers, and sigmoid-routed dropless experts.

The second language-model family of the zoo (``models/gpt.py`` is the
first) and the first whose layers are not all of one kind. Per layer
``x = h + Mixer(RMSNorm(h))``, ``h = x + FF(RMSNorm(x))``; logits
``= RMSNorm(h_L) @ E^T`` (tied head). The mixer is, by the config's
``layer_types``:

- ``conv`` — a gated short convolution: ``[B, C, X] = split3(u @
  W_in)``, ``z = B * X``, a causal depthwise convolution of
  ``conv_kernel`` taps over ``z`` (``layers.short_conv``), ``y = (C *
  c) @ W_out``. Its state is the last ``conv_kernel - 1`` rows of
  ``z`` of a SEQUENCE — a serving slot's, not a page's;
- ``full_attention`` — GQA with a per-head RMSNorm on q and k, RoPE
  (rotate-half) on the whole head, causal softmax.

The feed-forward is a dense SwiGLU in the first ``n_dense_layers``
layers and the dropless expert layer of ``models/moe.py``
(``moe_dropless``) in the others. No projection has a bias.

**Layout.** Layers are grouped by kind so that the programs stay
small: the leading dense layers are unrolled, and what follows is a
``lax.scan`` over PERIODS of the layer pattern (the shortest unit the
rest of ``layer_types`` repeats: ``(full_attention, conv, conv,
conv)`` in the published model's first 14 layers), the period's
sub-layers written out in the scan's body. ``params["lead"]`` is a
list of layer trees, ``params["periods"]`` a list (one entry a
sub-layer of the period) of trees stacked over the periods.

**One layer stack, three callers.** :func:`layers` runs the stack and
leaves two things to its caller: ``attend(q, k, v, cache, li) -> (o,
cache)`` (how layer ``li`` of the attention layers reads and writes
the K/V cache) and ``conv(z, w, state, li) -> (c, state)`` (how layer
``li`` of the conv layers reads and writes its state). ``LFM2.apply``
(the full-sequence forward, training-shaped) hands in plain causal
attention and a stateless convolution; the serving engine's prefill
chunk and decode programs (serving/engine.py) hand in the paged pool
and the slot-indexed state. :func:`cache_spec` tells the engine what
to allocate.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from torchbooster_tpu.models import layers as L
from torchbooster_tpu.models.gpt import _rope
from torchbooster_tpu.models.moe import moe_dropless
from torchbooster_tpu.ops.attention import mha_reference

CONV, ATTENTION = "conv", "full_attention"
# What the paged engine does not serve for this family, and why
# (serving/engine.py raises these at build): with slot state, pages
# are no longer all of a sequence; the rest is GPT-shaped code.
_STATE = "the conv layers' slot-indexed state: "
UNSERVED = {
    "prefix_cache": _STATE + "a prefix hit would skip the chunks that "
                             "build it",
    "speculative": _STATE + "a rewind would need the state of an "
                            "earlier position",
    "parallel_sampling (fork)": _STATE + "a fork would need a copy of "
                                         "the parent's",
    "host_spill": _STATE + "a spilled page carries none of it",
    "disagg (prefill_only)": _STATE + "an exported page carries none "
                                      "of it",
    "tp": "the tp layout of attn_qkv and of the pool's rows is "
          "GPTConfig's",
    "cache_dtype: int8": "the int8 rows and their per-head scales are "
                         "wired for GPTConfig's attention core",
    "decode_backend: pallas": "the paged-attention kernel's head "
                              "split is GPTConfig's",
    "structured": "the programs of a model with its own layer stack "
                  "carry no legality-mask operand",
    "adapters (lora)": "the adapter stacks are laid out for GPTConfig's "
                       "fused attn_qkv",
    "weights (int8/int4)": "the quantizer walks GPTConfig's block tree",
}
# the published model's layer pattern (24 layers: 18 conv, 6 attention)
LAYER_TYPES = (
    CONV, CONV, ATTENTION, CONV, CONV, CONV, ATTENTION, CONV, CONV, CONV,
    ATTENTION, CONV, CONV, CONV, ATTENTION, CONV, CONV, CONV, ATTENTION,
    CONV, CONV, ATTENTION, CONV, CONV)


@dataclass(frozen=True)
class LFM2Config:
    vocab: int = 65536
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 8
    dense_width: int = 7168         # SwiGLU width of the dense layers
    expert_width: int = 1792        # SwiGLU width of one expert
    n_experts: int = 32
    top_k: int = 4
    n_dense_layers: int = 2         # leading layers with a dense MLP
    layer_types: tuple[str, ...] = LAYER_TYPES
    conv_kernel: int = 3            # taps (the config's conv_L_cache)
    rope_base: float = 1_000_000.0
    norm_eps: float = 1e-5
    routed_scaling: float = 1.0
    seq_len: int = 128_000

    def __post_init__(self):
        unknown = set(self.layer_types) - {CONV, ATTENTION}
        if unknown:
            raise ValueError(f"layer_types: unknown kinds {sorted(unknown)}")
        if not 0 <= self.n_dense_layers <= len(self.layer_types):
            raise ValueError("n_dense_layers exceeds the layer count")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def plan(self) -> tuple[tuple[str, ...], tuple[str, ...], int]:
        """``(lead, period, n_periods)``: the mixer kinds of the
        leading dense layers, of one period of the rest, and how often
        the period repeats (an irregular rest is one period)."""
        lead = self.layer_types[:self.n_dense_layers]
        rest = self.layer_types[self.n_dense_layers:]
        for p in range(1, len(rest) + 1):
            if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p):
                return lead, rest[:p], len(rest) // p
        return lead, (), 0

    def cache_spec(self):
        """What a serving engine allocates for this model: K/V rows
        for the attention layers only, and one slot-indexed state — the
        conv layers' last ``conv_kernel - 1`` inputs."""
        from torchbooster_tpu.serving.kv_pages import CacheSpec

        n_conv = self.layer_types.count(CONV)
        return CacheSpec(
            kv_layers=self.layer_types.count(ATTENTION),
            kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            slot_states={"conv": (n_conv, self.conv_kernel - 1,
                                  self.d_model)} if n_conv else {})


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------

def _layer_init(rng: jax.Array, cfg: LFM2Config, kind: str, moe: bool,
                dtype: Any) -> dict:
    """One layer: matrices N(0, 0.02), the residual branches' output
    projections scaled by 1/sqrt(2L), gains 1, conv taps N(0, 0.5),
    the experts' selection bias 0."""
    ks = iter(jax.random.split(rng, 8))
    d, std = cfg.d_model, 0.02
    res_std = std / (2 * cfg.n_layers) ** 0.5
    mat = lambda shape, s=std: {
        "kernel": s * jax.random.normal(next(ks), shape, dtype)}
    gain = lambda n: {"scale": jnp.ones((n,), dtype)}
    lp = {"op_norm": gain(d), "ffn_norm": gain(d)}
    if kind == CONV:
        lp["conv_in"] = mat((d, 3 * d))
        lp["conv"] = mat((cfg.conv_kernel, d), 0.5)
        lp["conv_out"] = mat((d, d), res_std)
    else:
        hd = cfg.head_dim
        lp["attn_qkv"] = mat((d, (cfg.n_heads + 2 * cfg.kv_heads) * hd))
        lp["q_norm"], lp["k_norm"] = gain(hd), gain(hd)
        lp["attn_out"] = mat((cfg.n_heads * hd, d), res_std)
    if moe:
        e, w = cfg.n_experts, cfg.expert_width
        lp["moe_gate"] = mat((d, e))
        lp["moe_bias"] = jnp.zeros((e,), jnp.float32)
        lp["moe_fc1"] = mat((e, d, w))
        lp["moe_fc3"] = mat((e, d, w))
        lp["moe_fc2"] = mat((e, w, d), res_std)
    else:
        w = cfg.dense_width
        lp["mlp_fc1"], lp["mlp_fc3"] = mat((d, w)), mat((d, w))
        lp["mlp_fc2"] = mat((w, d), res_std)
    return lp


def _stack(trees: list[dict]) -> dict:
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *trees)


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------

EXPERT_KERNELS = ("moe_fc1", "moe_fc3", "moe_fc2")


def _layer(lp: dict, x: jax.Array, cfg: LFM2Config, kind: str, *,
           positions, attend: Callable, conv: Callable, cache, state,
           li, valid, first_group=0):
    """One layer of either mixer kind and either feed-forward kind
    (``"moe_gate" in lp``). Returns ``(x, cache, state, tokens per
    expert or None)``. The named scopes are docs/observability.md's:
    the new ones (``conv_mix``, ``moe_route``, ``moe_experts``) sit
    INSIDE the ones the benchmark's readers already know."""
    b, s, d = x.shape
    norm = lambda g, t: L.rms_norm_f32(g["scale"], t, cfg.norm_eps)
    if kind == CONV:
        with jax.named_scope("attn_qkv"):
            gate_b, gate_c, xin = jnp.split(
                L.dense(lp["conv_in"], norm(lp["op_norm"], x)), 3, axis=-1)
        with jax.named_scope("attn_core"), jax.named_scope("conv_mix"):
            c, state = conv(gate_b * xin, lp["conv"]["kernel"], state, li)
            y = gate_c * c
        with jax.named_scope("attn_out"):
            x = x + L.dense(lp["conv_out"], y)
    else:
        hd, n_q, n_kv = cfg.head_dim, cfg.n_heads, cfg.kv_heads
        with jax.named_scope("attn_qkv"):
            qkv = L.dense(lp["attn_qkv"], norm(lp["op_norm"], x))
            q = qkv[..., :n_q * hd].reshape(b, s, n_q, hd)
            k = qkv[..., n_q * hd:(n_q + n_kv) * hd].reshape(b, s, n_kv, hd)
            v = qkv[..., (n_q + n_kv) * hd:].reshape(b, s, n_kv, hd)
            q = _rope(norm(lp["q_norm"], q), positions, cfg.rope_base)
            k = _rope(norm(lp["k_norm"], k), positions, cfg.rope_base)
        with jax.named_scope("attn_core"):
            o, cache = attend(q, k, v, cache, li)
        with jax.named_scope("attn_out"):
            x = x + L.dense(lp["attn_out"], o.reshape(b, s, n_q * hd))
    counts = None
    with jax.named_scope("mlp"):
        if "moe_gate" in lp:
            # the router reads the float32 norm, the experts its
            # rounding to the compute dtype
            u32 = L.rms_norm_f32(lp["ffn_norm"]["scale"],
                                 x.astype(jnp.float32), cfg.norm_eps)
            m, counts, _ = moe_dropless(
                lp, u32.astype(x.dtype), cfg.top_k, cfg.routed_scaling,
                valid=valid, first_group=first_group, route_on=u32)
        else:
            u = norm(lp["ffn_norm"], x)
            m = L.dense(lp["mlp_fc2"], jax.nn.silu(
                L.dense(lp["mlp_fc1"], u)) * L.dense(lp["mlp_fc3"], u))
        x = x + m
    return x, cache, state, counts


def layers(params: dict, x: jax.Array, cfg: LFM2Config, *, positions,
           attend: Callable, conv: Callable, cache=None, state=None,
           valid=None):
    """The whole layer stack over ``x (B, S, d)``: the leading dense
    layers unrolled, then the scan over periods. ``cache`` and
    ``state`` are the caller's (any pytree or None), carried through
    and handed to ``attend`` / ``conv`` with the layer's index AMONG
    ITS KIND (``li``: the row of the K/V pool, or of the conv state).
    ``valid (B, S)``: which tokens are real (expert routing skips the
    others). Returns ``(x, cache, state, tokens per expert
    (n_moe_layers, E) int32)``."""
    lead, period, n_periods = cfg.plan
    kw = dict(positions=positions, attend=attend, conv=conv, valid=valid)
    counts = []
    seen = {CONV: 0, ATTENTION: 0}
    for kind, lp in zip(lead, params["lead"]):
        x, cache, state, cnt = _layer(lp, x, cfg, kind, cache=cache,
                                      state=state, li=seen[kind], **kw)
        seen[kind] += 1
        if cnt is not None:
            counts.append(cnt[None])
    if n_periods:
        per_period = {k: period.count(k) for k in (CONV, ATTENTION)}
        # the experts are not scanned over: every period's lie in one
        # (n_periods * E, ...) stack the body reads in place, its own
        # period's by ``first_group`` (moe_dropless says why)
        held = [{k: {"kernel": lp[k]["kernel"].reshape(
                    -1, *lp[k]["kernel"].shape[2:])}
                 for k in EXPERT_KERNELS} for lp in params["periods"]]
        scanned = [{k: v for k, v in lp.items() if k not in EXPERT_KERNELS}
                   for lp in params["periods"]]

        def body(carry, inputs):
            x, cache, state = carry
            subs, i = inputs
            at = dict(seen)
            cnts = []
            for kind, lp, experts in zip(period, subs, held):
                li = at[kind] + i * per_period[kind]
                at[kind] += 1
                x, cache, state, cnt = _layer(
                    {**lp, **experts}, x, cfg, kind, cache=cache,
                    state=state, li=li, first_group=i * cfg.n_experts,
                    **kw)
                cnts.append(cnt)
            return (x, cache, state), jnp.stack(cnts)

        (x, cache, state), cnt = jax.lax.scan(
            body, (x, cache, state), (scanned, jnp.arange(n_periods)))
        counts.append(cnt.reshape(-1, cnt.shape[-1]))
    counts = jnp.concatenate(counts) if counts \
        else jnp.zeros((0, cfg.n_experts), jnp.int32)
    return x, cache, state, counts


@jax.named_scope("embed")
def embed(params: dict, ids: jax.Array, dtype: Any = None) -> jax.Array:
    return L.embedding(params["wte"], ids, dtype=dtype)


@jax.named_scope("head")
def head(params: dict, x: jax.Array, cfg: LFM2Config) -> jax.Array:
    """Final RMSNorm and the tied head; logits in float32."""
    x = L.rms_norm_f32(params["norm_f"]["scale"], x, cfg.norm_eps)
    return jnp.dot(x, params["wte"]["table"].astype(x.dtype).T,
                   preferred_element_type=jnp.float32)


def _causal_attend(q, k, v, cache, li):
    return mha_reference(q, k, v, causal=True), cache


def _fresh_conv(z, w, state, li):
    return L.short_conv(z, w)[0], state


class LFM2:
    """Namespace: ``init`` / ``apply``, as the zoo's other models."""

    @staticmethod
    def init(rng: jax.Array, cfg: LFM2Config = LFM2Config(),
             dtype: Any = jnp.float32) -> dict:
        lead, period, n_periods = cfg.plan
        k_emb, k_lead, k_per = jax.random.split(rng, 3)
        lead_keys = jax.random.split(k_lead, max(len(lead), 1))
        per_keys = jax.random.split(
            k_per, max(n_periods * len(period), 1)
        ).reshape(max(n_periods, 1), max(len(period), 1), -1)
        return {
            "wte": L.embedding_init(k_emb, cfg.vocab, cfg.d_model,
                                    dtype=dtype),
            "lead": [_layer_init(lead_keys[i], cfg, kind, False, dtype)
                     for i, kind in enumerate(lead)],
            "periods": [_stack([_layer_init(per_keys[p, j], cfg, kind,
                                            True, dtype)
                                for p in range(n_periods)])
                        for j, kind in enumerate(period)],
            "norm_f": {"scale": jnp.ones((cfg.d_model,), dtype)},
        }

    @staticmethod
    def apply(params: dict, ids: jax.Array, cfg: LFM2Config,
              compute_dtype: Any = None, return_counts: bool = False):
        """Full-sequence forward: ``ids (B, S)`` -> logits ``(B, S,
        vocab)`` float32 (and the tokens per expert ``(n_moe_layers,
        E)`` with ``return_counts``). Plain causal attention and a
        convolution that starts every sequence from zeros: what a
        training step runs, and what the served path must equal."""
        x = embed(params, ids, dtype=compute_dtype)
        x, _, _, counts = layers(
            params, x, cfg, positions=jnp.arange(ids.shape[1]),
            attend=_causal_attend, conv=_fresh_conv)
        logits = head(params, x, cfg)
        return (logits, counts) if return_counts else logits


__all__ = ["ATTENTION", "CONV", "LAYER_TYPES", "LFM2", "LFM2Config",
           "UNSERVED", "embed", "head", "layers"]
