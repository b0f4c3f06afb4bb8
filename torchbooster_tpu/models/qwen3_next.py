"""Qwen3-Next: gated delta-rule linear-attention layers beside gated
full-attention layers, softmax-routed dropless experts and a gated
shared expert in every layer (``Qwen3-Next-80B-A3B-Instruct`` is the
published model the benchmark runs through it).

The fifth language-model family of the zoo and the first whose
mixers keep a MATRIX a sequence. Every layer has one shape, pre-norm
with zero-centred RMSNorms (``N0(x) = x * rsqrt(mean(x^2) + eps) * (1
+ w)``): ``h = x + Mixer(N0(x))``, ``y = h + MoE(N0(h))``; logits ``=
N0(y_L) @ W_head`` (untied head). By the config's ``layer_types``:

- ``linear_attention`` — Gated DeltaNet. ``[q | k | v | z] = u @
  W_qkvz``, ``[b | a] = u @ W_ba``; ``[q | k | v]`` passes a causal
  depthwise convolution of ``conv_kernel`` taps (no bias) and SiLU;
  ``q`` and ``k`` are L2-normalised per head (``q`` then scaled by
  ``d_k^-1/2``), the ``k_heads`` key heads repeated to the ``v_heads``
  value heads (head ``i`` serves value heads ``r*i .. r*i + r - 1``);
  ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``.
  Per value head and token, with a state ``S (d_k, d_v)``::

      S <- e^g S;   S <- S + k (beta (v - S^T k))^T;   o = S^T q

  then ``RMSNorm(o) * w * silu(z)`` per head (``w`` NOT zero-centred)
  and ``W_out``. A prefill runs the same recurrence in its CHUNKED
  form (:func:`delta_chunked`: blocks of ``DELTA_BLOCK`` tokens, the
  WY form by matrix products), a decode step token by token
  (:func:`delta_step`, which the decode lanes' kernel
  ``ops/delta_lanes.py`` computes on the live slots' rows in place);
  the state is float32 throughout.
- ``full_attention`` — gated attention: ``[q | g]`` per head from one
  projection, ``k``, ``v`` of ``n_kv_heads``; zero-centred per-head
  norms on ``q`` and ``k``; RoPE (rotate-half) on the first
  ``rotary_dim`` lanes of a head; ``Attn = (o * sigmoid(g)) @ W_o``.

**Feed-forward.** Every layer: ``s = softmax(u @ W_r)`` over all
``n_experts`` in float32, the ``top_k`` renormalised
(``norm_topk_prob``), each expert a SwiGLU; plus a shared SwiGLU
scaled by ``sigmoid(u @ w_sg)``. ``experts_held = (first, n)`` is one
expert-parallel rank's share (``moe_dropless``).

**Layout.** As ``models/afmoe.py``: a ``lax.scan`` over PERIODS of the
layer pattern (``(linear, linear, linear, full)`` in the published
model), the kinds static in the body; the routed experts of all
periods in one stack read in place by ``first_group``.

**One layer stack, two callers.** :func:`layers` leaves to its caller
``attend(q, k, v, cache, li) -> (o, cache)`` (full layer ``li``'s
K/V) and ``carry(step, xs, state, li, name=, scopes=, in_place=) ->
(ys, state)``: the rows of slot state ``name`` of linear layer ``li``
— ``conv``, the convolution's last ``conv_kernel - 1`` inputs, or
``delta``, ``S`` — loaded for the sequences of ``xs``, handed to
``step(xs, rows, valid)`` (the mathematics, here) and stored back,
under ``scopes[0]`` for a prefill's tokens and ``scopes[1]`` for the
decode lanes; where a state names ``in_place(xs, array, li, live)``
the decode lanes hand it the stacked array instead, and it reads and
writes the live slots' rows itself (``S``: ``ops/delta_lanes.py``).
``Qwen3Next.apply``
hands in plain causal attention and zero states; the serving engine
hands in the paged pool and the slot-indexed state
(serving/engine.py). :func:`cache_spec` tells it what to allocate.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from torchbooster_tpu.models import layers as L
from torchbooster_tpu.models.gpt import _rope
from torchbooster_tpu.models.lfm2 import _stack
from torchbooster_tpu.models.mla_moe import swiglu
from torchbooster_tpu.models.moe import moe_dropless
from torchbooster_tpu.ops.attention import mha_reference
from torchbooster_tpu.ops.delta_lanes import delta_lanes

LINEAR, FULL = "linear_attention", "full_attention"
# What the paged engine does not serve for this family, and why
# (serving/engine.py raises these at build): a linear layer keeps ONE
# state a sequence, of its whole past, so pages are not the whole of a
# sequence; the rest is GPT-shaped code.
_STATE = "a linear-attention layer's state is one matrix a slot for " \
         "the sequence's whole past: "
UNSERVED = {
    "prefix_cache": _STATE + "a page carries none of it, so a prefix "
                             "hit would skip the chunks that build it",
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
# the published model's layer pattern: three linear, one full, x 12
LAYER_TYPES = (LINEAR, LINEAR, LINEAR, FULL) * 12
# tokens of one block of the chunked delta rule: a prefill's
# sequential depth is its length over this
DELTA_BLOCK = 64
HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Qwen3NextConfig:
    vocab: int = 151_936
    d_model: int = 2048
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256             # NOT d_model // n_heads
    rotary_dim: int = 64            # partial_rotary_factor 0.25
    k_heads: int = 16               # linear_num_key_heads
    v_heads: int = 32               # linear_num_value_heads
    k_head_dim: int = 128
    v_head_dim: int = 128
    conv_kernel: int = 4
    expert_width: int = 512
    shared_width: int = 512
    n_experts: int = 512            # the ROUTER's width
    experts_held: tuple[int, int] = (0, 512)    # (first, n) held here
    top_k: int = 10
    layer_types: tuple[str, ...] = LAYER_TYPES
    rope_base: float = 10_000_000.0
    norm_eps: float = 1e-6
    seq_len: int = 262_144

    def __post_init__(self):
        unknown = set(self.layer_types) - {LINEAR, FULL}
        if unknown:
            raise ValueError(f"layer_types: unknown kinds {sorted(unknown)}")
        first, n = self.experts_held
        if not (0 <= first and n >= 1 and first + n <= self.n_experts):
            raise ValueError(
                f"experts_held {self.experts_held} is no slice of the "
                f"router's {self.n_experts} experts")
        if self.n_heads % self.n_kv_heads or self.v_heads % self.k_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads "
                             "and v_heads of k_heads")
        if not 0 < self.rotary_dim <= self.head_dim or self.rotary_dim % 2:
            raise ValueError("rotary_dim must be even and within a head")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads

    @property
    def key_dim(self) -> int:
        return self.k_heads * self.k_head_dim

    @property
    def value_dim(self) -> int:
        return self.v_heads * self.v_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``[q | k | v]``."""
        return 2 * self.key_dim + self.value_dim

    @property
    def plan(self) -> tuple[tuple[str, ...], tuple[str, ...], int]:
        """``((), period, n_periods)``: every layer has experts, so no
        layer leads the scan; the kinds of one period and how often it
        repeats (an irregular pattern is one period)."""
        kinds = self.layer_types
        for p in range(1, len(kinds) + 1):
            if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
                return (), kinds[:p], len(kinds) // p
        return (), (), 0

    def cache_spec(self):
        """What a serving engine allocates for this model: K/V rows of
        ``n_kv_heads * head_dim`` lanes for the full layers only, and
        two slot-indexed states of the linear layers — ``conv``, the
        convolution's last ``conv_kernel - 1`` inputs in the compute
        dtype, and ``delta``, ``S`` of every value head in float32."""
        from torchbooster_tpu.serving.kv_pages import CacheSpec

        n_lin = self.layer_types.count(LINEAR)
        states = {
            "conv": ((n_lin, self.conv_kernel - 1, self.conv_dim), None),
            "delta": ((n_lin, self.v_heads, self.k_head_dim,
                       self.v_head_dim), jnp.float32)}
        return CacheSpec(
            kv_layers=self.layer_types.count(FULL),
            kv_heads=self.n_kv_heads, head_dim=self.head_dim,
            slot_states=states if n_lin else {})


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------

def _layer_init(rng: jax.Array, cfg: Qwen3NextConfig, kind: str,
                dtype: Any) -> dict:
    """One layer: matrices N(0, 0.02), the zero-centred norms' ``w``
    0, the gated norm's gain 1, conv taps N(0, 0.5), ``A_log`` and
    ``dt_bias`` so that a token's decay ``e^g`` lies about 0.9–0.999."""
    ks = iter(jax.random.split(rng, 16))
    d, std = cfg.d_model, 0.02
    mat = lambda shape, s=std: {
        "kernel": s * jax.random.normal(next(ks), shape, dtype)}
    zero = lambda n: {"scale": jnp.zeros((n,), dtype)}
    e, w, sw = cfg.experts_held[1], cfg.expert_width, cfg.shared_width
    lp = {"in_norm": zero(d), "post_norm": zero(d),
          "moe_gate": mat((d, cfg.n_experts)),
          "moe_fc1": mat((e, d, w)), "moe_fc3": mat((e, d, w)),
          "moe_fc2": mat((e, w, d)),
          "shared_fc1": mat((d, sw)), "shared_fc3": mat((d, sw)),
          "shared_fc2": mat((sw, d)), "shared_gate": mat((d, 1))}
    if kind == LINEAR:
        hv = cfg.v_heads
        lp.update(
            attn_qkvz=mat((d, cfg.conv_dim + cfg.value_dim)),
            attn_ba=mat((d, 2 * hv)),
            conv=mat((cfg.conv_kernel, cfg.conv_dim), 0.5),
            a_log=jnp.log(jnp.linspace(0.1, 1.0, hv)).astype(jnp.float32),
            dt_bias=jnp.full((hv,), -4.0, jnp.float32),
            out_norm={"scale": jnp.ones((cfg.v_head_dim,), dtype)},
            attn_out=mat((cfg.value_dim, d)))
    else:
        hd = cfg.head_dim
        lp.update(
            # [q | k | v | g]: one product over the normed input
            attn_qkvg=mat((d, (2 * cfg.n_heads + 2 * cfg.kv_heads) * hd)),
            q_norm=zero(hd), k_norm=zero(hd),
            attn_out=mat((cfg.n_heads * hd, d)))
    return lp


# ---------------------------------------------------------------------
# the delta rule
# ---------------------------------------------------------------------

def delta_step(state: jax.Array, q, k, v, g, beta):
    """ONE token of the recurrence for every sequence and value head:
    ``state (B, H, d_k, d_v)``, ``q``, ``k (B, H, d_k)``, ``v (B, H,
    d_v)``, ``g``, ``beta (B, H)``, all float32. Both products with the
    state (``S^T k`` and ``S^T q``) read it as it came, and ``o = e^g
    S^T q + delta (k . q)`` is ``S_new^T q``: the new state is written
    and never read back. Returns ``(o (B, H, d_v), state)``."""
    decay = jnp.exp(g)[..., None]
    kv = jnp.sum(state * k[..., :, None], axis=-2)
    sq = jnp.sum(state * q[..., :, None], axis=-2)
    delta = beta[..., None] * (v - decay * kv)
    state = decay[..., None] * state + k[..., :, None] * delta[..., None, :]
    o = decay * sq + delta * jnp.sum(k * q, axis=-1, keepdims=True)
    return o, state


def _nilpotent_inverse(a: jax.Array) -> jax.Array:
    """``(I + A)^-1`` of a strictly lower-triangular ``A (..., T, T)``
    as ``(I - A)(I + A^2)(I + A^4)...`` — ``log2 T`` squarings, each a
    matrix product, where forward substitution would take ``T``
    dependent steps."""
    t = a.shape[-1]
    mm = partial(jnp.matmul, precision=HIGHEST)
    power = -a
    out = jnp.eye(t, dtype=a.dtype) + power
    for _ in range(max(t - 1, 1).bit_length() - 1):
        power = mm(power, power)
        out = out + mm(out, power)
    return out


def delta_chunked(state: jax.Array, q, k, v, g, beta,
                  block: int = DELTA_BLOCK):
    """The recurrence over a sequence in blocks of ``block`` tokens
    (the WY form): ``q``, ``k (B, L, H, d_k)``, ``v (B, L, H, d_v)``,
    ``g``, ``beta (B, L, H)`` float32, ``state (B, H, d_k, d_v)``. In
    a block, with ``G`` the cumulative log-decay and ``D_ij = e^(G_i -
    G_j)`` for ``i >= j``: ``T = (I + tril_-1((beta k) k^T * D))^-1``,
    ``U = T (beta v)``, ``W = T (beta k e^G)``; then ``v' = U - W S``,
    ``o = (q e^G) S + tril(q k^T * D) v'``, ``S <- e^(G_last) S + (k
    e^(G_last - G))^T v'``. The blocks run in order (a ``lax.scan``),
    everything inside one is matrix products. A token with ``beta`` 0
    and ``g`` 0 (a pad) leaves the state as it was. Returns ``(o (B, L,
    H, d_v), state)``."""
    b, n, h, dk = k.shape
    pad = -n % block
    if pad:
        widen = lambda t: jnp.pad(
            t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    nb = (n + pad) // block
    # (blocks, B, H, block, ...)
    cut = lambda t: jnp.moveaxis(
        t.reshape(b, nb, block, h, *t.shape[3:]), (1, 3), (0, 2))
    q, k, v, g, beta = map(cut, (q, k, v, g, beta))
    mm = partial(jnp.einsum, precision=HIGHEST)
    lower = jnp.tril(jnp.ones((block, block), bool))
    strict = jnp.tril(jnp.ones((block, block), bool), -1)

    def one(s, blk):
        qb, kb, vb, gb, bb = blk
        cum = jnp.cumsum(gb, axis=-1)                       # (B, H, T)
        diff = cum[..., :, None] - cum[..., None, :]
        decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
        kbeta = kb * bb[..., None]
        a = jnp.where(strict, mm("bhid,bhjd->bhij", kbeta, kb) * decay, 0)
        t = _nilpotent_inverse(a)
        u = mm("bhij,bhjd->bhid", t, vb * bb[..., None])
        w = mm("bhij,bhjd->bhid", t, kbeta * jnp.exp(cum)[..., None])
        v_new = u - mm("bhik,bhkv->bhiv", w, s)
        inner = jnp.where(lower, mm("bhid,bhjd->bhij", qb, kb) * decay, 0)
        o = mm("bhik,bhkv->bhiv", qb * jnp.exp(cum)[..., None], s) \
            + mm("bhij,bhjv->bhiv", inner, v_new)
        last = cum[..., -1:]
        s = s * jnp.exp(last)[..., None] + mm(
            "bhik,bhiv->bhkv", kb * jnp.exp(last - cum)[..., None], v_new)
        return s, o

    state, o = jax.lax.scan(one, state, (q, k, v, g, beta))
    # (blocks, B, H, block, d_v) -> (B, L, H, d_v)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, nb * block, h, -1)
    return o[:, :n], state


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------

EXPERT_KERNELS = ("moe_fc1", "moe_fc3", "moe_fc2")


def _norm0(w: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    """The family's zero-centred RMSNorm: the gain is ``1 + w``, taken
    in float32 (``w`` stored as it is, not ``1 + w`` rounded)."""
    return L.rms_norm_f32(1.0 + w.astype(jnp.float32), x, eps)


def _l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _conv_step(lp: dict, cfg: Qwen3NextConfig, xs, rows, valid):
    """The linear layer's convolution over a batch of sequences (a
    ``step`` handed to ``carry``): ``xs = ([q | k | v] before it (B, S,
    conv_dim),)``, ``rows (B, K-1, conv_dim)`` the inputs before ``xs``,
    ``valid (B, S)`` the real tokens (a prompt's, before any pad).
    Returns ``(silu(conv) (B, S, conv_dim) float32, the last K-1 inputs
    before the first token that is not real)``."""
    c, zz = L.short_conv(xs[0], lp["conv"]["kernel"], rows)
    n_real = jnp.sum(valid, axis=1, dtype=jnp.int32)
    keep = n_real[:, None] + jnp.arange(cfg.conv_kernel - 1)
    return jax.nn.silu(c.astype(jnp.float32)), \
        jnp.take_along_axis(zz, keep[:, :, None], axis=1)


def _delta_step(xs, state, valid):
    """The delta rule over a batch of sequences (a ``step`` handed to
    ``carry``): ``xs = (q, k (B, S, H, d_k), v (B, S, H, d_v), g, beta
    (B, S, H))`` float32, ``state (B, H, d_k, d_v)`` as it stood before
    ``xs``, ``valid (B, S)``: a token that is not real neither decays
    nor writes the state. The chunked form (a prefill; the decode lanes
    take :func:`_delta_in_place`). Returns ``(o (B, S, H, d_v),
    state)``."""
    q, k, v, g, beta = xs
    live = valid.astype(jnp.float32)[..., None]
    return delta_chunked(state.astype(jnp.float32), q, k, v, g * live,
                         beta * live)


def _delta_in_place(xs, state, li, live):
    """The decode lanes' delta rule on the stacked state itself (the
    ``in_place`` handed to ``carry``): ``xs`` as :func:`_delta_step`'s,
    one token a slot; ``state (layers, slots, H, d_k, d_v)``; only the
    ``live (slots,)`` slots' ``S`` of layer ``li`` is read and written
    (``ops/delta_lanes.py``). Returns ``(o (B, 1, H, d_v), state)``."""
    q, k, v, g, beta = (t[:, 0] for t in xs)
    o, state = delta_lanes(state, li, q, k, v, g, beta, live)
    return o[:, None], state


def _gdn(lp: dict, x: jax.Array, cfg: Qwen3NextConfig, *, carry: Callable,
         state, li):
    """A linear-attention mixer over ``x (B, S, d)``, residual added.
    Its two states go through ``carry``: the convolution's rows under
    ``attn_core/qkv_conv``, ``S`` under ``attn_core/delta_chunk`` (a
    chunk's tokens) and ``attn_core/delta_lanes`` (the decode lanes)."""
    b, s, _ = x.shape
    kdim, hk, hv = cfg.key_dim, cfg.k_heads, cfg.v_heads
    with jax.named_scope("attn_qkv"):
        u = _norm0(lp["in_norm"]["scale"], x, cfg.norm_eps)
        qkvz = L.dense(lp["attn_qkvz"], u)
        ba = L.dense(lp["attn_ba"], u)
        mixed, z = qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:]
        b_in, a_in = ba[..., :hv], ba[..., hv:]
    with jax.named_scope("attn_core"):
        mixed, state = carry(partial(_conv_step, lp, cfg), (mixed,), state,
                             li, name="conv", scopes=("qkv_conv",) * 2)
        q = mixed[..., :kdim].reshape(b, s, hk, -1)
        k = mixed[..., kdim:2 * kdim].reshape(b, s, hk, -1)
        v = mixed[..., 2 * kdim:].reshape(b, s, hv, -1)
        # key head i serves value heads r*i .. r*i + r - 1
        q = jnp.repeat(_l2norm(q) * cfg.k_head_dim ** -0.5, hv // hk, 2)
        k = jnp.repeat(_l2norm(k), hv // hk, axis=2)
        beta = jax.nn.sigmoid(b_in.astype(jnp.float32))
        g = -jnp.exp(lp["a_log"].astype(jnp.float32)) * jax.nn.softplus(
            a_in.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
        o, state = carry(_delta_step, (q, k, v, g, beta), state, li,
                         name="delta", scopes=("delta_chunk", "delta_lanes"),
                         in_place=_delta_in_place)
    with jax.named_scope("attn_out"):
        # RMSNorm(o) * w * silu(z), per value head
        o = L.rms_norm_f32(lp["out_norm"]["scale"], o, cfg.norm_eps)
        o = o * jax.nn.silu(z.astype(jnp.float32).reshape(o.shape))
        x = x + L.dense(lp["attn_out"],
                        o.reshape(b, s, -1).astype(x.dtype))
    return x, state


def _partial_rope(x: jax.Array, positions, cfg: Qwen3NextConfig):
    """RoPE (rotate-half) on the first ``rotary_dim`` lanes of a head;
    the rest pass."""
    r = cfg.rotary_dim
    return jnp.concatenate(
        [_rope(x[..., :r], positions, cfg.rope_base), x[..., r:]], -1)


def _full(lp: dict, x: jax.Array, cfg: Qwen3NextConfig, *, positions,
          attend: Callable, cache, li):
    """A gated full-attention mixer over ``x (B, S, d)``, residual
    added. Its attention sits under ``attn_core/attn_full``, the name
    the window-and-full family's full layer has."""
    b, s, _ = x.shape
    hd, n_q, n_kv = cfg.head_dim, cfg.n_heads, cfg.kv_heads
    norm = lambda g, t: _norm0(g["scale"], t, cfg.norm_eps)
    with jax.named_scope("attn_qkv"):
        qkvg = L.dense(lp["attn_qkvg"], norm(lp["in_norm"], x))
        cut = (n_q * hd, (n_q + n_kv) * hd, (n_q + 2 * n_kv) * hd)
        q = qkvg[..., :cut[0]].reshape(b, s, n_q, hd)
        k = qkvg[..., cut[0]:cut[1]].reshape(b, s, n_kv, hd)
        v = qkvg[..., cut[1]:cut[2]].reshape(b, s, n_kv, hd)
        gate = qkvg[..., cut[2]:]
        q = _partial_rope(norm(lp["q_norm"], q), positions, cfg)
        k = _partial_rope(norm(lp["k_norm"], k), positions, cfg)
    with jax.named_scope("attn_core"), jax.named_scope("attn_full"):
        o, cache = attend(q, k, v, cache, li)
    with jax.named_scope("attn_out"):
        with jax.named_scope("attn_gate"):
            o = (o.reshape(b, s, n_q * hd).astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))
                 ).astype(x.dtype)
        x = x + L.dense(lp["attn_out"], o)
    return x, cache


def _moe(lp: dict, x: jax.Array, cfg: Qwen3NextConfig, *, valid,
         first_group):
    """The expert layer, residual added: routed experts (softmax over
    all ``n_experts``, top-k renormalised) plus the shared expert
    scaled by its sigmoid gate. Returns ``(x, (tokens per held expert,
    pairs elsewhere))``."""
    with jax.named_scope("mlp"):
        # the router and the shared gate read the float32 norm, the
        # experts its rounding to the compute dtype
        u32 = _norm0(lp["post_norm"]["scale"], x.astype(jnp.float32),
                     cfg.norm_eps)
        u = u32.astype(x.dtype)
        m, held, elsewhere = moe_dropless(
            lp, u, cfg.top_k, 1.0, valid=valid, first_group=first_group,
            route_on=u32, held=cfg.experts_held, route_eps=0.0,
            scoring="softmax")
        with jax.named_scope("moe_shared"):
            gate = jax.nn.sigmoid(jnp.dot(
                u32, lp["shared_gate"]["kernel"].astype(jnp.float32),
                precision=HIGHEST))
            m = m + (swiglu(lp, u, "shared").astype(jnp.float32)
                     * gate).astype(x.dtype)
        return x + m, (held, elsewhere)


def layers(params: dict, x: jax.Array, cfg: Qwen3NextConfig, *,
           positions, attend: Callable, carry: Callable, cache=None,
           state=None, valid=None):
    """The whole layer stack over ``x (B, S, d)``: a scan over
    periods. ``cache`` and ``state`` are the caller's (any pytree or
    None), carried through and handed to ``attend`` / ``carry`` with
    the layer's index AMONG ITS KIND (``li``: the row of the K/V pool,
    or of the slot states). ``valid (B, S)``: which tokens are real.
    Returns ``(x, cache, state, {"held": tokens per held expert
    (n_layers, n) int32, "elsewhere": pairs routed to experts not held
    (n_layers,) int32})``."""
    _, period, n_periods = cfg.plan
    n_held = cfg.experts_held[1]
    valid = jnp.ones(x.shape[:2], bool) if valid is None else valid
    per_period = {k: period.count(k) for k in (LINEAR, FULL)}
    experts = [{k: {"kernel": lp[k]["kernel"].reshape(
                   -1, *lp[k]["kernel"].shape[2:])}
                for k in EXPERT_KERNELS} for lp in params["periods"]]
    scanned = [{k: v for k, v in lp.items() if k not in EXPERT_KERNELS}
               for lp in params["periods"]]

    def body(carried, inputs):
        x, cache, state = carried
        subs, i = inputs
        at = {LINEAR: 0, FULL: 0}
        cnts = []
        for kind, lp, ex in zip(period, subs, experts):
            li = at[kind] + i * per_period[kind]
            at[kind] += 1
            if kind == LINEAR:
                x, state = _gdn(lp, x, cfg, carry=carry, state=state,
                                li=li)
            else:
                x, cache = _full(lp, x, cfg, positions=positions,
                                 attend=attend, cache=cache, li=li)
            x, cnt = _moe({**lp, **ex}, x, cfg, valid=valid,
                          first_group=i * n_held)
            cnts.append(cnt)
        return (x, cache, state), (jnp.stack([c[0] for c in cnts]),
                                   jnp.stack([c[1] for c in cnts]))

    (x, cache, state), (held, elsewhere) = jax.lax.scan(
        body, (x, cache, state), (scanned, jnp.arange(n_periods)))
    return x, cache, state, {"held": held.reshape(-1, n_held),
                             "elsewhere": elsewhere.reshape(-1)}


@jax.named_scope("embed")
def embed(params: dict, ids: jax.Array, dtype: Any = None) -> jax.Array:
    return L.embedding(params["wte"], ids, dtype=dtype)


@jax.named_scope("head")
def head(params: dict, x: jax.Array, cfg: Qwen3NextConfig) -> jax.Array:
    """Final zero-centred RMSNorm and the untied head; logits in
    float32."""
    x = _norm0(params["norm_f"]["scale"], x, cfg.norm_eps)
    return jnp.dot(x, params["head"]["kernel"].astype(x.dtype),
                   preferred_element_type=jnp.float32)


def zero_rows(cfg: Qwen3NextConfig, batch: int, dtype: Any) -> dict:
    """A fresh sequence's slot-state rows, one a sequence."""
    return {"conv": jnp.zeros((batch, cfg.conv_kernel - 1, cfg.conv_dim),
                              dtype),
            "delta": jnp.zeros((batch, cfg.v_heads, cfg.k_head_dim,
                                cfg.v_head_dim), jnp.float32)}


class Qwen3Next:
    """Namespace: ``init`` / ``apply``, as the zoo's other models."""

    @staticmethod
    def init(rng: jax.Array, cfg: Qwen3NextConfig = Qwen3NextConfig(),
             dtype: Any = jnp.float32) -> dict:
        _, period, n_periods = cfg.plan
        k_emb, k_head, k_per = jax.random.split(rng, 3)
        keys = jax.random.split(k_per, n_periods * len(period)).reshape(
            n_periods, len(period), -1)
        return {
            "wte": L.embedding_init(k_emb, cfg.vocab, cfg.d_model,
                                    dtype=dtype),
            "head": {"kernel": 0.02 * jax.random.normal(
                k_head, (cfg.d_model, cfg.vocab), dtype)},
            "periods": [_stack([_layer_init(keys[p, j], cfg, kind, dtype)
                                for p in range(n_periods)])
                        for j, kind in enumerate(period)],
            "norm_f": {"scale": jnp.zeros((cfg.d_model,), dtype)},
        }

    @staticmethod
    def apply(params: dict, ids: jax.Array, cfg: Qwen3NextConfig,
              compute_dtype: Any = None, return_counts: bool = False):
        """Full-sequence forward: ``ids (B, S)`` -> logits ``(B, S,
        vocab)`` float32 (and the routed pairs' counts with
        ``return_counts``). Plain causal attention and every linear
        layer's state starting from zeros: what a training step runs,
        and what the served path (a paged pool, slot states carried
        across chunks and steps) must equal."""
        x = embed(params, ids, dtype=compute_dtype)

        def attend(q, k, v, cache, li):
            return mha_reference(q, k, v, causal=True), cache

        def carry(step, xs, state, li, name, scopes, in_place=None):
            with jax.named_scope(scopes[0]):
                rows = zero_rows(cfg, ids.shape[0], x.dtype)[name]
                return step(xs, rows, jnp.ones(ids.shape, bool))[0], state

        x, _, _, counts = layers(
            params, x, cfg, positions=jnp.arange(ids.shape[1]),
            attend=attend, carry=carry)
        logits = head(params, x, cfg)
        return (logits, counts) if return_counts else logits


__all__ = ["DELTA_BLOCK", "FULL", "LAYER_TYPES", "LINEAR", "Qwen3Next",
           "Qwen3NextConfig", "UNSERVED", "delta_chunked", "delta_step",
           "embed", "head", "layers", "zero_rows"]
