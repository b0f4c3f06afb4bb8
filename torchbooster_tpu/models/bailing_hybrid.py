"""Bailing hybrid: Kimi-delta-attention (KDA) linear-attention layers
beside head-gated latent-attention (MLA) layers, a shared expert and
sigmoid-routed experts selected by group (``Ling-3.0-flash``,
``model_type: bailing_hybrid``, is the published model the benchmark
runs through it).

The sixth language-model family of the zoo, and the first whose
linear layers decay each KEY CHANNEL on its own and whose model keeps
a latent page pool and slot states side by side. Every layer is
pre-norm with plain RMSNorms: ``h = x + Mixer(RMSNorm(x))``, ``y = h +
FF(RMSNorm(h))``; logits ``= RMSNorm(y_L) @ W_head`` (untied head). A
layer ``i`` is MLA where ``(i + 1) % layer_group_size == 0``, KDA
elsewhere (``layer_types``):

- ``kda`` — Kimi Delta Attention (arXiv:2510.26692). ``[q | k | v | f
  | o_g] = u @ W_qkvfg``, ``b = u @ W_b``; ``[q | k | v]`` passes a
  causal depthwise convolution of ``conv_kernel`` taps and SiLU; ``q``
  and ``k`` L2-normalised per head (``q`` then scaled by
  ``d_k^-1/2``); ``beta = sigmoid(b)`` a head; the gate is a VECTOR a
  head, bounded: ``g = gate_lower_bound * sigmoid(exp(A_log_h) * (f +
  dt_bias)) in (lower_bound, 0)^{d_k}``. Per head and token, with a
  state ``S (d_k, d_v)`` in float32::

      S <- Diag(e^g) S;   S <- S + beta k (v - S^T k)^T;   o = S^T q

  then ``RMSNorm(o) * w * sigmoid(o_g)`` per head and ``W_out``. A
  prefill chunk runs the recurrence in its CHUNKED form
  (:func:`kda_chunked`: blocks of ``KDA_BLOCK`` tokens by matrix
  products, their pairwise decays taken in sub-blocks of ``KDA_SUB``),
  a decode step token by token (:func:`kda_step`, which the decode
  lanes' kernel ``ops/delta_lanes.kda_lanes`` computes on the live
  slots' rows in place).
- ``mla`` — ``models/mla_moe.attention`` (one ``[c | k_r]`` row a
  token, absorbed on the serving path) with plain RoPE frequencies and
  a head-wise output gate (``attn_gate``: ``o_h * sigmoid(u @
  W_gate)_h`` before ``W_o``). The published rotary lanes are
  INTERLEAVED pairs; here they are rotate-half over lanes permuted
  once when the weights are built (:func:`rope_permutation`: the
  attention's products are the same, lane for lane permuted).

**Feed-forward.** A dense SwiGLU in the first ``n_dense_layers``
layers; after them a shared SwiGLU added unweighted plus the dropless
routed experts of ``models/moe.py`` with SIGMOID scores selected by
group (``n_group`` groups, the ``topk_group`` best kept, ``top_k``
chosen among them by ``s + b``), weighted ``routed_scaling * s[sel] /
sum(s[sel])``. ``experts_held = (first, n)`` is one expert-parallel
rank's share.

**Layout.** As ``models/qwen3_next.py``: the leading dense layers
unrolled (``params["lead"]``), then a ``lax.scan`` over PERIODS of the
remaining pattern, the kinds static in the body; the routed experts of
all periods in one stack read in place by ``first_group``.

**One layer stack, two callers.** :func:`layers` leaves to its caller
``attend(q, k, v, cache, li) -> (o, cache)`` (MLA layer ``li``'s
rows) and ``carry(step, xs, state, li, name=, scopes=, in_place=)``
(KDA layer ``li``'s slot state: ``conv``, the convolution's last
``conv_kernel - 1`` inputs, and ``kda``, ``S``), as
``models/qwen3_next.py`` describes. ``BailingHybrid.apply`` hands in
plain causal attention in the expanded form and zero states; the
serving engine hands in the latent pool and the slot-indexed states
(serving/engine.py). :func:`BailingHybridConfig.cache_spec` tells it
what to allocate.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from torchbooster_tpu.models import layers as L
from torchbooster_tpu.models import mla_moe as _mla_moe
from torchbooster_tpu.models import qwen3_next as _qwen3_next
from torchbooster_tpu.models.lfm2 import _stack
from torchbooster_tpu.models.mla_moe import swiglu
from torchbooster_tpu.models.moe import moe_dropless
from torchbooster_tpu.ops.attention import mha_reference
from torchbooster_tpu.ops.delta_lanes import kda_lanes

KDA, MLA = "kda", "mla"
# What the paged engine does not serve for this family, and why
# (serving/engine.py raises these at build): a KDA layer's state is
# one matrix a slot of its whole past, the MLA layer's cache a latent
# row with no V half, and the rest is GPT-shaped code.
UNSERVED = {
    **_qwen3_next.UNSERVED,
    "cache_dtype: int8": _mla_moe.UNSERVED["cache_dtype: int8"],
    "decode_backend: pallas": _mla_moe.UNSERVED["decode_backend: pallas"],
    "tp": _mla_moe.UNSERVED["tp"],
    "swiglu_limit": "the clamped SwiGLU of the published layers 34-41 "
                    "(expert_swiglu_limit_list, "
                    "share_expert_swiglu_limit_list) is not written",
}
# tokens of one block of the chunked rule (its sequential depth is a
# prompt's length over this), and of one sub-block: a pairwise decay
# e^(G_i - G_j) is taken through the middle of the sub-block, and with
# g >= -5 a sub-block of 16 keeps each factor within e^(+-40) (float32
# ends at e^88), where a block of 64 would span e^320
KDA_BLOCK, KDA_SUB = 64, 16
HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class BailingHybridConfig:
    vocab: int = 157_184
    d_model: int = 2560
    n_heads: int = 32               # KDA and MLA heads alike
    head_dim: int = 128             # a KDA head's d_k = d_v
    conv_kernel: int = 4
    gate_lower_bound: float = -5.0  # kda_lower_bound (the safe gate)
    nope_dim: int = 128
    rope_dim: int = 64
    latent_dim: int = 512           # kv_lora_rank
    v_dim: int = 128
    dense_width: int = 6144
    expert_width: int = 768
    shared_width: int = 768
    n_experts: int = 512            # the ROUTER's width
    experts_held: tuple[int, int] = (0, 512)    # (first, n) held here
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling: float = 2.5
    layer_types: tuple[str, ...] = ((KDA,) * 5 + (MLA,)) * 7
    n_dense_layers: int = 2
    rope_base: float = 6_000_000.0
    norm_eps: float = 1e-6
    # the largest SwiGLU clamp of the layers held (0: none): refused
    swiglu_limit: float = 0.0
    seq_len: int = 262_144

    def __post_init__(self):
        unknown = set(self.layer_types) - {KDA, MLA}
        if unknown:
            raise ValueError(f"layer_types: unknown kinds {sorted(unknown)}")
        if self.swiglu_limit:
            raise NotImplementedError(
                f"a swiglu_limit of {self.swiglu_limit} is not served: "
                f"{UNSERVED['swiglu_limit']}")
        first, n = self.experts_held
        if not (0 <= first and n >= 1 and first + n <= self.n_experts):
            raise ValueError(
                f"experts_held {self.experts_held} is no slice of the "
                f"router's {self.n_experts} experts")
        if self.n_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError("n_group must divide n_experts and "
                             "topk_group lie in [1, n_group]")
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("n_dense_layers leaves no expert layer")

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kv_heads(self) -> int:
        return 1

    n_kv_heads = kv_heads

    @property
    def key_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: ``[q | k | v]``."""
        return 3 * self.key_dim

    @property
    def row_dim(self) -> int:
        """What one token leaves in an MLA layer's cache."""
        return self.latent_dim + self.rope_dim

    @property
    def mla(self) -> _mla_moe.MLAMoEConfig:
        """The MLA layer as ``models/mla_moe.attention`` reads it:
        plain RoPE frequencies (no YaRN), so the softmax scale is
        ``(nope_dim + rope_dim)^-1/2``."""
        return _mla_moe.MLAMoEConfig(
            vocab=self.vocab, d_model=self.d_model, n_heads=self.n_heads,
            nope_dim=self.nope_dim, rope_dim=self.rope_dim,
            latent_dim=self.latent_dim, v_dim=self.v_dim,
            n_experts=self.n_experts, experts_held=self.experts_held,
            rope_base=self.rope_base, yarn_factor=1.0,
            norm_eps=self.norm_eps, seq_len=self.seq_len)

    @property
    def plan(self) -> tuple[tuple[str, ...], tuple[str, ...], int]:
        """``(lead, period, n_periods)``: the mixer kinds of the leading
        dense layers, of one period of the rest, and how often it
        repeats (an irregular rest is one period)."""
        lead = self.layer_types[:self.n_dense_layers]
        rest = self.layer_types[self.n_dense_layers:]
        for p in range(1, len(rest) + 1):
            if len(rest) % p == 0 and rest == rest[:p] * (len(rest) // p):
                return lead, rest[:p], len(rest) // p
        return lead, (), 0

    def cache_spec(self):
        """What a serving engine allocates for this model: a LATENT
        pool for the MLA layers (one ``row_dim``-lane row a token whose
        leading ``latent_dim`` lanes are the values) and two
        slot-indexed states of the KDA layers — ``conv``, the
        convolution's last ``conv_kernel - 1`` inputs in the compute
        dtype, kept as ONE row a slot (a trailing axis of 3 beside the
        slots' made the compiler relayout the whole state twice a
        layer in the mixed program), and ``kda``, ``S`` of every head
        in float32."""
        from torchbooster_tpu.serving.kv_pages import CacheSpec

        n_kda = self.layer_types.count(KDA)
        states = {
            "conv": ((n_kda, (self.conv_kernel - 1) * self.conv_dim), None),
            "kda": ((n_kda, self.n_heads, self.head_dim, self.head_dim),
                    jnp.float32)}
        return CacheSpec(kv_layers=self.layer_types.count(MLA), kv_heads=1,
                         head_dim=self.row_dim, value_dim=self.latent_dim,
                         slot_states=states if n_kda else {})


def rope_permutation(rope_dim: int) -> np.ndarray:
    """The lanes of an INTERLEAVED rotary block (pairs ``(2i, 2i+1)``
    turned by ``theta^(-2i/r)``) in rotate-half order (pairs ``(i, i +
    r/2)``, the same frequencies): ``x[..., perm]`` rotated by
    rotate-half is the interleaved rotation of ``x``, permuted alike.
    The weights that make the rotary lanes (the query's and ``k_r``'s
    columns, the query norm's gain) are permuted once with it."""
    return np.concatenate([np.arange(0, rope_dim, 2),
                           np.arange(1, rope_dim, 2)])


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------

def _layer_init(rng: jax.Array, cfg: BailingHybridConfig, kind: str,
                moe: bool, dtype: Any) -> dict:
    """One layer: matrices N(0, 0.02), gains 1, ``A_log`` 0 and
    ``dt_bias`` spread over ``[-8.5, -4.5]`` (decays of half-lives
    about 10 to 1,000 tokens), selection bias 0."""
    ks = iter(jax.random.split(rng, 16))
    d, h, std = cfg.d_model, cfg.n_heads, 0.02
    mat = lambda shape: {
        "kernel": std * jax.random.normal(next(ks), shape, dtype)}
    gain = lambda n: {"scale": jnp.ones((n,), dtype)}
    lp = {"in_norm": gain(d), "post_norm": gain(d)}
    if kind == KDA:
        kd = cfg.key_dim
        lp.update(
            attn_qkvfg=mat((d, 5 * kd)), attn_b=mat((d, h)),
            conv=mat((cfg.conv_kernel, cfg.conv_dim)),
            a_log=jnp.zeros((h,), jnp.float32),
            dt_bias=jnp.linspace(-8.5, -4.5, kd, dtype=jnp.float32),
            out_norm=gain(cfg.head_dim), attn_out=mat((kd, d)))
    else:
        lp.update(
            attn_q=mat((h * (cfg.nope_dim + cfg.rope_dim), d)),
            q_norm=gain(cfg.nope_dim + cfg.rope_dim),
            attn_dkv=mat((d, cfg.row_dim)), kv_norm=gain(cfg.latent_dim),
            attn_ukv=mat((h * (cfg.nope_dim + cfg.v_dim), cfg.latent_dim)),
            attn_gate=mat((d, h)), attn_out=mat((h * cfg.v_dim, d)))
    if moe:
        e, w, sw = cfg.experts_held[1], cfg.expert_width, cfg.shared_width
        lp.update(moe_gate=mat((d, cfg.n_experts)),
                  moe_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
                  moe_fc1=mat((e, d, w)), moe_fc3=mat((e, d, w)),
                  moe_fc2=mat((e, w, d)), shared_fc1=mat((d, sw)),
                  shared_fc3=mat((d, sw)), shared_fc2=mat((sw, d)))
    else:
        w = cfg.dense_width
        lp.update(mlp_fc1=mat((d, w)), mlp_fc3=mat((d, w)),
                  mlp_fc2=mat((w, d)))
    return lp


# ---------------------------------------------------------------------
# the per-channel delta rule
# ---------------------------------------------------------------------

def kda_step(state: jax.Array, q, k, v, g, beta):
    """ONE token of the recurrence for every sequence and head:
    ``state (B, H, d_k, d_v)``, ``q``, ``k``, ``g (B, H, d_k)``, ``v
    (B, H, d_v)``, ``beta (B, H)``, all float32. ``(Diag(a) S)^T x =
    S^T (a x)`` with ``a = e^g``, so both products read the state as it
    came, and ``o = S^T (a q) + delta (k . q)`` is the new state's
    ``S_new^T q``. Returns ``(o (B, H, d_v), state)``."""
    a = jnp.exp(g)
    kv = jnp.sum(state * (a * k)[..., :, None], axis=-2)
    sq = jnp.sum(state * (a * q)[..., :, None], axis=-2)
    delta = beta[..., None] * (v - kv)
    state = a[..., None] * state + k[..., :, None] * delta[..., None, :]
    return sq + delta * jnp.sum(k * q, axis=-1, keepdims=True), state


def kda_chunked(state: jax.Array, q, k, v, g, beta,
                block: int = KDA_BLOCK, sub: int = KDA_SUB):
    """The recurrence over a sequence in blocks of ``block`` tokens:
    ``q``, ``k``, ``g (B, L, H, d_k)``, ``v (B, L, H, d_v)``, ``beta
    (B, L, H)`` float32, ``state (B, H, d_k, d_v)``. In a block, with
    ``G`` the cumulative log-gate VECTOR: ``T = (I + tril_-1(beta
    (k_i . e^(G_i - G_j) k_j)))^-1``, ``U = T (beta v)``, ``W = T (beta
    k e^G)`` (both by a triangular solve); then ``v' = U - W S``, ``o = (q e^G) S + tril(q_i .
    e^(G_i - G_j) k_j) v'``, ``S <- e^(G_last) S + (k e^(G_last -
    G))^T v'`` — ``qwen3_next.delta_chunked``'s WY form with a decay a
    key channel. The pairwise decays are taken row sub-block by row
    sub-block: ``e^(G_i - G_j) = e^(G_i - R_I) e^(R_I - G_j)`` with
    ``R_I`` the cumulative gate after the first half of sub-block
    ``I``; inside ``I`` each factor lies within ``e^(+-sub/2 * min
    g)`` (``g >= -5``: ``e^(+-40)`` for sub-blocks of 16, where a block
    of 64 would span ``e^320`` and float32 ends at ``e^88``), and a
    column before ``I`` has a factor at most 1; columns past ``I`` are
    zeroed before the exponential. Every exponent is summed over the
    tokens between its two ends, never taken as the difference of two
    long cumulative sums (at ``g = -5`` those reach -320, where float32
    keeps 3e-5 of them). The blocks run in order (a ``lax.scan``); a
    token with ``beta`` 0 and ``g`` 0 (a pad) leaves the state as it
    was. Returns ``(o (B, L, H, d_v), state)``."""
    b, n, h, dk = k.shape
    pad = -n % block
    if pad:
        widen = lambda t: jnp.pad(
            t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
    nb, ns = (n + pad) // block, block // sub
    # (blocks, B, H, block, ...)
    cut = lambda t: jnp.moveaxis(
        t.reshape(b, nb, block, h, *t.shape[3:]), (1, 3), (0, 2))
    q, k, v, g, beta = map(cut, (q, k, v, g, beta))
    mm = partial(jnp.einsum, precision=HIGHEST)
    lower = jnp.tril(jnp.ones((block, block), bool))
    strict = jnp.tril(jnp.ones((block, block), bool), -1)
    sb = jnp.arange(ns)
    # sub-block K lies strictly between J and I: [I, J, K]
    between_of = (sb[None, :, None] < sb[None, None, :]) \
        & (sb[None, None, :] < sb[:, None, None])
    # exclusive sums after each position, along ``axis``
    after = lambda t, axis: jnp.flip(jnp.cumsum(jnp.flip(t, axis), axis),
                                     axis) - t

    def one(s, blk):
        qb, kb, vb, gb, bb = blk                # (B, H, T, ...)
        lead = gb.shape[:2]
        by_sub = lambda t: t.reshape(*lead, ns, sub, dk)
        # through the middle of a sub-block its factors stay far from
        # float32's smallest normal, e^-87
        g4, half = by_sub(gb), sub // 2
        to_ref = jnp.concatenate(                  # G_i - R_I in I
            [-after(g4[..., :half, :], -2),
             jnp.cumsum(g4[..., half:, :], axis=-2)], axis=-2)
        ahead = jnp.sum(g4[..., :half, :], axis=-2)   # R_I - E_I
        rest = after(g4, -2)                      # E_(J+1) - G_j in J
        whole = jnp.sum(g4, axis=-2)              # (B, H, ns, d_k)
        between = jnp.sum(jnp.where(between_of[..., None],
                                    whole[:, :, None, None], 0), axis=-2)
        # R_I - G_j for row sub-block I and column j of sub-block J <= I
        span = jnp.where(
            (sb[:, None] == sb[None, :])[..., None, None],
            -to_ref[..., None, :, :, :],
            jnp.where((sb[None, :] < sb[:, None])[..., None, None],
                      rest[..., None, :, :, :] + between[..., None, :]
                      + ahead[..., :, None, None, :],
                      -jnp.inf))          # (B, H, ns(I), ns(J), sub, d_k)
        cols = by_sub(kb)[..., None, :, :, :] * jnp.exp(span)
        cols = cols.reshape(*lead, ns, block, dk)
        rows = jnp.exp(to_ref).reshape(*lead, block, dk)
        pair = lambda x: mm(
            "bhnid,bhnjd->bhnij", by_sub(x * rows), cols
        ).reshape(*lead, block, block)
        a = jnp.where(strict, pair(kb) * bb[..., None], 0)
        # (I + A)^-1 applied by a triangular solve: keys that point the
        # same way (SiLU'd and normed, they do) put entries near 1 in
        # A, and the product series (I - A)(I + A^2)... cancels terms
        # of 1e4 in float32 (1e-2 off the recurrence by the 49th token)
        solve = partial(jax.scipy.linalg.solve_triangular,
                        jnp.eye(block, dtype=a.dtype) + a, lower=True,
                        unit_diagonal=True)
        eg = jnp.exp(jnp.cumsum(gb, axis=-2))     # e^G from the block's start
        u = solve(vb * bb[..., None])
        w = solve(kb * bb[..., None] * eg)
        v_new = u - mm("bhik,bhkv->bhiv", w, s)
        inner = jnp.where(lower, pair(qb), 0)
        o = mm("bhik,bhkv->bhiv", qb * eg, s) \
            + mm("bhij,bhjv->bhiv", inner, v_new)
        total = jnp.sum(gb, axis=-2)              # G_last, (B, H, d_k)
        s = s * jnp.exp(total)[..., None] + mm(
            "bhik,bhiv->bhkv", kb * jnp.exp(after(gb, -2)), v_new)
        return s, o

    state, o = jax.lax.scan(one, state, (q, k, v, g, beta))
    # (blocks, B, H, block, d_v) -> (B, L, H, d_v)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, nb * block, h, -1)
    return o[:, :n], state


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------

EXPERT_KERNELS = ("moe_fc1", "moe_fc3", "moe_fc2")


def _conv_rows(lp: dict, cfg: BailingHybridConfig, xs, rows, valid):
    """``qwen3_next._conv_step`` over a slot's convolution rows kept
    flat, ``(B, (K-1) * conv_dim)`` (a ``step`` handed to ``carry``)."""
    c, keep = _qwen3_next._conv_step(
        lp, cfg, xs, rows.reshape(rows.shape[0], cfg.conv_kernel - 1, -1),
        valid)
    return c, keep.reshape(keep.shape[0], -1)


def _kda_step(xs, state, valid):
    """The rule over a batch of sequences (a ``step`` handed to
    ``carry``): ``xs = (q, k (B, S, H, d_k), v (B, S, H, d_v), g (B, S,
    H, d_k), beta (B, S, H))`` float32, ``state (B, H, d_k, d_v)`` as it
    stood before ``xs``, ``valid (B, S)``: a token that is not real
    neither decays nor writes the state. The chunked form (a prefill;
    the decode lanes take :func:`_kda_in_place`)."""
    q, k, v, g, beta = xs
    live = valid.astype(jnp.float32)[..., None]
    return kda_chunked(state.astype(jnp.float32), q, k, v,
                       g * live[..., None], beta * live)


def _kda_in_place(xs, state, li, live):
    """The decode lanes' rule on the stacked state itself (the
    ``in_place`` handed to ``carry``): only the ``live (slots,)``
    slots' ``S`` of layer ``li`` is read and written
    (``ops/delta_lanes.kda_lanes``)."""
    q, k, v, g, beta = (t[:, 0] for t in xs)
    o, state = kda_lanes(state, li, q, k, v, g, beta, live)
    return o[:, None], state


def _kda(lp: dict, u: jax.Array, cfg: BailingHybridConfig, *,
         carry: Callable, state, li):
    """A KDA mixer over the normed ``u (B, S, d)``, without the
    residual. Its two states go through ``carry``: the convolution's
    rows under ``attn_core/qkv_conv``, ``S`` under
    ``attn_core/kda_chunk`` (a chunk's tokens) and
    ``attn_core/kda_lanes`` (the decode lanes)."""
    b, s, _ = u.shape
    kd, h, hd = cfg.key_dim, cfg.n_heads, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        proj = L.dense(lp["attn_qkvfg"], u)
        qkv, f, og = (proj[..., :3 * kd], proj[..., 3 * kd:4 * kd],
                      proj[..., 4 * kd:])
        b_in = L.dense(lp["attn_b"], u)
    with jax.named_scope("attn_core"):
        qkv, state = carry(partial(_conv_rows, lp, cfg), (qkv,), state,
                           li, name="conv", scopes=("qkv_conv",) * 2)
        heads = lambda t: t.reshape(b, s, h, hd)
        q = _qwen3_next._l2norm(heads(qkv[..., :kd])) * hd ** -0.5
        k = _qwen3_next._l2norm(heads(qkv[..., kd:2 * kd]))
        v = heads(qkv[..., 2 * kd:])
        beta = jax.nn.sigmoid(b_in.astype(jnp.float32))
        # the safe gate: a vector a head, in (lower_bound, 0)
        a = jnp.exp(lp["a_log"].astype(jnp.float32))[:, None]
        g = cfg.gate_lower_bound * jax.nn.sigmoid(a * heads(
            f.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32)))
        o, state = carry(_kda_step, (q, k, v, g, beta), state, li,
                         name="kda", scopes=("kda_chunk", "kda_lanes"),
                         in_place=_kda_in_place)
    with jax.named_scope("attn_out"):
        # RMSNorm(o) * w * sigmoid(o_g), per head
        o = L.rms_norm_f32(lp["out_norm"]["scale"], o, cfg.norm_eps)
        o = o * jax.nn.sigmoid(og.astype(jnp.float32).reshape(o.shape))
        return L.dense(lp["attn_out"], o.reshape(b, s, -1).astype(u.dtype)), \
            state


def _layer(lp: dict, x: jax.Array, cfg: BailingHybridConfig, kind: str, *,
           positions, attend: Callable, carry: Callable, cache, state, li,
           valid, form, first_group=0):
    """One layer of either mixer kind and either feed-forward kind
    (``"moe_gate" in lp``). Returns ``(x, cache, state, (tokens per
    held expert, pairs elsewhere) or None)``."""
    u = L.rms_norm_f32(lp["in_norm"]["scale"], x, cfg.norm_eps)
    if kind == KDA:
        y, state = _kda(lp, u, cfg, carry=carry, state=state, li=li)
    else:
        y, cache = _mla_moe.attention(lp, u, cfg.mla, positions=positions,
                                      attend=attend, cache=cache, li=li,
                                      form=form)
    x = x + y
    counts = None
    with jax.named_scope("mlp"):
        if "moe_gate" in lp:
            # the router reads the float32 norm, the experts its
            # rounding to the compute dtype
            u32 = L.rms_norm_f32(lp["post_norm"]["scale"],
                                 x.astype(jnp.float32), cfg.norm_eps)
            u = u32.astype(x.dtype)
            m, held, elsewhere = moe_dropless(
                lp, u, cfg.top_k, cfg.routed_scaling, valid=valid,
                first_group=first_group, route_on=u32,
                held=cfg.experts_held, route_eps=0.0,
                n_group=cfg.n_group, topk_group=cfg.topk_group)
            with jax.named_scope("moe_shared"):
                m = m + swiglu(lp, u, "shared")
            counts = (held, elsewhere)
        else:
            m = swiglu(lp, L.rms_norm_f32(lp["post_norm"]["scale"], x,
                                          cfg.norm_eps), "mlp")
        x = x + m
    return x, cache, state, counts


def layers(params: dict, x: jax.Array, cfg: BailingHybridConfig, *,
           positions, attend: Callable, carry: Callable, cache=None,
           state=None, valid=None, form: str = "absorbed"):
    """The whole layer stack over ``x (B, S, d)``: the leading dense
    layers unrolled, then a scan over periods. ``cache`` and ``state``
    are the caller's (any pytree or None), carried through and handed
    to ``attend`` / ``carry`` with the layer's index AMONG ITS KIND
    (``li``: the row of the latent pool, or of the slot states).
    ``valid (B, S)``: which tokens are real; ``form``: the MLA layers'
    (``"absorbed"``, the serving engine's, or ``"expanded"``). Returns
    ``(x, cache, state, {"held": tokens per held expert (n_moe_layers,
    n) int32, "elsewhere": pairs routed to experts not held
    (n_moe_layers,) int32})``."""
    lead, period, n_periods = cfg.plan
    n_held = cfg.experts_held[1]
    valid = jnp.ones(x.shape[:2], bool) if valid is None else valid
    kw = dict(positions=positions, attend=attend, carry=carry,
              valid=valid, form=form)
    seen = {KDA: 0, MLA: 0}
    for kind, lp in zip(lead, params["lead"]):
        x, cache, state, _ = _layer(lp, x, cfg, kind, cache=cache,
                                    state=state, li=seen[kind], **kw)
        seen[kind] += 1
    held = jnp.zeros((0, n_held), jnp.int32)
    elsewhere = jnp.zeros((0,), jnp.int32)
    if n_periods:
        per_period = {k: period.count(k) for k in (KDA, MLA)}
        # the experts are not scanned over: every period's lie in one
        # (n_periods * n, ...) stack the body reads in place
        experts = [{k: {"kernel": lp[k]["kernel"].reshape(
                       -1, *lp[k]["kernel"].shape[2:])}
                    for k in EXPERT_KERNELS} for lp in params["periods"]]
        scanned = [{k: v for k, v in lp.items() if k not in EXPERT_KERNELS}
                   for lp in params["periods"]]

        def body(carried, inputs):
            x, cache, state = carried
            subs, i = inputs
            at = dict(seen)
            cnts = []
            for kind, lp, ex in zip(period, subs, experts):
                li = at[kind] + i * per_period[kind]
                at[kind] += 1
                x, cache, state, cnt = _layer(
                    {**lp, **ex}, x, cfg, kind, cache=cache, state=state,
                    li=li, first_group=i * n_held, **kw)
                cnts.append(cnt)
            return (x, cache, state), (jnp.stack([c[0] for c in cnts]),
                                       jnp.stack([c[1] for c in cnts]))

        (x, cache, state), (held, elsewhere) = jax.lax.scan(
            body, (x, cache, state), (scanned, jnp.arange(n_periods)))
        held = held.reshape(-1, n_held)
        elsewhere = elsewhere.reshape(-1)
    return x, cache, state, {"held": held, "elsewhere": elsewhere}


@jax.named_scope("embed")
def embed(params: dict, ids: jax.Array, dtype: Any = None) -> jax.Array:
    return L.embedding(params["wte"], ids, dtype=dtype)


@jax.named_scope("head")
def head(params: dict, x: jax.Array, cfg: BailingHybridConfig) -> jax.Array:
    """Final RMSNorm and the untied head; logits in float32."""
    x = L.rms_norm_f32(params["norm_f"]["scale"], x, cfg.norm_eps)
    return jnp.dot(x, params["head"]["kernel"].astype(x.dtype),
                   preferred_element_type=jnp.float32)


def zero_rows(cfg: BailingHybridConfig, batch: int, dtype: Any) -> dict:
    """A fresh sequence's slot-state rows, one a sequence."""
    return {"conv": jnp.zeros((batch, (cfg.conv_kernel - 1) * cfg.conv_dim),
                              dtype),
            "kda": jnp.zeros((batch, cfg.n_heads, cfg.head_dim,
                              cfg.head_dim), jnp.float32)}


class BailingHybrid:
    """Namespace: ``init`` / ``apply``, as the zoo's other models."""

    @staticmethod
    def init(rng: jax.Array,
             cfg: BailingHybridConfig = BailingHybridConfig(),
             dtype: Any = jnp.float32) -> dict:
        lead, period, n_periods = cfg.plan
        k_emb, k_head, k_lead, k_per = jax.random.split(rng, 4)
        lead_keys = jax.random.split(k_lead, max(len(lead), 1))
        keys = jax.random.split(k_per, max(n_periods * len(period), 1))
        return {
            "wte": L.embedding_init(k_emb, cfg.vocab, cfg.d_model,
                                    dtype=dtype),
            "head": {"kernel": 0.02 * jax.random.normal(
                k_head, (cfg.d_model, cfg.vocab), dtype)},
            "lead": [_layer_init(lead_keys[i], cfg, kind, False, dtype)
                     for i, kind in enumerate(lead)],
            "periods": [_stack([_layer_init(keys[p * len(period) + j], cfg,
                                            kind, True, dtype)
                                for p in range(n_periods)])
                        for j, kind in enumerate(period)],
            "norm_f": {"scale": jnp.ones((cfg.d_model,), dtype)},
        }

    @staticmethod
    def apply(params: dict, ids: jax.Array, cfg: BailingHybridConfig,
              compute_dtype: Any = None, return_counts: bool = False):
        """Full-sequence forward: ``ids (B, S)`` -> logits ``(B, S,
        vocab)`` float32 (and the routed pairs' counts with
        ``return_counts``). Plain causal attention in the EXPANDED form
        and every KDA layer's state starting from zeros: what a
        training step runs, and what the served path (a latent pool,
        slot states carried across chunks and steps) must equal."""
        x = embed(params, ids, dtype=compute_dtype)

        def attend(q, k, v, cache, li):
            return mha_reference(q, k, v, causal=True, sm_scale=1.0), cache

        def carry(step, xs, state, li, name, scopes, in_place=None):
            with jax.named_scope(scopes[0]):
                rows = zero_rows(cfg, ids.shape[0], x.dtype)[name]
                return step(xs, rows, jnp.ones(ids.shape, bool))[0], state

        x, _, _, counts = layers(
            params, x, cfg, positions=jnp.arange(ids.shape[1]),
            attend=attend, carry=carry, form="expanded")
        logits = head(params, x, cfg)
        return (logits, counts) if return_counts else logits


__all__ = ["BailingHybrid", "BailingHybridConfig", "KDA", "KDA_BLOCK",
           "KDA_SUB", "MLA", "UNSERVED", "embed", "head", "kda_chunked",
           "kda_step", "layers", "rope_permutation", "zero_rows"]
