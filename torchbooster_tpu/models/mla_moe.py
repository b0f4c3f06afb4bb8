"""Latent attention (MLA) with a shared expert beside routed experts:
the DeepSeek-V2/V3 family's block (``sarvam_mla`` is the published
model the benchmark runs through it).

The third language-model family of the zoo (``models/gpt.py``,
``models/lfm2.py``). Per layer ``x = h + Attn(RMSNorm(h))``, ``h = x +
FF(RMSNorm(x))``; logits ``= RMSNorm(h_L) @ W_head`` (untied head).

**Attention.** Every layer attends through a LATENT: one row ``[c |
k_r]`` a token, shared by all heads — ``c`` (``latent_dim`` lanes,
RMS-normed) is what keys and values are up-projected from, ``k_r``
(``rope_dim`` lanes) the one rotary key. ``q = u @ W_q`` per head
``[q_nope | q_rope]``, RMS-normed over the head (one gain for all
heads), ``q_rope`` and ``k_r`` rotated by YaRN-scaled frequencies
(:func:`yarn_frequencies`). The softmax scale is ``q_dim^-0.5 *
mscale^2`` (:attr:`MLAMoEConfig.softmax_scale`). Two algebraically
equal forms:

- **expanded** — ``[k_nope,h | v_h] = c @ W_ukv`` per head, ``k_h =
  [k_nope,h | k_r]``: plain multi-head attention (what the
  full-sequence :meth:`MLAMoE.apply` runs);
- **absorbed** — ``W_uk`` folded into the query, ``q'_h = [q_nope,h @
  W_uk,h^T | q_rope,h]``, scores against the row itself, ``o'_h =
  softmax . c``, then ``o_h = o'_h @ W_uv,h``: attention with ONE KV
  head of ``latent_dim + rope_dim`` lanes whose values are its leading
  ``latent_dim`` lanes — what a cache wants, since a token leaves one
  row and never its ``n_heads x (q_dim + v_dim)`` expansion.

**Feed-forward.** A dense SwiGLU in the first ``n_dense_layers``
layers; after them a shared expert (a dense SwiGLU over every token,
added unweighted) plus the dropless routed experts of
``models/moe.py``. The router is ``n_experts`` wide whatever this
device holds: ``experts_held = (first, n)`` says which experts live
here (one expert-parallel rank's share), a pair routed elsewhere keeps
its place in the top-k and in the renormalisation and adds nothing
here — on one device the layer runs without its exchange, and that
partial result is what goes on to the next layer.

**Layout.** ``attn_q`` and ``attn_ukv`` are stored OUTPUT-major,
``(heads * q_dim, d_model)`` and ``(heads * (nope_dim + v_dim),
latent_dim)``: the per-head products that follow them make the
compiler read both that way, and stored ``(in, out)`` each is copied
transposed on every step of every serving program (compiled for the
v5e: 100 MB + 17 MB a layer). The leading dense layers are unrolled
(``params["lead"]``, a list), then ONE ``lax.scan`` runs over the
expert layers (``params["stack"]``, leaves stacked over them); the
routed experts of all scanned layers lie in one ``(layers * n, ...)``
stack the body reads in place by ``first_group`` (``moe_dropless``
says why), and the body indexes the two large attention matrices
itself, so that their read carries a scope's name.

**One layer stack, two callers.** :func:`layers` leaves to its caller
``attend(q, k, v, cache, li) -> (o, cache)``. The queries arrive
SCALED (no further ``1/sqrt(d)``). ``form="expanded"``: ``q (B, S, H,
q_dim)``, ``k (B, S, H, q_dim)``, ``v (B, S, H, v_dim)``.
``form="absorbed"``: ``q (B, S, H, latent_dim + rope_dim)``, ``k (B,
S, 1, latent_dim + rope_dim)`` — the row — and ``v`` None: the values
are ``k``'s leading ``latent_dim`` lanes, and ``o`` comes back ``(B,
S, H, latent_dim)``. The serving engine (serving/engine.py) hands in
the paged pool and takes the absorbed form; :func:`cache_spec` tells it
what to allocate.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from torchbooster_tpu.models import layers as L
from torchbooster_tpu.models.gpt import _rope
from torchbooster_tpu.models.moe import moe_dropless
from torchbooster_tpu.ops.attention import mha_reference

# What the paged engine does not serve for this family, and why: the
# latent pool is one row a token with no V half and one KV head, and
# the programs of a model with its own layer stack carry the plain
# operands only (serving/engine.py raises these at build).
UNSERVED = {
    "prefix_cache": "the latent sweep is not wired for pages shared "
                    "between slots (reference lanes x 64 heads of "
                    "query columns a page)",
    "speculative": "the verify program is GPTConfig's (_block_core "
                   "over per-head K/V rows)",
    "parallel_sampling (fork)": "the fork's page copy and branch picks "
                                "are wired for the GPT programs only",
    "host_spill": "the spill payload is per-head int8 K and V pages; a "
                  "latent page has one row and no V half",
    "disagg (prefill_only)": "the page wire format is per-head K and V "
                             "pages; a latent page has no V half",
    "tp": "a latent cache has one KV head and cannot be split by "
          "heads (the deployment replicates attention and shards the "
          "experts)",
    "cache_dtype: int8": "int8 rows carry one scale a KV head; the "
                         "latent row's norm-ed latent and rotary key "
                         "need scales of their own",
    "decode_backend: pallas": "the paged-attention kernel reads "
                              "per-head K and V pages of one width",
    "structured": "the programs of a model with its own layer stack "
                  "carry no legality-mask operand",
    "adapters (lora)": "the adapter stacks are laid out for GPTConfig's "
                       "fused attn_qkv",
    "weights (int8/int4)": "the quantizer walks GPTConfig's block tree",
}


@dataclass(frozen=True)
class MLAMoEConfig:
    vocab: int = 262144
    d_model: int = 4096
    n_heads: int = 64
    nope_dim: int = 128             # a head's un-rotated query/key lanes
    rope_dim: int = 64              # its rotary lanes (the one k_r's)
    latent_dim: int = 512           # kv_lora_rank: the cached latent
    v_dim: int = 128                # a head's value lanes
    dense_width: int = 16384        # SwiGLU width of the dense layers
    expert_width: int = 2048        # SwiGLU width of one routed expert
    shared_width: int = 2048        # ... of the shared expert
    n_experts: int = 128            # the ROUTER's width
    experts_held: tuple[int, int] = (0, 128)    # (first, n) held here
    top_k: int = 8
    n_layers: int = 32
    n_dense_layers: int = 1
    rope_base: float = 10_000.0
    yarn_factor: float = 40.0
    yarn_original: int = 4096       # positions before the scaling
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    routed_scaling: float = 2.5
    seq_len: int = 131_072

    def __post_init__(self):
        first, n = self.experts_held
        if not (0 <= first and n >= 1 and first + n <= self.n_experts):
            raise ValueError(
                f"experts_held {self.experts_held} is no slice of the "
                f"router's {self.n_experts} experts")
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("n_dense_layers leaves no expert layer")

    @property
    def q_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def row_dim(self) -> int:
        """What one token leaves in the cache a layer."""
        return self.latent_dim + self.rope_dim

    @property
    def kv_heads(self) -> int:
        return 1

    n_kv_heads = kv_heads

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def softmax_scale(self) -> float:
        m = 1.0
        if self.yarn_factor > 1:
            m = 0.1 * self.yarn_mscale_all_dim \
                * math.log(self.yarn_factor) + 1.0
        return self.q_dim ** -0.5 * m * m

    def cache_spec(self):
        """One row a token and layer: ``row_dim`` lanes of key whose
        leading ``latent_dim`` are the values — no V half."""
        from torchbooster_tpu.serving.kv_pages import CacheSpec

        return CacheSpec(kv_layers=self.n_layers, kv_heads=1,
                         head_dim=self.row_dim, value_dim=self.latent_dim)


def yarn_frequencies(cfg: MLAMoEConfig) -> np.ndarray:
    """The rotary frequencies ``(rope_dim / 2,)`` under YaRN
    (``deepseek_yarn``): dimensions that turn fast within the original
    window keep ``f_i = base^(-2i/dim)``, those that turn slowly are
    interpolated (``f_i / factor``), a linear ramp between. The
    cos / sin are not scaled (``mscale / mscale_all_dim = 1``); the
    softmax scale carries ``mscale^2``."""
    dim, half = cfg.rope_dim, cfg.rope_dim // 2
    f = cfg.rope_base ** (-2.0 * np.arange(half) / dim)
    if cfg.yarn_factor <= 1:
        return f.astype(np.float32)
    turns = lambda r: dim * math.log(
        cfg.yarn_original / (2 * math.pi * r)) / (2 * math.log(cfg.rope_base))
    low = max(math.floor(turns(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(turns(cfg.yarn_beta_slow)), half - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (f * (1 - ramp) + f / cfg.yarn_factor * ramp).astype(np.float32)


# ---------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------

def _layer_init(rng: jax.Array, cfg: MLAMoEConfig, moe: bool,
                dtype: Any) -> dict:
    """One layer: matrices N(0, 0.02), the residual branches' output
    projections scaled by 1/sqrt(2L), gains 1, selection bias 0."""
    ks = iter(jax.random.split(rng, 12))
    d, h, std = cfg.d_model, cfg.n_heads, 0.02
    res_std = std / (2 * cfg.n_layers) ** 0.5
    mat = lambda shape, s=std: {
        "kernel": s * jax.random.normal(next(ks), shape, dtype)}
    gain = lambda n: {"scale": jnp.ones((n,), dtype)}
    lp = {
        "attn_norm": gain(d), "ffn_norm": gain(d),
        "attn_q": mat((h * cfg.q_dim, d)), "q_norm": gain(cfg.q_dim),
        "attn_dkv": mat((d, cfg.row_dim)), "kv_norm": gain(cfg.latent_dim),
        "attn_ukv": mat((h * (cfg.nope_dim + cfg.v_dim), cfg.latent_dim)),
        "attn_out": mat((h * cfg.v_dim, d), res_std),
    }
    if moe:
        e, w, sw = cfg.experts_held[1], cfg.expert_width, cfg.shared_width
        lp["moe_gate"] = mat((d, cfg.n_experts))
        lp["moe_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
        lp["moe_fc1"], lp["moe_fc3"] = mat((e, d, w)), mat((e, d, w))
        lp["moe_fc2"] = mat((e, w, d), res_std)
        lp["shared_fc1"], lp["shared_fc3"] = mat((d, sw)), mat((d, sw))
        lp["shared_fc2"] = mat((sw, d), res_std)
    else:
        w = cfg.dense_width
        lp["mlp_fc1"], lp["mlp_fc3"] = mat((d, w)), mat((d, w))
        lp["mlp_fc2"] = mat((w, d), res_std)
    return lp


# ---------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------

EXPERT_KERNELS = ("moe_fc1", "moe_fc3", "moe_fc2")
BY_INDEX = ("attn_q", "attn_ukv")   # read by layer index, not scanned
FORMS = ("expanded", "absorbed")


def swiglu(lp: dict, u: jax.Array, name: str) -> jax.Array:
    """``(silu(u @ W1) * (u @ W3)) @ W2`` of the dense MLP (``name``
    ``"mlp"``) or the shared expert (``"shared"``)."""
    return L.dense(lp[name + "_fc2"], jax.nn.silu(
        L.dense(lp[name + "_fc1"], u)) * L.dense(lp[name + "_fc3"], u))


def attention(lp: dict, u: jax.Array, cfg: MLAMoEConfig, *, positions,
              attend: Callable, cache, li, form: str, row=None):
    """The latent-attention block over the normed ``u (B, S, d)`` in
    either form (module docstring), without the residual; where ``lp``
    holds ``attn_gate`` ``(d, heads)``, each head's output is scaled by
    its ``sigmoid(u @ W_gate)`` before ``W_o``. ``row``: the
    layer's index into ``attn_q`` / ``attn_ukv`` where the caller hands
    those two STACKED over layers (:func:`layers` does, so that their
    read is named). Returns ``(y (B, S, d), cache)``."""
    b, s, _ = u.shape
    h, nope, lat = cfg.n_heads, cfg.nope_dim, cfg.latent_dim
    freqs = jnp.asarray(yarn_frequencies(cfg))
    kernel = lambda name: (
        lp[name]["kernel"] if row is None
        else jax.lax.dynamic_index_in_dim(lp[name]["kernel"], row, 0,
                                          keepdims=False)).astype(u.dtype)
    with jax.named_scope("attn_qkv"):
        # [k_nope,h | v_h] a head, out of the latent (output-major)
        w_ukv = kernel("attn_ukv").reshape(h, nope + cfg.v_dim, lat)
        q = jnp.einsum("bsd,nd->bsn", u, kernel("attn_q")
                       ).reshape(b, s, h, cfg.q_dim)
        q = L.rms_norm_f32(lp["q_norm"]["scale"], q, cfg.norm_eps)
        # the softmax scale rides the query from here on
        q = jnp.concatenate(
            [q[..., :nope],
             _rope(q[..., nope:], positions, freqs=freqs)], -1)
        q = (q.astype(jnp.float32) * cfg.softmax_scale).astype(u.dtype)
        ckr = L.dense(lp["attn_dkv"], u)            # [c | k_r], one row
        c = L.rms_norm_f32(lp["kv_norm"]["scale"], ckr[..., :lat],
                           cfg.norm_eps)
        # the one rotary key, (B, S, 1, rope_dim)
        k_r = _rope(ckr[..., None, lat:], positions, freqs=freqs)
    if form == "absorbed":
        with jax.named_scope("attn_core"):
            with jax.named_scope("mla_absorb"):
                # W_uk folded into the query: (B, S, H, latent)
                q_abs = jnp.concatenate(
                    [jnp.einsum("bshn,hnc->bshc", q[..., :nope],
                                w_ukv[:, :nope]), q[..., nope:]], -1)
            k_row = jnp.concatenate([c[:, :, None], k_r], -1)
            o_lat, cache = attend(q_abs, k_row, None, cache, li)
            with jax.named_scope("mla_absorb"):
                o = jnp.einsum("bshc,hvc->bshv", o_lat.astype(u.dtype),
                               w_ukv[:, nope:])
    elif form == "expanded":
        with jax.named_scope("attn_core"):
            with jax.named_scope("mla_expand"):
                kv = jnp.einsum("bsc,hnc->bshn", c, w_ukv)
                k = jnp.concatenate(
                    [kv[..., :nope],
                     jnp.broadcast_to(k_r, (b, s, h, cfg.rope_dim))], -1)
            o, cache = attend(q, k, kv[..., nope:], cache, li)
    else:
        raise ValueError(f"form must be one of {FORMS}, got {form!r}")
    with jax.named_scope("attn_out"):
        if "attn_gate" in lp:
            # a head-wise output gate, one scalar a head
            # (models/bailing_hybrid.py): o_h * sigmoid(u @ W_gate)_h
            with jax.named_scope("attn_gate"):
                gate = jax.nn.sigmoid(
                    L.dense(lp["attn_gate"], u).astype(jnp.float32))
                o = (o.astype(jnp.float32) * gate[..., None]
                     ).astype(u.dtype)
        return L.dense(lp["attn_out"], o.reshape(b, s, h * cfg.v_dim)), cache


def _layer(lp: dict, x: jax.Array, cfg: MLAMoEConfig, *, positions,
           attend: Callable, cache, li, valid, form, first_group=0,
           row=None):
    """One layer of either feed-forward kind (``"moe_gate" in lp``).
    Returns ``(x, cache, (tokens per held expert, pairs elsewhere) or
    None)``. The named scopes are docs/observability.md's: the new
    ones (``mla_absorb`` / ``mla_expand``, ``moe_shared``) sit INSIDE
    the ones the benchmark's readers already know."""
    norm = lambda g, t: L.rms_norm_f32(g["scale"], t, cfg.norm_eps)
    y, cache = attention(lp, norm(lp["attn_norm"], x), cfg,
                         positions=positions, attend=attend, cache=cache,
                         li=li, form=form, row=row)
    x = x + y
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
                held=cfg.experts_held)
            with jax.named_scope("moe_shared"):
                m = m + swiglu(lp, u, "shared")
            counts = (held, elsewhere)
        else:
            m = swiglu(lp, norm(lp["ffn_norm"], x), "mlp")
        x = x + m
    return x, cache, counts


def layers(params: dict, x: jax.Array, cfg: MLAMoEConfig, *, positions,
           attend: Callable, cache=None, valid=None, form: str = "absorbed",
           conv=None, state=None):
    """The whole layer stack over ``x (B, S, d)``: the leading dense
    layers unrolled, then the scan over the expert layers. ``cache``
    is the caller's (any pytree or None), carried through and handed
    to ``attend`` with the layer's index ``li`` (the row of the pool).
    ``valid (B, S)``: which tokens are real (expert routing skips the
    others). ``conv`` / ``state`` are the engine's for a model with
    slot state: this one has none and hands ``state`` back as it came.
    Returns ``(x, cache, state, {"held": tokens per held expert
    (n_moe_layers, n) int32, "elsewhere": pairs routed to experts not
    held (n_moe_layers,) int32})``."""
    n_lead, n_held = cfg.n_dense_layers, cfg.experts_held[1]
    kw = dict(positions=positions, attend=attend, valid=valid, form=form)
    for li, lp in enumerate(params["lead"]):
        x, cache, _ = _layer(lp, x, cfg, cache=cache, li=li, **kw)
    stack = params["stack"]
    # the experts are not scanned over: every layer's lie in one
    # (layers * n, ...) stack the body reads in place
    experts = {k: {"kernel": stack[k]["kernel"].reshape(
        -1, *stack[k]["kernel"].shape[2:])} for k in EXPERT_KERNELS}
    # nor are attention's two large matrices: the scan's own slicing
    # of them carries no name (the compiler streams a layer's W_q out
    # of the stack in an op of its own: 100 MB, unscoped), so the body
    # indexes them itself, inside ``attn_qkv``
    whole = {**experts, **{k: stack[k] for k in BY_INDEX}}
    scanned = {k: v for k, v in stack.items() if k not in whole}

    def body(carry, inputs):
        x, cache = carry
        lp, i = inputs
        x, cache, counts = _layer(
            {**lp, **whole}, x, cfg, cache=cache, li=n_lead + i,
            first_group=i * n_held, row=i, **kw)
        return (x, cache), counts

    (x, cache), (held, elsewhere) = jax.lax.scan(
        body, (x, cache), (scanned, jnp.arange(cfg.n_moe_layers)))
    return x, cache, state, {"held": held, "elsewhere": elsewhere}


@jax.named_scope("embed")
def embed(params: dict, ids: jax.Array, dtype: Any = None) -> jax.Array:
    return L.embedding(params["wte"], ids, dtype=dtype)


@jax.named_scope("head")
def head(params: dict, x: jax.Array, cfg: MLAMoEConfig) -> jax.Array:
    """Final RMSNorm and the untied head; logits in float32."""
    x = L.rms_norm_f32(params["norm_f"]["scale"], x, cfg.norm_eps)
    return jnp.dot(x, params["head"]["kernel"].astype(x.dtype),
                   preferred_element_type=jnp.float32)


class MLAMoE:
    """Namespace: ``init`` / ``apply``, as the zoo's other models."""

    @staticmethod
    def init(rng: jax.Array, cfg: MLAMoEConfig = MLAMoEConfig(),
             dtype: Any = jnp.float32) -> dict:
        k_emb, k_head, k_lead, k_stack = jax.random.split(rng, 4)
        lead_keys = jax.random.split(k_lead, max(cfg.n_dense_layers, 1))
        stack_keys = jax.random.split(k_stack, max(cfg.n_moe_layers, 1))
        stack = [_layer_init(stack_keys[i], cfg, True, dtype)
                 for i in range(cfg.n_moe_layers)]
        return {
            "wte": L.embedding_init(k_emb, cfg.vocab, cfg.d_model,
                                    dtype=dtype),
            "head": {"kernel": 0.02 * jax.random.normal(
                k_head, (cfg.d_model, cfg.vocab), dtype)},
            "lead": [_layer_init(lead_keys[i], cfg, False, dtype)
                     for i in range(cfg.n_dense_layers)],
            "stack": jax.tree.map(lambda *a: jnp.stack(a), *stack),
            "norm_f": {"scale": jnp.ones((cfg.d_model,), dtype)},
        }

    @staticmethod
    def apply(params: dict, ids: jax.Array, cfg: MLAMoEConfig,
              compute_dtype: Any = None, return_counts: bool = False,
              form: str = "expanded"):
        """Full-sequence forward: ``ids (B, S)`` -> logits ``(B, S,
        vocab)`` float32 (and the routed pairs' counts with
        ``return_counts``). Plain causal attention in the EXPANDED
        form by default: what a training step runs, and what the
        served path (absorbed, through the cache) must equal."""
        x = embed(params, ids, dtype=compute_dtype)

        def attend(q, k, v, cache, li):
            if v is None:       # absorbed: the row's latent lanes
                v = k[..., :cfg.latent_dim]
            return mha_reference(q, k, v, causal=True, sm_scale=1.0), cache

        x, _, _, counts = layers(
            params, x, cfg, positions=jnp.arange(ids.shape[1]),
            attend=attend, form=form)
        logits = head(params, x, cfg)
        return (logits, counts) if return_counts else logits


__all__ = ["MLAMoE", "MLAMoEConfig", "UNSERVED", "attention", "embed",
           "head", "layers", "swiglu", "yarn_frequencies"]
