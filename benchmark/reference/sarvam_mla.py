"""Plain latent-attention (``sarvam_mla``) in float32 ``jax.numpy``:
the forward pass, in the EXPANDED form.

The reference the sarvam-105b cell's ``correct`` is decided against,
and the tier-1 parity tests' (tests/test_sarvam_mla.py). It follows
the DeepSeek-V2/V3 family's equations with no kernel, cache, batching
or import from the program, reads weights in
``benchmark/weights_sarvam_mla.py``'s flat layout (a leaf stacked over
the layers of its kind) and the configuration file's dict under the
published keys, and runs every matrix product at ``highest``
precision. One sequence at a time, one layer at a time: a layer's
weights are widened to float32 as the loop reaches it (an expert only
at its turn), attention goes through in blocks of queries and the
head only over the positions asked for. The program decodes in the
ABSORBED form (``W_uk`` folded into the query, values summed as
latents); this file never does: every head's keys and values are
up-projected from the latent and attended as plain multi-head
attention.

    h0 = E[ids];  x = h + Attn_i(RMSNorm(h));  h = x + FF_i(RMSNorm(x))
    logits = RMSNorm(h_L) @ W_head
    RMSNorm: x * rsqrt(mean(x^2) + eps) * g
    attention:   q = u @ W_q, per head [q_nope (n) | q_rope (r)];
                 q_h <- RMSNorm_{n+r}(q_h) (one gain for all heads)
                 [c | k_r] = u @ W_dkv;  c <- RMSNorm_c(c)
                 RoPE (rotate-half over r dims, YaRN frequencies) on
                 q_rope (per head) and k_r (one for all heads)
                 [k_nope,h | v_h] = c @ W_ukv;  k_h = [k_nope,h | k_r]
                 causal softmax(q_h . k_h * s) v_h, heads concatenated,
                 @ W_o;  s = (n+r)^-0.5 * m^2, m = 0.1 ln(factor) + 1
    YaRN:        f_i = theta^(-2i/r), i < r/2
                 d(t) = r ln(orig / (2 pi t)) / (2 ln theta)
                 low = floor(d(beta_fast)), high = ceil(d(beta_slow)),
                 clamped to [0, r/2 - 1]
                 ramp_i = clip((i - low) / (high - low), 0, 1)
                 w_i = f_i (1 - ramp_i) + f_i / factor * ramp_i
    dense FF:    (silu(u @ W1) * (u @ W3)) @ W2
    expert FF:   Shared(u) + sum_{e in sel, e held} w_e Expert_e(u)
                 s = sigmoid(u @ W_g) (E wide); sel = top_k(s + b);
                 w = scale * s[sel] / (sum(s[sel]) + 1e-6)

**The share.** ``experts_held = {first, count}``: the weights hold
experts ``first .. first + count - 1`` of the ``E`` the router scores.
A pair whose expert is not held keeps its place in the top-k and in
the renormalisation and adds nothing: what the absent experts would
have added is left out, here and in the program alike, and that
partial result is what goes on to the next layer. The vocabulary is
the configuration's slice (a smaller vocabulary).

Departures from the published model, all listed in the configuration
file's ``assumed``: a direct query projection (the row has no
``q_lora_rank``); ``use_qk_norm`` taken as the per-head query norm
above with the latent's norm on the key side; rotate-half pair layout;
sigmoid scoring with ``norm_topk_prob`` and no group-limited routing;
the router's scores and top-k in float32; the ``1e-6`` in the
renormalisation; ``[k_nope | v]`` of a head as one fused matrix (the
same product); weights random from the seed.

``quant`` is the hook of the CONTROL, not of the reference: applied to
both operands of every matrix product (``fp8``: float8 e4m3, the
nearest precision under the bfloat16 the configuration states).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256           # queries attended at a time ...
H_BLOCK = 16            # ... by this many heads
EXPERT = ("mo_w1", "mo_w3", "mo_w2")


def fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 and back."""
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def yarn(cfg: dict) -> np.ndarray:
    """The rotary frequencies ``(r / 2,)`` (module docstring)."""
    r, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    half = r // 2
    i = np.arange(half)
    f = theta ** (-2.0 * i / r)
    d = lambda t: r * math.log(sc["original_max_position_embeddings"]
                               / (2 * math.pi * t)) / (2 * math.log(theta))
    low = min(max(math.floor(d(sc["beta_fast"])), 0), half - 1)
    high = min(max(math.ceil(d(sc["beta_slow"])), 0), half - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f * (1 - ramp) + f / sc["factor"] * ramp).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    sc = cfg["rope_scaling"]
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rope(x, freqs):
    """Rotate-half over (S, heads, r), position = row."""
    half = x.shape[-1] // 2
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(u, lw, cfg, quant=None):
    """The expanded form over the normed ``u (S, d)``. Heads go
    through in groups of ``H_BLOCK`` and queries in blocks of
    ``Q_BLOCK`` (memory only: each head's sum is its own, the output
    projection's rows are summed over the groups)."""
    s, d = u.shape
    h, n, r = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
               cfg["qk_rope_head_dim"])
    c_dim, vd, eps = cfg["kv_lora_rank"], cfg["v_head_dim"], \
        cfg["rms_norm_eps"]
    freqs = jnp.asarray(yarn(cfg))
    scale = softmax_scale(cfg)
    row = _mm(u, lw["at_dkv"], quant)
    c = _rms(row[:, :c_dim], lw["at_kvn"], eps)
    k_r = _rope(row[:, None, c_dim:], freqs)                # (S, 1, r)
    hb = math.gcd(h, H_BLOCK)
    block = min(Q_BLOCK, s)
    n_blocks = -(-s // block)
    # a group's columns of W_q and W_ukv, its rows of W_o
    by_group = lambda w, axis: jnp.moveaxis(
        w.reshape(*w.shape[:axis], h // hb, -1, *w.shape[axis + 1:]),
        axis, 0)

    def group(weights):
        w_q, w_ukv, w_o = weights
        q = _rms(_mm(u, w_q, quant).reshape(s, hb, n + r), lw["at_qn"],
                 eps)
        q = jnp.concatenate([q[..., :n], _rope(q[..., n:], freqs)], -1)
        kv = _mm(c, w_ukv, quant).reshape(s, hb, n + vd)
        k = jnp.concatenate(
            [kv[..., :n], jnp.broadcast_to(k_r, (s, hb, r))],
            -1).transpose(1, 2, 0)                          # (hb, n+r, S)
        v = kv[..., n:].transpose(1, 0, 2)                  # (hb, S, vd)
        qb = jnp.pad(q, ((0, n_blocks * block - s), (0, 0), (0, 0)))
        qb = qb.reshape(n_blocks, block, hb, n + r).transpose(0, 2, 1, 3)

        def one(args):
            qh, lo = args                                   # (hb, b, n+r)
            scores = _mm(qh, k, quant) * scale
            rows = lo + jnp.arange(block)
            scores = jnp.where(rows[:, None] >= jnp.arange(s)[None, :],
                               scores, -jnp.inf)
            return _mm(jax.nn.softmax(scores, axis=-1), v, quant)

        o = jax.lax.map(one, (qb, jnp.arange(n_blocks) * block))
        o = o.transpose(0, 2, 1, 3).reshape(n_blocks * block, hb * vd)[:s]
        return _mm(o, w_o, quant)

    parts = jax.lax.map(group, (by_group(lw["at_q"], 1),
                                by_group(lw["at_ukv"], 1),
                                by_group(lw["at_out"], 0)))
    return parts.sum(0)


def _swiglu(u, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(u, w1, quant)) * _mm(u, w3, quant), w2,
               quant)


def route(u, lw, cfg, quant=None):
    """(weights (S, E) with zeros off the selection, selection (S, k)),
    over ALL ``E`` experts the router scores."""
    scores = jax.nn.sigmoid(_mm(u, lw["mo_gate"], quant))
    biased = scores + lw["mo_bias"] \
        if cfg["moe_router_enable_expert_bias"] else scores
    _, sel = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-6)
    w = w * cfg["routed_scaling_factor"]
    full = jnp.zeros_like(scores).at[
        jnp.arange(u.shape[0])[:, None], sel].set(w)
    return full, sel


def routed(u, lw, cfg, quant=None):
    """The routed experts' part of an expert layer as THIS share gives
    it: every held expert on every token, the others' weight 0."""
    full, _ = route(u, lw, cfg, quant)
    first, count = cfg["held"]
    f32 = lambda t: t.astype(jnp.float32)

    def one(acc, expert):
        w1, w3, w2, w_e = expert
        return acc + w_e[:, None] * _swiglu(u, f32(w1), f32(w3), f32(w2),
                                            quant), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (lw["mo_w1"], lw["mo_w3"], lw["mo_w2"],
         full.T[first:first + count]))
    return out


def shared(u, lw, quant=None):
    return _swiglu(u, lw["mo_s1"], lw["mo_s3"], lw["mo_s2"], quant)


@partial(jax.jit, static_argnames=("cfg", "dense", "quant"))
def _layer(x, lw, cfg, dense, quant):
    cfg = _unfreeze(cfg)
    # the experts stay as stored until their turn in the scan
    lw = {k: t if k in EXPERT else t.astype(jnp.float32)
          for k, t in lw.items()}
    eps = cfg["rms_norm_eps"]
    x = x + attention(_rms(x, lw["at_norm"], eps), lw, cfg, quant)
    if dense:
        u = _rms(x, lw["ff_norm"], eps)
        return x + _swiglu(u, lw["ff_w1"], lw["ff_w3"], lw["ff_w2"], quant)
    u = _rms(x, lw["mo_norm"], eps)
    return x + shared(u, lw, quant) + routed(u, lw, cfg, quant)


def static(cfg: dict) -> tuple:
    """The numbers the layer functions read, hashable for ``jit``."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "kv_lora_rank", "v_head_dim", "rms_norm_eps", "rope_theta",
            "num_experts_per_tok", "moe_router_enable_expert_bias",
            "routed_scaling_factor")
    share = cfg.get("experts_held") or {"first": 0,
                                        "count": cfg["num_experts"]}
    return tuple((k, cfg[k]) for k in keys) + (
        ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),
        ("held", (share["first"], share["count"])))


def _unfreeze(cfg: tuple) -> dict:
    out = dict(cfg)
    out["rope_scaling"] = dict(out["rope_scaling"])
    return out


def layer_weights(w: dict, cfg: dict, i: int) -> tuple[dict, bool]:
    """Layer ``i``'s rows of the flat leaves and whether its
    feed-forward is dense."""
    n_dense = cfg["first_k_dense_replace"]
    dense = i < n_dense
    row = {"at": i, "ff": i, "mo": i - n_dense}
    want = ("at", "ff" if dense else "mo")
    return ({name: leaf[row[name[:2]]] for name, leaf in w.items()
             if name[:2] in want and name[2] == "_"}, dense)


def hidden(w: dict, ids, cfg: dict, quant=None, upto: int | None = None):
    """Hidden states ``(S, d)`` float32 of ONE sequence after ``upto``
    layers (all by default), before the final norm."""
    x = w["wte"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    n = cfg["num_hidden_layers"] if upto is None else upto
    for i in range(n):
        lw, dense = layer_weights(w, cfg, i)
        x = _layer(x, lw, static(cfg), dense, quant)
    return x


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm_f, head, eps, quant):
    x = _rms(x, norm_f.astype(jnp.float32), eps)
    return _mm(x, head.astype(jnp.float32), quant)


def logits(w: dict, ids, cfg: dict, quant=None, positions=None):
    """Float32 logits ``(S, V)`` of one sequence through the untied
    head, or only the rows ``positions``."""
    x = hidden(w, ids, cfg, quant)
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return _head(x, w["norm_f"], w["head"], cfg["rms_norm_eps"], quant)


def _row(prompt, served, pad_to):
    seq = list(prompt) + list(served)
    n, p = len(seq), len(prompt)
    row = jnp.asarray(seq + [0] * ((pad_to or n) - n), jnp.int32)
    return row, jnp.arange(p - 1, n - 1)


def _gaps(at, tokens):
    tokens = jnp.asarray(tokens, jnp.int32)
    return at.max(-1) - jnp.take_along_axis(at, tokens[:, None], -1)[:, 0]


def served_gaps(w: dict, prompt, served, cfg: dict,
                pad_to: int | None = None):
    """One forward over ``prompt + served``; for every served token the
    gap ``best logit - served token's logit`` at the position that
    predicted it (0 where the served token IS the reference's best).
    ``pad_to`` pads the row so every request shares one compiled shape
    (every layer is causal, so right-padding is harmless)."""
    row, pos = _row(prompt, served, pad_to)
    return _gaps(logits(w, row, cfg, None, pos), served)


def control_gaps(w: dict, prompt, served, cfg: dict, quant,
                 pad_to: int | None = None):
    """The control's reading on the same positions: the gap, in the
    REFERENCE's logits, of the token the lower-precision forward puts
    first."""
    row, pos = _row(prompt, served, pad_to)
    ref = logits(w, row, cfg, None, pos)
    return _gaps(ref, logits(w, row, cfg, quant, pos).argmax(-1))
