"""Plain LFM2-MoE in float32 ``jax.numpy``: the forward pass.

The reference the LFM2 cell's ``correct`` is decided against, and the
tier-1 parity tests' (tests/test_lfm2.py). It follows the published
model's equations with no kernel, cache, batching or import from the
program, reads weights in ``benchmark/weights_lfm2.py``'s flat layout
(a leaf stacked over the layers of its kind) and the configuration
file's dict under the published keys, and runs every matrix product at
``highest`` precision. One sequence at a time, one layer at a time: a
layer's weights are widened to float32 as the loop reaches it (the
bfloat16 model stays as it is stored), attention goes through in
blocks of queries and the head only over the positions asked for.

    h0 = E[ids];  x = h + Mixer_i(RMSNorm(h));  h = x + FF_i(RMSNorm(x))
    logits = RMSNorm(h_L) @ E^T
    RMSNorm: x * rsqrt(mean(x^2) + eps) * g
    conv mixer:  [B, C, X] = split3(u @ W_in);  z = B * X
                 c_t = sum_j w[j] * z_{t-(K-1)+j}  (z_t = 0 for t < 0)
                 y = (C * c) @ W_out
    attention:   q, k, v = split(u @ W_qkv) (H / G / G heads of hd)
                 q, k <- RMSNorm_hd(q), RMSNorm_hd(k); RoPE (rotate-
                 half, theta, all hd dims); causal softmax(q k^T /
                 sqrt(hd)) v, KV head g serving query heads g*H/G ..;
                 @ W_o
    dense FF:    (silu(u @ W1) * (u @ W3)) @ W2
    expert FF:   s = sigmoid(u @ W_g); sel = top_k(s + b);
                 w = scale * s[sel] / (sum(s[sel]) + 1e-6)
                 out = sum_{e in sel} w_e * Expert_e(u)   (every
                 expert computed on every token, the others' weight 0)

Departures from the published model, all listed in the configuration
file's ``assumed``: the head is tied to the embedding (the config row
has no ``tie_word_embeddings`` key); the router's scores and top-k are
float32; the ``1e-6`` in the renormalisation; q, k and v are one fused
matrix ``[W_q | W_k | W_v]`` (the same product); weights are random
from the seed.

``quant`` is the hook of the CONTROL, not of the reference: applied to
both operands of every matrix product (``fp8``: float8 e4m3, the
nearest precision under the bfloat16 the configuration states).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024          # queries attended at a time


def fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 and back."""
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotate-half over (S, heads, hd), position = row."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _conv_mixer(u, lw, cfg, quant):
    gate_b, gate_c, x = jnp.split(_mm(u, lw["cv_in"], quant), 3, axis=-1)
    z = gate_b * x
    taps = lw["cv_w"]
    k, s = taps.shape[0], z.shape[0]
    padded = jnp.pad(z, ((k - 1, 0), (0, 0)))
    c = sum(taps[j] * padded[j:j + s] for j in range(k))
    return _mm(gate_c * c, lw["cv_out"], quant)


def _attn_mixer(u, lw, cfg, quant):
    s, d = u.shape
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = d // h, cfg["norm_eps"]
    qkv = _mm(u, lw["at_qkv"], quant)
    q = qkv[:, :h * hd].reshape(s, h, hd)
    k = qkv[:, h * hd:(h + g) * hd].reshape(s, g, hd)
    v = qkv[:, (h + g) * hd:].reshape(s, g, hd)
    q = _rope(_rms(q, lw["at_qn"], eps), cfg["rope_theta"])
    k = _rope(_rms(k, lw["at_kn"], eps), cfg["rope_theta"])
    # every query head gets its KV head's rows
    k = jnp.repeat(k, h // g, axis=1).transpose(1, 2, 0)     # (h, hd, s)
    v = jnp.repeat(v, h // g, axis=1).transpose(1, 0, 2)     # (h, s, hd)
    outs = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK].transpose(1, 0, 2)           # (h, b, hd)
        scores = _mm(qb, k, quant) / math.sqrt(hd)
        rows = lo + jnp.arange(qb.shape[1])
        scores = jnp.where(rows[:, None] >= jnp.arange(s)[None, :],
                           scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1)
        outs.append(_mm(attn, v, quant).transpose(1, 0, 2))
    o = jnp.concatenate(outs).reshape(s, h * hd)
    return _mm(o, lw["at_out"], quant)


def _swiglu(u, w1, w3, w2, quant):
    return _mm(jax.nn.silu(_mm(u, w1, quant)) * _mm(u, w3, quant), w2,
               quant)


def route(u, lw, cfg, quant=None):
    """(weights (S, E) with zeros off the selection, selection (S, k))."""
    scores = jax.nn.sigmoid(_mm(u, lw["mo_gate"], quant))
    k = cfg["num_experts_per_tok"]
    biased = scores + lw["mo_bias"] if cfg["use_expert_bias"] else scores
    _, sel = jax.lax.top_k(biased, k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    w = w * cfg["routed_scaling_factor"]
    full = jnp.zeros_like(scores).at[
        jnp.arange(u.shape[0])[:, None], sel].set(w)
    return full, sel


def _moe_ff(u, lw, cfg, quant):
    full, _ = route(u, lw, cfg, quant)
    f32 = lambda t: t.astype(jnp.float32)

    def one(acc, expert):
        w1, w3, w2, w_e = expert
        return acc + w_e[:, None] * _swiglu(u, f32(w1), f32(w3), f32(w2),
                                            quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (lw["mo_w1"], lw["mo_w3"], lw["mo_w2"], full.T))
    return out


@partial(jax.jit, static_argnames=("cfg", "kind", "dense", "quant"))
def _layer(x, lw, cfg, kind, dense, quant):
    cfg = dict(cfg)
    # the experts stay as stored until their turn in the scan
    lw = {k: t if k in ("mo_w1", "mo_w3", "mo_w2")
          else t.astype(jnp.float32) for k, t in lw.items()}
    eps = cfg["norm_eps"]
    if kind == "conv":
        x = x + _conv_mixer(_rms(x, lw["cv_norm"], eps), lw, cfg, quant)
    else:
        x = x + _attn_mixer(_rms(x, lw["at_norm"], eps), lw, cfg, quant)
    if dense:
        u = _rms(x, lw["ff_norm"], eps)
        return x + _swiglu(u, lw["ff_w1"], lw["ff_w3"], lw["ff_w2"], quant)
    return x + _moe_ff(_rms(x, lw["mo_norm"], eps), lw, cfg, quant)


def _static(cfg: dict) -> tuple:
    """The numbers the layer functions read, hashable for ``jit``."""
    keys = ("num_attention_heads", "num_key_value_heads", "norm_eps",
            "rope_theta", "num_experts_per_tok", "use_expert_bias",
            "norm_topk_prob", "routed_scaling_factor")
    return tuple((k, cfg[k]) for k in keys)


def layer_weights(w: dict, cfg: dict, i: int) -> tuple[dict, str, bool]:
    """Layer ``i``'s rows of the flat leaves, its mixer kind and
    whether its feed-forward is dense."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    kind = kinds[i]
    dense = i < cfg["num_dense_layers"]
    row = {"cv": kinds[:i].count("conv"),
           "at": kinds[:i].count("full_attention"),
           "ff": i, "mo": i - cfg["num_dense_layers"]}
    want = ("cv" if kind == "conv" else "at", "ff" if dense else "mo")
    return ({name: leaf[row[name[:2]]] for name, leaf in w.items()
             if name[:2] in want and name[2] == "_"}, kind, dense)


def hidden(w: dict, ids, cfg: dict, quant=None, upto: int | None = None):
    """Hidden states ``(S, d)`` float32 of ONE sequence after ``upto``
    layers (all by default), before the final norm."""
    x = w["wte"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    n = cfg["num_hidden_layers"] if upto is None else upto
    for i in range(n):
        lw, kind, dense = layer_weights(w, cfg, i)
        x = _layer(x, lw, _static(cfg), kind, dense, quant)
    return x


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, norm_f, wte, eps, quant):
    x = _rms(x, norm_f.astype(jnp.float32), eps)
    return _mm(x, wte.astype(jnp.float32).T, quant)


def logits(w: dict, ids, cfg: dict, quant=None, positions=None):
    """Float32 logits ``(S, V)`` of one sequence through the tied
    head, or only the rows ``positions``."""
    x = hidden(w, ids, cfg, quant)
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return _head(x, w["norm_f"], w["wte"], cfg["norm_eps"], quant)


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
