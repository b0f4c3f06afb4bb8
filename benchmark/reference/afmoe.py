"""Plain AFMoE (``model_type: afmoe``; Trinity-Large-Preview) in
float32 ``jax.numpy``: the forward pass over a whole sequence.

The reference the trinity-large-preview cell's ``correct`` is decided
against, and the tier-1 parity tests' (tests/test_afmoe.py). No
kernel, no cache, no batching, no import from the program: the window
of a sliding layer is a MASK over the whole sequence (the program
keeps a ring of the last positions a slot and recovers each row's
position from the slot's length; this file never does). It reads
weights in ``benchmark/weights_afmoe.py``'s flat layout (a leaf
stacked over the layers of its kind) and the configuration file's
dict under the published keys, and runs every matrix product at
``highest`` precision (``reference/sarvam_mla.py``'s ``_mm``; that
file's small numeric helpers are shared, the equations are this
one's). One sequence at a time, one layer at a time: a layer's weights
are widened to float32 as the loop reaches it (an expert only at its
turn), attention goes through one KV head's six query heads and one
block of queries at a time, so that 48 heads x 17,152 positions fit
beside the weights, and the head only over the positions asked for.

    x0 = E[ids] * sqrt(d)                              (mup_enabled)
    h = x + N2(Attn_i(N1(x)));  y = h + N4(FF_i(N3(h)))   (sandwich)
    logits = RMSNorm(y_L) @ W_head
    RMSNorm: x * rsqrt(mean(x^2) + eps) * g            (eps 1e-5)
    attention:   q = u W_q (H x D), k = u W_k, v = u W_v (G x D),
                 g = u W_g (H x D); no biases
                 q_h <- RMSNorm_D(q_h), k_g <- RMSNorm_D(k_g)
                 (one gain a projection)
                 sliding_attention layers: RoPE (rotate-half over all
                 D lanes, theta) on q, k; key j visible to query i iff
                 0 <= i - j < window
                 full_attention layers: NO position encoding; causal
                 o_h = softmax(q_h . k_{h // (H/G)} / sqrt(D)) v
                 Attn = (o * sigmoid(g)) @ W_o
    dense FF:    (silu(u W1) * (u W3)) W2              (layers < n_dense)
    expert FF:   Shared(u) + sum_{e in sel, e held} w_e Expert_e(u)
                 s = sigmoid(u W_r) (E wide); sel = top_k(s + b);
                 w = route_scale * s[sel] / (sum(s[sel]) + 1e-20)

**The share.** ``experts_held = {first, count}``: the weights hold
experts ``first .. first + count - 1`` of the ``E`` the router scores.
A pair whose expert is not held keeps its place in the top-k and in
the renormalisation and adds nothing, here and in the program alike.
The vocabulary is the configuration's slice (a smaller vocabulary).

Every line the public config does not state is listed in the
configuration file's ``assumed``: the sandwich's four norms and where
they sit; the per-head q/k norm with one gain a projection, before
RoPE; RoPE on sliding layers only, rotate-half, all lanes; the gate as
a sigmoid on the attended values before ``W_o``; ``mup_enabled`` as
the embedding's ``sqrt(d)``; ``route_norm`` as the renormalisation
over the chosen scores with ``1e-20``; the selection bias used for
selection only; the router in float32; weights random from the seed.

``quant`` is the hook of the CONTROL, not of the reference: applied to
both operands of every matrix product (``fp8``: float8 e4m3, the
nearest precision under the bfloat16 the configuration states).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference.sarvam_mla import (  # noqa: F401  (fp8: the control's)
    _gaps, _head, _mm, _rms, _row, _swiglu, fp8)

Q_BLOCK = 256           # queries attended at a time, one KV head's
EXPERT = ("mo_w1", "mo_w3", "mo_w2")
SLIDING, FULL = "sliding_attention", "full_attention"


def _rope(x, theta):
    """Rotate-half over ALL lanes of (S, heads, D), position = row."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(u, lw, cfg, kind, quant=None):
    """Gated attention over the normed ``u (S, d)`` of a layer of
    ``kind``. KV heads go through one at a time and queries in blocks
    of ``Q_BLOCK`` (memory only: each head's sum is its own)."""
    s = u.shape[0]
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    eps, window = cfg["rms_norm_eps"], cfg["sliding_window"]
    rep = h // g
    q = _rms(_mm(u, lw["at_q"], quant).reshape(s, h, d), lw["at_qn"], eps)
    k = _rms(_mm(u, lw["at_k"], quant).reshape(s, g, d), lw["at_kn"], eps)
    v = _mm(u, lw["at_v"], quant).reshape(s, g, d)
    gate = jax.nn.sigmoid(_mm(u, lw["at_g"], quant))
    if kind == SLIDING:
        q, k = _rope(q, float(cfg["rope_theta"])), \
            _rope(k, float(cfg["rope_theta"]))
    block = min(Q_BLOCK, s)
    n_blocks = -(-s // block)
    qb = jnp.pad(q, ((0, n_blocks * block - s), (0, 0), (0, 0)))
    # (G, blocks, rep, block, D): a KV head's query heads, by block
    qb = qb.reshape(n_blocks, block, g, rep, d).transpose(2, 0, 3, 1, 4)
    cols = jnp.arange(s)

    def group(args):
        qg, kg, vg = args               # (blocks, rep, block, D), (S, D)

        def one(args):
            qh, lo = args
            scores = _mm(qh, kg.T, quant) / d ** 0.5    # (rep, block, S)
            back = (lo + jnp.arange(block))[:, None] - cols[None, :]
            seen = back >= 0
            if kind == SLIDING:
                seen = seen & (back < window)
            scores = jnp.where(seen, scores, -jnp.inf)
            return _mm(jax.nn.softmax(scores, axis=-1), vg, quant)

        return jax.lax.map(one, (qg, jnp.arange(n_blocks) * block))

    o = jax.lax.map(group, (qb, k.transpose(1, 0, 2),
                            v.transpose(1, 0, 2)))
    # (G, blocks, rep, block, D) -> (S, H * D), head = g * rep + r
    o = o.transpose(1, 3, 0, 2, 4).reshape(n_blocks * block, h * d)[:s]
    return _mm(o * gate, lw["at_out"], quant)


def route(u, lw, cfg, quant=None):
    """(weights (S, E) with zeros off the selection, selection (S, k)),
    over ALL ``E`` experts the router scores."""
    scores = jax.nn.sigmoid(_mm(u, lw["mo_gate"], quant))
    _, sel = jax.lax.top_k(scores + lw["mo_bias"],
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["route_norm"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]
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


@partial(jax.jit, static_argnames=("cfg", "kind", "dense", "quant"))
def _layer(x, lw, cfg, kind, dense, quant):
    cfg = dict(cfg)
    # the experts stay as stored until their turn in the scan
    lw = {k: t if k in EXPERT else t.astype(jnp.float32)
          for k, t in lw.items()}
    eps = cfg["rms_norm_eps"]
    x = x + _rms(attention(_rms(x, lw["at_n1"], eps), lw, cfg, kind,
                           quant), lw["at_n2"], eps)
    if dense:
        u = _rms(x, lw["ff_n3"], eps)
        m = _swiglu(u, lw["ff_w1"], lw["ff_w3"], lw["ff_w2"], quant)
        return x + _rms(m, lw["ff_n4"], eps)
    u = _rms(x, lw["mo_n3"], eps)
    m = shared(u, lw, quant) + routed(u, lw, cfg, quant)
    return x + _rms(m, lw["mo_n4"], eps)


def static(cfg: dict) -> tuple:
    """The numbers the layer functions read, hashable for ``jit``."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "sliding_window",
            "num_experts_per_tok", "route_norm", "route_scale")
    share = cfg.get("experts_held") or {"first": 0,
                                        "count": cfg["num_experts"]}
    return tuple((k, cfg[k]) for k in keys) + (
        ("held", (share["first"], share["count"])),)


def layer_weights(w: dict, cfg: dict, i: int) -> tuple[dict, str, bool]:
    """Layer ``i``'s rows of the flat leaves, its attention kind (a
    cut names the published layers it runs: ``layers_held`` indexes
    ``layer_types``) and whether its feed-forward is dense."""
    n_dense = cfg["num_dense_layers"]
    kept = cfg.get("layers_held", range(cfg["num_hidden_layers"]))
    dense = i < n_dense
    row = {"at": i, "ff": i, "mo": i - n_dense}
    want = ("at", "ff" if dense else "mo")
    return ({name: leaf[row[name[:2]]] for name, leaf in w.items()
             if name[:2] in want and name[2] == "_"},
            cfg["layer_types"][kept[i]], dense)


def hidden(w: dict, ids, cfg: dict, quant=None, upto: int | None = None):
    """Hidden states ``(S, d)`` float32 of ONE sequence after ``upto``
    layers (all by default), before the final norm."""
    x = w["wte"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    if cfg["mup_enabled"]:
        x = x * cfg["hidden_size"] ** 0.5
    n = cfg["num_hidden_layers"] if upto is None else upto
    for i in range(n):
        lw, kind, dense = layer_weights(w, cfg, i)
        x = _layer(x, lw, static(cfg), kind, dense, quant)
    return x


def logits(w: dict, ids, cfg: dict, quant=None, positions=None):
    """Float32 logits ``(S, V)`` of one sequence through the untied
    head, or only the rows ``positions``."""
    x = hidden(w, ids, cfg, quant)
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return _head(x, w["norm_f"], w["head"], cfg["rms_norm_eps"], quant)


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
