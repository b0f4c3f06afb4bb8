"""Plain Qwen3-Next (``model_type: qwen3_next``;
Qwen3-Next-80B-A3B-Instruct) in float32 ``jax.numpy``: the forward pass
over a whole sequence.

The reference the qwen3-next-80b-a3b cell's ``correct`` is decided
against, and the tier-1 parity tests' (tests/test_qwen3_next.py). No
kernel, no cache, no batching, no import from the program: the delta
rule is the TOKEN-BY-TOKEN recurrence that defines it (the program
runs a prefill in its chunked form), full attention a causal mask over
the whole sequence. It reads weights in
``benchmark/weights_qwen3_next.py``'s flat layout (a leaf stacked over
the layers of its kind) and the configuration file's dict under the
published keys, and runs every matrix product at ``highest`` precision.
One sequence at a time, one layer at a time: a layer's weights are
widened to float32 as the loop reaches it (the experts a block at a
time, at their turn), attention goes through one KV head's query heads
and one block of queries at a time, and the head only over the
positions asked for.

**Departures from the published description** (each also in the
configuration file's ``assumed``):

- the multi-token-prediction module the model card lists (``MTP 1``)
  is not here and not served: it only drafts tokens, and the
  published ``config`` does not describe it;
- the in-projections' layouts: ``[q | k | v | z]`` and ``[b | a]`` in
  that order, and the full layer's query and gate as two matrices
  (the published checkpoint interleaves them per head; with random
  weights only the split matters);
- weights random from the seed (``weights_qwen3_next.py``), the
  decays' spread included;
- one chip's share: experts ``experts_held`` of each layer's published
  count, the vocabulary's slice, the first ``num_hidden_layers``.

The equations::

    x0 = E[ids]
    h = x + Mixer_i(N0(x));  y = h + MoE(N0(h))
    logits = N0(y_L) @ W_head
    N0(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)          (eps 1e-6)
    linear layer (i + 1) % 4 != 0, Gated DeltaNet:
        [q | k | v | z] = u W_qkvz;  [b | a] = u W_ba
        [q | k | v] <- silu(causal depthwise conv, 4 taps, no bias)
        q, k L2-normalised per head (eps 1e-6), q *= d_k^-1/2;
        key head i repeated to value heads 2i, 2i + 1
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        per value head and token, S (d_k x d_v) from zeros:
            S <- e^g S;  S <- S + k (beta (v - S^T k))^T;  o = S^T q
        out = (RMSNorm(o) * w * silu(z) per head) @ W_out
    full layer, gated attention:
        q, g = u W_q, u W_g (H x D);  k, v = u W_k, u W_v (G x D)
        q_h <- N0_D(q_h), k_g <- N0_D(k_g);  RoPE (theta, rotate-half)
        on the first partial_rotary_factor x D lanes
        o_h = softmax(q_h . k_{h // (H/G)} / sqrt(D), causal) v
        out = (o * sigmoid(g)) @ W_o
    MoE: p = softmax(u W_r) over all E; sel = top_k(p);
        w = p[sel] / sum(p[sel]);  sum_{e in sel, e held} w_e Expert_e(u)
        + sigmoid(u w_sg) Shared(u);  Expert, Shared: SwiGLU

``quant`` is the hook of the CONTROL, not of the reference: applied to
both operands of every matrix product (``fp8``: float8 e4m3, the
nearest precision under the bfloat16 the configuration states).
``state_dtype`` is the other control's: ``S`` rounded to it after every
token (bfloat16, where the configuration states float32).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference.sarvam_mla import (  # noqa: F401  (fp8: the control's)
    _gaps, _mm, _row, _swiglu, fp8)

Q_BLOCK = 256           # queries attended at a time, one KV head's
EXPERT_BLOCK = 16       # routed experts computed at a time
EXPERT = ("mo_w1", "mo_w3", "mo_w2")
LINEAR, FULL = "linear_attention", "full_attention"


def _rms0(x, w, eps):
    """The zero-centred RMSNorm: the gain is ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _rope(x, theta, rot):
    """Rotate-half over the first ``rot`` lanes of (S, heads, D),
    position = row; the other lanes pass."""
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., rot:]], -1)


def linear_attention(u, lw, cfg, quant=None, state_dtype=None):
    """Gated DeltaNet over the normed ``u (S, d)``: the recurrence, one
    token at a time (a ``lax.scan`` over the sequence)."""
    s = u.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    key, conv_dim = hk * dk, 2 * hk * dk + hv * dv
    qkvz = _mm(u, lw["la_qkvz"], quant)
    ba = _mm(u, lw["la_ba"], quant)
    mixed, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:]
    pad = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(lw["la_conv"][j] * pad[j:j + s]
                            for j in range(taps)))
    q = _l2(mixed[:, :key].reshape(s, hk, dk)) * dk ** -0.5
    k = _l2(mixed[:, key:2 * key].reshape(s, hk, dk))
    v = mixed[:, 2 * key:].reshape(s, hv, dv)
    q, k = jnp.repeat(q, hv // hk, axis=1), jnp.repeat(k, hv // hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(lw["la_alog"]) * jax.nn.softplus(ba[:, hv:] + lw["la_dt"])

    def token(state, inp):
        qt, kt, vt, gt, bt = inp
        state = state * jnp.exp(gt)[:, None, None]
        delta = (vt - jnp.sum(state * kt[:, :, None], axis=1)) * bt[:, None]
        state = state + kt[:, :, None] * delta[:, None, :]
        if state_dtype is not None:
            state = state.astype(state_dtype).astype(jnp.float32)
        return state, jnp.sum(state * qt[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    eps = cfg["rms_norm_eps"]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * lw["la_norm"] * jax.nn.silu(z.reshape(s, hv, dv))
    return _mm(o.reshape(s, hv * dv), lw["la_out"], quant)


def full_attention(u, lw, cfg, quant=None):
    """Gated attention over the normed ``u (S, d)``, causal. KV heads go
    through one at a time and queries in blocks of ``Q_BLOCK`` (memory
    only: each head's sum is its own)."""
    s = u.shape[0]
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    eps, rep = cfg["rms_norm_eps"], h // g
    rot = int(d * cfg["partial_rotary_factor"])
    theta = float(cfg["rope_theta"])
    q = _rms0(_mm(u, lw["fa_q"], quant).reshape(s, h, d), lw["fa_qn"], eps)
    k = _rms0(_mm(u, lw["fa_k"], quant).reshape(s, g, d), lw["fa_kn"], eps)
    v = _mm(u, lw["fa_v"], quant).reshape(s, g, d)
    gate = jax.nn.sigmoid(_mm(u, lw["fa_g"], quant))
    q, k = _rope(q, theta, rot), _rope(k, theta, rot)
    block = min(Q_BLOCK, s)
    n_blocks = -(-s // block)
    qb = jnp.pad(q, ((0, n_blocks * block - s), (0, 0), (0, 0)))
    # (G, blocks, rep, block, D): a KV head's query heads, by block
    qb = qb.reshape(n_blocks, block, g, rep, d).transpose(2, 0, 3, 1, 4)
    cols = jnp.arange(s)

    def group(args):
        qg, kg, vg = args

        def one(args):
            qh, lo = args
            scores = _mm(qh, kg.T, quant) / d ** 0.5
            seen = (lo + jnp.arange(block))[:, None] >= cols[None, :]
            scores = jnp.where(seen, scores, -jnp.inf)
            return _mm(jax.nn.softmax(scores, axis=-1), vg, quant)

        return jax.lax.map(one, (qg, jnp.arange(n_blocks) * block))

    o = jax.lax.map(group, (qb, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 3, 0, 2, 4).reshape(n_blocks * block, h * d)[:s]
    return _mm(o * gate, lw["fa_out"], quant)


def route(u, lw, cfg, quant=None):
    """(weights (S, E) with zeros off the selection, selection (S, k)),
    softmax over ALL ``E`` experts the router scores."""
    probs = jax.nn.softmax(_mm(u, lw["mo_gate"], quant), axis=-1)
    top, sel = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    full = jnp.zeros_like(probs).at[
        jnp.arange(u.shape[0])[:, None], sel].set(top)
    return full, sel


def routed(u, lw, cfg, quant=None):
    """The routed experts' part of an expert layer as THIS share gives
    it: every held expert on every token, the others' weight 0; the
    held experts ``EXPERT_BLOCK`` at a time."""
    full, _ = route(u, lw, cfg, quant)
    first, count = cfg["held"]
    f32 = lambda t: t.astype(jnp.float32)
    weights = full.T[first:first + count]               # (count, S)
    blocks = -(-count // EXPERT_BLOCK)
    pad = blocks * EXPERT_BLOCK - count
    stack = lambda t: jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)
                              ).reshape(blocks, EXPERT_BLOCK, *t.shape[1:])

    def block(acc, experts):
        w1, w3, w2, w_e = experts
        one = lambda a, b, c: _swiglu(u, f32(a), f32(b), f32(c), quant)
        out = jax.vmap(one)(w1, w3, w2)                 # (block, S, d)
        return acc + jnp.einsum("es,esd->sd", w_e, out,
                                precision=jax.lax.Precision.HIGHEST), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(u),
                          tuple(map(stack, (lw["mo_w1"], lw["mo_w3"],
                                            lw["mo_w2"], weights))))
    return out


def shared(u, lw, quant=None):
    """The shared expert, scaled by its sigmoid gate."""
    return _swiglu(u, lw["mo_s1"], lw["mo_s3"], lw["mo_s2"], quant) \
        * jax.nn.sigmoid(_mm(u, lw["mo_sg"], quant))


@partial(jax.jit, static_argnames=("cfg", "kind", "quant", "state_dtype"))
def _layer(x, lw, cfg, kind, quant, state_dtype):
    cfg = dict(cfg)
    # the experts stay as stored until their turn in the scan
    lw = {k: t if k in EXPERT else t.astype(jnp.float32)
          for k, t in lw.items()}
    eps = cfg["rms_norm_eps"]
    u = _rms0(x, lw["ly_n1"], eps)
    x = x + (linear_attention(u, lw, cfg, quant, state_dtype)
             if kind == LINEAR else full_attention(u, lw, cfg, quant))
    u = _rms0(x, lw["ly_n2"], eps)
    return x + shared(u, lw, quant) + routed(u, lw, cfg, quant)


def static(cfg: dict) -> tuple:
    """The numbers the layer functions read, hashable for ``jit``."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "partial_rotary_factor",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "num_experts_per_tok",
            "norm_topk_prob")
    share = cfg.get("experts_held") or {"first": 0,
                                        "count": cfg["num_experts"]}
    return tuple((k, cfg[k]) for k in keys) + (
        ("held", (share["first"], share["count"])),)


def layer_kinds(cfg: dict) -> list[str]:
    every = cfg["full_attention_interval"]
    return [FULL if (i + 1) % every == 0 else LINEAR
            for i in range(cfg["num_hidden_layers"])]


def layer_weights(w: dict, cfg: dict, i: int) -> tuple[dict, str]:
    """Layer ``i``'s rows of the flat leaves and its mixer's kind."""
    kinds = layer_kinds(cfg)
    kind = kinds[i]
    row = {"ly": i, "mo": i, "la" if kind == LINEAR else "fa":
           kinds[:i].count(kind)}
    return ({name: leaf[row[name[:2]]] for name, leaf in w.items()
             if name[:2] in row and name[2] == "_"}, kind)


def hidden(w: dict, ids, cfg: dict, quant=None, state_dtype=None):
    """Hidden states ``(S, d)`` float32 of ONE sequence after every
    layer, before the final norm."""
    x = w["wte"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        lw, kind = layer_weights(w, cfg, i)
        x = _layer(x, lw, static(cfg), kind, quant, state_dtype)
    return x


def logits(w: dict, ids, cfg: dict, quant=None, positions=None,
           state_dtype=None):
    """Float32 logits ``(S, V)`` of one sequence through the untied
    head, or only the rows ``positions``."""
    x = hidden(w, ids, cfg, quant, state_dtype)
    if positions is not None:
        x = x[jnp.asarray(positions)]
    x = _rms0(x, w["norm_f"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _mm(x, w["head"].astype(jnp.float32), quant)


def served_gaps(w: dict, prompt, served, cfg: dict,
                pad_to: int | None = None):
    """One forward over ``prompt + served``; for every served token the
    gap ``best logit - served token's logit`` at the position that
    predicted it (0 where the served token IS the reference's best).
    ``pad_to`` pads the row so every request shares one compiled shape
    (every layer is causal, so right-padding is harmless)."""
    row, pos = _row(prompt, served, pad_to)
    return _gaps(logits(w, row, cfg, None, pos), served)


def control_gaps(w: dict, prompt, served, cfg: dict, quant=None,
                 pad_to: int | None = None, state_dtype=None):
    """A control's reading on the same positions: the gap, in the
    REFERENCE's logits, of the token the lower-precision forward
    (``quant`` on every product, or the state kept in
    ``state_dtype``) puts first."""
    row, pos = _row(prompt, served, pad_to)
    ref = logits(w, row, cfg, None, pos)
    low = logits(w, row, cfg, quant, pos, state_dtype)
    return _gaps(ref, low.argmax(-1))
