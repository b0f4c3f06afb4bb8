"""Plain Bailing hybrid (``model_type: bailing_hybrid``; Ling-3.0-flash)
in float32 ``jax.numpy``: the forward pass over a whole sequence.

The reference the ling-3.0-flash cell's ``correct`` is decided
against, and the tier-1 parity tests' (tests/test_bailing_hybrid.py).
No kernel, no cache, no batching, no import from the program: Kimi
Delta Attention is the TOKEN-BY-TOKEN recurrence that defines it (the
program runs a prefill in its chunked form), latent attention the
EXPANDED form over a causal mask (the program decodes absorbed), the
rotary lanes INTERLEAVED as published (the program permutes them to
rotate-half's order), and the routed experts' group-limited selection
written out group by group. It reads weights in
``benchmark/weights_bailing_hybrid.py``'s flat layout and the
configuration file's dict under the published keys, and runs every
matrix product at ``highest`` precision. One sequence at a time, one
layer at a time: a layer's weights are widened to float32 as the loop
reaches it (the experts a block at a time, at their turn), attention
goes through in groups of heads and blocks of queries, and the head
only over the positions asked for.

**Departures from the published description** (each also in the
configuration file's ``assumed``):

- the multi-token-prediction module (``num_nextn_predict_layers`` 1,
  an MLA layer by ``mtp_use_kda: false``) is not here and not served:
  it only drafts tokens, and the engine's drafts are prompt lookups
  that cannot rewind a slot's state;
- the MLA ratio follows ``layer_group_size`` (6: five KDA layers to
  one MLA), not the "3 KDA : 1 MLA" of the family's description;
- the KDA in-projections' layout: ``q``, ``k``, ``v``, ``f`` and the
  output gate as five matrices, each of ``q``, ``k``, ``v`` with a
  4-tap convolution of its own (the config leaves the layout open;
  with random weights only the split matters);
- the bounded gate as the config's keys name it: ``g = kda_lower_bound
  * sigmoid(exp(A_log) * (u W_f + dt_bias))``, ``A_log`` a head,
  ``dt_bias`` a channel;
- ``use_qk_norm`` taken as the per-head query norm over ``n + r``
  lanes with the latent's norm on the key side (as sarvam's reading);
- weights random from the seed (``weights_bailing_hybrid.py``), the
  decays' spread and the router's selection bias included;
- one chip's share: experts ``experts_held`` of each layer's published
  count, the vocabulary's slice, the layers ``layers_held``.

The equations::

    x0 = E[ids];  h = x + Mixer_i(N(x));  y = h + FF_i(N(h))
    logits = N(y_L) @ W_head;   N(x) = x rsqrt(mean(x^2) + eps) w
    KDA (layer i with (i + 1) % layer_group_size != 0), H heads of D:
        q, k, v = silu(conv4(u W_q)), silu(conv4(u W_k)), silu(conv4(u W_v))
        q, k L2-normalised per head (eps 1e-6), q *= D^-1/2
        beta = sigmoid(u W_b) (a head)
        g = lb * sigmoid(exp(A_log_h) (u W_f + dt_bias)) (a channel)
        per head and token, S (D x D) from zeros:
            S <- Diag(e^g) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
        out = (N_D(o) * sigmoid(u W_g) per head) @ W_o
    MLA:
        q = u W_q per head [q_nope (n) | q_rope (r)];  q_h <- N_{n+r}(q_h)
        [c | k_r] = u W_dkv;  c <- N_c(c)
        RoPE (theta, INTERLEAVED pairs (2i, 2i+1), f_i = theta^(-2i/r))
        on q_rope and k_r;  [k_nope,h | v_h] = c W_ukv
        o_h = softmax((q_h . [k_nope,h | k_r]) (n+r)^-1/2, causal) v_h
        out = (o_h * sigmoid(u W_gate)_h, heads concatenated) @ W_o
    dense FF (layers below first_k_dense_replace): SwiGLU
    expert FF: Shared(u) + sum_{e in sel, e held} w_e Expert_e(u)
        s = sigmoid(u W_r) (E wide);  b the selection bias
        groups of E / n_group experts; a group scores the sum of its
        two highest s + b; the topk_group best groups are kept;
        sel = the top_k of s + b among their experts;
        w = routed_scaling_factor * s[sel] / sum(s[sel])

``quant`` is the hook of the CONTROL, not of the reference: applied to
both operands of every matrix product (``fp8``: float8 e4m3, the
nearest precision under the bfloat16 the configuration states).
``state_dtype`` is the other control's: ``S`` rounded to it after every
token (bfloat16, where the configuration states float32).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from reference.sarvam_mla import (  # noqa: F401  (fp8: the control's)
    _gaps, _mm, _rms, _row, _swiglu, fp8)

Q_BLOCK = 256           # queries attended at a time ...
H_BLOCK = 16            # ... by this many heads
EXPERT_BLOCK = 8        # routed experts computed at a time (8 x 16,640
                        # positions x 2,560 in float32: 1.4 GB a block)
EXPERT = ("mo_w1", "mo_w3", "mo_w2")
KDA, MLA = "kda", "mla"


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _conv(x, taps):
    """Causal depthwise convolution of ``x (S, C)`` by ``taps (K, C)``,
    then SiLU."""
    s, k = x.shape[0], taps.shape[0]
    pad = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(taps[j] * pad[j:j + s] for j in range(k)))


def kda(u, lw, cfg, quant=None, state_dtype=None):
    """Kimi Delta Attention over the normed ``u (S, d)``: the
    recurrence, one token at a time (a ``lax.scan`` over the
    sequence)."""
    s = u.shape[0]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    heads = lambda t: t.reshape(s, h, d)
    q = _l2(heads(_conv(_mm(u, lw["kd_q"], quant), lw["kd_cq"]))) \
        * d ** -0.5
    k = _l2(heads(_conv(_mm(u, lw["kd_k"], quant), lw["kd_ck"])))
    v = heads(_conv(_mm(u, lw["kd_v"], quant), lw["kd_cv"]))
    beta = jax.nn.sigmoid(_mm(u, lw["kd_b"], quant))
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(lw["kd_alog"])[:, None]
        * heads(_mm(u, lw["kd_f"], quant) + lw["kd_dt"]))

    def token(state, inp):
        qt, kt, vt, gt, bt = inp
        state = state * jnp.exp(gt)[:, :, None]
        delta = (vt - jnp.sum(state * kt[:, :, None], axis=1)) * bt[:, None]
        state = state + kt[:, :, None] * delta[:, None, :]
        if state_dtype is not None:
            state = state.astype(state_dtype).astype(jnp.float32)
        return state, jnp.sum(state * qt[:, :, None], axis=1)

    _, o = jax.lax.scan(token, jnp.zeros((h, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms(o, lw["kd_norm"], cfg["rms_norm_eps"]) \
        * jax.nn.sigmoid(heads(_mm(u, lw["kd_g"], quant)))
    return _mm(o.reshape(s, h * d), lw["kd_out"], quant)


def _rope(x, theta):
    """INTERLEAVED RoPE over (S, heads, r), position = row: lanes
    ``2i`` and ``2i + 1`` turned by ``theta^(-2i/r)``."""
    r = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None], jnp.sin(angles)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(x.shape)


def mla(u, lw, cfg, quant=None):
    """The expanded form over the normed ``u (S, d)``, head-gated. Heads
    go through in groups of ``H_BLOCK`` and queries in blocks of
    ``Q_BLOCK`` (memory only: each head's sum is its own, the output
    projection's rows are summed over the groups)."""
    s, _ = u.shape
    h, n, r = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
               cfg["qk_rope_head_dim"])
    c_dim, vd, eps = cfg["kv_lora_rank"], cfg["v_head_dim"], \
        cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    scale = (n + r) ** -0.5
    row = _mm(u, lw["ml_dkv"], quant)
    c = _rms(row[:, :c_dim], lw["ml_kvn"], eps)
    k_r = _rope(row[:, None, c_dim:], theta)                # (S, 1, r)
    gate = jax.nn.sigmoid(_mm(u, lw["ml_gate"], quant))     # (S, H)
    hb = math.gcd(h, H_BLOCK)
    block = min(Q_BLOCK, s)
    n_blocks = -(-s // block)
    by_group = lambda w, axis: jnp.moveaxis(
        w.reshape(*w.shape[:axis], h // hb, -1, *w.shape[axis + 1:]),
        axis, 0)

    def group(weights):
        w_q, w_ukv, w_o, g_h = weights
        q = _rms(_mm(u, w_q, quant).reshape(s, hb, n + r), lw["ml_qn"], eps)
        q = jnp.concatenate([q[..., :n], _rope(q[..., n:], theta)], -1)
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
        o = o.transpose(0, 2, 1, 3).reshape(n_blocks * block, hb, vd)[:s]
        o = o * g_h.T[:, :, None]                           # head-wise gate
        return _mm(o.reshape(s, hb * vd), w_o, quant)

    parts = jax.lax.map(group, (by_group(lw["ml_q"], 1),
                                by_group(lw["ml_ukv"], 1),
                                by_group(lw["ml_out"], 0),
                                gate.T.reshape(h // hb, hb, s)))
    return parts.sum(0)


def route(u, lw, cfg, quant=None):
    """(weights (S, E) with zeros off the selection, selection (S, k)),
    over ALL ``E`` experts the router scores, selected by group."""
    scores = jax.nn.sigmoid(_mm(u, lw["mo_gate"], quant))
    biased = scores + lw["mo_bias"]
    s, e = biased.shape
    n_group, keep = cfg["n_group"], cfg["topk_group"]
    groups = biased.reshape(s, n_group, e // n_group)
    # a group's score: the sum of its two highest s + b
    top2 = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)      # (S, groups)
    rank = jnp.argsort(-top2, axis=-1)
    kept = jnp.zeros((s, n_group), bool).at[
        jnp.arange(s)[:, None], rank[:, :keep]].set(True)
    allowed = jnp.repeat(kept, e // n_group, axis=-1)
    _, sel = jax.lax.top_k(jnp.where(allowed, biased, -jnp.inf),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    full = jnp.zeros_like(scores).at[
        jnp.arange(s)[:, None], sel].set(w)
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


@partial(jax.jit, static_argnames=("cfg", "kind", "dense", "quant",
                                   "state_dtype"))
def _layer(x, lw, cfg, kind, dense, quant, state_dtype):
    cfg = dict(cfg)
    # the experts stay as stored until their turn in the scan
    lw = {k: t if k in EXPERT else t.astype(jnp.float32)
          for k, t in lw.items()}
    eps = cfg["rms_norm_eps"]
    u = _rms(x, lw["ly_n1"], eps)
    x = x + (kda(u, lw, cfg, quant, state_dtype) if kind == KDA
             else mla(u, lw, cfg, quant))
    u = _rms(x, lw["ly_n2"], eps)
    if dense:
        return x + _swiglu(u, lw["ff_w1"], lw["ff_w3"], lw["ff_w2"], quant)
    return x + _swiglu(u, lw["mo_s1"], lw["mo_s3"], lw["mo_s2"], quant) \
        + routed(u, lw, cfg, quant)


def static(cfg: dict) -> tuple:
    """The numbers the layer functions read, hashable for ``jit``."""
    keys = ("num_attention_heads", "head_dim", "qk_nope_head_dim",
            "qk_rope_head_dim", "kv_lora_rank", "v_head_dim",
            "rms_norm_eps", "rope_theta", "kda_lower_bound",
            "num_experts_per_tok", "norm_topk_prob", "n_group",
            "topk_group", "routed_scaling_factor")
    share = cfg.get("experts_held") or {"first": 0,
                                        "count": cfg["num_experts"]}
    return tuple((k, cfg[k]) for k in keys) + (
        ("held", (share["first"], share["count"])),)


def layer_plan(cfg: dict) -> list[tuple[str, bool, dict]]:
    """Every layer held: its mixer's kind, whether its feed-forward is
    dense, and its row in each kind of leaf."""
    at = cfg.get("layers_held", list(range(cfg["num_hidden_layers"])))
    kinds = [MLA if (i + 1) % cfg["layer_group_size"] == 0 else KDA
             for i in at]
    dense = [i < cfg["first_k_dense_replace"] for i in at]
    plan = []
    for j, (kind, ff) in enumerate(zip(kinds, dense)):
        row = {"ly": j, "kd" if kind == KDA else "ml":
               kinds[:j].count(kind), "ff" if ff else "mo":
               dense[:j].count(ff)}
        plan.append((kind, ff, row))
    return plan


def hidden(w: dict, ids, cfg: dict, quant=None, state_dtype=None):
    """Hidden states ``(S, d)`` float32 of ONE sequence after every
    layer, before the final norm."""
    x = w["wte"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    for kind, dense, row in layer_plan(cfg):
        lw = {name: leaf[row[name[:2]]] for name, leaf in w.items()
              if name[:2] in row and name[2] == "_"}
        x = _layer(x, lw, static(cfg), kind, dense, quant, state_dtype)
    return x


def logits(w: dict, ids, cfg: dict, quant=None, positions=None,
           state_dtype=None):
    """Float32 logits ``(S, V)`` of one sequence through the untied
    head, or only the rows ``positions``."""
    x = hidden(w, ids, cfg, quant, state_dtype)
    if positions is not None:
        x = x[jnp.asarray(positions)]
    x = _rms(x, w["norm_f"].astype(jnp.float32), cfg["rms_norm_eps"])
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
