"""Plain GPT-2 in float32 ``jax.numpy``: forward, loss, gradient, AdamW.

The reference every cell's ``correct`` is decided against. It follows
the published model (pre-norm blocks, learned positions, ``gelu_new``,
tied head) with no kernel, cache, batching trick or import from the
program, reads weights in ``benchmark/weights.py``'s flat layout, and
runs every matrix product at ``highest`` precision (on a TPU a float32
product is otherwise computed in bfloat16 passes). Rows go through in
blocks so a full-width model fits beside nothing else on one chip.

``quant`` is the hook of the CONTROL, not of the reference: a function
applied to both operands of every matrix product (``fp8`` below rounds
them to float8 e4m3, the nearest precision under the bfloat16 the
configurations state). The reference itself passes ``None``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_KEYS = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
              "ln2_g", "ln2_b", "fc_w", "fc_b", "out_w", "out_b")


def fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 and back, gradient passed straight through
    (an e4m3 tangent would flush every small gradient to zero, which
    fails for the wrong reason)."""
    rounded = x.astype(jnp.float8_e4m3fn).astype(x.dtype)
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _ln(x, g, b, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lw, n_head, eps, quant):
    b, s, d = x.shape
    hd = d // n_head
    h = _ln(x, lw["ln1_g"], lw["ln1_b"], eps)
    qkv = _mm(h, lw["qkv_w"], quant) + lw["qkv_b"]
    q, k, v = (t.reshape(b, s, n_head, hd).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = _mm(q, k.transpose(0, 1, 3, 2), quant) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    o = _mm(attn, v, quant).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + _mm(o, lw["proj_w"], quant) + lw["proj_b"]
    h = _ln(x, lw["ln2_g"], lw["ln2_b"], eps)
    h = _gelu_new(_mm(h, lw["fc_w"], quant) + lw["fc_b"])
    return x + _mm(h, lw["out_w"], quant) + lw["out_b"]


def hidden(w: dict, ids: jax.Array, n_head: int, eps: float = 1e-5,
           quant=None) -> jax.Array:
    """Final-norm hidden states (B, S, d) in float32. Weights may be
    stored narrower (served bfloat16): each layer is widened as the
    scan reaches it, never the whole model at once."""
    f32 = lambda t: t.astype(jnp.float32)
    s = ids.shape[1]
    x = f32(w["wte"][ids]) + f32(w["wpe"][:s])[None]

    @jax.checkpoint
    def layer(x, lw):
        return _block(x, {k: f32(t) for k, t in lw.items()}, n_head, eps,
                      quant), None

    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in LAYER_KEYS})
    return _ln(x, f32(w["lnf_g"]), f32(w["lnf_b"]), eps)


def logits(w: dict, ids: jax.Array, n_head: int, eps: float = 1e-5,
           quant=None) -> jax.Array:
    """(B, S, V) float32 logits through the tied head."""
    h = hidden(w, ids, n_head, eps, quant)
    return _mm(h, w["wte"].astype(jnp.float32).T, quant)


def nll_sum(w, ids, labels, n_head, eps=1e-5, quant=None):
    """Sum over tokens of the next-token negative log-likelihood."""
    lg = logits(w, ids, n_head, eps, quant)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).sum()


@partial(jax.jit, static_argnames=("n_head", "eps", "quant"))
def _block_value_and_grad(w, ids, labels, n_head, eps, quant):
    return jax.value_and_grad(nll_sum)(w, ids, labels, n_head, eps, quant)


_add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))


def loss_and_grad(w: dict, blocks, n_head: int, eps: float = 1e-5,
                  quant=None):
    """Mean token loss and its gradient over one batch handed over as
    row blocks ``[(ids, labels), ...]``: a block at a time, so that a
    full-width model fits."""
    total, grads, n = 0.0, None, 0
    for ids, labels in blocks:
        val, g = _block_value_and_grad(w, ids, labels, n_head, eps, quant)
        total = total + val
        grads = g if grads is None else _add(grads, g)
        n += ids.size
    return total / n, jax.tree.map(lambda g: g / n, grads)


# ---------------------------------------------------------------------
# the optimizer the train cells state: clip, AdamW, warm-up schedule
# ---------------------------------------------------------------------

def lr_at(step: int, hyper: dict) -> float:
    """Linear warm-up from ``lr * initial_multiplier`` to ``lr`` over
    ``warmup`` steps (the only phase the first steps see)."""
    t = min(step / max(hyper["warmup"], 1), 1.0)
    lo = hyper["lr"] * hyper["initial_multiplier"]
    return lo + (hyper["lr"] - lo) * t


@jax.jit
def leaf_norms(tree):
    """Per-leaf L2 norms of a flat tree, the fused attention
    projection split into its q, k, v parts (a key's bias has no
    gradient under softmax and would hide in the fused leaf)."""
    out = {k: v for k, v in tree.items() if not k.startswith("qkv_")}
    for kind in ("w", "b"):
        for part, piece in zip("qkv", jnp.split(tree[f"qkv_{kind}"], 3, -1)):
            out[f"{part}_{kind}"] = piece
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)))), out)


@jax.jit
def _clip(grads, clip):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / (norm + 1e-6))
    return jax.tree.map(lambda g: g * scale, grads)


@jax.jit
def _adamw(w, g, m, v, t, lr, b1, b2, eps, wd):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)

    def upd(p, m, v):
        mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)

    return jax.tree.map(upd, w, m, v), m, v


def follow(w0: dict, batches, n_head: int, hyper: dict, *,
           eps: float = 1e-5, quant=None) -> dict:
    """Take ``len(batches)`` optimizer steps from ``w0`` on
    ``batches`` (each a list of row blocks) and report what the program is
    compared on: each step's loss, the per-leaf norm of the first
    gradient as the optimizer gets it (after clipping), and the
    per-leaf norm of the parameters' change after the last step."""
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    w, m, v = w0, zeros(w0), zeros(w0)
    losses, grad_norms = [], None
    for step, blocks in enumerate(batches):
        loss, g = loss_and_grad(w, blocks, n_head, eps, quant)
        g = _clip(g, hyper["clip"])
        if grad_norms is None:
            grad_norms = leaf_norms(g)
        w, m, v = _adamw(w, g, m, v, float(step + 1), lr_at(step, hyper),
                         hyper["b1"], hyper["b2"], hyper["adam_eps"],
                         hyper["weight_decay"])
        losses.append(float(loss))
    delta = leaf_norms(jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        w, w0))
    host = lambda t: {k: float(x) for k, x in t.items()}
    return {"losses": losses, "grad_norms": host(grad_norms),
            "delta_norms": host(delta)}


# ---------------------------------------------------------------------
# serving: how far below the reference's best a served token lies
# ---------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_head", "eps", "quant"))
def _row_logits(w, ids, n_head, eps, quant):
    return logits(w, ids[None], n_head, eps, quant)[0]


def served_gaps(w: dict, prompt, served, n_head: int, eps: float = 1e-5,
                pad_to: int | None = None):
    """One forward over ``prompt + served``; for every served token the
    gap ``best logit - served token's logit`` at the position that
    predicted it (0 where the served token IS the reference's best).
    ``pad_to`` pads the row so every request shares one compiled shape
    (causal attention makes right-padding harmless)."""
    seq = list(prompt) + list(served)
    n, p = len(seq), len(prompt)
    row = jnp.asarray(seq + [0] * ((pad_to or n) - n), jnp.int32)
    lg = _row_logits(w, row, n_head, eps, None)
    pos = jnp.arange(p - 1, n - 1)
    at = lg[pos]
    tok = jnp.asarray(served, jnp.int32)
    return at.max(-1) - jnp.take_along_axis(at, tok[:, None], -1)[:, 0]


def control_gaps(w: dict, prompt, served, n_head: int, quant,
                 eps: float = 1e-5, pad_to: int | None = None):
    """The control's reading on the same positions: the gap, in the
    REFERENCE's logits, of the token the lower-precision forward puts
    first."""
    seq = list(prompt) + list(served)
    n, p = len(seq), len(prompt)
    row = jnp.asarray(seq + [0] * ((pad_to or n) - n), jnp.int32)
    ref = _row_logits(w, row, n_head, eps, None)
    low = _row_logits(w, row, n_head, eps, quant)
    pos = jnp.arange(p - 1, n - 1)
    at, pick = ref[pos], low[pos].argmax(-1)
    return at.max(-1) - jnp.take_along_axis(at, pick[:, None], -1)[:, 0]
