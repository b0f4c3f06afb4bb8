"""Loss functions the reference recipes use (torch.nn.functional there:
cross_entropy w/ label smoothing ref resnet.py:61, bce_with_logits ref
vae.py:112, mse ref adain.py:134-135). All reduce to scalar means and
compute in fp32 for bf16 safety.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("loss")
def cross_entropy(logits: jax.Array, labels: jax.Array,
                  label_smoothing: float = 0.0) -> jax.Array:
    """Softmax cross entropy with integer labels (+ label smoothing,
    ref resnet.py:61)."""
    logits = logits.astype(jnp.float32)
    n_classes = logits.shape[-1]
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(log_probs, labels[..., None], axis=-1)[..., 0]
    if label_smoothing:
        smooth = -log_probs.mean(axis=-1)
        nll = (1.0 - label_smoothing) * nll + label_smoothing * smooth
        del n_classes
    return nll.mean()


def lm_head_cross_entropy(hidden: jax.Array, table: jax.Array,
                          labels: jax.Array,
                          label_smoothing: float = 0.0,
                          chunk_size: int = 4096) -> jax.Array:
    """Mean cross-entropy of ``hidden @ table.T`` against ``labels``
    WITHOUT keeping the (T, vocab) logits alive.

    At GPT-2 vocab (50257), a (B·S, V) logits tensor is the single
    largest activation of the step (bf16, B=16, S=1024 → 1.6 GB), and
    autodiff saves it for backward. Here tokens stream through the head
    in ``chunk_size`` chunks under a ``lax.scan`` with per-chunk
    ``jax.checkpoint`` — peak logits memory is (chunk, V) and backward
    recomputes each chunk's matmul (MXU FLOPs for HBM, the standard
    trade on TPU). Same math as :func:`cross_entropy` on the full
    logits (tested to parity, grads included).

    ``hidden``: (..., d) — flattened internally; ``table``: (vocab, d)
    (an embedding table; pass ``head_kernel.T`` for an untied head).
    """
    d = hidden.shape[-1]
    x2 = hidden.reshape(-1, d)
    y = labels.reshape(-1)
    t = x2.shape[0]
    chunk = min(chunk_size, t)
    n_chunks = -(-t // chunk)
    pad = n_chunks * chunk - t
    x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    y = jnp.pad(y, (0, pad))
    valid = jnp.pad(jnp.ones((t,), jnp.float32), (0, pad))

    xs = x2.reshape(n_chunks, chunk, d)
    ys = y.reshape(n_chunks, chunk)
    vs = valid.reshape(n_chunks, chunk)

    def body(total, inp):
        xc, yc, mc = inp
        with jax.named_scope("head"):
            logits = (xc @ table.astype(xc.dtype).T).astype(jnp.float32)
        with jax.named_scope("loss"):
            log_probs = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                log_probs, yc[:, None], axis=-1)[:, 0]
            if label_smoothing:
                smooth = -log_probs.mean(axis=-1)
                nll = (1.0 - label_smoothing) * nll \
                    + label_smoothing * smooth
            return total + jnp.sum(nll * mc), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                            (xs, ys, vs))
    return total / t


def bce_with_logits(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Numerically-stable binary cross entropy from logits
    (ref vae.py:112)."""
    logits = logits.astype(jnp.float32)
    targets = targets.astype(jnp.float32)
    return jnp.mean(
        jnp.maximum(logits, 0.0) - logits * targets
        + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def mse_loss(pred: jax.Array, target: jax.Array) -> jax.Array:
    return jnp.mean(jnp.square(pred.astype(jnp.float32)
                               - target.astype(jnp.float32)))


def l2_loss(pred: jax.Array, target: jax.Array) -> jax.Array:
    return 0.5 * mse_loss(pred, target)


__all__ = ["bce_with_logits", "cross_entropy", "l2_loss",
           "lm_head_cross_entropy", "mse_loss"]
