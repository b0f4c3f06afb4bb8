"""Quantized weight serving: int8/int4 params with in-matmul dequant.

Decode is HBM-bandwidth-bound, and at serving batch sizes the WEIGHT
stream (every block kernel + the LM-head table, re-read per step) is
the larger term next to the already-int8 KV pages (PR 8 / PR 16). This
module narrows that stream the same way the KV path did: store the
bytes narrow, widen INSIDE the matmul's operand read.

Two formats, selected by ``serving.weights.dtype`` (config.py) and
distinguished in the tree by the ``qkernel`` leaf dtype — never by a
static flag, so every compiled path dispatches on tree structure alone:

- **int8** — symmetric per-OUTPUT-CHANNEL absmax (``scale =
  absmax/127`` over the input axis, the same absmax convention as
  ``comms/quantized.py``'s per-bucket transport quantizer, minus its
  stochastic rounding: a one-shot weight pass wants deterministic
  round-to-nearest). Per-output-channel scales FACTOR OUT of the dot —
  ``y = (x @ q) * s`` — so the kernel streams 1 byte/elem and the
  int8→compute widening fuses into the dot's operand read exactly like
  the int8 KV pages' (models/gpt.py ``_grouped_cache_attention``).
  The factored form also commutes with the serving-tp layout
  (serving/tp.py): row-parallel partial products psum BEFORE the
  (replicated or column-sharded) scale multiply touches them.
- **int4** — per-GROUP absmax along the INPUT axis (``group_size``
  consecutive input rows share a ``absmax/7`` scale), two values
  packed per byte (even input index = low nibble, stored offset-8 in
  ``[1, 15]``), ``qkernel`` dtype **uint8** at half the input length.
  Group scales do NOT factor out of the dot, so the int4 path unpacks
  to compute dtype right before the matmul — the HBM stream is still
  0.5 byte/elem + scales; the widening is exactly the fused convert
  the int8 path relies on, applied pre-dot. int4 rounding costs real
  logit error — ``tests/test_quant_lora.py`` holds int4 to a bounded
  dequantization error and int8 to exact greedy parity.

The token embedding (``wte``) quantizes to int8 PER-ROW in both modes
(``qtable`` + ``qscale (vocab, 1)``): rows must stay gather-addressable
for the embedding lookup (a grouped int4 row would need an unpack per
gathered token), and under tied embeddings the LM head's
``x @ table.T`` re-reads the FULL table every step — leaving it bf16
would cap the modeled bytes/step win well under the 1.9× gate.
``wpe``, layer norms, biases, and MoE expert tensors stay full
precision (position/norm/bias bytes are noise next to the kernels;
expert streaming has its own roofline).

``quantize_params`` is a ONE-SHOT host-side pass at engine build time
(ServingConfig.make) — never inside a compiled step. Quantize BEFORE
``qkv_to_tp_major``: the permute takes qkernel/qscale along their
output axis like any other column layout fact (models/gpt.py).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

# dense sub-dicts under params["blocks"] whose kernels quantize; MoE
# tensors (moe_*) and norms deliberately absent
_BLOCK_KERNELS = ("attn_qkv", "attn_proj", "mlp_fc1", "mlp_fc2",
                  "mlp_fc3")


def _quantize_int8(kernel: jax.Array) -> dict:
    """Per-output-channel symmetric int8: scale over the input axis
    (-2), shape ``(..., 1, dout)`` fp32 — broadcastable against the
    dot output after the input axis contracts away."""
    k32 = kernel.astype(jnp.float32)
    scale = jnp.max(jnp.abs(k32), axis=-2, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(k32 / scale), -127, 127).astype(jnp.int8)
    return {"qkernel": q, "qscale": scale}


def _quantize_int4(kernel: jax.Array, group_size: int) -> dict:
    """Per-(input-group, output-channel) int4: ``group_size``
    consecutive input rows share an ``absmax/7`` scale; values stored
    offset-8 (``[1, 15]``, level 0 = code 8) and packed two per byte
    along the INPUT axis — even input index in the low nibble."""
    din = kernel.shape[-2]
    if group_size < 2 or group_size % 2:
        raise ValueError(
            f"weights.group_size must be an even int >= 2, got "
            f"{group_size}")
    if din % group_size:
        raise ValueError(
            f"weights.group_size={group_size} does not divide the "
            f"kernel input dim {din} — int4 groups must tile the "
            "input axis exactly")
    lead = kernel.shape[:-2]
    dout = kernel.shape[-1]
    k32 = kernel.astype(jnp.float32).reshape(
        *lead, din // group_size, group_size, dout)
    scale = jnp.max(jnp.abs(k32), axis=-2, keepdims=True) / 7.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(k32 / scale), -7, 7).astype(jnp.int32)
    q = (q + 8).reshape(*lead, din, dout).astype(jnp.uint8)
    packed = (q[..., 0::2, :] | (q[..., 1::2, :] << 4)).astype(
        jnp.uint8)
    return {"qkernel": packed, "qscale": scale[..., 0, :]}


def _unpack_int4(qkernel: jax.Array, qscale: jax.Array,
                 dtype: Any) -> jax.Array:
    """Packed ``(..., din/2, dout)`` uint8 + ``(..., G, dout)`` group
    scales -> full ``(..., din, dout)`` kernel in ``dtype``. Written
    so the uint8 stream is the only HBM-side read and the widening
    happens on the way into the consuming dot."""
    lo = (qkernel & 0xF).astype(jnp.int8) - 8
    hi = (qkernel >> 4).astype(jnp.int8) - 8
    lead = qkernel.shape[:-2]
    din = qkernel.shape[-2] * 2
    dout = qkernel.shape[-1]
    k = jnp.stack([lo, hi], axis=-2)          # (..., din/2, 2, dout)
    n_groups = qscale.shape[-2]
    k = k.reshape(*lead, n_groups, din // n_groups, dout)
    k = k.astype(jnp.float32) * qscale[..., :, None, :]
    return k.reshape(*lead, din, dout).astype(dtype)


def qmatmul(params: dict, x: jax.Array) -> jax.Array:
    """``x @ dequant(kernel)`` for a quantized dense dict (no bias —
    the callers' bias handling is format-independent). int8: the dot
    runs over the 1-byte kernel and the per-output-channel scale
    applies to the (small) output. int4: unpack-to-compute-dtype feeds
    the dot directly. Shape-agnostic, so tp-sharded per-rank slices
    (serving/tp.py) flow through unchanged — the int8 scale multiply
    commutes with the row-parallel psum because every rank holds the
    same (or its own column slice of the) output-channel scales."""
    q = params["qkernel"]
    s = params["qscale"]
    if q.dtype == jnp.int8:
        y = x @ q.astype(x.dtype)
        return y * s[..., 0, :].astype(x.dtype)
    if q.dtype == jnp.uint8:
        return x @ _unpack_int4(q, s, x.dtype)
    raise ValueError(
        f"qkernel dtype {q.dtype} is not a quantized weight format "
        "(int8 = per-channel, uint8 = packed int4)")


def dequant_kernel(params: dict, dtype: Any = jnp.float32) -> jax.Array:
    """Full-precision reconstruction of one quantized dense kernel —
    offline consumers only (``GPT.head_table``, parity tests); the
    serving hot paths go through :func:`qmatmul` and never
    materialize this."""
    q = params["qkernel"]
    s = params["qscale"]
    if q.dtype == jnp.int8:
        return (q.astype(jnp.float32) * s).astype(dtype)
    return _unpack_int4(q, s, dtype)


def _quantize_table(table: jax.Array) -> dict:
    """Per-row int8 for the embedding table: ``qtable (vocab, d)`` +
    ``qscale (vocab, 1)`` fp32 — rows gather whole (embedding lookup)
    and the scale rides the vocab axis of the tied head's
    ``x @ table.T`` output."""
    t32 = table.astype(jnp.float32)
    scale = jnp.max(jnp.abs(t32), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(t32 / scale), -127, 127).astype(jnp.int8)
    return {"qtable": q, "qscale": scale}


def quantize_params(params: dict, dtype: str = "int8",
                    group_size: int = 64) -> dict:
    """One-shot weight quantization pass over a GPT params tree:
    every block dense kernel (attn_qkv/attn_proj/mlp_fc1/fc2/fc3) and
    the untied head kernel move to ``qkernel``/``qscale`` in the
    requested format; ``wte`` moves to per-row int8 ``qtable``/
    ``qscale`` in BOTH formats (gather-addressable rows — see module
    docstring). Biases, norms, ``wpe``, MoE experts, and the
    ``_tp_major`` marker pass through untouched. Idempotence is
    rejected loudly — re-quantizing quantized params would silently
    square the rounding error."""
    if dtype not in ("int8", "int4"):
        raise ValueError(
            f"weights dtype must be 'int8' or 'int4', got {dtype!r}")
    if is_quantized(params):
        raise ValueError(
            "params are already weight-quantized "
            f"({weights_dtype(params)}) — a second quantize_params "
            "pass would re-round already-rounded values")

    def q_dense(p: dict) -> dict:
        out = {k: v for k, v in p.items() if k != "kernel"}
        if dtype == "int8":
            out.update(_quantize_int8(p["kernel"]))
        else:
            out.update(_quantize_int4(p["kernel"], group_size))
        return out

    blocks = dict(params["blocks"])
    for name in _BLOCK_KERNELS:
        if name in blocks:
            blocks[name] = q_dense(blocks[name])
    out = {**params, "blocks": blocks}
    out["wte"] = {k: v for k, v in params["wte"].items()
                  if k != "table"}
    out["wte"].update(_quantize_table(params["wte"]["table"]))
    if "head" in params:
        out["head"] = q_dense(params["head"])
    return out


def is_quantized(params: dict) -> bool:
    """True when the tree carries quantized weights (the ``qtable``
    leaf — wte quantizes in every format, so it is the reliable
    witness)."""
    return "qtable" in params.get("wte", {})


def weights_dtype(params: dict) -> str:
    """``"bf16"`` (meaning: full-precision kernels, whatever their
    float dtype), ``"int8"``, or ``"int4"`` — read off the tree
    structure, the same dispatch the compiled paths use."""
    if not is_quantized(params):
        return "bf16"
    qkv = params.get("blocks", {}).get("attn_qkv", {})
    q = qkv.get("qkernel")
    if q is not None and q.dtype == jnp.uint8:
        return "int4"
    return "int8"


def weight_stream_bytes(params: dict) -> int:
    """Modeled per-decode-step weight HBM bytes: every block dense
    leaf (kernel or qkernel+qscale, plus bias), the LM head (untied
    kernel, or the tied wte table the head matmul re-reads whole),
    and the final norm. Embedding GATHERS (a few rows) and ``wpe``
    are excluded — they do not scale with the stream. This is the
    numerator/denominator of the modeled ratio in
    docs/performance.md's "Quantized-weight roofline" section; host
    arithmetic only."""
    total = 0

    def leaf_bytes(p: dict) -> int:
        n = 0
        for key in ("kernel", "qkernel", "qscale", "bias"):
            if key in p:
                leaf = p[key]
                n += leaf.size * jnp.dtype(leaf.dtype).itemsize
        return n

    for name in _BLOCK_KERNELS:
        if name in params["blocks"]:
            total += leaf_bytes(params["blocks"][name])
    if "head" in params:
        total += leaf_bytes(params["head"])
    else:
        wte = params["wte"]
        for key in ("table", "qtable", "qscale"):
            if key in wte:
                leaf = wte[key]
                total += leaf.size * jnp.dtype(leaf.dtype).itemsize
    for key in ("scale", "bias"):
        if key in params.get("ln_f", {}):
            leaf = params["ln_f"][key]
            total += leaf.size * jnp.dtype(leaf.dtype).itemsize
    return int(total)


__all__ = ["dequant_kernel", "is_quantized", "qmatmul",
           "quantize_params", "weight_stream_bytes", "weights_dtype"]
