"""Functional neural-net layers: init fns returning plain dicts, apply
fns taking (params, x).

The building blocks for the model zoo. Conventions:
- images are NHWC (batch, height, width, channels) — channels ride the
  TPU lane dimension so convs tile straight onto the MXU;
- params are nested dicts of jnp arrays; init fns split their key as
  needed; dtype of params defaults to fp32 (master weights), compute
  casting is the caller's choice;
- every apply fn is shape-polymorphic over the batch dim and jit-safe
  (no python control flow on traced values).
"""
from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax


# =========================================================================
# Initializers
# =========================================================================

def _fan_in_scale(rng: jax.Array, shape: Sequence[int], fan_in: int,
                  dtype: Any, distribution: str = "uniform") -> jax.Array:
    """Kaiming/LeCun-style fan-in scaled init (torch Linear/Conv default
    is kaiming-uniform with a=sqrt(5) → uniform(±1/sqrt(fan_in)))."""
    if distribution == "uniform":
        bound = 1.0 / math.sqrt(fan_in)
        return jax.random.uniform(rng, shape, dtype, -bound, bound)
    std = math.sqrt(2.0 / fan_in)
    return jax.random.normal(rng, shape, dtype) * std


def normal_init(rng: jax.Array, shape: Sequence[int], std: float = 0.02,
                dtype: Any = jnp.float32) -> jax.Array:
    return jax.random.normal(rng, shape, dtype) * std


# =========================================================================
# Dense
# =========================================================================

def dense_init(rng: jax.Array, din: int, dout: int, use_bias: bool = True,
               std: float | None = None, dtype: Any = jnp.float32) -> dict:
    kr, _ = jax.random.split(rng)
    if std is None:
        kernel = _fan_in_scale(kr, (din, dout), din, dtype)
    else:
        kernel = normal_init(kr, (din, dout), std, dtype)
    params = {"kernel": kernel}
    if use_bias:
        params["bias"] = jnp.zeros((dout,), dtype)
    return params


def dense(params: dict, x: jax.Array) -> jax.Array:
    if "qkernel" in params:
        # quantized weight serving (models/quant.py): the kernel
        # streams 1 byte/elem (0.5 packed int4) and widens inside the
        # dot's operand read — the same fused-convert contract as the
        # int8 KV pages
        from torchbooster_tpu.models.quant import qmatmul

        y = qmatmul(params, x)
    else:
        y = x @ params["kernel"].astype(x.dtype)
    if "bias" in params:
        y = y + params["bias"].astype(x.dtype)
    return y


# =========================================================================
# Convolution (NHWC, HWIO kernels)
# =========================================================================

def conv_init(rng: jax.Array, kernel: int | tuple[int, int], cin: int,
              cout: int, use_bias: bool = True,
              dtype: Any = jnp.float32) -> dict:
    kh, kw = (kernel, kernel) if isinstance(kernel, int) else kernel
    kr, _ = jax.random.split(rng)
    fan_in = kh * kw * cin
    params = {"kernel": _fan_in_scale(kr, (kh, kw, cin, cout), fan_in, dtype)}
    if use_bias:
        params["bias"] = jnp.zeros((cout,), dtype)
    return params


def conv(params: dict, x: jax.Array, stride: int | tuple[int, int] = 1,
         padding: str | int = "SAME") -> jax.Array:
    strides = (stride, stride) if isinstance(stride, int) else tuple(stride)
    if isinstance(padding, int):
        padding = [(padding, padding), (padding, padding)]
    y = lax.conv_general_dilated(
        x, params["kernel"].astype(x.dtype), strides, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if "bias" in params:
        y = y + params["bias"].astype(x.dtype)
    return y


def conv_transpose(params: dict, x: jax.Array,
                   stride: int | tuple[int, int] = 2,
                   padding: str = "SAME") -> jax.Array:
    strides = (stride, stride) if isinstance(stride, int) else tuple(stride)
    y = lax.conv_transpose(
        x, params["kernel"].astype(x.dtype), strides, padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if "bias" in params:
        y = y + params["bias"].astype(x.dtype)
    return y


# =========================================================================
# Pooling
# =========================================================================

def max_pool(x: jax.Array, window: int = 2, stride: int | None = None,
             padding: str | int = "VALID") -> jax.Array:
    stride = window if stride is None else stride
    if isinstance(padding, int):
        # torch-style symmetric padding (XLA "SAME" pads asymmetrically
        # on stride-2, which breaks exact parity with torch imports)
        padding = [(0, 0), (padding, padding), (padding, padding), (0, 0)]
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1),
        (1, stride, stride, 1), padding)


def avg_pool(x: jax.Array, window: int = 2, stride: int | None = None,
             padding: str = "VALID") -> jax.Array:
    stride = window if stride is None else stride
    summed = lax.reduce_window(
        x, 0.0, lax.add, (1, window, window, 1), (1, stride, stride, 1),
        padding)
    return summed / (window * window)


def global_avg_pool(x: jax.Array) -> jax.Array:
    return x.mean(axis=(1, 2))


# =========================================================================
# Normalization (stateless — see models/__init__ design note)
# =========================================================================

def norm_init(channels: int, dtype: Any = jnp.float32) -> dict:
    return {"scale": jnp.ones((channels,), dtype),
            "bias": jnp.zeros((channels,), dtype)}


def group_norm(params: dict, x: jax.Array, groups: int = 32,
               eps: float = 1e-5, relu: bool = False,
               impl: str = "auto") -> jax.Array:
    """GroupNorm over NHWC (the BatchNorm replacement: batch-independent,
    sync-free across replicas). ``groups`` is clipped to the channel
    count so narrow layers degrade to InstanceNorm-ish behavior.
    ``relu=True`` fuses the activation into the same pass (free on the
    pallas path — it rides the normalize write).

    ``impl``: "auto" resolves to the XLA formulation everywhere:
    XLA fuses the affine(+relu) into the producing conv's epilogue,
    which a standalone pallas kernel (ops/group_norm.py) cannot inside
    a conv net (neither path has a benchmark cell or a ledger line:
    ROADMAP D6). The pallas
    kernel remains opt-in (``impl="pallas"``) for standalone large-
    spatial normalization with no adjacent producer to fuse into.

    XLA path is TPU-shaped too: channels sit on the lane dimension, so
    the big-tensor reductions run over the *spatial* axes only
    (per-channel moments, fp32 accumulation); the group combine happens
    on the tiny ``(n, c)`` stats, and normalize+affine folds into one
    fused multiply-add pass (``y = x·A + B``). The naive
    reshape-to-(…, g, c/g) formulation reduces over sub-lane chunks and
    cost ~60% of a ResNet-50 forward."""
    n, h, w, c = x.shape
    groups = min(groups, c)
    while c % groups:
        groups -= 1
    if impl in ("pallas", "pallas_interpret"):
        from torchbooster_tpu.ops.group_norm import group_norm_fused

        return group_norm_fused(params["scale"], params["bias"], x,
                                groups, eps, relu=relu,
                                interpret=(impl == "pallas_interpret"))
    # one pass over x: per-channel first/second moments. Square in fp32 —
    # squaring in bf16 then E[x²]−E[x]² cancels catastrophically when
    # |mean| ≫ std and can push the variance below -eps (NaN from rsqrt).
    xf = x.astype(jnp.float32)
    s1 = jnp.mean(xf, axis=(1, 2))                              # (n, c)
    s2 = jnp.mean(lax.square(xf), axis=(1, 2))
    # group combine on the (n, groups, c/g) stats — tiny
    gs1 = s1.reshape(n, groups, -1).mean(axis=2)                # (n, g)
    gs2 = s2.reshape(n, groups, -1).mean(axis=2)
    # clamp: fp32 cancellation can still leave a tiny negative variance
    var = jnp.maximum(gs2 - lax.square(gs1), 0.0)
    inv = lax.rsqrt(var + eps)                                  # (n, g)
    per_c = c // groups
    mean_c = jnp.repeat(gs1, per_c, axis=1)                     # (n, c)
    inv_c = jnp.repeat(inv, per_c, axis=1)
    scale = inv_c * params["scale"].astype(jnp.float32)
    shift = params["bias"].astype(jnp.float32) - mean_c * scale
    y = xf * scale[:, None, None, :] + shift[:, None, None, :]
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def layer_norm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    mean = x.mean(axis=-1, keepdims=True)
    var = x.var(axis=-1, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    return y * params["scale"].astype(x.dtype) + params["bias"].astype(x.dtype)


def rms_norm(params: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * params["scale"].astype(x.dtype)


def rms_norm_f32(scale: jax.Array, x: jax.Array,
                 eps: float = 1e-5) -> jax.Array:
    """RMSNorm over the minor axis, computed in float32 whatever
    ``x``'s dtype and cast back: ``x * rsqrt(mean(x^2) + eps) * g``.
    Serves a whole row (``scale (d,)``) and a per-head q/k norm
    (``scale (head_dim,)`` against ``(..., heads, head_dim)``) alike."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(lax.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def short_conv(z: jax.Array, w: jax.Array,
               prev: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Causal depthwise convolution with a SHORT kernel, as a sum of
    shifted products (channels stay on the lanes; no conv op for a
    3-tap filter): ``c_t = sum_j w[j] * z_{t - (K-1) + j}``.
    ``z (B, S, d)``, ``w (K, d)``, ``prev (B, K-1, d)`` the inputs
    before ``z_0`` (None: zeros, a sequence's start). Returns ``(c
    (B, S, d), zz (B, K-1+S, d))`` where ``zz`` is ``prev`` and ``z``
    in one row: a caller that carries state keeps ``zz[:, n:n+K-1]``
    after ``n`` real inputs."""
    k = w.shape[0]
    if prev is None:
        prev = jnp.zeros((z.shape[0], k - 1, z.shape[2]), z.dtype)
    zz = jnp.concatenate([prev.astype(z.dtype), z], axis=1)
    s = z.shape[1]
    c = sum(w[j].astype(z.dtype) * zz[:, j:j + s] for j in range(k))
    return c, zz


def instance_norm(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Parameter-free instance norm over NHWC spatial dims (the core of
    AdaIN, ref adain.py:55-63)."""
    mean = x.mean(axis=(1, 2), keepdims=True)
    var = x.var(axis=(1, 2), keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps)


# =========================================================================
# Embedding
# =========================================================================

def embedding_init(rng: jax.Array, vocab: int, dim: int, std: float = 0.02,
                   dtype: Any = jnp.float32) -> dict:
    return {"table": normal_init(rng, (vocab, dim), std, dtype)}


def embedding(params: dict, ids: jax.Array,
              dtype: Any = None) -> jax.Array:
    if "qtable" in params:
        # per-row int8 table (models/quant.py): gather the narrow
        # rows and their scales, dequantize only the gathered handful
        rows = jnp.take(params["qtable"], ids, axis=0)
        scales = jnp.take(params["qscale"], ids, axis=0)
        out = rows.astype(jnp.float32) * scales
        return out.astype(dtype) if dtype is not None else out
    table = params["table"]
    if dtype is not None:
        table = table.astype(dtype)
    return jnp.take(table, ids, axis=0)


__all__ = [
    "avg_pool", "conv", "conv_init", "conv_transpose", "dense",
    "dense_init", "embedding", "embedding_init", "global_avg_pool",
    "group_norm", "instance_norm", "layer_norm", "max_pool", "norm_init",
    "normal_init", "rms_norm", "rms_norm_f32", "short_conv",
]
