"""Pallas fused GroupNorm(+ReLU) for TPU — forward and backward.

GroupNorm is the framework's BatchNorm replacement (batch-independent,
sync-free across replicas; see models/layers.py). On the XLA path it
costs three-plus passes over the activation per application (moments
read, affine read+write, and several more in autodiff) — measured ~30%
of a ResNet-50 train step, which is bandwidth- not FLOP-bound. These
kernels cut it to the minimum HBM traffic: forward reads x once and
writes y (+ tiny per-channel stats); backward reads x and dy once and
writes dx (+ tiny per-channel partials). The optional fused ReLU makes
the activation free (it rides the same write).

Tiling: x is viewed as (N, H·W, C) and the grid is (N, C/cb) — one
sample × one channel block per program, fully parallel. Group moments
never cross channel blocks because ``cb`` is a multiple of the group
width C/groups. Group combination of per-channel sums happens via a
tiny (cb, cb) same-group one-hot matmul on the MXU — no lane-dim
reshapes, and the result lands already broadcast back to channels.

Backward math (per group g of m = H·W·(C/groups) elements):
  x̂    = (x − μ_g)·inv_g,   dŷ = mask·dy·scale
  dx   = inv_g · (dŷ − mean_g(dŷ) − x̂·mean_g(dŷ·x̂))
  dscale_c = Σ_hw mask·dy·x̂,   dbias_c = Σ_hw mask·dy
where mask = [y > 0] when ReLU is fused (recomputed in-kernel), else 1.

Dispatch lives in models/layers.py — where this kernel is OPT-IN
(``impl="pallas"``), not the default: XLA fuses the affine(+relu) into
the producing conv's epilogue, which a standalone norm kernel cannot
(see layers.group_norm and docs/performance.md). The kernel earns its
keep for standalone large-spatial normalization with no adjacent
producer op to fuse into; the XLA formulation in layers.py is the
numerical ground truth in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _group_matrix(cb: int, mod_c: int, group_w: int) -> jax.Array:
    """(cb, cb) f32 matrix with M[i, j] = 1 iff (tile-local) channels
    i, j share a group — s @ M group-sums per-channel stats AND
    broadcasts the result back to channels in one tiny MXU op.
    ``mod_c`` handles the folded layout (see ``_fold``): folded channel
    j is real channel j % mod_c."""
    i = jax.lax.broadcasted_iota(jnp.int32, (cb, cb), 0) % mod_c // group_w
    j = jax.lax.broadcasted_iota(jnp.int32, (cb, cb), 1) % mod_c // group_w
    return (i == j).astype(jnp.float32)


def _pick_chunk(hw: int, cb: int) -> int:
    """Spatial chunk: f32 temporaries live per-chunk (the full bf16 x
    tile sits in VMEM, but fp32 intermediates at stem size — 12544×64×4B
    ×4 buffers — blow the 16MB scoped-vmem budget, of which pallas
    double-buffered block refs already take ~10MB). Largest divisor of
    hw that keeps a chunk's fp32 footprint ≤ 768KB, 8-aligned."""
    budget = max(8, (768 * 1024) // (4 * cb))
    if hw <= budget:
        return hw
    for d in range(budget - budget % 8, 7, -8):
        if hw % d == 0:
            return d
    return hw


def _fwd_kernel(x_ref, scale_ref, bias_ref, y_ref, stats_ref, *,
                mod_c: int, group_w: int, count: int, eps: float,
                relu: bool):
    hw, cb = x_ref.shape
    m = _group_matrix(cb, mod_c, group_w)
    inv_count = 1.0 / count
    chunk = _pick_chunk(hw, cb)

    def moments(i, carry):
        s1, s2 = carry
        xc = x_ref[pl.ds(i * chunk, chunk), :].astype(jnp.float32)
        return (s1 + jnp.sum(xc, axis=0, keepdims=True),
                s2 + jnp.sum(xc * xc, axis=0, keepdims=True))

    zeros = jnp.zeros((1, cb), jnp.float32)
    s1, s2 = jax.lax.fori_loop(0, hw // chunk, moments, (zeros, zeros))
    mean = (s1 @ m) * inv_count                        # per-channel, grouped
    ex2 = (s2 @ m) * inv_count
    var = jnp.maximum(ex2 - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)                     # (1, cb)

    a = inv * scale_ref[...].astype(jnp.float32)
    b = bias_ref[...].astype(jnp.float32) - mean * a

    def affine(i, _):
        sl = pl.ds(i * chunk, chunk)
        y = x_ref[sl, :].astype(jnp.float32) * a + b
        if relu:
            y = jnp.maximum(y, 0.0)
        y_ref[sl, :] = y.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, hw // chunk, affine, 0)
    stats_ref[0:1, :] = mean
    stats_ref[1:2, :] = inv


def _bwd_kernel(x_ref, dy_ref, stats_ref, scale_ref, bias_ref,
                dx_ref, part_ref, *, mod_c: int, group_w: int, count: int,
                relu: bool):
    hw, cb = x_ref.shape
    m = _group_matrix(cb, mod_c, group_w)
    inv_count = 1.0 / count
    chunk = _pick_chunk(hw, cb)
    mean = stats_ref[0:1, :]
    inv = stats_ref[1:2, :]
    scale = scale_ref[...].astype(jnp.float32)
    bias = bias_ref[...].astype(jnp.float32)

    def _chunk_vals(i):
        sl = pl.ds(i * chunk, chunk)
        xhat = (x_ref[sl, :].astype(jnp.float32) - mean) * inv
        dy = dy_ref[sl, :].astype(jnp.float32)
        if relu:
            dy = jnp.where(xhat * scale + bias > 0, dy, 0.0)
        return sl, xhat, dy

    def sums(i, carry):
        t1, t2, ps, pb = carry
        _, xhat, dy = _chunk_vals(i)
        dxhat = dy * scale
        return (t1 + jnp.sum(dxhat, axis=0, keepdims=True),
                t2 + jnp.sum(dxhat * xhat, axis=0, keepdims=True),
                ps + jnp.sum(dy * xhat, axis=0, keepdims=True),
                pb + jnp.sum(dy, axis=0, keepdims=True))

    zeros = jnp.zeros((1, cb), jnp.float32)
    t1, t2, ps, pb = jax.lax.fori_loop(
        0, hw // chunk, sums, (zeros, zeros, zeros, zeros))
    g1 = (t1 @ m) * inv_count
    g2 = (t2 @ m) * inv_count

    def write_dx(i, _):
        sl, xhat, dy = _chunk_vals(i)
        dx = inv * (dy * scale - g1 - xhat * g2)
        dx_ref[sl, :] = dx.astype(dx_ref.dtype)
        return 0

    jax.lax.fori_loop(0, hw // chunk, write_dx, 0)
    part_ref[0:1, :] = ps                              # dscale partial
    part_ref[1:2, :] = pb                              # dbias partial


def _pick_cb(c: int, groups: int) -> int:
    """Channel-block width: Mosaic-legal (multiple of 128 or the full
    channel dim) and a multiple of the group width so group stats stay
    tile-local."""
    if c <= 128:
        return c
    group_w = c // groups
    cb = 128
    while cb % group_w or c % cb:
        cb += 128
        if cb >= c:
            return c
    return cb


def _fold(hw: int, c: int) -> int:
    """Lane-fold factor: channels ride the 128-wide lane dimension, so
    a C<128 tile wastes (and *pays VMEM for*) the padding — C=64 tiles
    allocate 2x their data. Folding ``f`` consecutive spatial positions
    into the channel dim gives a dense (hw/f, f·c) view; the group
    matrix handles the interleaved group pattern via ``mod_c``."""
    if c >= 128 or 128 % c or hw % (128 // c):
        return 1
    return 128 // c


def _layout(x_shape, groups):
    n, h, w, c = x_shape
    hw = h * w
    group_w = c // groups
    f = _fold(hw, c)
    hw_v, c_v = hw // f, c * f
    # folded groups interleave across the whole folded width: single
    # channel tile; unfolded layouts block channels normally
    cb = c_v if f > 1 else _pick_cb(c_v, groups)
    return hw_v, c_v, cb, f, group_w


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gn(scale, bias, x, groups, eps, relu, interpret):
    y, _ = _gn_fwd_pallas(scale, bias, x, groups, eps, relu, interpret)
    return y


def _gn_fwd_pallas(scale, bias, x, groups, eps, relu, interpret):
    n, h, w, c = x.shape
    hw_v, c_v, cb, f, group_w = _layout(x.shape, groups)
    kernel = functools.partial(
        _fwd_kernel, mod_c=c if f > 1 else cb, group_w=group_w,
        count=h * w * group_w, eps=eps, relu=relu)
    y, stats = pl.pallas_call(
        kernel,
        grid=(n, c_v // cb),
        in_specs=[
            pl.BlockSpec((None, hw_v, cb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, cb), lambda i, j: (0, j)),
            pl.BlockSpec((1, cb), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((None, hw_v, cb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 2, cb), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, hw_v, c_v), x.dtype),
            jax.ShapeDtypeStruct((n, 2, c_v), jnp.float32),
        ],
        interpret=interpret,
    )(x.reshape(n, hw_v, c_v), jnp.tile(scale, f).reshape(1, c_v),
      jnp.tile(bias, f).reshape(1, c_v))
    return y.reshape(n, h, w, c), stats


def _gn_vjp_fwd(scale, bias, x, groups, eps, relu, interpret):
    y, stats = _gn_fwd_pallas(scale, bias, x, groups, eps, relu, interpret)
    return y, (scale, bias, x, stats)


def _gn_vjp_bwd(groups, eps, relu, interpret, res, dy):
    scale, bias, x, stats = res
    n, h, w, c = x.shape
    hw_v, c_v, cb, f, group_w = _layout(x.shape, groups)
    kernel = functools.partial(
        _bwd_kernel, mod_c=c if f > 1 else cb, group_w=group_w,
        count=h * w * group_w, relu=relu)
    dx, part = pl.pallas_call(
        kernel,
        grid=(n, c_v // cb),
        in_specs=[
            pl.BlockSpec((None, hw_v, cb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, hw_v, cb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 2, cb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, cb), lambda i, j: (0, j)),
            pl.BlockSpec((1, cb), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((None, hw_v, cb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 2, cb), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, hw_v, c_v), x.dtype),
            jax.ShapeDtypeStruct((n, 2, c_v), jnp.float32),
        ],
        interpret=interpret,
    )(x.reshape(n, hw_v, c_v), dy.reshape(n, hw_v, c_v), stats,
      jnp.tile(scale, f).reshape(1, c_v), jnp.tile(bias, f).reshape(1, c_v))
    # fold partials back: folded channel j is real channel j % c
    part = part.reshape(n, 2, f, c).sum(axis=(0, 2))
    return (part[0].astype(scale.dtype), part[1].astype(bias.dtype),
            dx.reshape(n, h, w, c))


_gn.defvjp(_gn_vjp_fwd, _gn_vjp_bwd)


def group_norm_fused(scale: jax.Array, bias: jax.Array, x: jax.Array,
                     groups: int, eps: float = 1e-5, relu: bool = False,
                     interpret: bool = False) -> jax.Array:
    """Fused GroupNorm(+ReLU) over NHWC via the pallas kernels above.
    ``groups`` must divide C (the caller — layers.group_norm — already
    clips it)."""
    return _gn(scale, bias, x, groups, eps, relu, interpret)


__all__ = ["group_norm_fused"]
