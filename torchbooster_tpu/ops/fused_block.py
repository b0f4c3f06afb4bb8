"""Fused 1×1-conv + GroupNorm + ReLU pallas kernel (bottleneck body).

The premise: a ResNet step is HBM-bound, and GroupNorm is extra full
passes over each conv's output (write y → read y for moments → read y
again for normalize). (No cell of the benchmark runs a conv net, so
neither the premise nor this kernel has a number on the current
stack: ROADMAP D6.) A 1×1 conv IS a matmul, so this
kernel computes, per sample, in one VMEM residency:

    y = x @ w            (MXU, fp32 accumulation)
    per-group moments    (channel sums → group combine)
    out = relu((y − μ)·rstd·γ + β)

and writes ONLY ``out`` to HBM — the conv output never round-trips.
Two of the three norms in every ResNet bottleneck sit behind 1×1 convs
(conv1 and the widest, conv3), so this removes ~2/3 of the norm
traffic.

Group moments inside the kernel use a *membership matrix*: per-channel
sums (one sublane reduction) are multiplied by a constant
``(C, C)`` block-diagonal averaging matrix, giving per-channel group
means directly — no lane-splitting reshape (the layout trap of the
naive XLA formulation: docs/performance.md, "Lane-dim discipline").

Backward is ``custom_vjp`` in plain XLA: it *recomputes* ``y = x @ w``
from the inputs (MXU FLOPs are cheap here; the step is bandwidth-bound)
so the only residuals are the inputs plus the tiny per-(sample,channel)
moments — no extra activation tensor is saved.

No reference counterpart (the reference never fuses; torch eager runs
each op to memory). Used by models/resnet.py when shapes qualify;
dispatch is shape- and backend-gated, XLA path remains the fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# per-sample VMEM working set must fit comfortably; beyond this the
# XLA path takes over (stem-sized spatial maps)
_VMEM_BUDGET_BYTES = 12 * 2**20
# scoped-vmem ceiling passed to Mosaic (default 16M): gives the fp32
# stack temporaries ~2× headroom over the _cell_bytes model's budget
_VMEM_LIMIT_BYTES = 32 * 2**20


def _resolve_groups(groups: int, c: int) -> int:
    groups = min(groups, c)
    while c % groups:
        groups -= 1
    return groups


def _membership(c: int, groups: int, denom: float) -> np.ndarray:
    """(C, C) averaging matrix: A[i, j] = 1/denom iff group(i)==group(j).
    ``sums_per_channel @ A`` = per-channel group mean."""
    cpg = c // groups
    a = np.zeros((c, c), np.float32)
    for g in range(groups):
        a[g * cpg:(g + 1) * cpg, g * cpg:(g + 1) * cpg] = 1.0 / denom
    return a


def _fwd_kernel(x_ref, w_ref, scale_ref, bias_ref, avg_ref,
                o_ref, mu_ref, rstd_ref, *, relu: bool, eps: float):
    x = x_ref[:]                                   # (G, M, Cin)
    w = w_ref[:]                                   # (Cin, Cout)
    # batched matmul: contract Cin, G rides as a leading dim
    y = jax.lax.dot_general(
        x, w, (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (G, M, Cout)

    s1 = jnp.sum(y, axis=1)                        # (G, Cout)
    s2 = jnp.sum(y * y, axis=1)
    avg = avg_ref[:]                               # (Cout, Cout)
    mean = s1 @ avg                                # per-channel group mean
    m2 = s2 @ avg
    var = m2 - mean * mean
    rstd = jax.lax.rsqrt(var + eps)

    a = rstd * scale_ref[:].astype(jnp.float32)    # (G, Cout)
    b = bias_ref[:].astype(jnp.float32) - mean * a
    out = y * a[:, None, :] + b[:, None, :]
    if relu:
        out = jnp.maximum(out, 0.0)
    o_ref[:] = out.astype(o_ref.dtype)
    mu_ref[:] = mean[:, None, :]
    rstd_ref[:] = rstd[:, None, :]


def _cell_bytes(g: int, m: int, cin: int, cout: int, itemsize: int,
                taps: int = 1, x_copies: int = 1) -> int:
    """VMEM working set of one grid cell processing ``g`` samples.
    Counts what Mosaic actually keeps live on the kernel stack (an
    optimistic x+y+out model chose g=4 at the 56²/C=64 stage and OOMed
    the 16M scoped-vmem limit at 21.9M on chip): the x block double-
    buffered by the DMA pipeline (×2, plus the 3×3 kernel's padded
    copy), three fp32 (M, Cout) temporaries (the accumulator, the
    ``acc·acc`` moment square, the normalized out before the cast) and
    the cast output + its DMA buffer, plus the resident weight
    (``taps``·Cin·Cout — 9 for 3×3) and membership matrix."""
    per_sample = (x_copies + 1) * m * cin * itemsize \
        + 3 * m * cout * 4 + 2 * m * cout * itemsize
    return taps * cin * cout * itemsize + cout * cout * 4 + g * per_sample


def _samples_per_cell(b: int, m: int, cin: int, cout: int, itemsize: int,
                      taps: int = 1, x_copies: int = 1) -> int:
    """Largest power-of-two divisor of ``b`` whose working set fits the
    VMEM budget. Bigger cells amortize per-grid-step overhead (a (B,)
    grid of tiny cells measured ~47% SLOWER end-to-end than XLA:
    thousands of cell dispatches per train step dominate the win from
    fewer HBM passes). Callers gate on :func:`fits`/:func:`fits3`
    first (same accounting), so g=1 always fits here."""
    best = 1
    g = 1
    while g <= b:
        if b % g == 0 and _cell_bytes(g, m, cin, cout, itemsize, taps,
                                      x_copies) <= _VMEM_BUDGET_BYTES:
            best = g
        g *= 2
    return best


def _fwd(x3, w, scale, bias, groups: int, eps: float, relu: bool,
         interpret: bool):
    b, m, cin = x3.shape
    cout = w.shape[-1]
    cpg = cout // groups
    avg = jnp.asarray(_membership(cout, groups, float(m * cpg)))
    g = _samples_per_cell(b, m, cin, cout, x3.dtype.itemsize)
    kernel = functools.partial(_fwd_kernel, relu=relu, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(b // g,),
        in_specs=[
            pl.BlockSpec((g, m, cin), lambda i: (i, 0, 0)),
            pl.BlockSpec((cin, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((cout, cout), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, m, cout), lambda i: (i, 0, 0)),
            # moments ride as (B, 1, C): a (g, 1, C) block's trailing
            # dims equal the array dims, which Mosaic requires (a flat
            # (g, C) block of a (B, C) array is not 8-sublane tileable)
            pl.BlockSpec((g, 1, cout), lambda i: (i, 0, 0)),
            pl.BlockSpec((g, 1, cout), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, m, cout), x3.dtype),
            jax.ShapeDtypeStruct((b, 1, cout), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, cout), jnp.float32),
        ],
        # cells are independent: let Mosaic pipeline DMA across them.
        # vmem_limit raised over the 16M scoped default: the stack's
        # fp32 temporaries run ~1.4× past the _cell_bytes model (the
        # fused_s2d chip OOM), and headroom beats a mis-priced cell
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x3, w, scale.reshape(1, -1), bias.reshape(1, -1), avg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _conv1x1_gn(x3, w, scale, bias, groups, eps, relu, interpret):
    out, _, _ = _fwd(x3, w, scale, bias, groups, eps, relu, interpret)
    return out


def _conv1x1_gn_fwd(x3, w, scale, bias, groups, eps, relu, interpret):
    out, mu, rstd = _fwd(x3, w, scale, bias, groups, eps, relu, interpret)
    return out, (x3, w, scale, bias, mu[:, 0, :], rstd[:, 0, :])


def _conv1x1_gn_bwd(groups, eps, relu, interpret, res, dout):
    """XLA backward; recomputes y = x @ w instead of saving it (the
    step is HBM-bound — a spare MXU matmul is cheaper than an (B, M, C)
    residual round-trip)."""
    x3, w, scale, bias, mu, rstd = res
    b, m, cout = dout.shape
    cpg = cout // groups

    y = jnp.einsum("bmi,io->bmo", x3, w,
                   preferred_element_type=jnp.float32)
    xhat = (y - mu[:, None, :]) * rstd[:, None, :]
    scale32 = scale.astype(jnp.float32)
    r = dout.astype(jnp.float32)
    if relu:
        pre = xhat * scale32 + bias.astype(jnp.float32)
        r = r * (pre > 0)
    dbias = jnp.sum(r, axis=(0, 1)).astype(bias.dtype)
    dscale = jnp.sum(r * xhat, axis=(0, 1)).astype(scale.dtype)

    gh = r * scale32
    # group means over (M, cpg) — reduce spatial first (lane-friendly),
    # then combine the tiny per-channel sums into groups
    def gmean(t):
        s = jnp.sum(t, axis=1)                         # (B, Cout)
        g = s.reshape(b, groups, cpg).sum(-1) / (m * cpg)
        return jnp.repeat(g, cpg, axis=-1)[:, None, :]  # (B, 1, Cout)

    dy = rstd[:, None, :] * (gh - gmean(gh) - xhat * gmean(gh * xhat))
    dx = jnp.einsum("bmo,io->bmi", dy, w.astype(jnp.float32)
                    ).astype(x3.dtype)
    dw = jnp.einsum("bmi,bmo->io", x3.astype(jnp.float32), dy
                    ).astype(w.dtype)
    return dx, dw, dscale, dbias


_conv1x1_gn.defvjp(_conv1x1_gn_fwd, _conv1x1_gn_bwd)


def fits(x: jax.Array, cout: int) -> bool:
    """Shape gate: one sample's working set must fit the VMEM budget
    (same accounting as the grid planner — real itemsizes, fp32 y),
    and the matmul must be lane-viable. When this is False the caller
    must take the XLA path; the kernel is never launched over-budget."""
    _, h, w_, cin = x.shape
    m = h * w_
    return _cell_bytes(1, m, cin, cout,
                       x.dtype.itemsize) <= _VMEM_BUDGET_BYTES \
        and cin >= 8 and cout >= 8


# =========================================================================
# 3×3 conv + GN (+ReLU): nine shifted-tap matmuls in one VMEM residency
# =========================================================================

def _fwd3_kernel(x_ref, w_ref, scale_ref, bias_ref, avg_ref,
                 o_ref, *, relu: bool, eps: float, w_sp: int):
    """x block (G, M=H·W, Cin) in row-major spatial order; w (3,3,Cin,
    Cout). Each tap (dy, dx) is a shift of the M axis by dy·W+dx with
    the column-wrap rows masked — nine (G·M, Cin)@(Cin, Cout) matmuls
    accumulate in fp32, then the same moments/normalize epilogue as the
    1×1 kernel. Only ``out`` leaves the chip."""
    x = x_ref[:]                                    # (G, M, Cin)
    g, m, cin = x.shape
    cout = w_ref.shape[-1]
    pad = jnp.zeros((g, w_sp + 1, cin), x.dtype)
    xp = jnp.concatenate([pad, x, pad], axis=1)     # (G, M + 2W+2, Cin)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, m, 1), 1) % w_sp

    acc = jnp.zeros((g, m, cout), jnp.float32)
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            shift = dy * w_sp + dx
            # static python slice (shift is a trace-time constant):
            # lowers to lax.slice — Mosaic has no dynamic_slice rule
            # for TC kernels, so dynamic_slice_in_dim fails on chip
            start = w_sp + 1 + shift
            src = xp[:, start:start + m, :]         # rows m+shift
            if dx:
                valid = ((col + dx) >= 0) & ((col + dx) < w_sp)
                src = src * valid.astype(src.dtype)
            w_tap = w_ref[dy + 1, dx + 1]           # (Cin, Cout)
            acc = acc + jax.lax.dot_general(
                src, w_tap, (((2,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    s1 = jnp.sum(acc, axis=1)                       # (G, Cout)
    s2 = jnp.sum(acc * acc, axis=1)
    avg = avg_ref[:]
    mean = s1 @ avg
    var = s2 @ avg - mean * mean
    rstd = jax.lax.rsqrt(var + eps)
    a = rstd * scale_ref[:].astype(jnp.float32)
    b = bias_ref[:].astype(jnp.float32) - mean * a
    out = acc * a[:, None, :] + b[:, None, :]
    if relu:
        out = jnp.maximum(out, 0.0)
    o_ref[:] = out.astype(o_ref.dtype)


def _ref_conv3x3_gn(x4, w, scale, bias, groups, eps, relu):
    """XLA formulation — the backward (via jax.vjp) and the test oracle.
    Spatial-axis moments then group combine, matching layers.group_norm's
    lane-friendly layout."""
    y = jax.lax.conv_general_dilated(
        x4, w.astype(x4.dtype), (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    n, h, w_sp, c = y.shape
    cpg = c // groups
    y32 = y.astype(jnp.float32)
    s1 = jnp.sum(y32, axis=(1, 2))                  # (N, C)
    s2 = jnp.sum(y32 * y32, axis=(1, 2))
    denom = h * w_sp * cpg
    gmean = s1.reshape(n, groups, cpg).sum(-1) / denom
    gm2 = s2.reshape(n, groups, cpg).sum(-1) / denom
    mean = jnp.repeat(gmean, cpg, axis=-1)[:, None, None, :]
    var = jnp.repeat(gm2, cpg, axis=-1)[:, None, None, :] - mean * mean
    rstd = jax.lax.rsqrt(var + eps)
    out = (y32 - mean) * rstd * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    return out.astype(x4.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _conv3x3_gn(x4, w, scale, bias, groups, eps, relu, interpret):
    b, h, w_sp, cin = x4.shape
    cout = w.shape[-1]
    cpg = cout // groups
    m = h * w_sp
    avg = jnp.asarray(_membership(cout, groups, float(m * cpg)))
    g = _samples_per_cell(b, m, cin, cout, x4.dtype.itemsize,
                          taps=9, x_copies=2)
    kernel = functools.partial(_fwd3_kernel, relu=relu, eps=eps,
                               w_sp=w_sp)
    out = pl.pallas_call(
        kernel,
        grid=(b // g,),
        in_specs=[
            pl.BlockSpec((g, m, cin), lambda i: (i, 0, 0)),
            pl.BlockSpec((3, 3, cin, cout), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((1, cout), lambda i: (0, 0)),
            pl.BlockSpec((cout, cout), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((g, m, cout), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, m, cout), x4.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x4.reshape(b, m, cin), w, scale.reshape(1, -1),
      bias.reshape(1, -1), avg)
    return out.reshape(b, h, w_sp, cout)


def _conv3x3_gn_fwd(x4, w, scale, bias, groups, eps, relu, interpret):
    out = _conv3x3_gn(x4, w, scale, bias, groups, eps, relu, interpret)
    return out, (x4, w, scale, bias)


def _conv3x3_gn_bwd(groups, eps, relu, interpret, res, dout):
    """Differentiate the XLA reference formulation (jax.vjp) — exact
    math, remat-style recompute, no activation residuals saved."""
    x4, w, scale, bias = res
    _, vjp = jax.vjp(
        lambda *a: _ref_conv3x3_gn(*a, groups, eps, relu),
        x4, w, scale, bias)
    return vjp(dout)


_conv3x3_gn.defvjp(_conv3x3_gn_fwd, _conv3x3_gn_bwd)


def conv3x3_gn_relu(x, kernel, scale, bias, groups: int = 32,
                    eps: float = 1e-5, relu: bool = True,
                    interpret: bool = False) -> jax.Array:
    """Fused ``relu(group_norm(conv3x3(x)))`` over NHWC, stride 1,
    padding 1. ``kernel``: (3, 3, Cin, Cout). Differentiable via
    ``custom_vjp`` (backward = autodiff of the XLA reference)."""
    groups = _resolve_groups(groups, kernel.shape[-1])
    return _conv3x3_gn(x, kernel.astype(x.dtype), scale, bias,
                       groups, eps, relu, interpret)


def fits3(x: jax.Array, cout: int) -> bool:
    """VMEM gate for the 3×3 kernel: padded input copy doubles the x
    share and the resident weight is 9·Cin·Cout."""
    _, h, w_, cin = x.shape
    m = h * w_
    return _cell_bytes(1, m, cin, cout, x.dtype.itemsize, taps=9,
                       x_copies=2) <= _VMEM_BUDGET_BYTES \
        and cin >= 8 and cout >= 8


def conv1x1_gn_relu(x, kernel, scale, bias, groups: int = 32,
                    eps: float = 1e-5, relu: bool = True,
                    stride: int = 1, interpret: bool = False) -> jax.Array:
    """Fused ``relu(group_norm(conv1x1(x)))`` over NHWC.

    ``kernel``: (1, 1, Cin, Cout) or (Cin, Cout). ``stride`` > 1 is the
    1×1 projection case: spatial subsampling commutes with a 1×1 conv,
    so the input is strided-sliced first (an XLA gather, fused into the
    kernel's input read). Differentiable via ``custom_vjp``.
    """
    if kernel.ndim == 4:
        kernel = kernel.reshape(kernel.shape[-2], kernel.shape[-1])
    if stride != 1:
        x = x[:, ::stride, ::stride, :]
    b, h, w_, cin = x.shape
    cout = kernel.shape[-1]
    groups = _resolve_groups(groups, cout)
    x3 = x.reshape(b, h * w_, cin)
    out = _conv1x1_gn(x3, kernel.astype(x.dtype), scale, bias,
                      groups, eps, relu, interpret)
    return out.reshape(b, h, w_, cout)


__all__ = ["conv1x1_gn_relu", "conv3x3_gn_relu", "fits", "fits3"]
