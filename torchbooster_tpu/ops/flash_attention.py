"""Pallas flash attention for TPU: blocked online-softmax attention,
forward AND backward (trainable via ``jax.custom_vjp``).

The reference framework has no attention at all (SURVEY §5.7); this is
the TPU-native hot op for the north-star transformer. Memory-bound
naive attention materializes the (S, S) score matrix in HBM; these
kernels stream K/V blocks through VMEM with the online-softmax
recurrence so scores never leave the chip — in both directions.

Kernel shape contract: q (B*H, S_q, D), k/v (B*H, S_kv, D).

Forward grid is (batch·heads, q_blocks, kv_blocks) with the KV
dimension innermost and sequential ("arbitrary" semantics): each grid
step sees only one (block_k, D) K/V tile in VMEM — VMEM use is
O(block_q·D + block_k·D) regardless of sequence length — while the
online-softmax state (running max / sum / accumulator) persists in
VMEM scratch across the KV sweep. Causal masking skips fully-masked
KV tiles via pl.when. When differentiated, the forward additionally
emits the per-row logsumexp ``L = m + log(l)``, padded to 8 lanes (the
sublane width — the smallest Mosaic-legal minor dim) so it stores/
loads as a clean (block_q, 8) tile at 1/16th the footprint of the
conventional 128-lane padding.

Backward follows the FlashAttention-2 factorization — probabilities
are *recomputed* from Q·Kᵀ and the saved logsumexp, never saved:
  delta = rowsum(dO ∘ O)          (in-kernel, from tiles already in VMEM)
  P     = exp(scale·QKᵀ − L)                 (recomputed per tile)
  dV    = Pᵀ dO
  dS    = P ∘ (dO Vᵀ − delta)
  dQ    = scale · dS K        — grid (BH, q_blocks, kv_blocks)
  dK    = scale · dSᵀ Q       — grid (BH, kv_blocks, q_blocks)
Two kernels, each accumulating its output tile in fp32 VMEM scratch
over its inner sweep, so dQ rows and dK/dV rows are each written to
HBM exactly once and no atomics/psums are needed.

On CPU (tests) the kernels run in interpret mode; `attention` in
ops.attention only dispatches here on TPU backends.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchbooster_tpu.ops._pallas_util import (
    CompilerParams as _CompilerParams,
    resolve_interpret as _resolve_interpret,
)

NEG_INF = -1e30
# Per-row residual (lse) lane padding. Mosaic requires a block's minor
# dim be a multiple of 128 OR equal to the full array dim — so a (bh,
# seq, 8) array with (block_q, 8) tiles is legal and 16x smaller than
# the 128-lane padding jax's bundled kernel uses (verified on v5e).
LANES = 8
MIN_BLOCK = 8  # sublane width — smallest sane tile edge


def tileable(seq: int, block: int | None = None) -> bool:
    """True when :func:`flash_attention` can tile ``seq`` — the auto
    dispatcher checks this and falls back to the XLA reference instead
    of crashing on awkward lengths. Delegates to :func:`_pick_block` so
    the predicate can never drift from the actual tiling policy —
    including the ``TB_FLASH_BLOCK_*`` env defaults: with no explicit
    ``block``, BOTH resolved defaults must tile (the caller doesn't say
    whether ``seq`` is a q or kv length, and a predicate that passes on
    one geometry while the kernel runs the other is the drift this
    function exists to prevent)."""
    blocks = ([block] if block is not None
              else [_block_default("Q"), _block_default("K")])
    try:
        for b in blocks:
            _pick_block(b, seq, "seq")
        return True
    except ValueError:
        return False


def _pick_block(block: int, seq: int, name: str) -> int:
    """Shrink ``block`` (by halving) until it divides ``seq``. Stops at
    MIN_BLOCK: degenerate tiles (block 1-4) either fail to compile on
    TPU or run pathologically slowly, so an un-tileable length is an
    explicit error, not a silent slowdown."""
    block = min(block, seq)
    while seq % block and block > MIN_BLOCK:
        block //= 2
    if seq % block:
        raise ValueError(
            f"cannot tile {name}={seq}: no power-of-two block >= "
            f"{MIN_BLOCK} divides it; pad the sequence or pass an "
            f"explicit block size that divides it")
    return block


# =========================================================================
# Forward kernel
# =========================================================================

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, block_q: int, block_k: int, causal: bool, sm_scale: float,
                seq_q: int, seq_kv: int):
    q_index = pl.program_id(1)
    kv_index = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kv_index == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal alignment matches mha_reference's tril(offset=seq_kv-seq_q):
    # query row i attends keys [0, i + seq_kv - seq_q] — queries align to
    # the *last* keys (the decode-with-KV-cache convention)
    offset = seq_kv - seq_q
    if causal:
        # any key in this tile visible to any query in the q tile?
        visible = (q_index + 1) * block_q + offset > kv_index * block_k
    else:
        visible = True

    @pl.when(visible)
    def _body():
        q = q_ref[:].astype(jnp.float32) * sm_scale
        k = k_ref[:]
        v = v_ref[:]
        scores = q @ k.astype(jnp.float32).T      # (block_q, block_k) on MXU

        if causal:
            q_pos = q_index * block_q + offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kv_index * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)

        m_prev = m_scr[:, 0]
        l_prev = l_scr[:, 0]
        m_cur = jnp.maximum(m_prev, scores.max(axis=1))
        correction = jnp.exp(m_prev - m_cur)
        p = jnp.exp(scores - m_cur[:, None])
        l_scr[:, 0] = l_prev * correction + p.sum(axis=1)
        m_scr[:, 0] = m_cur
        acc_scr[:] = (acc_scr[:] * correction[:, None]
                      + p @ v.astype(jnp.float32))

    @pl.when(kv_index == n_kv - 1)
    def _finalize():
        o_ref[:] = (acc_scr[:] / l_scr[:, 0][:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse = m_scr[:, 0] + jnp.log(l_scr[:, 0])
            lse_ref[:] = jax.lax.broadcast_in_dim(
                lse, (block_q, LANES), (0,))


def _fwd_pallas(q, k, v, *, causal, sm_scale, block_q, block_k, interpret,
                save_residuals):
    bh, seq_q, head_dim = q.shape
    bh_kv, seq_kv, _ = k.shape
    kv_rep = bh // bh_kv
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
        sm_scale=sm_scale, seq_q=seq_q, seq_kv=seq_kv)
    grid = (bh, seq_q // block_q, seq_kv // block_k)
    out_shape = [jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype)]
    out_specs = [pl.BlockSpec((None, block_q, head_dim),
                              lambda b, i, j: (b, i, 0))]
    if save_residuals:
        out_shape.append(
            jax.ShapeDtypeStruct((bh, seq_q, LANES), jnp.float32))
        out_specs.append(pl.BlockSpec((None, block_q, LANES),
                                      lambda b, i, j: (b, i, 0)))
    else:
        out_shape.append(None)
        out_specs.append(None)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, head_dim), lambda b, i, j: (b, i, 0)),
            # GQA: rows of the GROUPED k/v (bh_kv = bh_q // kv_rep) —
            # q heads in one group are contiguous in the flat bh order,
            # so the grouped row is simply b // kv_rep
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, i, j, r=kv_rep: (b // r, j, 0)),
            pl.BlockSpec((None, block_k, head_dim),
                         lambda b, i, j, r=kv_rep: (b // r, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),       # running max
            pltpu.VMEM((block_q, 1), jnp.float32),       # running sum
            pltpu.VMEM((block_q, head_dim), jnp.float32),  # accumulator
        ],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out, lse


# =========================================================================
# Backward kernels
# =========================================================================

def _recompute_p(q_ref, k_ref, lse_ref, *, sm_scale, causal, block_q,
                 block_k, q_index, kv_index, offset):
    """(block_q, block_k) normalized probabilities from the saved
    logsumexp. Masked positions go through NEG_INF *before* the exp so
    an unmasked large score can never overflow it."""
    q = q_ref[:].astype(jnp.float32) * sm_scale
    scores = q @ k_ref[:].astype(jnp.float32).T
    if causal:
        q_pos = q_index * block_q + offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kv_index * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)
    return jnp.exp(scores - lse_ref[:, :1])


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
               dq_scr, delta_scr, *, block_q: int, block_k: int,
               causal: bool, sm_scale: float, seq_q: int, seq_kv: int):
    q_index = pl.program_id(1)
    kv_index = pl.program_id(2)
    n_kv = pl.num_programs(2)
    offset = seq_kv - seq_q

    @pl.when(kv_index == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # delta = rowsum(dO ∘ O): one cheap elementwise pass over tiles
        # already streaming into VMEM — computing it here avoids a whole
        # (bh, seq, LANES) fp32 residual array in HBM
        delta_scr[:, 0] = jnp.sum(
            o_ref[:].astype(jnp.float32) * do_ref[:].astype(jnp.float32),
            axis=-1)

    if causal:
        visible = (q_index + 1) * block_q + offset > kv_index * block_k
    else:
        visible = True

    @pl.when(visible)
    def _body():
        p = _recompute_p(q_ref, k_ref, lse_ref, sm_scale=sm_scale,
                         causal=causal, block_q=block_q, block_k=block_k,
                         q_index=q_index, kv_index=kv_index, offset=offset)
        do = do_ref[:].astype(jnp.float32)
        dp = do @ v_ref[:].astype(jnp.float32).T      # (block_q, block_k)
        ds = p * (dp - delta_scr[:, 0][:, None])
        dq_scr[:] += sm_scale * (ds @ k_ref[:].astype(jnp.float32))

    @pl.when(kv_index == n_kv - 1)
    def _finalize():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, block_q: int,
                block_k: int, causal: bool, sm_scale: float, seq_q: int,
                seq_kv: int, n_qblocks: int):
    # NOTE the transposed grid: (BH_kv, kv_blocks, q_blocks·rep), the q
    # sweep innermost — each GROUPED kv tile owns its dK/dV rows and
    # sweeps all q tiles of every head in its group; the causal mask
    # depends only on the POSITION part of the sweep index.
    kv_index = pl.program_id(1)
    sweep = pl.program_id(2)
    q_index = sweep % n_qblocks
    n_sweep = pl.num_programs(2)
    offset = seq_kv - seq_q

    @pl.when(sweep == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if causal:
        visible = (q_index + 1) * block_q + offset > kv_index * block_k
    else:
        visible = True

    @pl.when(visible)
    def _body():
        p = _recompute_p(q_ref, k_ref, lse_ref, sm_scale=sm_scale,
                         causal=causal, block_q=block_q, block_k=block_k,
                         q_index=q_index, kv_index=kv_index, offset=offset)
        do = do_ref[:].astype(jnp.float32)
        dv_scr[:] += p.T @ do
        dp = do @ v_ref[:].astype(jnp.float32).T
        # recomputed per visit: block_q·D mul-adds, noise next to the
        # block_q·block_k·D matmuls above
        delta = jnp.sum(o_ref[:].astype(jnp.float32) * do, axis=-1)
        ds = p * (dp - delta[:, None])
        dk_scr[:] += sm_scale * (ds.T @ q_ref[:].astype(jnp.float32))

    @pl.when(sweep == n_sweep - 1)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, *, causal, sm_scale, block_q,
                block_k, interpret):
    bh, seq_q, head_dim = q.shape
    bh_kv, seq_kv, _ = k.shape
    kv_rep = bh // bh_kv

    q_spec = pl.BlockSpec((None, block_q, head_dim),
                          lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((None, block_k, head_dim),
                           lambda b, i, j, r=kv_rep: (b // r, j, 0))
    row_spec = pl.BlockSpec((None, block_q, LANES),
                            lambda b, i, j: (b, i, 0))
    common = dict(causal=causal, sm_scale=sm_scale, block_q=block_q,
                  block_k=block_k, seq_q=seq_q, seq_kv=seq_kv)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, seq_q // block_q, seq_kv // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, row_spec],
        out_specs=pl.BlockSpec((None, block_q, head_dim),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, out, do, lse)

    # transposed grid: (bh_kv, kv_blocks, q_blocks·rep) — each GROUPED
    # kv row owns its dK/dV tile and sweeps every q tile of every query
    # head in its group (the group members' contributions accumulate in
    # the same VMEM scratch; j decomposes as g·n_q + q_block)
    n_q = seq_q // block_q
    q_spec_t = pl.BlockSpec(
        (None, block_q, head_dim),
        lambda b, i, j, r=kv_rep, n=n_q: (b * r + j // n, j % n, 0))
    kv_spec_t = pl.BlockSpec((None, block_k, head_dim),
                             lambda b, i, j: (b, i, 0))
    row_spec_t = pl.BlockSpec(
        (None, block_q, LANES),
        lambda b, i, j, r=kv_rep, n=n_q: (b * r + j // n, j % n, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_qblocks=n_q, **common),
        grid=(bh_kv, seq_kv // block_k, n_q * kv_rep),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, q_spec_t,
                  row_spec_t],
        out_specs=[
            pl.BlockSpec((None, block_k, head_dim), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, head_dim), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh_kv, seq_kv, head_dim), k.dtype),
            jax.ShapeDtypeStruct((bh_kv, seq_kv, head_dim), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, head_dim), jnp.float32),
                        pltpu.VMEM((block_k, head_dim), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, out, do, lse)
    return dq, dk, dv


# =========================================================================
# custom_vjp binding + public API
# =========================================================================

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _fwd_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                         block_q=block_q, block_k=block_k,
                         interpret=interpret, save_residuals=False)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _fwd_pallas(q, k, v, causal=causal, sm_scale=sm_scale,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret, save_residuals=True)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    return _bwd_pallas(q, k, v, out, lse, do, causal=causal,
                       sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                       interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _block_default(name: str) -> int:
    """Tile-size default, env-overridable (``TB_FLASH_BLOCK_Q`` /
    ``TB_FLASH_BLOCK_K``) so on-chip A/Bs can sweep tile geometry
    through callers that don't thread block sizes (the GPT train step);
    an explicit ``block_q=``/``block_k=`` argument always wins.
    Resolved OUTSIDE :func:`_flash_entry`'s jit so ITS cache keys on
    the resolved ints. NOTE: a caller that wraps :func:`flash_attention`
    in its own outer jit (the GPT train step) bakes the env read into
    that outer trace — mid-process sweeps must re-jit or use fresh
    processes."""
    return int(os.environ.get(f"TB_FLASH_BLOCK_{name}", 1024))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "interpret"))
def _flash_entry(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    return _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked attention over (BH, S, D) tensors; differentiable (the
    backward recomputes probabilities from the saved logsumexp — see
    module docstring). Block sizes shrink (by halving, floor 8) to
    divide the sequence lengths; the 1024 defaults measured ~2x faster
    than 128 at S=8k on v5e (the TPU grid runs blocks sequentially per
    core, so bigger tiles amortize overhead — VMEM, not parallelism,
    is the constraint).

    GQA-native: k/v may carry FEWER leading rows than q (q flattened
    batch-major with group-contiguous heads, k/v at grouped width) —
    the kernels index the grouped tiles directly, so expanded K/V never
    exist in HBM, and dK/dV come back at grouped width with the group's
    contributions accumulated in-kernel."""
    bh, seq_q, head_dim = q.shape
    bh_kv, seq_kv, _ = k.shape
    if bh % bh_kv:
        raise ValueError(f"flash_attention: q rows ({bh}) not divisible "
                         f"by grouped k/v rows ({bh_kv})")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    block_q = _pick_block(block_q if block_q is not None
                          else _block_default("Q"), seq_q, "seq_q")
    block_k = _pick_block(block_k if block_k is not None
                          else _block_default("K"), seq_kv, "seq_kv")
    # interpret=None -> the shared ops-wide policy (_pallas_util):
    # compiled on TPU backends, interpret mode elsewhere — resolved
    # OUTSIDE _flash_entry's jit so its cache keys on the bool
    return _flash_entry(q, k, v, causal, sm_scale, block_q, block_k,
                        _resolve_interpret(interpret))


__all__ = ["flash_attention", "tileable"]
