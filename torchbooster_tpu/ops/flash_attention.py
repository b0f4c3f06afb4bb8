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
VMEM scratch across the KV sweep. Every product runs at the operands'
own dtype (bf16 in training) with float32 accumulation, contracting the
shared axis directly; max, sum, logsumexp, delta and every accumulator
are float32. Causal masking skips fully-masked KV tiles via pl.when,
runs fully-visible tiles unmasked, and cuts the tile the diagonal
crosses into 128-wide strips that stop at the diagonal (``_visit``).
When differentiated, the forward additionally emits the per-row
logsumexp ``L = m + log(l)`` as dense ROWS, (BH, 1, S) — see
``_col_to_row``; it and the output carry ``jax.ad_checkpoint`` names
(``RESIDUALS``) so a caller's remat policy can keep them.

Backward follows the FlashAttention-2 factorization — probabilities
are *recomputed* from Q·Kᵀ and the saved logsumexp, never saved:
  delta = rowsum(dO ∘ O)
  P     = exp(scale·QKᵀ − L)                 (recomputed per tile)
  dV    = Pᵀ dO
  dS    = P ∘ (dO Vᵀ − delta)
  dQ    = scale · dS K        — grid (BH, q_blocks, kv_blocks)
  dK    = scale · dSᵀ Q       — grid (BH, kv_blocks, q_blocks)
Two kernels, each accumulating its output tile in fp32 VMEM scratch
over its inner sweep, so dQ rows and dK/dV rows are each written to
HBM exactly once and no atomics/psums are needed. The dK/dV kernel
computes the TRANSPOSED tile (keys down the rows: Sᵀ = K Qᵀ), so Pᵀ and
dSᵀ come out of the matrix unit already turned and nothing is
transposed on the vector side.

On CPU (tests) the kernels run in interpret mode; `attention` in
ops.attention only dispatches here on TPU backends.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchbooster_tpu.ops._pallas_util import (
    CompilerParams as _CompilerParams,
    resolve_interpret as _resolve_interpret,
)

NEG_INF = -1e30
MIN_BLOCK = 8  # sublane width — smallest sane tile edge
STRIP = 128    # lane width: what a diagonal tile is cut into, and what
#                a tile the compiled dK/dV kernel can read is made of
# jax.ad_checkpoint names of the forward's output and logsumexp
RESIDUALS = ("flash_out", "flash_lse")
LSE, DELTA = 0, 1   # the backward's per-query rows (_bwd_pallas)


def tileable(seq: int, block: int | None = None) -> bool:
    """True when :func:`flash_attention` can tile ``seq`` in whole lane
    tiles — the auto dispatcher checks this and falls back to the XLA
    reference instead of crashing (or crawling through 8-wide tiles) on
    awkward lengths: the dK/dV kernel reads its per-query terms as
    (1, block_q) rows, which the chip's compiler takes in multiples of
    128 lanes. Delegates to :func:`_pick_block` so the predicate can
    never drift from the actual tiling policy — including the
    ``TB_FLASH_BLOCK_*`` env defaults: with no explicit ``block``, BOTH
    resolved defaults must tile (the caller doesn't say whether ``seq``
    is a q or kv length, and a predicate that passes on one geometry
    while the kernel runs the other is the drift this function exists
    to prevent)."""
    blocks = ([block] if block is not None
              else [_block_default("Q"), _block_default("K")])
    try:
        return all(_pick_block(b, seq, "seq") % STRIP == 0 for b in blocks)
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

def _diag_strips(block_q: int, block_k: int, offset: int) -> int:
    """How many strips the tile the diagonal crosses is cut into. A
    square tile whose corner lies ON the diagonal (the offset a
    multiple of the tile) always holds the same lower triangle, so its
    visible part is cut, statically, into strips one lane tile (128)
    wide that stop at the diagonal — 36/64ths of a 1024-wide tile —
    instead of computing the masked half in full: on a v5e the finest
    strips measured fastest (PERF.md section 6, PR 31). Any other tile
    (1) is computed whole under its mask."""
    if block_q != block_k or offset % block_q or block_q % STRIP:
        return 1
    return block_q // STRIP


def _visit(tile, q_index, kv_index, *, causal: bool, block_q: int,
           block_k: int, offset: int, by_keys: bool) -> None:
    """Run ``tile(q_rows, k_rows, lead)`` over what causality leaves
    visible of the grid step's (block_q, block_k) tile. Alignment
    matches mha_reference's tril(offset=seq_kv-seq_q): query row i
    attends keys [0, i + offset] — queries align to the *last* keys
    (the decode-with-KV-cache convention). A tile every query of which
    sees every key runs unmasked (``lead`` None); a tile no query sees
    is skipped; the tiles the diagonal crosses run masked, ``lead`` =
    position of the slice's first query less that of its first key —
    whole, or strip by strip (:func:`_diag_strips`): a strip of queries
    with the keys up to its last row, or (``by_keys``, the dK/dV
    kernel) a strip of keys with the queries from its first row on."""
    whole_tile = (pl.ds(0, block_q), pl.ds(0, block_k))
    if not causal:
        tile(*whole_tile, None)
        return
    first_q = q_index * block_q + offset
    first_k = kv_index * block_k
    visible = first_q + block_q > first_k
    whole = first_q >= first_k + block_k - 1
    pl.when(whole)(lambda: tile(*whole_tile, None))

    @pl.when(visible & jnp.logical_not(whole))
    def _diagonal():
        n = _diag_strips(block_q, block_k, offset)
        if n == 1:
            tile(*whole_tile, first_q - first_k)
            return
        strip = block_q // n
        for i in range(n):
            if by_keys:
                tile(pl.ds(i * strip, block_q - i * strip),
                     pl.ds(i * strip, strip), 0)
            else:
                tile(pl.ds(i * strip, strip),
                     pl.ds(0, (i + 1) * strip), i * strip)


def _folds(sm_scale: float) -> bool:
    """A power-of-two scale (1/8 at head size 64) multiplies a bf16 or
    float32 tile EXACTLY, so it goes onto the (block, D) operand once
    instead of onto every (block_q, block_k) score; any other scale
    multiplies the float32 scores, as mha_reference does."""
    return math.frexp(sm_scale)[0] == 0.5


def _nt(a, b):
    """``a @ b.T`` with float32 accumulation, contracting the shared
    minor axis directly (no transpose is materialised) at the operands'
    own dtype — bf16 tiles go to the matrix unit as bf16."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _exact_pieces(x):
    """Float32 ``x`` as three bf16 pieces that sum to it exactly (8 + 8
    + 8 mantissa bits), so a product with them is exact on the matrix
    unit whatever precision it gives float32 operands."""
    pieces = []
    for _ in range(3):
        pieces.append(x.astype(jnp.bfloat16))
        x = x - pieces[-1].astype(jnp.float32)
    return pieces


# The per-query logsumexp lives in HBM as ROWS, (BH, 1, S): a column
# (S, 1) — how the forward's running max and sum lie — is padded to 128
# lanes there, 128 times its bytes (1.2 GB through the layer scan's
# saved stack at GPT-2 small, batch 16; PERF.md section 6, PR 31).

def _col_to_row(col):
    """(n, 1) float32 -> (1, n), exactly, on the matrix unit (cheaper
    on a v5e than a transpose of the lane-broadcast column): the mean
    of 128 copies of each bf16 piece, contracted along the lanes."""
    wide = jnp.broadcast_to(col, (col.shape[0], STRIP))
    mean = jnp.full((8, STRIP), 1.0 / STRIP, jnp.bfloat16)
    return sum(_nt(mean, piece) for piece in _exact_pieces(wide))[:1]


def _scores(rows, cols, *, sm_scale: float, lead, q_axis: int):
    """float32 ``scale * rows @ cols.T`` (the caller has folded a
    foldable scale into one operand); queries run along ``q_axis``.
    Masked positions (``lead`` given: see :func:`_visit`) go to NEG_INF
    *before* any exp so an unmasked large score can never overflow."""
    s = _nt(rows, cols)
    if not _folds(sm_scale):
        s = s * sm_scale
    if lead is not None:
        q_pos = lead + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return s


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

    def tile(qs, ks, lead):
        q = q_ref[qs, :]
        if _folds(sm_scale):
            q = q * sm_scale
        v = v_ref[ks, :]
        scores = _scores(q, k_ref[ks, :], sm_scale=sm_scale, lead=lead,
                         q_axis=0)                # (queries, keys)
        m_prev = m_scr[qs, :]
        m_cur = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
        correction = jnp.exp(m_prev - m_cur)
        p = jnp.exp(scores - m_cur)
        l_scr[qs, :] = (l_scr[qs, :] * correction
                        + p.sum(axis=1, keepdims=True))
        m_scr[qs, :] = m_cur
        # p goes to its product at the values' dtype, as mha_reference
        # casts probs; the row sum above is of the float32 p
        acc_scr[qs, :] = (acc_scr[qs, :] * correction
                          + _nn(p.astype(v.dtype), v))

    _visit(tile, q_index, kv_index, causal=causal, block_q=block_q,
           block_k=block_k, offset=seq_kv - seq_q, by_keys=False)

    @pl.when(kv_index == n_kv - 1)
    def _finalize():
        o_ref[:] = (acc_scr[:] / l_scr[:]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[:] = _col_to_row(m_scr[:] + jnp.log(l_scr[:]))


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
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32))
        out_specs.append(pl.BlockSpec((None, 1, block_q),
                                      lambda b, i, j: (b, 0, i)))
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
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# =========================================================================
# Backward kernels
# =========================================================================

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, rows_ref, dq_ref,
               dq_scr, cols_scr, *, block_q: int, block_k: int,
               causal: bool, sm_scale: float, seq_q: int, seq_kv: int):
    q_index = pl.program_id(1)
    kv_index = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kv_index == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # the per-query rows (_bwd_pallas) as columns: one transpose of
        # a sublane tile — the cheaper way on a v5e (PERF.md section 6)
        cols_scr[:] = rows_ref[:].T

    def tile(qs, ks, lead):
        q = q_ref[qs, :]
        if _folds(sm_scale):
            q = q * sm_scale
        k = k_ref[ks, :]
        scores = _scores(q, k, sm_scale=sm_scale, lead=lead, q_axis=0)
        p = jnp.exp(scores - cols_scr[qs, LSE:LSE + 1])   # (queries, keys)
        dp = _nt(do_ref[qs, :], v_ref[ks, :])
        ds = p * (dp - cols_scr[qs, DELTA:DELTA + 1])
        dq_scr[qs, :] += _nn(ds.astype(k.dtype), k)

    _visit(tile, q_index, kv_index, causal=causal, block_q=block_q,
           block_k=block_k, offset=seq_kv - seq_q, by_keys=False)

    @pl.when(kv_index == n_kv - 1)
    def _finalize():
        dq_ref[:] = (sm_scale * dq_scr[:]).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, rows_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, block_q: int,
                block_k: int, causal: bool, sm_scale: float, seq_q: int,
                seq_kv: int, n_qblocks: int):
    # NOTE the transposed grid: (BH_kv, kv_blocks, q_blocks·rep), the q
    # sweep innermost — each GROUPED kv tile owns its dK/dV rows and
    # sweeps all q tiles of every head in its group; the causal mask
    # depends only on the POSITION part of the sweep index.
    #
    # Everything here is the TRANSPOSE of the dQ kernel's tile — keys
    # down the rows, queries along the lanes — so all four products are
    # plain a @ b or a @ b.T on the matrix unit and no (block_q,
    # block_k) float32 tile is ever transposed: Sᵀ = K Qᵀ, dPᵀ = V dOᵀ,
    # dV += Pᵀ dO, dK += dSᵀ Q. The per-query logsumexp and delta
    # are used as they arrive: ROWS of (8, block_q).
    kv_index = pl.program_id(1)
    sweep = pl.program_id(2)
    n_sweep = pl.num_programs(2)

    @pl.when(sweep == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile(qs, ks, lead):
        q = q_ref[qs, :]
        k = k_ref[ks, :]
        if _folds(sm_scale):
            k = k * sm_scale
        do = do_ref[qs, :]
        scores_t = _scores(k, q, sm_scale=sm_scale, lead=lead,
                           q_axis=1)                  # (keys, queries)
        p_t = jnp.exp(scores_t - rows_ref[LSE:LSE + 1, qs])
        dv_scr[ks, :] += _nn(p_t.astype(do.dtype), do)
        dp_t = _nt(v_ref[ks, :], do)
        ds_t = p_t * (dp_t - rows_ref[DELTA:DELTA + 1, qs])
        dk_scr[ks, :] += _nn(ds_t.astype(q.dtype), q)

    _visit(tile, sweep % n_qblocks, kv_index, causal=causal,
           block_q=block_q, block_k=block_k, offset=seq_kv - seq_q,
           by_keys=True)

    @pl.when(sweep == n_sweep - 1)
    def _finalize():
        dk_ref[:] = (sm_scale * dk_scr[:]).astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, out, lse, do, *, causal, sm_scale, block_q,
                block_k, interpret):
    bh, seq_q, head_dim = q.shape
    bh_kv, seq_kv, _ = k.shape
    kv_rep = bh // bh_kv

    # what both kernels need per query, as dense rows of ONE sublane
    # tile: the logsumexp as it is stored, and delta = rowsum(dO ∘ O),
    # summed here by XLA (rows LSE and DELTA; the other six are padding)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, None, :]
    rows = jnp.concatenate(
        [lse, delta, jnp.zeros((bh, 6, seq_q), jnp.float32)], axis=1)

    q_spec = pl.BlockSpec((None, block_q, head_dim),
                          lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((None, block_k, head_dim),
                           lambda b, i, j, r=kv_rep: (b // r, j, 0))
    rows_spec = pl.BlockSpec((None, 8, block_q), lambda b, i, j: (b, 0, i))
    common = dict(causal=causal, sm_scale=sm_scale, block_q=block_q,
                  block_k=block_k, seq_q=seq_q, seq_kv=seq_kv)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, seq_q // block_q, seq_kv // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, rows_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32),
                        pltpu.VMEM((block_q, 8), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, rows)

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
    rows_spec_t = pl.BlockSpec(
        (None, 8, block_q),
        lambda b, i, j, r=kv_rep, n=n_q: (b * r + j // n, 0, j % n))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_qblocks=n_q, **common),
        grid=(bh_kv, seq_kv // block_k, n_q * kv_rep),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, rows_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct((bh_kv, seq_kv, head_dim), k.dtype),
            jax.ShapeDtypeStruct((bh_kv, seq_kv, head_dim), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, head_dim), jnp.float32),
                        pltpu.VMEM((block_k, head_dim), jnp.float32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dkv",
    )(q, k, v, do, rows)
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
    # what the kernel hands its backward, by name: a pallas_call is no
    # dot, so a jax.checkpoint policy that saves dots would run this
    # forward again inside the backward just to rebuild the two — a
    # caller's policy keeps them with save_only_these_names(*RESIDUALS)
    out = checkpoint_name(out, RESIDUALS[0])
    lse = checkpoint_name(lse, RESIDUALS[1])
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
    divide the sequence lengths. The 1024 defaults are the measured
    optimum on a v5e from S=1024 to S=8192 at head size 64 (the TPU
    grid runs blocks sequentially per core and a step's per-row work —
    the softmax's reductions — does not shrink with a narrower tile, so
    bigger tiles amortize it: forward + backward 4.0 / 4.7 / 7.4 ms at
    1024 / 512 / 256 for 16 x 12 heads of S=1024; 2048 runs out of
    VMEM; PERF.md section 6, PR 31). The causal saving comes from
    inside the diagonal tile instead (``_diag_strips``).

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
