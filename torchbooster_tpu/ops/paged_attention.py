"""Pallas paged flash-decode kernel: block-table walk IN-KERNEL, so a
decode step's HBM reads are the LIVE context, not the pool.

The XLA pool sweep (serving/engine.py ``_decode_fn``) reads every
pool page every step — ``n_pages · page_size`` K/V rows
per layer whatever the occupancy (docs/performance.md "Paged-decode
roofline"). This kernel is the vLLM-PagedAttention-shaped alternative:
the grid iterates a COMPACTED work list of the pool's live pages
(``BlockTables.kernel_args()`` — fixed shape ``n_pages - 1``, live
entries first, the rest padded to the reserved null page), and the
page ids ride a scalar-prefetch operand so each grid step's BlockSpec
index map picks its K/V page straight out of the pool by table value.
Dead padding entries all map to page 0; Pallas only re-fetches a block
when its index CHANGES between grid steps, so the padding tail costs
one null-page fetch, and bytes/step collapse from the pool to
``Σ_slots ceil(len/page) · page_size`` rows (+ one page).

Two deliberate shape choices, both inherited from the XLA sweep so the
engine's contracts transfer unchanged:

- **ref lanes, not slot-major pages.** The grid walks PAGES; each page
  attends the queries of every slot holding it (its ``refs`` lanes).
  A prefix page shared by k live requests is therefore read from HBM
  ONCE and serves all k — a slot-major walk (grid over (slot, slot's
  pages)) would re-read shared pages per sharer, paying the
  prefix-cache bytes back. Per-(page, lane) flash partials (o, m, l)
  accumulate into per-slot VMEM scratch with the standard
  online-softmax merge — the segment combine of the XLA sweep, but
  carried across grid steps in scratch instead of materialized and
  segment-summed.
- **a q_len axis instead of a separate verify kernel.** Queries are
  ``(max_slots, S, heads, head_dim)`` with ``S ∈ {1, 1 + draft_len}``:
  S = 1 IS the decode step, S = 1 + draft_len is the speculative
  verify step fused into the same single pass (per-position causal
  visibility ``tok_pos <= lengths[slot] + j`` — j = 0 reduces to the
  decode mask). Scratch/segment state keys (slot, position), exactly
  the verify sweep's segment ids.

Pool dtype follows the pool: bf16/fp32 pages read directly, int8
pages as ``(values, scales)`` pairs dequantized IN-KERNEL right after
the page lands in VMEM — the HBM stream stays at 1 byte/elem and the
widening never round-trips through HBM (the "does XLA fold the
convert" bet the sweep takes is a non-question here). GQA reads the
grouped page directly and expands to query heads on the VMEM copy.

On CPU the kernel runs in interpret mode (``_pallas_util.
default_interpret`` — the same policy as ``flash_attention.py``), so
the tier-1 parity matrix (tests/test_paged_kernel.py) proves
token-exactness against both the XLA sweep and the dense
``jit_generate`` control without a chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchbooster_tpu.ops._pallas_util import (
    CompilerParams as _CompilerParams,
    resolve_interpret as _resolve_interpret,
)

NEG_INF = -1e30   # the XLA sweep's mask value (_grouped_cache_attention)


def _paged_kernel(wp_ref, wr_ref, wpos_ref, len_ref,
                  q_ref, k_ref, v_ref, ks_ref, vs_ref, tvis_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, page_size: int,
                  n_lanes: int, rep: int, sm_scale: float,
                  n_slots: int, s_q: int):
    """One grid step = one live page: dequantize the page tile, then
    for each reference lane run the flash online-softmax update of
    that slot's ``s_q`` queries against the page's tokens, into the
    slot's persistent (m, l, acc) scratch rows."""
    i = pl.program_id(0)
    n_w = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # page tile -> fp32 VMEM values, dequantized here for int8 pools
    # (per-(token, head) scales broadcast over the head dim — the HBM
    # read was 1 byte/elem; only the VMEM copy widens)
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    if ks_ref is not None:
        k = k * ks_ref[:].astype(jnp.float32)
        v = v * vs_ref[:].astype(jnp.float32)
    if rep > 1:
        # grouped (GQA) page expands to query-head width on the VMEM
        # copy only — query head h reads grouped head h // rep, the
        # expand_kv_heads convention every consumer shares
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    kh = k.transpose(1, 0, 2)                     # (H, ps, Dh)
    vh = v.transpose(1, 0, 2)

    # absolute position of the page's tokens, and each query row's
    # visibility horizon: position j of the verify block sees tokens
    # <= lengths + j (j = 0 is exactly the decode mask — the token
    # written this step sits AT lengths and must see itself). In TREE
    # verify mode (tvis_ref set) the draft region is ancestor-only
    # instead: token at offset ``off = pos - lengths`` in (0, s_q) is
    # visible to query row j iff node ``off`` is an ancestor-or-self
    # of node j (``tvis[slot, j, off]``) — sibling branches of the
    # candidate tree never attend each other; the chain matrix
    # ``tvis[j, i] = i <= j`` reproduces the linear mask bit-for-bit.
    tok = wpos_ref[i] * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (s_q, page_size), 1)
    qpos = jax.lax.broadcasted_iota(jnp.int32, (s_q, page_size), 0)

    for lane in range(n_lanes):
        slot = wr_ref[i, lane]

        @pl.when(slot >= 0)
        def _lane(slot=slot):
            s_c = jnp.clip(slot, 0, n_slots - 1)
            if tvis_ref is None:
                visible = tok <= len_ref[s_c] + qpos   # (s_q, ps)
            else:
                off = tok - len_ref[s_c]               # (s_q, ps)
                # one-hot the offsets (off's rows are identical and
                # qpos is the row index, so ``off == qpos`` marks
                # row r where the token offset equals r) so the
                # per-row ancestor lookup is a tiny (s_q, s_q) @
                # (s_q, ps) dot — no dynamic gather in the kernel
                oh = (off == qpos).astype(jnp.float32)
                sel = jax.lax.dot_general(
                    tvis_ref[s_c].astype(jnp.float32), oh,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                visible = (off <= 0) | (
                    (off > 0) & (off < s_q) & (sel > 0.5))
            q3 = (q_ref[s_c].astype(jnp.float32) * sm_scale
                  ).transpose(1, 0, 2)             # (H, s_q, Dh)
            scores = jax.lax.dot_general(
                q3, kh, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)  # (H, s_q, ps)
            scores = jnp.where(visible[None], scores, NEG_INF)
            m_prev = m_scr[s_c]                    # (H, s_q)
            l_prev = l_scr[s_c]
            m_cur = jnp.maximum(m_prev, scores.max(axis=-1))
            corr = jnp.exp(m_prev - m_cur)
            # probabilities gated by the MASK, not the score value: a
            # fully-masked row (a write-ahead page past the slot's
            # length) would otherwise see exp(NEG_INF - NEG_INF) = 1
            # and poison l with page_size phantom tokens
            p = jnp.where(visible[None],
                          jnp.exp(scores - m_cur[..., None]), 0.0)
            m_scr[s_c] = m_cur
            l_scr[s_c] = l_prev * corr + p.sum(axis=-1)
            acc_scr[s_c] = (
                acc_scr[s_c] * corr[..., None]
                + jax.lax.dot_general(
                    p, vh, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32))

    @pl.when(i == n_w - 1)
    def _finalize():
        o = acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)[..., None]
        o_ref[:] = o.transpose(0, 2, 1, 3).astype(o_ref.dtype)


def paged_attention(q: jax.Array, pool_k, pool_v,
                    work_pages: jax.Array, work_refs: jax.Array,
                    work_pos: jax.Array, lengths: jax.Array, *,
                    page_size: int, sm_scale: float | None = None,
                    tree_vis: jax.Array | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """Paged flash-decode attention over the serving page pool.

    - ``q``: ``(max_slots, S, n_heads, head_dim)`` queries, ``S ∈
      {1, 1 + draft_len}`` (decode / fused speculative verify);
    - ``pool_k``/``pool_v``: ONE layer's pages with the heads split
      out, ``(n_pages, page_size, kv_heads, head_dim)`` — a plain
      bf16/fp32 array or an ``(int8 values, bf16 scales (..., 1))``
      pair (the engine converts from the pool's merged rows at this
      boundary: ``PagedEngine._kernel_pages``);
    - ``work_pages (W,)`` / ``work_refs (W, n_lanes)`` / ``work_pos
      (W,)``: the compacted live-page walk (``BlockTables.
      kernel_args()``): pool page id, holder slots (-1 empty lanes),
      and page position per entry — padding entries are page 0 with
      all lanes empty;
    - ``lengths (max_slots,)``: tokens currently visible per slot;
    - ``tree_vis (max_slots, S, S)`` (optional, tree speculative
      verify): ancestor-or-self matrix of the per-slot candidate
      TREE — query row j sees draft offset i iff ``tree_vis[slot, j,
      i]``; prior context (offsets <= 0) is always visible. ``None``
      (decode and linear verify) keeps the causal-chain mask
      bit-for-bit.

    Returns the normalized ``(max_slots, S, n_heads, head_dim)``
    attention output in ``q.dtype`` (garbage rows at slots no work
    entry references — inactive slots; callers ignore them, exactly as
    they do the XLA sweep's). All shapes are geometry-only, so the one
    trace the engine takes serves every occupancy — the zero-recompile
    contract holds through the kernel path unchanged."""
    n_slots, s_q, n_heads, head_dim = q.shape
    quantized = isinstance(pool_k, tuple)
    kv = pool_k[0] if quantized else pool_k
    kv_heads = kv.shape[2]
    rep = n_heads // kv_heads
    n_w = work_pages.shape[0]
    n_lanes = work_refs.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)

    body = functools.partial(
        _paged_kernel, page_size=page_size, n_lanes=n_lanes, rep=rep,
        sm_scale=sm_scale, n_slots=n_slots, s_q=s_q)
    tree = tree_vis is not None
    # optional operands (int8 scales, the tree-visibility matrix) are
    # spliced into the shared kernel body's signature as None refs
    # when absent, so ONE body serves all four layouts
    if quantized and tree:
        kernel = body
    elif quantized:
        def kernel(wp, wr, wpos, ln, q_r, k_r, v_r, ks_r, vs_r,
                   o_r, m_s, l_s, a_s):
            body(wp, wr, wpos, ln, q_r, k_r, v_r, ks_r, vs_r, None,
                 o_r, m_s, l_s, a_s)
    elif tree:
        def kernel(wp, wr, wpos, ln, q_r, k_r, v_r, tv_r,
                   o_r, m_s, l_s, a_s):
            body(wp, wr, wpos, ln, q_r, k_r, v_r, None, None, tv_r,
                 o_r, m_s, l_s, a_s)
    else:
        def kernel(wp, wr, wpos, ln, q_r, k_r, v_r, o_r, m_s, l_s, a_s):
            body(wp, wr, wpos, ln, q_r, k_r, v_r, None, None, None,
                 o_r, m_s, l_s, a_s)

    # the block-table walk: the page BlockSpec's index comes from the
    # PREFETCHED work list, so grid step i streams exactly pool page
    # work_pages[i] into VMEM — consecutive equal indices (the null-
    # page padding tail) are not re-fetched
    page_spec = pl.BlockSpec(
        (None, page_size, kv_heads, head_dim),
        lambda i, wp, wr, wpos, ln: (wp[i], 0, 0, 0))
    scale_spec = pl.BlockSpec(
        (None, page_size, kv_heads, 1),
        lambda i, wp, wr, wpos, ln: (wp[i], 0, 0, 0))
    full_spec = pl.BlockSpec((n_slots, s_q, n_heads, head_dim),
                             lambda i, wp, wr, wpos, ln: (0, 0, 0, 0))
    if quantized:
        in_specs = [full_spec, page_spec, page_spec,
                    scale_spec, scale_spec]
        operands = (q, pool_k[0], pool_v[0], pool_k[1], pool_v[1])
    else:
        in_specs = [full_spec, page_spec, page_spec]
        operands = (q, pool_k, pool_v)
    if tree:
        in_specs = in_specs + [pl.BlockSpec(
            (n_slots, s_q, s_q),
            lambda i, wp, wr, wpos, ln: (0, 0, 0))]
        operands = operands + (jnp.asarray(tree_vis, jnp.int32),)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_w,),
        in_specs=in_specs,
        out_specs=full_spec,
        scratch_shapes=[
            pltpu.VMEM((n_slots, n_heads, s_q), jnp.float32),  # m
            pltpu.VMEM((n_slots, n_heads, s_q), jnp.float32),  # l
            pltpu.VMEM((n_slots, n_heads, s_q, head_dim),
                       jnp.float32),                           # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_slots, s_q, n_heads, head_dim), q.dtype),
        compiler_params=_CompilerParams(
            # the whole grid shares the per-slot scratch state — the
            # walk is sequential by construction
            dimension_semantics=("arbitrary",)),
        interpret=_resolve_interpret(interpret),
    )(jnp.asarray(work_pages, jnp.int32),
      jnp.asarray(work_refs, jnp.int32),
      jnp.asarray(work_pos, jnp.int32),
      jnp.asarray(lengths, jnp.int32), *operands)


__all__ = ["paged_attention"]
