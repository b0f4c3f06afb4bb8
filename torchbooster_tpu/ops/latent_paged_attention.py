"""Pallas paged flash attention over a LATENT pool: one row a token,
one KV head serving every query head, the values the row's leading
lanes (models/mla_moe.py, the absorbed form). ONE kernel for the
decode lanes (a token a slot) and for a prefill chunk (its tokens in
blocks): a GROUP of query tokens walks one block table.

Why a kernel and not the pool sweep of ``kv_pages.sweep_attention``:
the sweep returns one flash partial a (page, head) and merges them per
slot afterwards. With per-head K/V a page's partials are a small
fraction of the page; with a latent row every one of the ``n_heads``
query heads reads the WHOLE row, so a page's partials (``n_heads x
value_dim`` float32) outweigh the page itself (``page_size x width``
bfloat16) and the merge moves more bytes than the pool holds — and a
chunk's float32 scores over its slot's table (chunk x heads x table)
are hundreds of MB a layer. Here neither leaves the chip: the grid
walks GROUPS, and within a group its block table ``pages_per_step``
pages at a time, carrying the online-softmax state ``(m, l, acc)`` of
the group's ``tokens x n_heads`` query rows in VMEM scratch across the
walk — the flash structure, table-major.

- The pool is read IN PLACE and in its storage layout: the operand is
  the stacked pool ``(layers, n_pages, page_size, width)`` itself, left
  in HBM; the layer index and the block tables ride as scalar-prefetch
  operands, and the kernel copies ``tables[group, block *
  pages_per_step + i]`` page by page into a double-buffered VMEM
  scratch — no slice, gather or relayout of the pool outside the
  kernel, and a block's pages are in flight while the block before it
  is attended (a group's first block is the one exposed wait). The
  ``pages_per_step`` pages lie contiguous in the scratch, so a block
  is ONE score product and ONE value product.
- Bytes and operations track what is VISIBLE: a group's blocks past
  its last token's position (and every block of a dead group) are
  neither fetched nor attended — a decode step reads the live context,
  not the pool, and a prefill chunk its prompt so far, not its slot's
  table: a chunk's cost follows the prompt's true length.
- Numerics: operands in the pool's dtype, float32 accumulation and
  softmax, the probabilities rounded to the pool's dtype for the value
  product (a float32 pool stays exact). The queries arrive SCALED.

On CPU the kernel runs in interpret mode (``_pallas_util``), so the
tier-1 parity tests (tests/test_sarvam_mla.py) hold it to the float32
reference without a chip; tests/test_tpu_aot_compile.py compiles it
for the described v5e at the benchmark cell's size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchbooster_tpu.ops._pallas_util import (
    CompilerParams as _CompilerParams,
    resolve_interpret as _resolve_interpret,
)

NEG_INF = -1e30
PAGES_PER_STEP = 8      # pages of one table a grid step attends
STAT_LANES = 128        # m and l are kept lane-replicated
VMEM_LIMIT = 48 * 2**20


def _latent_kernel(li_ref, tab_ref, nblk_ref, pos_ref, q_ref, pool_ref,
                   o_ref, buf, sem, m_scr, l_scr, acc_scr, *,
                   page_size: int, value_dim: int, n_pp: int,
                   max_pages: int, n_heads: int):
    """One grid step = ``n_pp`` consecutive pages of one group's
    table: the flash online-softmax update of the group's query rows
    (token-major, ``n_heads`` rows a token) against their tokens, into
    the group's ``(m, l, acc)`` scratch. The pages come by DMA
    straight out of the pool (``pool_ref`` stays in HBM),
    double-buffered: a block's pages are in flight while the block
    before it is attended."""
    g, b = pl.program_id(0), pl.program_id(1)
    n_live = nblk_ref[g]

    def pages_of(block, half):
        """The DMAs of ``block``'s pages into buffer ``half``."""
        out = []
        for i in range(n_pp):
            at = jnp.minimum(block * n_pp + i, max_pages - 1)
            out.append(pltpu.make_async_copy(
                pool_ref.at[li_ref[0], tab_ref[g * max_pages + at]],
                buf.at[half, i], sem.at[half, i]))
        return out

    @pl.when(b == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

        @pl.when(n_live > 0)
        def _first():
            for dma in pages_of(0, 0):
                dma.start()

    @pl.when(b < n_live)
    def _attend():
        half = b % 2

        @pl.when(b + 1 < n_live)
        def _next():
            for dma in pages_of(b + 1, 1 - half):
                dma.start()

        for dma in pages_of(b, half):
            dma.wait()
        q = q_ref[:]                                # (rows, width)
        n_rows, width = q.shape
        n_tok = n_pp * page_size
        rows = buf[half].reshape(n_tok, width)      # the block's tokens
        scores = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)     # (rows, n_tok)
        # a query token sees the positions up to and including its own
        # (a decode step's token was just written AT its position)
        at = b * n_tok + jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, n_tok), 1)
        q_pos = pos_ref[g] + jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, n_tok), 0) // n_heads
        visible = at <= q_pos
        scores = jnp.where(visible, scores, NEG_INF)
        m_prev = m_scr[:]
        m = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m)                  # (rows, STAT_LANES)
        # gated by the MASK: a fully masked row would otherwise see
        # exp(NEG_INF - NEG_INF) = 1 a token
        p = jnp.where(visible, jnp.exp(scores - m[:, :1]), 0.0)
        m_scr[:] = m
        l_scr[:] = l_scr[:] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr[:, :1] + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_dim],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(b == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[:] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def latent_paged_attention(q: jax.Array, pool: jax.Array, layer,
                           tables: jax.Array, first_pos: jax.Array,
                           live: jax.Array, *, n_heads: int,
                           value_dim: int,
                           pages_per_step: int = PAGES_PER_STEP,
                           interpret: bool | None = None) -> jax.Array:
    """Attention of GROUPS of query tokens, each group over the latent
    rows of one block table.

    - ``q (groups, tokens * n_heads, row_dim)``: SCALED absorbed
      queries, token-major (a token's ``n_heads`` rows together;
      ``row_dim <= width``, zero-padded to the row). A decode step:
      one group a slot, one token; a prefill chunk: its tokens in
      consecutive blocks, every group on the seating slot's table;
    - ``pool (layers, n_pages, page_size, width)``: the stacked latent
      pool (``kv_pages.make_pool`` of a latent spec), read in place;
    - ``layer``: the layer's index into it (a traced scalar in a scan);
    - ``tables (groups, max_pages_per_slot)``: each group's block
      table;
    - ``first_pos (groups,)``: the position of a group's first token —
      token ``t`` of the group sits at ``first_pos + t`` and sees the
      table's positions ``0 .. first_pos + t``, its own included (the
      rows of the step's tokens are written before the call);
    - ``live (groups,)``: False -> the group is skipped.

    Returns ``(groups, tokens * n_heads, value_dim)`` float32,
    normalised (zeros at dead groups). Shapes are geometry-only: one
    trace serves every occupancy and every prompt length."""
    n_groups, n_rows, row_dim = q.shape
    _, _, page_size, width = pool.shape
    max_pages = tables.shape[1]
    n_pp = min(pages_per_step, max_pages)
    n_blocks = -(-max_pages // n_pp)
    if row_dim < width:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, width - row_dim)))
    q = q.astype(pool.dtype)
    # blocks the walk reaches: to the group's LAST token's position
    last_pos = first_pos + n_rows // n_heads - 1
    n_live = jnp.where(live, jnp.minimum(
        last_pos // (n_pp * page_size) + 1, n_blocks), 0)

    row = lambda g, b, li, tab, nblk, pos: (g, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_groups, n_blocks),
        in_specs=[pl.BlockSpec((None, n_rows, width), row),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, n_rows, value_dim), row),
        scratch_shapes=[
            pltpu.VMEM((2, n_pp, page_size, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2, n_pp)),
            pltpu.VMEM((n_rows, STAT_LANES), jnp.float32),      # m
            pltpu.VMEM((n_rows, STAT_LANES), jnp.float32),      # l
            pltpu.VMEM((n_rows, value_dim), jnp.float32),       # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, page_size=page_size,
                          value_dim=value_dim, n_pp=n_pp,
                          max_pages=max_pages, n_heads=n_heads),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_groups, n_rows, value_dim),
                                       jnp.float32),
        compiler_params=_CompilerParams(
            # a group's blocks share its scratch state: sequential
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=_resolve_interpret(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(tables, jnp.int32).reshape(-1),
      n_live.astype(jnp.int32), jnp.asarray(first_pos, jnp.int32),
      q, pool)


__all__ = ["latent_paged_attention"]
