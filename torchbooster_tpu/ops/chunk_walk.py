"""Pallas walk of a prefill chunk's PRIOR context over per-head K/V
pool rows: the chunk's ``C`` query tokens of one slot attend the pages
its table names, read in place, up to the chunk's own position — for a
paged pool (a block table) and a window layer's ring alike.

Why a kernel and not a gather: the engine's chunk used to gather ALL
of its slot's table out of the pool and attend it with XLA, masked to
the positions before the chunk — every table entry read whatever the
prompt's length, and float32 scores ``(C, heads, table positions)``
written to HBM and read back (217 MB a sliding layer in the
window-and-full family's cell). Here:

- The pool is read IN PLACE: the operands are the stacked K and V
  pools ``(layers, n_pages, page_size, width)`` left in HBM; the layer
  index, the table and the chunk's cursors ride as scalar-prefetch
  operands, and the kernel copies each page by DMA into a
  double-buffered VMEM scratch. A page at or past the chunk's start
  (or, in a ring, below the window's lower edge: the caller's table
  starts there) is neither fetched nor attended.
- Scores, the softmax statistics and the accumulator live in VMEM, in
  float32; only the normalised output and one statistic a row leave.
- HEADS, not whole rows, meet the matrix unit: the grid walks the
  row's lane BLOCKS — the smallest run of whole 128-lane tiles that
  holds whole heads (two heads of 64, one of 128, one of 256) — and
  each block's pages come by DMA of that block's lanes only, so every
  page is still fetched once. Within a block the queries are laid out
  block-diagonally over its few heads (``lane_walk``'s device, at a
  block's width, not the row's: the redundant products are the heads
  of one block, not all of them).
- Numerics as ``lane_walk``: operands in the pool's dtype, float32
  scores, softmax and accumulation; probabilities into the value
  product as two halves of the pool's dtype (``hi`` + ``lo``).
- The result is the float32 flash partial ``(o, m, l)`` of
  ``models.gpt._grouped_cache_attention(state=True)``: the engine
  merges it with the chunk's own causal block as before. ``o`` comes
  out normalised with ``m`` the log-sum-exp and ``l`` 1 (0 for a row
  that saw nothing): the same partial, one statistic the lighter.

**Lowered once per shape.** The kernel's body does not grow with the
chunk, the table, the window or the number of blocks: loops are a grid
axis (lane blocks) and ``lax.fori_loop`` (items, pages); the Python
loops run over the heads of ONE lane block. The public function calls
a module-level ``jax.jit`` with the geometry static, so call sites of
the same shapes in one program (the window-and-full family's four
sliding layers) share ONE lowered callee, and a process traces each
geometry once (``lane_walk`` is called the same way).

On CPU the kernel runs in interpret mode (``_pallas_util``): the
tier-1 tests (tests/test_chunk_walk.py and the engines' parity tests)
hold it to the gather path and a dense float32 reference;
tests/test_tpu_aot_compile.py compiles it for the described v5e at
the benchmark cells' geometries and guards the lowering's size.
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

NEG_INF = -1e30
LANES = 128
# keys an item attends: its pages' rows, at least one page. Scores of
# an item are (query rows x keys) float32 in VMEM: 1536 x 512 x 4 =
# 3 MB at the window-and-full family's geometry
ITEM_KEYS = 512
STAT_LANES = 128        # m and l are kept lane-replicated
VMEM_LIMIT = 64 * 2**20


def lane_block(width: int, head_dim: int) -> int:
    """Lanes of one block of the walk: the least common multiple of
    the head and the 128-lane tile where it divides the row, else the
    whole row (a head width that straddles tiles unevenly)."""
    block = head_dim * LANES // math.gcd(head_dim, LANES)
    return block if width % block == 0 else width


def _walk_kernel(li_ref, tab_ref, meta_ref, q_ref, k_hbm, v_hbm,
                 o_ref, lse_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr,
                 *scratch, page_size: int, n_pp: int, n_lb: int,
                 block: int, hb: int, head_dim: int, n_q: int,
                 scale: float, window: int | None):
    """Grid step ``lb``: lane block ``lb`` of every query row, over the
    chunk's prior pages ``n_pp`` at a time (a ``fori_loop`` of items),
    the flash online-softmax update into ``(m, l, acc)``, written out
    normalised at the block's end. The first item of the next block is
    fetched while the last of this one is attended."""
    lb = pl.program_id(0)
    base, start, count, n_items = (meta_ref[i] for i in range(4))
    scratch = list(scratch)
    q_scr = scratch.pop(0) if hb > 1 else None
    lo_scr = scratch.pop(0) if window is not None else None
    n_tok = n_pp * page_size
    n_rows = m_scr.shape[0]

    def each_page(blk, j, half, act):
        """``act`` on the K and V DMAs of item ``j`` of lane block
        ``blk`` into buffer ``half``: a loop over its pages, so a page
        past ``count`` is not copied."""
        e0 = j * n_pp

        def page(t, carry):
            src = (li_ref[0], tab_ref[e0 + t])
            if n_lb > 1:
                src += (slice(None),
                        pl.ds(pl.multiple_of(
                            jnp.asarray(blk, jnp.int32) * block, LANES),
                            block))
            act(pltpu.make_async_copy(k_hbm.at[src], kbuf.at[half, t],
                                      sem.at[0, half, t]))
            act(pltpu.make_async_copy(v_hbm.at[src], vbuf.at[half, t],
                                      sem.at[1, half, t]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(n_pp, count - e0), page, 0)

    start_dma = lambda blk, j, half: each_page(
        blk, j, half, lambda dma: dma.start())

    @pl.when(lb == 0)
    def _first():
        # a page an item does not fetch meets a zero probability: its
        # value rows must be finite, and the buffer's are not known
        # before their first fetch
        vbuf[...] = jnp.zeros_like(vbuf)
        if window is not None:
            # each row's lowest visible key position is its query's
            # position less the window: rows are (head, rep, token)
            # with the token minor
            tok = jax.lax.broadcasted_iota(
                jnp.int32, (n_rows // n_q, n_q, STAT_LANES), 1)
            lo_scr[...] = tok.reshape(n_rows, STAT_LANES) + (
                start - window)

        @pl.when(n_items > 0)
        def _fetch():
            start_dma(0, 0, 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    if hb > 1:
        @pl.when(n_items > 0)
        def _rows():
            # the rows once a head of the block: head h's copy of the
            # queries keeps head h's lanes and zeros elsewhere
            q = q_ref[...]
            own = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1) \
                // head_dim
            for h in range(hb):
                q_scr[h * q.shape[0]:(h + 1) * q.shape[0]] = jnp.where(
                    own == h, q, jnp.zeros((), q.dtype))

    def body(j, carry):
        half = (lb * n_items + j) % 2
        # the next item: this block's, or after its last the next
        # block's first
        more = j + 1 < n_items

        @pl.when(more | (lb + 1 < n_lb))
        def _next():
            start_dma(jnp.where(more, lb, lb + 1),
                      jnp.where(more, j + 1, 0), 1 - half)

        each_page(lb, j, half, lambda dma: dma.wait())
        q = q_scr[...] if hb > 1 else q_ref[...]
        keys = kbuf[half].reshape(n_tok, block)
        scores = jax.lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, (1, n_tok), 1)
        k_pos = (base + j * n_pp) * page_size + col
        visible = (col < (count - j * n_pp) * page_size) & (k_pos < start)
        if window is not None:
            visible = visible & (k_pos > lo_scr[:, :1])
        scores = jnp.where(visible, scores, NEG_INF)
        m_prev = m_scr[...]
        m = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m)                  # (rows, STAT_LANES)
        # gated by the MASK: a row that sees nothing in this item
        # would otherwise count exp(NEG_INF - NEG_INF) = 1 a column
        p = jnp.where(visible, jnp.exp(scores - m[:, :1]), 0.0)
        m_scr[...] = m
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        values = vbuf[half].reshape(n_tok, block)
        weighted = lambda w: jax.lax.dot_general(
            w, values, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # the probabilities as two halves of the pool's dtype, hi + lo
        hi = p.astype(values.dtype)
        pv = weighted(hi)
        if values.dtype != jnp.float32:
            pv += weighted((p - hi.astype(jnp.float32)).astype(values.dtype))
        acc_scr[...] = acc_scr[...] * corr[:, :1] + pv
        return carry

    jax.lax.fori_loop(0, n_items, body, 0)

    l = l_scr[...]
    o = acc_scr[...] / jnp.maximum(l[:, :1], 1e-30)
    if hb > 1:
        # each head's copy of the rows keeps its own lanes
        per = o.shape[0] // hb
        own = jax.lax.broadcasted_iota(jnp.int32, (per, block), 1) \
            // head_dim
        o = sum(jnp.where(own == h, o[h * per:(h + 1) * per], 0.0)
                for h in range(hb))
    o_ref[...] = o
    lse = jnp.where(l > 0, m_scr[...] + jnp.log(jnp.maximum(l, 1e-30)),
                    NEG_INF)                        # (rows, STAT_LANES)
    lse_ref[0] = lse.T[:1]


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "window", "pages_per_item", "interpret"))
def _chunk_walk(q, pool_k, pool_v, layer, table, base, start, *,
                kv_heads: int, window: int | None, pages_per_item: int,
                interpret: bool):
    """:func:`chunk_walk_attention` with its geometry static: ONE
    callee a shape in a program."""
    _, n_q, n_heads, head_dim = q.shape
    rep = n_heads // kv_heads
    _, _, page_size, width = pool_k.shape
    n_entries = table.shape[0]
    block = lane_block(width, head_dim)
    n_lb = width // block
    hb = min(block // head_dim, kv_heads)
    n_pp = min(pages_per_item, n_entries)
    # rows (rep, token), a KV head's lanes each: row (r, c) holds head
    # (g, r)'s query of token c in KV head g's lanes
    rows = jnp.swapaxes(q[0].reshape(n_q, kv_heads, rep, head_dim), 0, 2)
    rows = jnp.swapaxes(rows, 1, 2).reshape(rep * n_q, kv_heads * head_dim)
    rows = jnp.pad(rows, ((0, 0), (0, width - kv_heads * head_dim)))
    n_rows = hb * rep * n_q
    # the table entries before the chunk, from ``base`` on
    count = jnp.clip(-(-start // page_size) - base, 0, n_entries)
    meta = jnp.stack([base, start, count, -(-count // n_pp)]).astype(
        jnp.int32)

    per_block = lambda lb, *_: (0, lb)
    scratch = [
        pltpu.VMEM((2, n_pp, page_size, block), pool_k.dtype),
        pltpu.VMEM((2, n_pp, page_size, block), pool_v.dtype),
        pltpu.SemaphoreType.DMA((2, 2, n_pp)),
        pltpu.VMEM((n_rows, STAT_LANES), jnp.float32),      # m
        pltpu.VMEM((n_rows, STAT_LANES), jnp.float32),      # l
        pltpu.VMEM((n_rows, block), jnp.float32),           # acc
    ]
    if hb > 1:
        scratch.append(pltpu.VMEM((n_rows, block), pool_k.dtype))
    if window is not None:
        scratch.append(pltpu.VMEM((n_rows, STAT_LANES), jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_lb,),
        in_specs=[pl.BlockSpec((rep * n_q, block), per_block),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((rep * n_q, block), per_block),
                   pl.BlockSpec((1, 1, n_rows), lambda lb, *_: (lb, 0, 0))],
        scratch_shapes=scratch,
    )
    o, lse = pl.pallas_call(
        functools.partial(_walk_kernel, page_size=page_size, n_pp=n_pp,
                          n_lb=n_lb, block=block, hb=hb,
                          head_dim=head_dim, n_q=n_q,
                          scale=head_dim ** -0.5, window=window),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rep * n_q, width), jnp.float32),
                   jax.ShapeDtypeStruct((n_lb, 1, n_rows), jnp.float32)],
        name="chunk_walk",
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(layer.reshape(1), table.astype(jnp.int32),
      meta, rows.astype(pool_k.dtype), pool_k, pool_v)
    # back to the merge's layout: o (1, C, g, rep, hd), m and l (1, g,
    # rep, C); the statistics' rows are (block, head of it, rep, token)
    o = o[:, :kv_heads * head_dim].reshape(rep, n_q, kv_heads, head_dim)
    o = jnp.transpose(o, (1, 2, 0, 3))[None]
    lse = lse.reshape(n_lb * hb, rep, n_q)[:kv_heads][None]
    return o, lse, (lse > NEG_INF / 2).astype(jnp.float32)


def chunk_walk_attention(q: jax.Array, pool_k: jax.Array,
                         pool_v: jax.Array, layer, table: jax.Array, base,
                         start, *, kv_heads: int,
                         window: int | None = None,
                         pages_per_item: int | None = None,
                         interpret: bool | None = None) -> tuple:
    """A prefill chunk's attention over its slot's PRIOR context, the
    pools read in place.

    - ``q (1, C, n_heads, head_dim)``: the chunk's queries, token ``c``
      at position ``start + c``;
    - ``pool_k`` / ``pool_v (layers, n_pages, page_size, width)``: the
      stacked pools of plain rows (``kv_pages.make_pool``);
    - ``layer``: the layer's index into them (a traced scalar in a
      scan);
    - ``table (entries,)``: the pages the slot reads in position order
      — entry ``e`` holds the slot's sequence page ``base + e`` (a
      block table: ``base`` 0; a window layer's ring from the window's
      lower edge for the chunk's first query: ``kv_pages.ring_table``);
    - ``start``: the chunk's first position. Keys before it are
      visible, and with ``window`` only those less than ``window``
      behind the query: ``0 <= q_pos - k_pos < window``.

    Entries ``0 .. ceil(start / page_size) - base - 1`` are fetched,
    ``pages_per_item`` at a time (None: :data:`ITEM_KEYS` rows' worth),
    nothing else. Returns the float32 partial ``(o (1, C, kv_heads,
    rep, head_dim), m, l (1, kv_heads, rep, C))`` with ``o``
    normalised, ``m`` the log-sum-exp and ``l`` 1 — 0, with ``o`` 0 and
    ``m`` -1e30, for a query that saw nothing (the chunk at position
    0)."""
    page_size = pool_k.shape[2]
    if pages_per_item is None:
        pages_per_item = max(1, ITEM_KEYS // page_size)
    return _chunk_walk(
        q, pool_k, pool_v, jnp.asarray(layer, jnp.int32), table,
        jnp.asarray(base, jnp.int32), jnp.asarray(start, jnp.int32),
        kv_heads=kv_heads, window=window, pages_per_item=pages_per_item,
        interpret=_resolve_interpret(interpret))


__all__ = ["chunk_walk_attention", "lane_block"]
