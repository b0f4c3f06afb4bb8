"""Pallas walk of the decode lanes over per-head K/V pool rows: each
decoding slot's ONE query attends the pages its table names up to its
own position, read in place, for a paged pool and a ring pool alike.

Why a kernel and not the pool sweep of ``kv_pages.sweep_attention``:
the sweep reads every page of a layer's pool every step, whatever is
live; its bytes are the pool's. Here the bytes follow the live
context:

- The pool is read IN PLACE and in its storage layout: the operands
  are the stacked K and V pools ``(layers, n_pages, page_size,
  width)`` themselves, left in HBM; the layer index, the tables and
  the compacted walk ride as scalar-prefetch operands, and the kernel
  copies each page by DMA into a double-buffered VMEM scratch — no
  slice, gather or relayout of the pool outside the kernel.
- ONE loop over the live ``(slot, block)`` items, compacted outside
  the kernel (:func:`_walk_items`): a slot that is not decoding has
  no item, a block is ``pages_per_step`` consecutive table entries,
  and a page past the slot's position (or below a window's lower
  edge) is neither fetched nor attended. The DMAs of item ``i + 1``
  are in flight while item ``i`` is attended, across slot boundaries:
  the walk's first wait is its one exposed wait, not each slot's.
- The rows are never split by head (a relayout of the layer's pool).
  The queries arrive as pool rows (``kv_pages.to_rows``: row ``r``
  holds head ``(g, r)``'s query in KV head g's lanes) and are laid
  out BLOCK-DIAGONALLY in VMEM once a slot, as the sweep lays out its
  columns: row ``(r, g)`` holds head ``(g, r)``'s query in head g's
  lanes and zeros elsewhere, so scores are ``q_rows @ K_rows^T``
  contracting the whole row and GQA and MHA are one code path. The
  value product ``p @ V_rows`` gives every row the weighted sum of ALL
  heads' lanes, of which it keeps its own head's: the output is pool
  rows again, ``(slots, rep, width)``, no larger than the queries.
- Numerics: operands in the pool's dtype, float32 scores, softmax and
  accumulation (a float32 pool stays exact). The probabilities enter
  the value product as two halves of the pool's dtype, ``hi`` and the
  rounding ``lo`` it left, so that a bfloat16 pool's output carries
  what the sweep's float32 probabilities gave it: rounded once to
  bfloat16, as ``latent_paged_attention`` and ``flash_attention`` do,
  they doubled the served logits' distance from the float32 reference
  in the toy serve rehearsal (benchmark/tests).
- Each slot walks its OWN table: a prefix page shared by several
  slots is read once per sharer — still far under the pool.

On CPU the kernel runs in interpret mode (``_pallas_util``), so the
tier-1 tests (tests/test_lane_walk.py and the engine's parity tests)
hold it to the sweep and to a dense float32 reference without a chip;
tests/test_tpu_aot_compile.py compiles it for the described v5e at
the benchmark cells' geometries.
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
# an item's pages: about this many bytes of K (and as many of V), at
# least 4 and at most 16 pages. On a TPU v5e the walk's time over the
# pages it fetched was flat from 4 to 16 pages an item at 213 KB and
# 131 KB pages (GPT-2 XL, the window-and-full family) and fell 21 %
# from 8 to 16 at 66 KB pages (LFM2): an item must be large enough to
# bury its fixed cost
ITEM_BYTES = 2**20
STAT_LANES = 128        # m and l are kept lane-replicated
VMEM_LIMIT = 64 * 2**20
FIRST, LAST = 1, 2      # an item's flags: it opens / closes its slot


def _walk_items(hi, live, n_pp: int, max_blocks: int):
    """The compacted walk: items ``(slot, first entry, entries, flags)``
    of every live slot's blocks, slot-major, padded to ``slots *
    max_blocks``; and how many are real. ``hi (slots,)``: a slot's
    last table entry to read (entries ``0 .. hi``)."""
    n_slots = hi.shape[0]
    n_blocks = jnp.where(live, hi // n_pp + 1, 0)
    ends = jnp.cumsum(n_blocks)
    i = jnp.arange(n_slots * max_blocks, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1),
                       n_slots - 1).astype(jnp.int32)
    block = i - (ends - n_blocks)[slot]
    first = block * n_pp
    count = jnp.clip(hi[slot] - first + 1, 0, n_pp)
    flags = jnp.where(block == 0, FIRST, 0) \
        + jnp.where(block == n_blocks[slot] - 1, LAST, 0)
    return (slot, first.astype(jnp.int32), count.astype(jnp.int32),
            flags.astype(jnp.int32), ends[-1:].astype(jnp.int32))


def _walk_kernel(li_ref, tab_ref, slot_ref, first_ref, count_ref,
                 flag_ref, n_ref, base_ref, pos_ref, q_ref, k_hbm, v_hbm,
                 o_ref, kbuf, vbuf, sem, q_scr, m_scr, l_scr, acc_scr, *,
                 page_size: int, n_pp: int, n_entries: int, kv_heads: int,
                 head_dim: int, scale: float, window: int | None):
    """The whole walk in one invocation: a loop over the compacted
    items, each the flash online-softmax update of its slot's query
    rows against ``n_pp`` pages, into the slot's ``(m, l, acc)``
    scratch, written out normalised at the slot's last item."""
    n_items = n_ref[0]
    n_tok = n_pp * page_size
    n_rows, width = q_scr.shape
    rep = n_rows // kv_heads
    # own[(r, g), c]: lane c of a row belongs to KV head g
    own = jax.lax.broadcasted_iota(jnp.int32, (n_rows, width), 1) \
        // head_dim == jax.lax.broadcasted_iota(
            jnp.int32, (n_rows, width), 0) % kv_heads

    def each_page(i, half, act):
        """``act`` on the K and V DMAs of each page item ``i`` fetches
        into buffer ``half``: a loop over its count, so a page past it
        is not copied."""
        s, e0 = slot_ref[i], first_ref[i]

        def page(j, carry):
            src = (li_ref[0], tab_ref[s * n_entries + e0 + j])
            act(pltpu.make_async_copy(k_hbm.at[src], kbuf.at[half, j],
                                      sem.at[0, half, j]))
            act(pltpu.make_async_copy(v_hbm.at[src], vbuf.at[half, j],
                                      sem.at[1, half, j]))
            return carry

        jax.lax.fori_loop(0, count_ref[i], page, 0)

    start = lambda i, half: each_page(i, half, lambda dma: dma.start())
    wait = lambda i, half: each_page(i, half, lambda dma: dma.wait())

    # a page an item does not fetch is masked out of the scores, but
    # its value rows still meet a zero probability: they must be
    # finite, and what the buffer holds before its first fetch is not
    # known
    vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(n_items > 0)
    def _first():
        start(0, 0)

    def body(i, carry):
        half = i % 2
        s, flags = slot_ref[i], flag_ref[i]

        @pl.when(i + 1 < n_items)
        def _next():
            start(i + 1, 1 - half)

        @pl.when((flags & FIRST) != 0)
        def _open():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)
            # the slot's query rows, block-diagonally: row (r, g) is
            # query row r with every lane but head g's zeroed
            q = q_ref[s].astype(jnp.float32)            # (rep, width)
            q = jnp.concatenate(
                [jnp.broadcast_to(q[r:r + 1], (kv_heads, width))
                 for r in range(rep)], axis=0) if rep > 1 else \
                jnp.broadcast_to(q, (kv_heads, width))
            q_scr[...] = jnp.where(own, q, 0.0).astype(q_scr.dtype)

        wait(i, half)
        q = q_scr[...]                              # (rows, width)
        keys = kbuf[half].reshape(n_tok, width)
        scores = jax.lax.dot_general(
            q, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, (n_rows, n_tok), 1)
        k_pos = (base_ref[s] + first_ref[i]) * page_size + col
        q_pos = pos_ref[s]
        visible = (col < count_ref[i] * page_size) & (k_pos <= q_pos)
        if window is not None:
            visible &= q_pos - k_pos < window
        scores = jnp.where(visible, scores, NEG_INF)
        m_prev = m_scr[...]
        m = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m)                  # (rows, STAT_LANES)
        # gated by the MASK: a row that sees nothing in this block
        # would otherwise count exp(NEG_INF - NEG_INF) = 1 a column
        p = jnp.where(visible, jnp.exp(scores - m[:, :1]), 0.0)
        m_scr[...] = m
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        values = vbuf[half].reshape(n_tok, width)
        weighted = lambda w: jax.lax.dot_general(
            w, values, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # the probabilities as two halves of the pool's dtype, hi + lo:
        # the value product carries ~16 bits of each, not 8
        hi = p.astype(values.dtype)
        pv = weighted(hi)
        if values.dtype != jnp.float32:
            pv += weighted((p - hi.astype(jnp.float32)).astype(values.dtype))
        acc_scr[...] = acc_scr[...] * corr[:, :1] + pv

        @pl.when((flags & LAST) != 0)
        def _close():
            # each row keeps its own head's lanes: the rows (r, .)
            # summed are query row r's output, in pool rows again
            o = jnp.where(own, acc_scr[...] / jnp.maximum(
                l_scr[:, :1], 1e-30), 0.0)
            for r in range(rep):
                o_ref[s, r:r + 1] = o[r * kv_heads:(r + 1) * kv_heads].sum(
                    axis=0, keepdims=True).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, n_items, body, 0)


def lane_walk_attention(q_rows: jax.Array, pool_k: jax.Array,
                        pool_v: jax.Array, layer, tables: jax.Array,
                        base: jax.Array, q_pos: jax.Array,
                        live: jax.Array, *, kv_heads: int, head_dim: int,
                        window: int | None = None,
                        pages_per_step: int | None = None,
                        interpret: bool | None = None) -> jax.Array:
    """Attention of each slot's ONE query over the pages its table
    names, the pools read in place.

    - ``q_rows (slots, rep, width)``: the queries as pool rows
      (``kv_pages.to_rows`` of ``(slots, rep, kv_heads, head_dim)``:
      row ``r`` holds head ``(g, r)``'s query in KV head g's lanes);
    - ``pool_k`` / ``pool_v (layers, n_pages, page_size, width)``: the
      stacked pools of plain rows, read in place;
    - ``layer``: the layer's index into them (a traced scalar in a
      scan);
    - ``tables (slots, entries)``: the pages a slot reads in position
      order — entry ``e`` holds the slot's sequence page ``base + e``
      (a block table: ``base`` 0; a ring in position order: ``base``
      the page of the window's lower edge);
    - ``base (slots,)``: the sequence page of entry 0;
    - ``q_pos (slots,)``: the query's position; it sees the positions
      up to and including its own (its row was written before the
      call) and, with ``window``, those less than ``window`` behind;
    - ``live (slots,)``: False -> the slot is skipped.

    Entries ``0 .. q_pos // page_size - base`` are fetched and
    attended, nothing else, ``pages_per_step`` of them an item (None:
    :data:`ITEM_BYTES` of pages, 4 to 16). Returns ``(slots, rep,
    width)`` float32 in ``q_rows``' layout, normalised (head ``(g,
    r)``'s output in row r, KV head g's lanes); a dead slot's rows are
    left unwritten. Shapes are geometry-only: one trace serves every
    occupancy and every length, and (a module-level ``jax.jit`` with the
    geometry static) the call sites of one shape in a program share ONE
    lowered callee."""
    if pages_per_step is None:
        page_bytes = pool_k.shape[2] * pool_k.shape[3] \
            * pool_k.dtype.itemsize
        pages_per_step = max(4, min(16, ITEM_BYTES // page_bytes))
    return _lane_walk(
        q_rows, pool_k, pool_v, jnp.asarray(layer, jnp.int32), tables,
        jnp.asarray(base, jnp.int32), jnp.asarray(q_pos, jnp.int32), live,
        kv_heads=kv_heads, head_dim=head_dim, window=window,
        pages_per_step=pages_per_step,
        interpret=_resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=(
    "kv_heads", "head_dim", "window", "pages_per_step", "interpret"))
def _lane_walk(q_rows, pool_k, pool_v, layer, tables, base, q_pos, live, *,
               kv_heads: int, head_dim: int, window: int | None,
               pages_per_step: int, interpret: bool):
    """:func:`lane_walk_attention` with its geometry static."""
    n_slots, rep, width = q_rows.shape
    n_rows = kv_heads * rep
    _, _, page_size, _ = pool_k.shape
    n_entries = tables.shape[1]
    n_pp = min(pages_per_step, n_entries)
    max_blocks = -(-n_entries // n_pp)
    hi = jnp.clip(q_pos // page_size - base, 0, n_entries - 1)
    slot, first, count, flags, n_items = _walk_items(
        hi, live, n_pp, max_blocks)

    whole = lambda *_: (0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(1,),
        in_specs=[pl.BlockSpec((n_slots, rep, width), whole),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((n_slots, rep, width), whole),
        scratch_shapes=[
            pltpu.VMEM((2, n_pp, page_size, width), pool_k.dtype),
            pltpu.VMEM((2, n_pp, page_size, width), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2, 2, n_pp)),
            pltpu.VMEM((n_rows, width), pool_k.dtype),          # q
            pltpu.VMEM((n_rows, STAT_LANES), jnp.float32),      # m
            pltpu.VMEM((n_rows, STAT_LANES), jnp.float32),      # l
            pltpu.VMEM((n_rows, width), jnp.float32),           # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_walk_kernel, page_size=page_size, n_pp=n_pp,
                          n_entries=n_entries, kv_heads=kv_heads,
                          head_dim=head_dim, scale=head_dim ** -0.5,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q_rows.shape, jnp.float32),
        name="lane_walk",
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(layer.reshape(1),
      jnp.asarray(tables, jnp.int32).reshape(-1),
      slot, first, count, flags, n_items, base, q_pos,
      q_rows.astype(pool_k.dtype), pool_k, pool_v)


__all__ = ["lane_walk_attention"]
