"""Paged KV cache: a fixed pool of K/V pages + per-slot block tables,
with REFCOUNTED pages and a prompt-prefix index so requests that share
a prompt prefix share the physical pages instead of recomputing them.

The dense decode cache (models/gpt.py ``jit_generate``) preallocates
``(B, S_cache, H_kv, Dh)`` per layer and every decode step streams ALL
of it — at realistic mixed lengths most of those bytes are padding
(docs/performance.md roofline: decode is HBM-bound on exactly these
reads). Here the cache is a pool of ``(n_pages, page_size, H_kv, Dh)``
pages per layer shared by every serving slot; a sequence occupies
``ceil(len / page_size)`` pages wired up by a per-slot block table, so
the bytes a decode step must stream are the POOL's — sized to expected
total occupancy — instead of ``max_slots × S_cache``.

Two cooperating halves:

- :func:`make_pool` — the device-side pool and the ONE place that
  fixes its shape: K and V are each ``(n_layers, n_pages, page_size,
  kv_width)``, heads and head dim merged into one minor ROW of
  ``kv_heads * head_dim`` lanes rounded up to the device's 128-lane
  tile (:func:`kv_width`; 1600 -> 1664 at GPT-2 XL, +4 %; 768 and 1280
  pad nothing). bf16/fp32, or int8 rows + bf16 scales ``(n_layers,
  n_pages, page_size, kv_heads)`` — the engine quantizes page writes
  with the SAME ``_quantize_kv`` the dense ``cache_dtype="int8"`` path
  uses. Why this shape: the device tiles an array's two minor axes in
  ``(8, 128)`` tiles, so a 5-D ``(..., kv_heads, head_dim)`` pool
  either pads its ``(25, 64)`` minor tiles 2.56 x in row-major or gets
  the pages-minor layout the compiler picks by default, which every
  program then relays on every touch (PERF.md, PR 25). With a minor
  axis that is a multiple of 128 the default layout IS row-major, a
  page is one contiguous slab, and no program needs a pinned layout.
  Everything else reads the width from the pool: :func:`to_rows` /
  :func:`from_rows` convert SMALL tensors (new tokens, a chunk's
  gathered pages, spill payloads) between ``(..., kv_heads, head_dim)``
  and pool rows; :func:`write_rows` / :func:`gather_pages` touch the
  stacked pool at ``[layer, page, ...]`` in place; :func:`sweep_attention` is the decode / verify read, which
  never splits the merged axis in memory; :func:`scan_layers` is the
  layer loop of the three serving programs, with the pool CARRIED
  (never a scan ``xs`` / ``ys``: those are two buffers and cost a copy
  of the pool a step).
- :class:`BlockTables` — HOST-side refcount/evict bookkeeping (plain
  integer index arithmetic on numpy arrays, nothing shape-dependent:
  seating, retiring, and evicting only change VALUES inside
  fixed-shape tables, so the compiled decode step — whose signature
  depends only on pool geometry — never recompiles).
- :class:`OperandBuffer` — what a step's program needs to know of
  that bookkeeping (and the engine's other small host-known
  integers), as ONE int32 host buffer with named static slices: the
  tables ARE views of it (:meth:`BlockTables.bind`), so an iteration
  hands them to the device with one transfer and the program slices
  them apart again.

**Two kinds of cache in one model.** Where a model's attention layers
do not all see the same keys (``CacheSpec.kv_kinds``:
models/afmoe.py), :func:`make_pool` builds one pool a KIND. The
``"full"`` layers' pool is everything this module describes: pages by
block table, growing with the sequence. A ``"window"`` layer needs the
last ``window`` positions only, and its pool is a RING a slot
(:func:`ring_pages`): slot ``s`` owns ``ring`` pages for good,
position ``p`` lives at ring page ``(p // page_size) % ring``, and a
row's position is recovered from the slot's length
(:func:`ring_positions`) — so visibility is ``0 <= q_pos - k_pos <
window`` on recovered positions, the same rule hides what a recycled
page still holds and what the slot's last tenant left, and NO host
bookkeeping exists for that pool: nothing to seat, retire, evict or
clear, no null page (a write that must land nowhere is dropped), fixed
shapes whatever the sequences' lengths.

**Page lifetime (PR 4: alloc/free → refcount/evict).** A page is in
exactly one of three states: *referenced* (``refcount > 0`` — one or
more slots hold it in their tables; a prefix page shared by k live
requests counts k), *cached* (``refcount == 0`` but the page is a
registered prompt prefix: its K/V stay resident and a later request
with the same prefix maps it straight into its table), or *free*.
Retire decrements refcounts and only truly frees orphaned
non-prefix pages; cached prefixes are reclaimed LRU — deepest chain
pages first, so a prefix shrinks from its tail — whenever an
allocation needs more pages than the free list holds.

**The prefix index.** Pages holding FULL pages of a prompt are
registered under the exact byte string of the prompt's tokens up to
and including that page (a chain key: collision-free by construction,
process-local). ``match_prefix`` walks the chain page by page; the
match is capped at ``(prompt_len - 1) // page_size`` pages so the
LAST prompt token is always recomputed — its logits seed the
request's first sampled token. Copy-on-write falls out of the
alignment rule: matched full pages are mapped shared, and the first
partial page plus everything after it allocate private pages, so a
decode write can never land on a shared page.

Page 0 is RESERVED as the null page: free slots' table entries and
inactive slots' write targets all point at it, its refcount stays 0
forever, and the attention sweep masks it out — so a dead slot can
scribble into the pool without a branch and without corrupting any
live sequence.

**The host spill tier (PR 16).** With a :class:`HostPagePool`
attached (``serving.host_spill.enabled``), LRU eviction becomes a
DEMOTION: the evicted page's K/V stream to a host-DRAM buffer (int8
values + per-(token, head) float32 scales — 1 byte/elem on the wire,
the quantized-transfer playbook) keyed by the SAME chain-key bytes
the HBM index uses, and the pool slot returns to the free list. The
three-way partition invariant is untouched — a host-resident page
occupies NO pool id, is never refcounted, and exists only as (key →
payload) in the host pool. :meth:`match_tiered` extends the chain
walk across both tiers in one lookup: the HBM-resident prefix first,
then the host-resident continuation, so the engine can map the HBM
pages shared and PROMOTE the host pages back (one fixed-shape H2D
copy instead of recompute FLOPs). The host pool is itself LRU under
a byte budget; pages that fall off its tail are gone for real.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

NULL_PAGE = 0
LANES = 128     # the device tiles an array's minor axis in 128 lanes
KINDS = ("full", "window")      # what a K/V layer's cache can be


class PoolExhausted(RuntimeError):
    """Raised when an allocation cannot be satisfied even after
    evicting cached prefixes (or when no free slot exists to fork
    into). A ``RuntimeError`` subclass so every existing
    ``except RuntimeError`` capacity handler keeps working — but
    callers that must distinguish genuine capacity pressure from a
    contract violation (the batcher's fork preempt-and-retry loop)
    catch THIS type and let anything else surface immediately."""


@dataclass(frozen=True)
class CacheSpec:
    """What a model asks the engine to hold for it: ``kv_layers``
    layers of paged K/V rows of ``kv_heads * head_dim`` lanes (the
    layers that attend — not every layer of a model with other mixers),
    and ``slot_states``: arrays indexed by serving SLOT, not by page —
    ``{name: (layers, *shape)}`` is allocated as ``(layers, max_slots,
    *shape)`` (:func:`make_slot_state`). A model with slot state is
    seated, preempted and retired like any other (its programs reset
    the state where a prefill starts at position 0), but its pages are
    not the whole of a sequence any more: the prefix cache, rewind,
    spill and fork refuse it at build (serving/engine.py)."""

    kv_layers: int
    kv_heads: int
    head_dim: int
    slot_states: dict = field(default_factory=dict)
    # a LATENT cache (models/mla_moe.py): one row a token — the key
    # row's leading ``value_dim`` lanes ARE the values, so no V half is
    # allocated (``make_pool``'s "v" is None) and the reads go through
    # ``ops/latent_paged_attention.py``
    value_dim: int | None = None
    # the KIND of each K/V layer where they differ (models/afmoe.py):
    # ``"full"`` layers hold every token of a sequence in pages its
    # block table names, as all layers do without this field;
    # ``"window"`` layers see the last ``window`` positions only and
    # keep them in a RING of pages a slot owns for good
    # (:func:`ring_pages`): a pool of its own, bounded whatever the
    # sequences' lengths, with no block table and no host bookkeeping
    kv_kinds: tuple[str, ...] | None = None
    window: int | None = None

    def __post_init__(self):
        kinds = self.kv_kinds
        if kinds is None:
            return
        if len(kinds) != self.kv_layers or set(kinds) - set(KINDS):
            raise ValueError(
                f"kv_kinds names {self.kv_layers} layers as one of "
                f"{KINDS}, got {kinds}")
        if "window" in kinds and not self.window:
            raise ValueError("a window layer needs the window's size")

    def layers_of(self, kind: str) -> int:
        """How many K/V layers are of ``kind`` (all are ``"full"``
        where the spec names no kinds)."""
        if self.kv_kinds is None:
            return self.kv_layers if kind == "full" else 0
        return self.kv_kinds.count(kind)


def cache_spec(cfg: Any) -> CacheSpec:
    """The model's own ``cfg.cache_spec()``, or for a ``GPTConfig``
    what it has always been: every layer attends."""
    own = getattr(cfg, "cache_spec", None)
    if own is not None:
        return own()
    return CacheSpec(cfg.n_layers, cfg.kv_heads,
                     cfg.d_model // cfg.n_heads)


def make_slot_state(spec: CacheSpec, max_slots: int,
                    dtype: Any = jnp.bfloat16) -> dict | None:
    """The slot-indexed states of ``spec``, zeroed: ``{name: (layers,
    max_slots, *shape)}``; None for a model that has none (its
    programs then carry no such operand)."""
    if not spec.slot_states:
        return None
    return {name: jnp.zeros((shape[0], max_slots, *shape[1:]), dtype)
            for name, shape in spec.slot_states.items()}


def kv_width(kv_heads: int, head_dim: int, shards: int = 1) -> int:
    """Lanes of one pool row: ``kv_heads * head_dim`` rounded up to a
    whole number of 128-lane tiles — per SHARD, so that a ``tp`` split
    of the row (serving/tp.py shards it on KV heads) hands every rank
    a contiguous, tile-aligned slice holding its own heads."""
    per_shard = kv_heads // shards * head_dim
    return shards * (-(-per_shard // LANES) * LANES)


def ring_pages(window: int, page_size: int, chunk_pages: int) -> int:
    """Pages of ONE slot's ring in a window layer's pool: the
    ``window`` positions a query may see, the chunk being written, and
    one page more. Position ``p`` lives at ring page ``(p //
    page_size) % ring`` (:func:`ring_positions` is the way back), so a
    write of up to ``chunk_pages`` pages lands on rows whose old
    positions lie more than ``window + page_size`` behind it: no query
    of that write's step, or of any later one, can see them."""
    if window % page_size:
        raise ValueError(
            f"page_size ({page_size}) must divide the attention window "
            f"({window}): a ring is whole pages")
    return window // page_size + chunk_pages + 1


def ring_positions(top_page, ring: int, page_size: int):
    """The absolute position of every row of a ring whose NEWEST page
    is the sequence's page ``top_page`` (traced; any leading shape):
    ``(..., ring, page_size)`` int32. Ring page ``r`` holds the newest
    page ``a <= top_page`` with ``a % ring == r``; where that is
    negative the page was never written by this sequence and its
    positions come out negative. What a recycled page still holds of
    older positions, or of the slot's last tenant, is thereby given
    the position of what SHOULD lie there: rows a sequence has not
    written yet read as positions ahead of it, and every mask of the
    form ``0 <= q_pos - k_pos < window`` hides them."""
    top = jnp.asarray(top_page, jnp.int32)[..., None]
    page = top - jnp.mod(top - jnp.arange(ring, dtype=jnp.int32), ring)
    return page[..., None] * page_size \
        + jnp.arange(page_size, dtype=jnp.int32)


def make_pool(cfg: Any, page_size: int, n_pages: int,
              cache_dtype: Any = None,
              compute_dtype: Any = jnp.bfloat16,
              shards: int = 1, ring: tuple[int, int] | None = None
              ) -> dict:
    """Allocate the device pool for a model config (or its
    :class:`CacheSpec`): ``{"k": ..., "v": ...}`` with each
    entry ``(kv_layers, n_pages, page_size, kv_width)`` (module
    docstring: heads and head dim merged into one 128-aligned minor
    row) — a plain array in ``compute_dtype``, or, when ``cache_dtype``
    is ``"int8"``, the pair ``(int8 rows, bf16 scales (n_layers,
    n_pages, page_size, kv_heads))``. ``shards`` is the engine's ``tp``
    (:func:`kv_width` pads per shard). A latent spec
    (``value_dim``) gets ONE array: ``"v"`` is None. A spec with
    window layers (``kv_kinds``) gets one pool a KIND: ``"k"`` and
    ``"v"`` are each ``{"full": (full layers, n_pages, ...), "window":
    (window layers, max_slots * ring pages, ...)}`` — ``ring =
    (max_slots, pages a slot's ring)`` (:func:`ring_pages`): slot ``s``
    owns pages ``[s * ring, (s + 1) * ring)`` of every window layer,
    so that pool's size follows from the slots and the window, never
    from ``n_pages`` or the sequences' lengths."""
    if cache_dtype not in (None, "int8", jnp.int8):
        raise ValueError(
            f"cache_dtype must be None or 'int8', got {cache_dtype!r}")
    spec = cfg if isinstance(cfg, CacheSpec) else cache_spec(cfg)
    if spec.layers_of("window"):
        if cache_dtype is not None or shards != 1 or ring is None:
            raise ValueError(
                "a pool with window layers is unsharded rows in the "
                "compute dtype, and needs ring=(max_slots, ring pages)")
        width = kv_width(spec.kv_heads, spec.head_dim)
        pages = {"full": n_pages, "window": ring[0] * ring[1]}
        half = lambda: {
            kind: jnp.zeros((spec.layers_of(kind), pages[kind],
                             page_size, width), compute_dtype)
            for kind in KINDS}
        return {"k": half(), "v": half()}
    shape = (spec.kv_layers, n_pages, page_size,
             kv_width(spec.kv_heads, spec.head_dim, shards))
    if cache_dtype in ("int8", jnp.int8):
        scale_shape = shape[:-1] + (spec.kv_heads,)
        mk = lambda: (jnp.zeros(shape, jnp.int8),
                      jnp.ones(scale_shape, jnp.bfloat16))
    else:
        mk = lambda: jnp.zeros(shape, compute_dtype)
    if spec.value_dim is not None:
        if cache_dtype is not None or shards != 1:
            raise ValueError("a latent pool is one unsharded array of "
                             "rows in the compute dtype")
        return {"k": mk(), "v": None}
    return {"k": mk(), "v": mk()}


# ---- the pool's device-side accessors ---------------------------------
# Everything below works on ONE half of the pool (K or V): a plain
# array or the int8 ``(rows, scales)`` pair. ``pool_map`` applies a
# per-leaf function to either form.

def pool_map(fn: Callable, pool, *rest):
    """``fn`` over a pool half's leaves (and the matching leaves of
    ``rest``): the array itself, or rows and scales of an int8 pair."""
    if isinstance(pool, tuple):
        return tuple(fn(a, *(r[i] for r in rest))
                     for i, a in enumerate(pool))
    return fn(pool, *rest)


def to_rows(x, width: int, shards: int = 1):
    """``(..., kv_heads, head_dim)`` -> pool rows ``(..., width)``:
    merge the two minor axes and zero-pad each shard's heads to its
    slice of the row. For SMALL tensors (new tokens, staged pages) —
    numpy in, numpy out; jax in, jax out."""
    xp = np if isinstance(x, np.ndarray) else jnp
    g, d = x.shape[-2:]
    x = x.reshape(*x.shape[:-2], shards, g // shards * d)
    pad = width // shards - x.shape[-1]
    if pad:
        x = xp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(*x.shape[:-2], width)


def from_rows(rows, kv_heads: int, head_dim: int, shards: int = 1):
    """Pool rows ``(..., width)`` -> ``(..., kv_heads, head_dim)``:
    the inverse of :func:`to_rows`, for a few pages at a time (the
    chunk's gathered context, a spill payload) — never for a layer's
    pool, where splitting the minor axis is a relayout of all of it."""
    lead = rows.shape[:-1]
    rows = rows.reshape(*lead, shards, rows.shape[-1] // shards)
    rows = rows[..., :kv_heads // shards * head_dim]
    return rows.reshape(*lead, kv_heads, head_dim)


def quantized_rows(q, scale, width: int):
    """``_quantize_kv``'s ``(values (..., g, d), scales (..., g, 1))``
    as the int8 pool's pair of leaves ``(rows, scales (..., g))``."""
    return to_rows(q, width), scale[..., 0]


def write_rows(pool, index: tuple, rows):
    """``pool[index] = rows`` per leaf, in place under donation:
    token rows at ``(layer, pages, offsets)``, whole pages at
    ``(layer, pages)``, or pages of every layer at ``(slice(None),
    pages)`` of the stacked pool (``rows``: the index's shape + what
    is left of the leaf's)."""
    return pool_map(lambda a, r: a.at[index].set(r.astype(a.dtype)),
                    pool, rows)


def gather_pages(pool, layer, pages):
    """``pool[layer, pages]``: a few pages of one layer, ``(n,
    page_size, ...)`` per leaf."""
    return pool_map(lambda a: a[layer, pages], pool)


def layer_pages(pool, layer):
    """One layer's pages ``(n_pages, page_size, ...)`` per leaf: a
    slice of the stacked pool that fuses into its consumer's operand
    read when the consumer takes ALL pages (:func:`sweep_attention`)."""
    return pool_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0,
                                               keepdims=False), pool)


def sweep_attention(q_lanes, k_pages, v_pages, visible, kv_heads: int):
    """The decode / verify READ of one layer's pages, in the pool's
    own layout: every page attends the queries of its reference lanes
    and returns its flash-style partial softmax — the contract of
    ``models.gpt._grouped_cache_attention(state=True)`` with pages as
    the batch axis, so the caller's online-softmax merge over (page,
    lane) partials is unchanged.

    - ``q_lanes (n_pages, Q, n_heads, head_dim)``: the queries each
      page serves (``Q`` = reference lanes x query positions);
    - ``k_pages`` / ``v_pages``: :func:`layer_pages` of the pool,
      ``(n_pages, page_size, width)`` rows or the int8 ``(rows,
      scales (n_pages, page_size, kv_heads))`` pair;
    - ``visible (n_pages, Q, page_size)``: False -> masked.

    Returns ``(o (n_pages, Q, kv_heads, rep, head_dim) float32
    unnormalised, m, l (n_pages, kv_heads, rep, Q))``.

    The merged ``kv_heads * head_dim`` row is never split in memory
    (that is a relayout of the layer's pool: PERF.md, PR 25). Instead
    the QUERIES are laid out block-diagonally — column ``(g, q, r)``
    of page p carries head ``(g, r)``'s query in head g's ``head_dim``
    lanes of the row and zeros in every other head's — so scores are
    one batched product ``rows (page_size, width) @ columns (width,
    kv_heads * Q * rep)`` contracting the whole row: products with the
    zeros add nothing, so each score is exactly the per-head dot, in
    the same operand dtype with float32 accumulation. The values
    mirror it: ``probs^T @ rows`` gives every column the weighted sum
    of ALL heads' lanes, of which its own head's are kept. Both
    products run on the matrix unit with the pool streamed once in its
    storage layout; the ``kv_heads``-fold redundant arithmetic is the
    price (small beside the pool's bytes while Q is small; ROADMAP
    S2 / D3 weigh it for many lanes). Numerics as the dense core:
    operands in pool dtype (``q``'s for an int8 pool, whose per-token
    scales factor out of both products), float32 accumulation and
    softmax, float32 probabilities into the value product."""
    n_pages, n_q, n_heads, head_dim = q_lanes.shape
    quantized = isinstance(k_pages, tuple)
    k_rows, k_scale = k_pages if quantized else (k_pages, None)
    v_rows, v_scale = v_pages if quantized else (v_pages, None)
    page_size, width = k_rows.shape[1:]
    rep = n_heads // kv_heads
    dot_t = q_lanes.dtype if quantized else k_rows.dtype
    exact = jax.lax.Precision.HIGHEST    # float32 operands stay float32
    # own[g, c]: lane c of a row belongs to head g
    own = jnp.pad(jnp.repeat(jnp.eye(kv_heads, dtype=bool), head_dim,
                             axis=1),
                  ((0, 0), (0, width - kv_heads * head_dim)))
    # the queries as rows (P, Q * rep, width), then one column per
    # (head g, query): that row with every other head's lanes zeroed.
    # Broadcasts only, lanes minor throughout, and (g, query) kept as
    # two axes of both products: merged into one, the columns and the
    # value product's result are written out, a layer's pool in size
    # each once Q > 1 (compiled for the v5e, PR 25); kept apart, both
    # fuse into the products
    q_rows = q_lanes.reshape(n_pages, n_q, kv_heads, rep, head_dim)
    q_rows = to_rows(jnp.swapaxes(q_rows, 2, 3), width).astype(dot_t)
    q_rows = q_rows.reshape(n_pages, 1, n_q * rep, width)
    cols = jnp.where(own[None, :, None, :], q_rows,
                     jnp.zeros((), dot_t))
    scores = jnp.einsum(
        "pjc,pgqc->pjgq", k_rows.astype(dot_t), cols, precision=exact,
        preferred_element_type=jnp.float32) / (head_dim ** 0.5)
    scores = scores.reshape(n_pages, page_size, kv_heads, n_q, rep)
    if quantized:
        scores = scores * k_scale[..., None, None]
    vis = jnp.transpose(visible, (0, 2, 1))[:, :, None, :, None]
    scores = jnp.where(vis, scores, -1e30)
    m = jnp.max(scores, axis=1)                   # (P, g, Q, rep)
    probs = jnp.exp(scores - m[:, None])
    l = jnp.sum(probs, axis=1)
    if quantized:
        probs = (probs * v_scale[..., None, None]).astype(dot_t)
        values = v_rows.astype(dot_t)
    else:
        values = v_rows.astype(jnp.float32)
    o = jnp.einsum(
        "pjgq,pjc->pgqc",
        probs.reshape(n_pages, page_size, kv_heads, n_q * rep),
        values, precision=exact, preferred_element_type=jnp.float32)
    # (P, g, Q * rep, width): keep head g's lanes of column (g, .)
    o = jnp.sum(jnp.where(own[None, :, None, :], o, 0.0), axis=1)
    o = from_rows(o, kv_heads, head_dim).reshape(
        n_pages, n_q, rep, kv_heads, head_dim)
    o = jnp.transpose(o, (0, 1, 3, 2, 4))
    to_state = lambda t: jnp.transpose(t, (0, 1, 3, 2))
    return o, to_state(m), to_state(l)


def scan_layers(layer: Callable, x, pool_k, pool_v, blocks,
                lora_w=None):
    """The layer loop of the three serving programs (prefill chunk,
    decode, speculative verify). ``layer(x, pool_k, pool_v, bp, li,
    lora) -> (x, pool_k, pool_v)`` runs one block: ``bp`` that layer's
    weights, ``li`` its index into the STACKED pool, ``lora`` its
    adapter stacks (None without ``lora_w``). The pool is loop-CARRIED
    beside ``x`` and updated in place at ``[li, ...]``; only the
    weights (and adapters) are scanned. Returns ``(x, pool_k,
    pool_v)``."""
    n_layers = jax.tree.leaves(blocks)[0].shape[0]

    def body(carry, inputs):
        return layer(*carry, *inputs), None

    carry, _ = jax.lax.scan(body, (x, pool_k, pool_v),
                            (blocks, jnp.arange(n_layers), lora_w))
    return carry


class HostPagePool:
    """The host-DRAM page spill tier: demoted prefix pages as
    ``chain-key bytes -> payload`` entries under a byte budget.

    A payload is an opaque dict of HOST numpy arrays (the engine's
    demotion callback builds it: int8 K/V values + float32 scales for
    one page across every layer) — this class only owns the
    residency policy: LRU by insertion/touch tick, evict-oldest when
    a ``put`` would overflow ``budget_bytes``. Pure host bookkeeping,
    no device handles anywhere — which is what lets a fleet move
    entries between replicas' pools with a plain numpy copy (the
    router's host-tier fetch) and lets the tier survive a replica
    death (host DRAM outlives the replica's device state).

    Counters (host integers, exported via ``debug_stats``/flight):
    ``n_spills`` pages demoted in, ``n_evictions`` pages dropped by
    the budget, ``used_bytes`` current residency."""

    def __init__(self, budget_bytes: int):
        if budget_bytes < 1:
            raise ValueError(
                f"host pool budget must be >= 1 byte, got "
                f"{budget_bytes}")
        self.budget_bytes = int(budget_bytes)
        self._pages: dict[bytes, dict] = {}
        self._nbytes: dict[bytes, int] = {}
        self._lru: dict[bytes, int] = {}
        self._tick = 0
        self.used_bytes = 0
        self.n_spills = 0
        self.n_evictions = 0

    def __contains__(self, key: bytes) -> bool:
        return key in self._pages

    def __len__(self) -> int:
        return len(self._pages)

    def keys(self) -> list[bytes]:
        return list(self._pages)

    def get(self, key: bytes) -> dict | None:
        """Peek a payload (no residency change)."""
        return self._pages.get(key)

    def put(self, key: bytes, payload: dict) -> list[bytes]:
        """Insert (or refresh) a page; returns the keys the byte
        budget pushed out. A payload larger than the whole budget is
        refused by eviction-to-empty — the page just drops (returned
        in the evicted list) rather than wedging the pool."""
        nbytes = sum(int(a.nbytes) for a in payload.values())
        self.pop(key)                    # refresh == replace
        evicted: list[bytes] = []
        while self._lru and self.used_bytes + nbytes > self.budget_bytes:
            old = min(self._lru, key=self._lru.get)
            self.pop(old)
            self.n_evictions += 1
            evicted.append(old)
        if nbytes > self.budget_bytes:
            self.n_evictions += 1
            return evicted + [key]
        self._tick += 1
        self._pages[key] = payload
        self._nbytes[key] = nbytes
        self._lru[key] = self._tick
        self.used_bytes += nbytes
        self.n_spills += 1
        return evicted

    def pop(self, key: bytes) -> dict | None:
        """Remove and return a payload (promotion consumes it)."""
        payload = self._pages.pop(key, None)
        if payload is not None:
            self.used_bytes -= self._nbytes.pop(key)
            del self._lru[key]
        return payload

    def check(self) -> None:
        """Structural invariants (the spill churn test's assert)."""
        assert self._pages.keys() == self._nbytes.keys() \
            == self._lru.keys(), "host pool key-map drift"
        assert self.used_bytes == sum(self._nbytes.values()), (
            "host pool byte accounting drift")
        assert self.used_bytes <= self.budget_bytes, (
            f"host pool over budget: {self.used_bytes} > "
            f"{self.budget_bytes}")


class OperandBuffer:
    """A step's host-known operands as ONE int32 host buffer.

    ``fields`` maps a name to a shape; each gets a static slice of
    ``host`` in the order given. The host fills the slices in place
    (:meth:`view` — numpy views, so what is kept in them costs no copy
    at all), ONE ``jax.device_put`` an iteration carries a snapshot of
    the whole buffer over (so the next iteration may write it again
    at once), and the program takes it apart with the same static
    offsets (:meth:`unpack`: slices and reshapes XLA fuses into their
    consumers). The layout follows shapes known at
    build, so it is one constant of every program that takes the
    buffer: churn moves VALUES, never a shape. Booleans ride as 0/1,
    unsigned words as their bit pattern."""

    def __init__(self, fields: dict[str, tuple[int, ...]]):
        self.fields: dict[str, tuple[int, tuple[int, ...]]] = {}
        size = 0
        for name, shape in fields.items():
            self.fields[name] = (size, tuple(shape))
            size += math.prod(shape)
        self.host = np.zeros(size, np.int32)

    def _slice(self, buf, name: str):
        start, shape = self.fields[name]
        return buf[start:start + math.prod(shape)].reshape(shape)

    def view(self, name: str) -> np.ndarray:
        """The host's writable view of one field."""
        return self._slice(self.host, name)

    def unpack(self, buf) -> dict:
        """Every field of a buffer of this layout, by name (inside a
        program: ``buf`` is the traced operand)."""
        return {name: self._slice(buf, name) for name in self.fields}


class BlockTables:
    """Host-side refcounted page bookkeeping for ``max_slots`` serving
    slots over a ``n_pages``-page pool (page 0 reserved null).

    All state is fixed-shape numpy; seat/retire/evict is integer index
    arithmetic. The decode step consumes the arrays :data:`OPERANDS`
    names, as slices of the engine's :class:`OperandBuffer`
    (:meth:`bind`) — the VALUES change per step, the shapes never do,
    so slot churn cannot trigger a recompile.

    Arrays:

    - ``tables (max_slots, max_pages_per_slot) int32`` — page ids per
      slot, ``NULL_PAGE`` where unassigned; prefix-shared pages appear
      in several slots' rows at the SAME index;
    - ``lengths (max_slots,) int32`` — tokens currently stored (set at
      :meth:`seat` time, grown by :meth:`advance`);
    - ``refcount (n_pages,) int32`` — number of slots holding the page
      (0 = free or cached);
    - ``refs (n_pages, n_ref_lanes) int32`` — WHICH slots hold the
      page, ``-1`` empty lanes (``n_ref_lanes`` = ``max_slots`` with
      the prefix cache, 1 without — no sharing means one lane
      suffices and the decode sweep pays nothing extra). This is the
      decode sweep's routing table: each page attends one query per
      referencing slot, so a page shared by k live requests serves
      all k in the one pool read;
    - ``page_pos (n_pages,) int32`` — the page's index within its
      holders' sequences (identical for every sharer — shared pages
      are prompt PREFIX pages, which sit at the same table index by
      construction);
    - ``active (max_slots,) bool`` — DECODE-READY slots. A seated slot
      mid-chunked-prefill holds pages and a length but stays inactive
      until :meth:`activate`;
    - ``last_ids (max_slots,) int32`` — each slot's most recent token
      (the decode step's input).

    ``prefix_cache=False`` (the default) degenerates to plain
    alloc/free: nothing is matched or registered, every refcount is 0
    or 1, and retire frees every page — the cold control the parity
    suite measures the cache against.

    ``parallel=True`` keeps the multi-lane ``refs`` table even without
    the prefix cache: :meth:`fork` maps one slot's FULL pages into n
    sibling slots' tables (copy-on-write parallel sampling — OpenAI
    ``n``/``best_of``), so a page needs a lane per potential sharer
    exactly as prefix sharing does. Off (the default), fork raises and
    the lane axis collapses to 1 as before.
    """

    def __init__(self, cfg: Any, page_size: int, n_pages: int,
                 max_slots: int, prefix_cache: bool = False,
                 parallel: bool = False):
        if page_size < 1 or n_pages < 2 or max_slots < 1:
            raise ValueError(
                f"need page_size >= 1, n_pages >= 2 (page 0 is the "
                f"reserved null page) and max_slots >= 1; got "
                f"page_size={page_size}, n_pages={n_pages}, "
                f"max_slots={max_slots}")
        self.page_size = page_size
        self.n_pages = n_pages
        self.max_slots = max_slots
        self.max_pages_per_slot = -(-cfg.seq_len // page_size)
        self.seq_len = cfg.seq_len
        self.prefix_cache = bool(prefix_cache)
        self.parallel = bool(parallel)
        self.tables = np.full((max_slots, self.max_pages_per_slot),
                              NULL_PAGE, np.int32)
        self.lengths = np.zeros(max_slots, np.int32)
        # rewind floors (speculative decoding, serving/speculative.py):
        # cow_len is the copy-on-write boundary — the shared/cached
        # prefix pages mapped at seat time end here, so the write
        # cursor (== lengths) must never drop below it; prompt_len is
        # the stricter floor rewind enforces (registered prefix pages
        # all sit inside the prompt, so a rewind can never strand an
        # index entry past the live length)
        self.cow_len = np.zeros(max_slots, np.int32)
        self.prompt_len = np.zeros(max_slots, np.int32)
        self.refcount = np.zeros(n_pages, np.int32)
        # reference lanes: with the prefix cache (or CoW fork-sharing)
        # every slot may share one page, so a page needs max_slots
        # lanes; without either no page ever has more than one holder
        # and the lane axis collapses to 1 — the cold engine's decode
        # sweep then pays ZERO extra query-side compute for the
        # sharing machinery
        share = self.prefix_cache or self.parallel
        self.n_ref_lanes = max_slots if share else 1
        self.refs = np.full((n_pages, self.n_ref_lanes), -1, np.int32)
        self.page_pos = np.zeros(n_pages, np.int32)
        self.active = np.zeros(max_slots, bool)
        self.last_ids = np.zeros(max_slots, np.int32)
        # prefix index: prompt-prefix bytes -> page id (bijective with
        # _page_key); _lru tracks refcount-0 cached pages by last-use
        # tick — retire assigns ticks tail-first so eviction shrinks a
        # cached prefix from its deepest page
        self._index: dict[bytes, int] = {}
        self._page_key: dict[int, bytes] = {}
        self._lru: dict[int, int] = {}
        self._tick = 0
        # LIFO free list: recently-freed pages are re-issued first
        # (their bytes are hottest in cache); page 0 never enters
        self._free = list(range(n_pages - 1, 0, -1))
        # the host spill tier (all optional; None = PR-4 behavior
        # bit-for-bit): host_pool holds demoted pages' payloads,
        # spill_fetch is the ENGINE's demotion callback (page id ->
        # host payload dict — the one deliberate device read of the
        # tier), on_tier_event is the fleet directory's feed
        # ((kind, chain-key bytes) on register/demote/promote/evict)
        self.host_pool: HostPagePool | None = None
        self.spill_fetch = None
        self.on_tier_event = None

    # ---- queries -------------------------------------------------
    @property
    def n_free_pages(self) -> int:
        return len(self._free)

    @property
    def n_cached_pages(self) -> int:
        """Resident refcount-0 prefix pages (LRU-evictable)."""
        return len(self._lru)

    @property
    def n_available_pages(self) -> int:
        """Free + evictable — the admission capacity check (cached
        prefixes never block an admission; they evict under it)."""
        return len(self._free) + len(self._lru)

    @property
    def n_host_pages(self) -> int:
        """Host-tier resident pages (0 with the spill tier off).
        Deliberately NOT part of :attr:`n_available_pages`: a host
        page occupies no pool id, so it neither consumes nor provides
        admission capacity."""
        return len(self.host_pool) if self.host_pool is not None else 0

    def free_slot(self) -> int | None:
        """Lowest unseated slot id, or None when all are occupied."""
        idle = np.flatnonzero(~self.active & (self.lengths == 0))
        return int(idle[0]) if idle.size else None

    def n_free_slots(self) -> int:
        """How many slots :meth:`free_slot` could hand out — the ONE
        definition of 'unseated' (inactive AND empty), so the
        batcher's reservation-aware admission gate and the seating
        code can never disagree on what counts as free."""
        return int(np.count_nonzero(~self.active & (self.lengths == 0)))

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def slot_pages(self, slot: int) -> np.ndarray:
        """The slot's live page ids, in sequence order."""
        n = self.pages_for(int(self.lengths[slot]))
        return self.tables[slot, :n].copy()

    def match_prefix(self, prompt: np.ndarray) -> int:
        """How many leading FULL pages of ``prompt`` are resident in
        the prefix index — capped at ``(len - 1) // page_size`` so the
        last prompt token always recomputes (its logits seed the first
        sampled token)."""
        return len(self.match_pages(prompt))

    def match_pages(self, prompt: np.ndarray) -> list[int]:
        """The resident page chain for ``prompt``'s leading full pages
        (same cap as :meth:`match_prefix`). The walk hashes the prompt
        prefix once per page — callers that need both the capacity
        check and the seating (engine ``admit_begin``) do ONE walk and
        hand the result to :meth:`seat`."""
        if not self.prefix_cache or len(prompt) < 1:
            return []
        prompt = np.ascontiguousarray(prompt, np.int32)
        limit = (len(prompt) - 1) // self.page_size
        pages: list[int] = []
        while len(pages) < limit:
            p = self._index.get(
                prompt[:(len(pages) + 1) * self.page_size].tobytes())
            if p is None:
                break
            pages.append(p)
        return pages

    def match_tiered(self, prompt: np.ndarray
                     ) -> tuple[list[int], list[bytes]]:
        """The two-tier chain walk, ONE lookup per page: the
        HBM-resident prefix (page ids, exactly :meth:`match_pages`)
        followed by its host-resident continuation (chain-key bytes
        the engine promotes). Same ``(len - 1) // page_size`` cap
        across the combined chain. A chain that leaves the host tier
        and re-enters HBM is cut at the host miss — seat maps only a
        LEADING contiguous run, and a mid-chain tier sandwich is a
        transient (the stranded HBM page demotes or evicts on its
        own)."""
        pages = self.match_pages(prompt)
        if self.host_pool is None or not self.prefix_cache \
                or len(prompt) < 1:
            return pages, []
        prompt = np.ascontiguousarray(prompt, np.int32)
        limit = (len(prompt) - 1) // self.page_size
        keys: list[bytes] = []
        while len(pages) + len(keys) < limit:
            key = prompt[:(len(pages) + len(keys) + 1)
                         * self.page_size].tobytes()
            if key not in self.host_pool:
                break
            keys.append(key)
        return pages, keys

    # ---- mutations -----------------------------------------------
    def seat(self, slot: int, prompt: np.ndarray,
             matched: list[int] | None = None
             ) -> tuple[np.ndarray, int]:
        """Claim ``slot`` for ``prompt``: map the matched cached
        prefix pages into its table (refcount++) and allocate private
        pages for the rest (evicting LRU cached prefixes under
        pressure). ``matched`` short-circuits the index walk with a
        fresh :meth:`match_pages` result (no mutation in between).
        The slot stays INACTIVE (no decode) until :meth:`activate` —
        the engine streams the unmatched prompt in via chunked
        prefill first. Returns ``(page_ids, n_matched)``; raises when
        the slot is busy or pages run out even after eviction (the
        caller checks :attr:`n_available_pages`)."""
        prompt = np.ascontiguousarray(prompt, np.int32).reshape(-1)
        if self.active[slot] or self.lengths[slot]:
            raise ValueError(f"slot {slot} is already occupied")
        if not 0 < len(prompt) < self.seq_len:
            raise ValueError(
                f"prompt length must be in (0, {self.seq_len}), got "
                f"{len(prompt)}")
        n_total = self.pages_for(len(prompt))
        if matched is None:
            matched = self.match_pages(prompt)
        n_matched = len(matched)
        # remember the matched pages' LRU ticks: a failed seat must
        # put them back EXACTLY as found — minting fresh ticks on
        # rollback would promote a chain that keeps failing to seat
        # to most-recently-used, evicting genuinely useful prefixes
        # ahead of it
        old_ticks = {p: self._lru[p] for p in matched if p in self._lru}
        for i, p in enumerate(matched):
            self._ref(slot, i, p)
        try:
            self._alloc(slot, np.arange(n_matched, n_total))
        except RuntimeError:
            for i in reversed(range(n_matched)):
                self._unref(slot, int(self.tables[slot, i]))
            self.tables[slot, :n_matched] = NULL_PAGE
            for p, tick in old_ticks.items():
                if p in self._lru:       # still refcount-0 cached
                    self._lru[p] = tick
            raise
        self.lengths[slot] = len(prompt)
        self.prompt_len[slot] = len(prompt)
        self.cow_len[slot] = n_matched * self.page_size
        self.last_ids[slot] = 0
        return self.tables[slot, :n_total].copy(), n_matched

    def activate(self, slot: int, first_id: int | None = None) -> None:
        """Mark a seated slot decode-ready (prefill done); ``first_id``
        seeds its decode input (the prefill's sampled token) — None
        where the prompt's last chunk was only launched and its token
        is still on the device (``last_ids`` follows when it lands)."""
        if not self.lengths[slot] or self.active[slot]:
            raise ValueError(
                f"slot {slot} is not seated-and-inactive")
        self.active[slot] = True
        if first_id is not None:
            self.last_ids[slot] = first_id

    def fork(self, parent_slot: int, n_children: int) -> list[int]:
        """Fork ``parent_slot`` into ``n_children`` sibling slots for
        copy-on-write parallel sampling (OpenAI ``n``/``best_of``):
        every FULL page of the parent maps shared into each child's
        table (refcount++, a refs lane per sharer — one pool read
        serves all branches, the same contract prefix sharing rides),
        and only the partial TAIL page allocates a private per-child
        page, because the tail is where both the parent's and every
        child's next writes land. The DEVICE copy of the tail page's
        K/V is the engine's job (``PagedEngine.fork`` issues one
        fixed-shape copy) — this method is pure host bookkeeping.

        Both the parent's and the children's copy-on-write floors rise
        to the shared-page boundary: pages the parent held privately
        become shared at fork, so no branch — the parent included —
        may ever rewind a write cursor back into them (``rewind``
        enforces it, ``check()`` asserts it).

        Children come back INACTIVE with the parent's length: the
        caller samples each branch's own first token and
        :meth:`activate`\\ s them (the fork happens at the prefill
        boundary, where the branches diverge from token one). On pool
        exhaustion every partially-forked child is rolled back and the
        ``RuntimeError`` propagates — the caller preempts or retries.
        """
        if not self.parallel:
            raise RuntimeError(
                "fork() needs BlockTables(parallel=True): without the "
                "multi-lane refs table a page cannot carry a second "
                "holder")
        if n_children < 1:
            raise ValueError(
                f"n_children must be >= 1, got {n_children}")
        if not self.active[parent_slot] or not self.lengths[parent_slot]:
            raise ValueError(
                f"slot {parent_slot} is not active — fork at the "
                "prefill boundary, after activate()")
        L = int(self.lengths[parent_slot])
        n_full = L // self.page_size
        n_live = self.pages_for(L)
        parent_row = self.tables[parent_slot]
        children: list[int] = []
        try:
            for _ in range(n_children):
                slot = self.free_slot()
                if slot is None:
                    raise PoolExhausted(
                        f"no free slot to fork into ({self.max_slots} "
                        "slots all seated)")
                mapped = 0
                try:
                    for i in range(n_full):
                        self._ref(slot, i, int(parent_row[i]))
                        mapped += 1
                    if n_live > n_full:
                        # the partial tail: a PRIVATE page per child —
                        # the write cursor of every branch sits in it
                        self._alloc(slot, np.asarray([n_full]))
                except PoolExhausted:
                    # this child's partial share map must unwind by
                    # hand: its lengths was never set, so retire()
                    # would see an empty slot and leak the refs
                    for i in reversed(range(mapped)):
                        self._unref(slot, int(self.tables[slot, i]))
                    self.tables[slot, :mapped] = NULL_PAGE
                    raise
                self.lengths[slot] = L
                self.prompt_len[slot] = self.prompt_len[parent_slot]
                self.cow_len[slot] = n_full * self.page_size
                self.last_ids[slot] = 0
                children.append(slot)
        except PoolExhausted:
            for slot in children:
                self.retire(slot)
            raise
        # the parent's previously-private full pages are shared now:
        # its own CoW floor rises with them (never falls)
        self.cow_len[parent_slot] = max(
            int(self.cow_len[parent_slot]), n_full * self.page_size)
        return children

    def register_prefix(self, slot: int, prompt: np.ndarray) -> int:
        """Publish the slot's FULL prompt pages into the prefix index
        (call once prefill has written them — their content is final:
        only the partial tail page ever grows). Returns how many new
        entries landed."""
        if not self.prefix_cache:
            return 0
        prompt = np.ascontiguousarray(prompt, np.int32).reshape(-1)
        n_new = 0
        for i in range(len(prompt) // self.page_size):
            key = prompt[:(i + 1) * self.page_size].tobytes()
            if key in self._index:
                continue                 # first writer wins
            p = int(self.tables[slot, i])
            if p == NULL_PAGE or p in self._page_key:
                continue
            self._index[key] = p
            self._page_key[p] = key
            if self.host_pool is not None:
                # a freshly-prefilled copy supersedes a stale host
                # payload (the HBM bytes are exact, the host ones
                # quantized) — one key never lives in both tiers
                self.host_pool.pop(key)
            if self.on_tier_event is not None:
                self.on_tier_event("register", key)
            n_new += 1
        return n_new

    def promote_keys(self, slot: int, keys: list[bytes],
                     start_idx: int) -> None:
        """Publish promoted pages back into the HBM prefix index:
        ``keys[i]`` describes the content the engine's promotion just
        wrote into the slot's page at table index ``start_idx + i``.
        Host bookkeeping only (the device copy already happened);
        first-writer-wins exactly like :meth:`register_prefix`, so a
        racing cold prefill that registered the same chain keeps its
        entry and the promoted copy just stays private to its slot."""
        for i, key in enumerate(keys):
            p = int(self.tables[slot, start_idx + i])
            if p == NULL_PAGE or key in self._index \
                    or p in self._page_key:
                continue
            self._index[key] = p
            self._page_key[p] = key
            if self.on_tier_event is not None:
                self.on_tier_event("promote", key)

    def ensure_next_page(self, slot: int) -> bool:
        """Make sure the page that position ``lengths[slot]`` (the
        next write) lands in exists — the ``n_tokens=1`` case of
        :meth:`ensure_write_pages`."""
        return self.ensure_write_pages(slot, 1)

    def ensure_write_pages(self, slot: int, n_tokens: int = 1) -> bool:
        """Make sure pages exist for the next ``n_tokens`` write
        positions ``[lengths, lengths + n_tokens)`` (clamped to the
        cache horizon); allocates every missing table entry in one
        shot, evicting cached prefix pages under pressure. The
        speculative verify step writes ``1 + draft_len`` positions
        per step, so it needs up to two pages ahead (``draft_len <
        page_size``); positions past a rejected draft keep their
        pages — always PRIVATE ones (the write cursor sits past the
        copy-on-write boundary), overwritten by the next step's
        writes before any visibility mask can reach them. Returns
        False when the pool is truly exhausted (the batcher then
        preempts) — the slot is untouched (:meth:`_alloc` checks
        capacity before evicting anything)."""
        length = int(self.lengths[slot])
        last = min(length + n_tokens, self.seq_len) - 1
        if last < length:
            return True
        idx = [i for i in range(length // self.page_size,
                                last // self.page_size + 1)
               if self.tables[slot, i] == NULL_PAGE]
        if not idx:
            return True
        try:
            self._alloc(slot, np.asarray(idx))
        except RuntimeError:
            return False
        return True

    def rewind(self, slot: int, new_length: int,
               last_id: int | None = None) -> None:
        """Explicitly reset the slot's length to drop speculatively
        written positions. ``PagedEngine.spec_step`` itself never
        needs this call — it only ever :meth:`advance`\\ s over
        ACCEPTED tokens, so rejected draft K/V is born past
        ``lengths`` (the rewind is implicit) — but a custom driver
        that advances optimistically, or anything else that must
        shrink a slot, goes through here so the floors below are
        enforced in ONE place (and ``check()`` asserts them for every
        slot, however its length got there). The device wrote K/V for
        every drafted position, but only the accepted prefix is real —
        dropping ``lengths`` back to ``new_length`` makes the poisoned
        tail invisible (every mask reads ``tok_pos <= lengths``) and
        the next step's writes land on top of it before it can ever
        surface. Pages past ``new_length`` stay allocated (they are
        the slot's PRIVATE draft-ahead pages — about to be re-used)
        and are never registered into the prefix index (only prompt
        pages register, at prefill time). The floor is the prompt: a
        rewind below ``prompt_len`` would re-open registered prefix
        pages — and below ``cow_len`` shared/cached pages — to decode
        writes, so both are rejected loudly. A rewind that actually
        drops positions leaves ``last_ids`` pointing at a DROPPED
        token — the next step would embed a rejected token as the
        slot's pending input and generate from it silently — so the
        caller must pass ``last_id``, the accepted pending token at
        position ``new_length`` (the tables don't store the token
        stream and cannot restore it themselves)."""
        if not self.lengths[slot]:
            raise ValueError(f"slot {slot} is not seated")
        # at seat time cow_len < prompt_len by the match cap, but a
        # FORK raises cow_len to the shared-page boundary — which for
        # a branch that has decoded past a page boundary sits ABOVE
        # its prompt, so both floors must hold
        floor = max(int(self.prompt_len[slot]),
                    int(self.cow_len[slot]))
        if not floor <= new_length <= int(self.lengths[slot]):
            raise ValueError(
                f"rewind target {new_length} outside "
                f"[prompt_len={floor}, lengths="
                f"{int(self.lengths[slot])}] for slot {slot} — a "
                "rewind below the prompt (and the copy-on-write "
                f"boundary at {int(self.cow_len[slot])}) would expose "
                "registered/shared prefix pages to decode writes")
        if new_length < int(self.lengths[slot]):
            if last_id is None:
                raise ValueError(
                    f"rewinding slot {slot} drops the token last_ids "
                    "points at; pass last_id (the accepted pending "
                    f"token at position {new_length}) or the next "
                    "step decodes from a rejected token")
            self.last_ids[slot] = last_id
        self.lengths[slot] = new_length

    def advance(self, slot: int, token_id: int) -> None:
        """Record one decoded token (already written on device at
        position ``lengths[slot]`` by the step that produced it)."""
        self.lengths[slot] += 1
        self.last_ids[slot] = token_id

    def launched(self, active: np.ndarray) -> None:
        """The first half of :meth:`advance`, for all the slots of a
        decode step at its LAUNCH: the program writes each one's token
        at ``lengths`` and the next write lands one further — known
        before the token is."""
        self.lengths[active] += 1

    def landed(self, active: np.ndarray, tokens: np.ndarray) -> None:
        """The second half, when the step's ``tokens`` have been read
        back: each decoded slot's most recent token."""
        self.last_ids[active] = tokens[active]

    def retire(self, slot: int) -> None:
        """Release the slot: every page's refcount drops by one; pages
        that hit zero either stay RESIDENT as cached prefixes (if
        registered) or return to the free list. Iterates the table
        tail-first so a cached prefix's deepest pages get the OLDEST
        LRU ticks and evict first — the chain shrinks from its tail,
        never breaking the match walk mid-prefix."""
        if not self.active[slot] and not self.lengths[slot]:
            return
        for p in self.tables[slot][::-1]:
            if p != NULL_PAGE:
                self._unref(slot, int(p))
        self.tables[slot] = NULL_PAGE
        self.lengths[slot] = 0
        self.cow_len[slot] = 0
        self.prompt_len[slot] = 0
        self.active[slot] = False
        self.last_ids[slot] = 0

    # ---- internals -----------------------------------------------
    def _ref(self, slot: int, idx: int, p: int) -> None:
        """Map an existing (cached or live-shared) page into a slot's
        table at index ``idx``."""
        assert self.page_pos[p] == idx, (
            f"prefix page {p} sits at position {self.page_pos[p]}, "
            f"matched at table index {idx}")
        if self.refcount[p] == 0:
            self._lru.pop(p, None)           # cached -> referenced
        lane = int(np.flatnonzero(self.refs[p] == -1)[0])
        self.refs[p, lane] = slot
        self.refcount[p] += 1
        self.tables[slot, idx] = p

    def _unref(self, slot: int, p: int) -> None:
        self.refcount[p] -= 1
        assert self.refcount[p] >= 0, f"page {p} refcount went negative"
        self.refs[p][self.refs[p] == slot] = -1
        if self.refcount[p] == 0:
            if p in self._page_key:          # registered prefix: cache
                self._tick += 1
                self._lru[p] = self._tick
            else:
                self.page_pos[p] = 0
                self._free.append(int(p))

    def _evict(self, n: int) -> int:
        """Reclaim up to ``n`` LRU cached prefix pages into the free
        list (dropping their index entries); returns how many. With
        the spill tier attached the reclaim is a DEMOTION: the page's
        K/V stream to the host pool (``spill_fetch`` — the engine's
        quantize-and-copy callback) under the same chain key before
        the pool slot frees, so a later request promotes instead of
        recomputing. The pool partition is unchanged either way —
        the page leaves the cached set and enters the free set."""
        got = 0
        while got < n and self._lru:
            p = min(self._lru, key=self._lru.get)
            del self._lru[p]
            key = self._page_key.pop(p)
            del self._index[key]
            if self.host_pool is not None and self.spill_fetch is not None:
                payload = self.spill_fetch(p)
                if payload is not None:
                    dropped = self.host_pool.put(key, payload)
                    if self.on_tier_event is not None:
                        self.on_tier_event("demote", key)
                        for k in dropped:
                            self.on_tier_event("host_evict", k)
                elif self.on_tier_event is not None:
                    self.on_tier_event("evict", key)
            elif self.on_tier_event is not None:
                self.on_tier_event("evict", key)
            self.page_pos[p] = 0
            self._free.append(int(p))
            got += 1
        return got

    def _alloc(self, slot: int, table_idx: np.ndarray) -> np.ndarray:
        if len(table_idx) > len(self._free) + len(self._lru):
            # raise BEFORE evicting: a doomed allocation must not
            # drain unrelated cached prefixes (dropping their index
            # entries for nothing) on its way to failing anyway
            raise PoolExhausted(
                f"KV page pool exhausted: need {len(table_idx)} pages, "
                f"{len(self._free)} free + {len(self._lru)} evictable "
                f"(n_pages={self.n_pages}, page_size={self.page_size})"
                "; size serving.n_pages to the worst-case live-token "
                "total or lower max_slots")
        short = len(table_idx) - len(self._free)
        if short > 0:
            self._evict(short)
        ids = np.array([self._free.pop() for _ in table_idx], np.int32)
        self.tables[slot, table_idx] = ids
        self.refcount[ids] = 1
        self.refs[ids, :] = -1
        self.refs[ids, 0] = slot
        # a page's position within its holders' sequences IS its table
        # index — the sweep reconstructs absolute token positions from it
        self.page_pos[ids] = np.asarray(table_idx, np.int32)
        return ids

    # ---- device view ---------------------------------------------
    # what a decode program reads of the tables, in the buffer's order
    OPERANDS = ("tables", "lengths", "refs", "page_pos", "active",
                "last_ids")

    def operand_fields(self) -> dict[str, tuple[int, ...]]:
        """The :class:`OperandBuffer` fields the tables fill. Fixed
        shapes by construction — only values change across seat/
        retire/evict, which is what keeps the compiled step signature
        occupancy-independent."""
        return {name: getattr(self, name).shape for name in self.OPERANDS}

    def bind(self, operands: OperandBuffer) -> None:
        """Move the int32 arrays INTO ``operands``: from here on they
        are views of its host buffer (every writer above writes in
        place, so none of them changes), and handing the tables to
        the device costs the host nothing but :meth:`pack`."""
        for name in self.OPERANDS:
            view = operands.view(name)
            view[...] = getattr(self, name)
            if name != "active":
                setattr(self, name, view)
        # ``active`` stays a bool array of its own (~active and
        # lengths[active] are all over the host code); pack() copies it
        self._active_operand = operands.view("active")

    def pack(self) -> None:
        """What is no view of the buffer, written into it: ``active``
        as 0/1. Call before the iteration's transfer."""
        self._active_operand[:] = self.active

    def kernel_args(self) -> dict:
        """The pallas decode kernel's COMPACTED live-page walk
        (ops/paged_attention.py): fixed ``n_pages - 1`` entries —
        every referenced page once (ascending pool order), then
        padding pinned to the reserved null page with empty lanes.
        The kernel's grid walks this list and fetches each entry's
        pool page by table VALUE; the all-null padding tail is fetched
        once, so HBM reads track the LIVE entries. Shapes are
        geometry-only (values change under churn — the same
        zero-recompile contract as the tables' own operands; host
        arrays, which the engine copies into its operand buffer). Cached
        refcount-0 prefix pages are deliberately absent: no live slot
        references them, so the kernel never pays for residency —
        exactly the pool-sweep cost the XLA backend cannot avoid."""
        n_w = self.n_pages - 1
        live = np.flatnonzero(self.refcount[1:] > 0) + 1
        work_pages = np.zeros(n_w, np.int32)
        work_refs = np.full((n_w, self.n_ref_lanes), -1, np.int32)
        work_pos = np.zeros(n_w, np.int32)
        n = len(live)
        work_pages[:n] = live
        work_refs[:n] = self.refs[live]
        work_pos[:n] = self.page_pos[live]
        return {"work_pages": work_pages, "work_refs": work_refs,
                "work_pos": work_pos}

    @property
    def n_live_pages(self) -> int:
        """Referenced (refcount > 0) pages — the pallas walk's real
        per-step page reads, and the live-bytes term of the two-regime
        roofline (docs/performance.md)."""
        return int(np.count_nonzero(self.refcount[1:] > 0))

    # ---- invariants (tests) --------------------------------------
    def check(self) -> None:
        """Structural invariants, asserted by the churn tests: page 0
        never allocated; referenced ∪ cached ∪ free = pool exactly
        once; refcounts equal the table references (never negative);
        refs lanes agree with the tables; page_pos agrees with every
        holder; the prefix index is a bijection and cached pages all
        carry keys."""
        free = set(self._free)
        cached = set(self._lru)
        assert NULL_PAGE not in free, "null page entered the free list"
        assert NULL_PAGE not in cached, "null page entered the cache"
        assert self.refcount[NULL_PAGE] == 0, "null page got referenced"
        assert len(free) == len(self._free), "free list holds duplicates"
        assert free.isdisjoint(cached)
        want = np.zeros(self.n_pages, np.int64)
        for slot in range(self.max_slots):
            n_live = self.pages_for(int(self.lengths[slot]))
            seen = set()
            for idx, p in enumerate(self.tables[slot]):
                p = int(p)
                if idx < n_live:
                    assert p != NULL_PAGE, (
                        f"slot {slot} live page {idx} unassigned")
                if p == NULL_PAGE:
                    continue
                assert p not in seen, f"slot {slot} holds page {p} twice"
                seen.add(p)
                want[p] += 1
                assert self.page_pos[p] == idx, (slot, idx, p)
                assert slot in set(self.refs[p].tolist()), (slot, p)
                if self.refcount[p] > 1:
                    # shared pages (prefix hits and fork sharing) must
                    # sit entirely BELOW every holder's write floor —
                    # max(cow_len, prompt_len), the same floor rewind
                    # enforces — so the write cursor (== lengths,
                    # never below that floor) can never touch one: a
                    # CoW tail page is never shared. Prefix-shared
                    # full PROMPT pages are covered by prompt_len (a
                    # registering slot's cow_len stays at its matched
                    # boundary); fork-shared pages past the prompt by
                    # the raised cow_len.
                    assert (idx + 1) * self.page_size <= max(
                        int(self.cow_len[slot]),
                        int(self.prompt_len[slot])), (
                        f"page {p} shared at slot {slot} index {idx} "
                        f"above the write floor (cow_len="
                        f"{int(self.cow_len[slot])}, prompt_len="
                        f"{int(self.prompt_len[slot])})")
                if idx >= n_live:
                    # draft-ahead pages past a rewound length: PRIVATE
                    # (a shared page past the live range would serve
                    # poisoned K/V to its sharers) and never reachable
                    # through the prefix index (a cached/registered
                    # page there would replay rejected drafts into a
                    # later request's context)
                    assert self.refcount[p] == 1, (
                        f"page {p} shared past slot {slot}'s length")
                    assert p not in self._page_key, (
                        f"registered prefix page {p} reachable past "
                        f"slot {slot}'s rewound length")
            if self.lengths[slot]:
                # the rewind floors: the write cursor (== lengths)
                # never re-enters the shared/cached prefix region, nor
                # the registered prompt pages
                assert self.lengths[slot] >= self.cow_len[slot], (
                    f"slot {slot} length {int(self.lengths[slot])} "
                    f"below the copy-on-write boundary "
                    f"{int(self.cow_len[slot])}")
                assert self.lengths[slot] >= self.prompt_len[slot], (
                    f"slot {slot} rewound below its prompt")
            else:
                assert not self.active[slot]
                assert (self.tables[slot] == NULL_PAGE).all()
                assert self.cow_len[slot] == 0
                assert self.prompt_len[slot] == 0
        assert (want == self.refcount).all(), "refcount drift vs tables"
        assert (self.refcount >= 0).all(), "negative refcount"
        for p in range(self.n_pages):
            lanes = [int(s) for s in self.refs[p] if s >= 0]
            assert len(lanes) == self.refcount[p], (p, lanes)
            assert len(set(lanes)) == len(lanes), f"page {p} lane dup"
        referenced = set(np.flatnonzero(self.refcount > 0).tolist())
        assert free.isdisjoint(referenced)
        assert cached.isdisjoint(referenced)
        assert len(free) + len(cached) + len(referenced) \
            == self.n_pages - 1, "pages leaked: partition != pool"
        assert len(self._index) == len(self._page_key)
        for key, p in self._index.items():
            assert self._page_key.get(p) == key, "index/page_key drift"
        for p in cached:
            assert p in self._page_key and self.refcount[p] == 0
        if self.host_pool is not None:
            # the spill tier's side of the three-way partition: host
            # pages occupy NO pool id (the pool partition above is
            # already exact without them), are never refcounted, and
            # one chain key never lives in both tiers
            self.host_pool.check()
            for key in self.host_pool.keys():
                assert key not in self._index, (
                    "chain key resident in both tiers")
                assert len(key) % (4 * self.page_size) == 0, (
                    "host pool key is not page-aligned int32 bytes")


__all__ = ["BlockTables", "CacheSpec", "HostPagePool", "NULL_PAGE",
           "PoolExhausted", "cache_spec", "from_rows", "gather_pages",
           "kv_width", "layer_pages", "make_pool", "make_slot_state",
           "pool_map", "quantized_rows", "ring_pages", "ring_positions",
           "scan_layers", "sweep_attention", "to_rows", "write_rows"]
