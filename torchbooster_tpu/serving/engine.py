"""Continuous-batching decode engine over the paged, prefix-shared KV
cache.

Prefill/decode split — both sides compile exactly ONCE:

- **prefill** streams a request's prompt in through fixed-size
  page-aligned CHUNKS (``prefill_chunk_pages`` pages each, issued
  between decode steps by the batcher): each chunk runs the SAME block
  math training uses (``models/gpt.py _block_core``), writes its K/V
  into the pages the block table assigned, and attends its prior
  context by WALKING the slot's own pages up to its own position, the
  pool read in place (``ops/chunk_walk.py``; an int8 pool, ``tp > 1``
  and the pallas backend gather them instead) — two flash-style
  partials (prior pages + the intra-chunk causal part) merged with
  the online-softmax combine. The chunk's shapes
  depend only on (chunk size, pool geometry, model); prompt length,
  chunk position, and page ids are traced VALUES, so one compiled
  chunk serves every prompt length — killing the old
  compile-per-page-count ``_prefill_fn`` — and a long prompt costs
  many small chunks instead of one decode-stalling prefill. Requests
  whose prompt prefix is resident in the page pool (kv_pages.py
  prefix index) skip the matched pages' chunks entirely: the
  cache-hit TTFT win is exactly the prefill compute not re-run.
- **decode** is ONE jitted step over all ``max_slots`` slots: embed
  each slot's last token at its own depth, write this step's K/V into
  each slot's current (always private) page, then attend by WALKING
  each decoding slot's table up to its own position
  (``kv_pages.walk_attention``, ops/lane_walk.py): the pages come out
  of the stacked pool in place, page by page, and a slot that is not
  decoding, or a page past a slot's position, costs nothing. An int8
  pool and ``tp > 1`` SWEEP the page pool once instead
  (``kv_pages.sweep_attention``): every page computes a flash-style
  partial softmax of its ``page_size`` tokens against the queries of
  EVERY slot referencing it (``refs`` lanes), and per-slot results
  combine across (page, lane) partials with the online-softmax merge
  (``segment_max``/``segment_sum`` keyed by the lane's slot).

Why the walk: the dense decode step streams ``max_slots × S_cache``
cache rows regardless of how many tokens each slot holds; the sweep
streams ``n_pages × page_size`` rows — the pool, which the operator
sizes to expected total occupancy; the walk streams the live context,
whole pages. Prefix sharing holds ONE copy of a shared prompt's pages
in the pool (each sharer's walk reads it). On an HBM-bound loop the
read bytes ARE the step time (PERF.md section 5 has the decode step's
device time by scope).

- **a chunk beside live slots is ONE program**: where an iteration
  has a pending chunk AND slots decoding, the decode lanes ride the
  chunk program (``mixed_step``; the second and last variant
  ``_chunk_fn`` compiles to): chunk tokens and lanes share one token
  axis through every weight product, so an iteration reads each weight
  once, launches once and reads back once, where a chunk program and a
  decode program back to back each stream every weight from HBM. What
  is per sequence (K/V writes, the two attentions, a conv state, the
  picks) stays split and reuses the two bodies above (``_Pieces``,
  ``_ride``). A chunk with nothing decoding beside it, and the plain
  decode step, are the programs they always were.

The pool itself (``kv_pages.make_pool`` owns its shape: ``(n_layers,
n_pages, page_size, kv_width)``, heads and head dim merged into one
128-aligned row) is donated to every program and updated IN PLACE:
the three layer loops carry it (``kv_pages.scan_layers``), write at
``[layer, page, offset]`` of the stacked array, and read a layer's
pages where they lie — no program produces a second buffer the size
of a layer's pool (tests/test_tpu_aot_compile.py holds the compiled
decode, chunk and mixed programs to that).

The compiled step's signature depends only on pool geometry
``(n_pages, page_size, max_slots)`` and the model config — admission,
retirement, and prefix-cache eviction change VALUES in fixed-shape
tables (kv_pages.py), so slot churn after warmup causes ZERO
recompiles (asserted in tests/test_serving.py via the jit cache
size).
"""
from __future__ import annotations

import inspect
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from torchbooster_tpu.models import layers as L
from torchbooster_tpu.models import afmoe as _afmoe
from torchbooster_tpu.models import bailing_hybrid as _bailing_hybrid
from torchbooster_tpu.models import lfm2 as _lfm2
from torchbooster_tpu.models import mla_moe as _mla_moe
from torchbooster_tpu.models import qwen3_next as _qwen3_next
from torchbooster_tpu.observability import get_registry, span
from torchbooster_tpu.models.quant import (
    weight_stream_bytes as _weight_stream_bytes,
    weights_dtype as _weights_dtype,
)
from torchbooster_tpu.models.gpt import (
    GPTConfig,
    _block_core,
    _check_pos,
    _filter_logits,
    _grouped_cache_attention,
    _lm_head,
    _make_branch_pick,
    _make_pick,
    _mask_logits,
    _quantize_kv,
    qkv_to_tp_major,
)
from torchbooster_tpu.ops.chunk_walk import chunk_walk_attention
from torchbooster_tpu.ops.latent_paged_attention import (
    PAGES_PER_STEP as LATENT_PAGES_PER_STEP,
    latent_paged_attention,
)
from torchbooster_tpu.ops.paged_attention import paged_attention
from torchbooster_tpu.serving.adapters import AdapterRegistry
from torchbooster_tpu.serving.kv_pages import (
    KINDS,
    NULL_PAGE,
    BlockTables,
    HostPagePool,
    OperandBuffer,
    cache_spec,
    from_rows,
    gather_pages,
    layer_pages,
    make_pool,
    make_slot_state,
    pool_map,
    quantized_rows,
    ring_pages,
    ring_table,
    scan_layers,
    sweep_attention,
    to_rows,
    walk_attention,
    write_rows,
)
from torchbooster_tpu.serving.tp import (
    check_tp,
    param_specs as _tp_param_specs,
    place as _tp_place,
    shard_engine_fn as _shard_engine_fn,
    step_traffic as _tp_step_traffic,
)
from torchbooster_tpu.serving.speculative import (
    PromptLookupDrafter,
    TreeLookupDrafter,
    accept_count,
    make_verify_fn,
    tree_accept_path,
    tree_masks,
)
from torchbooster_tpu.serving.structured import (
    SlotCursors,
    bytes_vocab,
    compile_response_format,
)


def _quantize_page_np(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host-side mirror of ``models.gpt._quantize_kv`` for one page
    slab (float32 in): symmetric per-(token, head) int8 over the head
    dim. The host payload keeps FLOAT32 scales — the compiled promote
    write casts to the pool's scale dtype, so an int8-pool round-trip
    through the host tier is bit-exact and a wide-pool round-trip
    costs exactly the int8 cache's noise budget, never more."""
    scale = np.max(np.abs(x), axis=-1, keepdims=True) / 127.0
    scale = np.maximum(scale, 1e-8).astype(np.float32)
    q = np.clip(np.round(x / scale), -127, 127).astype(np.int8)
    return q, scale


# query tokens of a prefill chunk one group of the latent paged kernel
# attends (x n_heads rows in VMEM: 8 x 64 heads = 512 rows)
LATENT_CHUNK_TOKENS = 8


def _served_model(cfg: Any):
    """The module whose ``embed`` / ``layers`` / ``head`` the chunk
    and decode programs call for this model config — chosen by the
    config's TYPE, never by a knob. None for a ``GPTConfig``: its
    programs run ``models/gpt.py``'s ``_block_core`` over stacked
    blocks (``kv_pages.scan_layers``), as they always have. A further
    architecture is a module with those three functions, a
    ``cache_spec()`` on its config and ``UNSERVED`` — the serving
    features it is refused, each with its reason — (models/lfm2.py,
    models/mla_moe.py, models/afmoe.py, models/qwen3_next.py,
    models/bailing_hybrid.py), and a line here."""
    if isinstance(cfg, _lfm2.LFM2Config):
        return _lfm2
    if isinstance(cfg, _mla_moe.MLAMoEConfig):
        return _mla_moe
    if isinstance(cfg, _afmoe.AfmoeConfig):
        return _afmoe
    if isinstance(cfg, _qwen3_next.Qwen3NextConfig):
        return _qwen3_next
    if isinstance(cfg, _bailing_hybrid.BailingHybridConfig):
        return _bailing_hybrid
    if isinstance(cfg, GPTConfig):
        return None
    raise TypeError(
        f"PagedEngine: no served model for a {type(cfg).__name__}")


def refuse_unserved(cfg: Any, asked: dict[str, bool]) -> None:
    """Raise ``NotImplementedError`` for the first serving feature in
    ``asked`` ({feature: was it asked for}) that a model with its own
    layer stack does not serve, naming the feature and the reason its
    module gives (``UNSERVED``)."""
    why = _served_model(cfg).UNSERVED
    for feature, on in asked.items():
        if on:
            raise NotImplementedError(
                f"serving feature {feature!r} is not implemented for "
                f"a {type(cfg).__name__}: {why[feature]}")


class _Pieces(NamedTuple):
    """What one kind of token brings to a serving program — a prefill
    chunk's (``PagedEngine._chunk_pieces``) or the decode lanes'
    (``_lane_pieces``): the embedded input and what is per SEQUENCE
    about it. The layer stack (``PagedEngine._layers``) runs the
    weights over ``x`` and calls back for the rest."""
    x: jax.Array            # (B, S, d) embedded tokens
    positions: jax.Array    # (B, S) absolute positions (rope)
    valid: jax.Array        # (B, S) real tokens (expert routing)
    # a latent pool: k is the token's one row, v and pool_v are None;
    # pools of two kinds (window and full layers): pool_k and pool_v
    # are {kind: array} and both closures take ``kind=`` as well
    write: Callable         # (k, v, pool_k, pool_v, li) -> pools
    read: Callable          # (q, k, v, pool_k, pool_v, li) -> o
    conv: Callable | None   # (z, w, state, li) -> (c, state)
    rows: Callable          # x -> (n, 1, d), the rows the head reads
    # a model's own recurrence over its slot state (models/qwen3_next.py):
    # (step, xs, state, li, name=, scopes=, in_place=None) -> (ys,
    # state) — the engine loads the sequences' rows of state ``name`` of
    # layer ``li``, calls ``step(xs, rows, valid) -> (ys, rows)`` and
    # stores what comes back, the chunk's side under ``scopes[0]``, the
    # lanes' ``[1]``; the lanes hand ``in_place(xs, array, li, live)``
    # the whole array instead, where the model gives one
    carry: Callable | None = None


def _ride(chunk: _Pieces, lanes: _Pieces) -> _Pieces:
    """The MIXED program's pieces: the lanes' ``(slots, 1, ...)``
    tokens ride behind the chunk's ``(1, C, ...)`` on one token axis
    ``(1, C + slots, ...)``, so every weight product sees both and
    reads its weight once. What is per sequence splits the axis again
    at the static ``C`` and goes to the two bodies that exist, ONE
    pool and one state threaded through both: both K/V writes land
    before either read (the seating slot is never active, so they
    touch disjoint pages, and a read between two writes would make
    the compiler keep a copy of the pool), the outputs are joined
    back. Expert routing sorts chunk and lane tokens together: one
    grouped product a projection reads each expert once."""
    C = chunk.x.shape[1]
    # lanes' (slots, 1, ...) as (1, slots, ...), and back
    turn = lambda t: jnp.moveaxis(t, 0, 1)
    join = lambda c, l: jnp.concatenate([c, turn(l)], axis=1)
    split = lambda t: (None, None) if t is None \
        else (t[:, :C], turn(t[:, C:]))

    def write(k, v, pk, pv, li, **kind):
        (kc, kl), (vc, vl) = split(k), split(v)
        return lanes.write(
            kl, vl, *chunk.write(kc, vc, pk, pv, li, **kind), li, **kind)

    def read(q, k, v, pk, pv, li, **kind):
        (qc, ql), (kc, kl), (vc, vl) = split(q), split(k), split(v)
        return join(chunk.read(qc, kc, vc, pk, pv, li, **kind),
                    lanes.read(ql, kl, vl, pk, pv, li, **kind))

    def conv(z, w, state, li):
        zc, zl = split(z)
        cc, state = chunk.conv(zc, w, state, li)
        cl, state = lanes.conv(zl, w, state, li)
        return join(cc, cl), state

    def rows(x):
        xc, xl = split(x)
        return jnp.concatenate([chunk.rows(xc), lanes.rows(xl)], axis=0)

    def carry(step, xs, state, li, **named):
        # the chunk's slot first, then the live lanes (which never
        # include it): the lanes keep what the chunk stored
        parts = [split(t) for t in xs]
        yc, state = chunk.carry(step, tuple(p[0] for p in parts), state,
                                li, **named)
        yl, state = lanes.carry(step, tuple(p[1] for p in parts), state,
                                li, **named)
        return join(yc, yl), state

    return _Pieces(join(chunk.x, lanes.x),
                   join(chunk.positions, lanes.positions),
                   join(chunk.valid, lanes.valid), write, read,
                   conv if chunk.conv is not None else None, rows,
                   carry if chunk.carry is not None else None)


def _merge_partials(a: tuple, b: tuple) -> tuple:
    """Two flash-style partials ``(o unnormalised (B, S_q, g, rep, D),
    m, l (B, g, rep, S_q))`` over disjoint keys as ONE such partial:
    the online-softmax combine. A partial that saw no key (``m`` =
    -1e30) gets weight 0 beside one that did."""
    (oa, ma, la), (ob, mb, lb) = a, b
    m = jnp.maximum(ma, mb)
    wa, wb = jnp.exp(ma - m), jnp.exp(mb - m)
    # (B, g, rep, S_q) weights -> (B, S_q, g, rep, 1)
    mv = lambda t: jnp.moveaxis(t, -1, 1)[..., None]
    return oa * mv(wa) + ob * mv(wb), m, la * wa + lb * wb


def _normalised(partial: tuple, q: jax.Array) -> jax.Array:
    """A merged partial as the attention's output, in ``q``'s layout
    ``(B, S_q, n_heads, head_dim)`` and dtype."""
    o, _, l = partial
    o = o / jnp.moveaxis(jnp.maximum(l, 1e-30), -1, 1)[..., None]
    return o.reshape(*q.shape[:2], -1, q.shape[3]).astype(q.dtype)


class _Flight(NamedTuple):
    """A launched decode step that has not landed: what
    ``PagedEngine._landed`` needs to read it back and book it."""
    active: np.ndarray      # (max_slots,) bool: the lanes that decode
    fetch: tuple            # device results to read: tokens first
    pending: dict | None    # the chunk's prefill where it rode
    last: bool              # ... and was its prompt's last

    def carries(self, slot: int) -> bool:
        """Whether the step decodes ``slot`` or ends its prompt."""
        return bool(self.active[slot]) or (
            self.last and self.pending["slot"] == slot)


class PagedEngine:
    """Single-compile continuous-batching decode over a paged KV pool
    with an optional prompt-prefix cache.

    ``admit_begin``/``prefill_step``/``step``/``retire`` are the whole
    lifecycle; the host-side batcher (serving/batcher.py) drives them,
    interleaving one prefill chunk per decode step so long prompts
    never stall in-flight decode — as ONE program an iteration
    (``mixed_step``) where a chunk is pending and slots are live.
    **Which modes ride the mixed program** is decided once at build
    (``self.mixes``), from the engine's own mode: the defaults ride,
    and so do ``cache_dtype="int8"``, ``decode_backend="pallas"``,
    ``structured``, ``prefix_cache`` / ``host_spill`` and a model with
    its own layer stack and slot state (their operands are slices of
    the one operand buffer or the two programs' trailing VALUE
    operands, and the chunk's and the lanes' bodies are the ones
    those modes already run). ``speculative`` (its
    verify step is another program), ``parallel_sampling`` (the chunk
    returns the fork's logits and picks by branch key), ``adapters``
    (lora lane ids are per batch row, and the mixed program has one),
    ``tp > 1`` (the shard_map wrappers fix the operand lists) and
    ``prefill_only`` (nothing decodes) keep two programs an
    iteration. **Which modes look ahead** (``self.looks_ahead``,
    decided beside it): those that ride the mixed program, less
    ``structured`` (its mask follows the token). Such an engine's
    programs with lanes take the last program's ``tokens`` as an
    operand and read a slot's last token from it where the buffer's
    ``known`` says the host has not read it yet, so the batcher may
    launch a step behind one still in flight (:meth:`step_ahead`;
    :meth:`step` and :meth:`mixed_step` stay synchronous: launch,
    then land at once). ``admit`` is the one-shot
    convenience (seat + drain this request's chunks). ``cache_dtype=
    "int8"`` stores quantized pages (``_quantize_kv`` — the same
    per-(token, head) scheme as the dense cache). ``temperature=0``
    decodes greedily; otherwise sampling follows ``_make_pick`` (the
    same filtering the dense path uses).

    ``prefix_cache=True`` keeps retired requests' full prompt pages
    resident (refcounted, LRU-evicted under pool pressure): a new
    request whose prompt prefix matches maps those pages into its
    block table and prefills only the tail — generated tokens are
    IDENTICAL to the cold path (the pages hold bitwise the same K/V a
    re-prefill would write). ``prefill_chunk_pages`` sizes the chunk
    (clamped to the slot's page budget).

    ``speculative=True`` switches decode to draft → batched-verify →
    accept/rewind (serving/speculative.py): host-side prompt-lookup
    drafting proposes up to ``draft_len`` tokens per slot and ONE
    compiled multi-token verify step scores them all, emitting
    ``accepted + 1`` tokens per pool read — greedy output stays
    token-for-token identical to the non-speculative engine. Drive it
    with :meth:`spec_step` (the batcher does); ``draft_len`` /
    ``ngram_min`` tune the drafter. Off (the default), no verify
    executable exists and the engine is bit-for-bit the
    non-speculative one.

    ``parallel_sampling=True`` turns on copy-on-write parallel
    decoding (OpenAI ``n``/``best_of``): :meth:`fork` splits a
    just-prefilled slot into n branches that SHARE every full page
    through the refs lanes (one pool read serves all branches — the
    same sharing contract the prefix cache rides, on both backends)
    and copy only the partial tail page; every slot samples with its
    own branch key (``fold_in(PRNGKey(seed), branch)`` folded again
    with the context length per step) and the decode step returns
    per-slot token logprobs for ``best_of`` ranking. Branch b's
    stream is token-exact vs an independent single-slot run admitted
    with the same ``(seed, branch=b)`` — greedy or seeded sampling —
    and fork churn adds zero decode compiles. Off (the default) the
    engine is bit-for-bit unchanged. Mutually exclusive with
    ``speculative``.

    ``spec_tree=True`` (requires ``speculative=True`` and greedy
    decoding) upgrades the linear draft chain to a TREE of candidate
    branches (serving/speculative.py ``TreeLookupDrafter``): up to
    ``tree_width`` distinct continuations ride the SAME ``1 +
    draft_len`` verify positions with ancestor-only visibility masks
    (traced values — adaptive tree shapes never recompile), the best
    accepted root-to-leaf path wins, and its K/V rows compact into
    contiguous positions in one fixed-shape pass. On unambiguous
    streams the tree degenerates to the linear chain bit-for-bit.

    ``decode_backend="pallas"`` swaps the decode AND verify steps'
    pool READ for the paged flash-decode kernel
    (ops/paged_attention.py): block tables walked in-kernel over a
    compacted live-page list, so bytes/step are the live context
    (``Σ ceil(len/page) · page_size`` rows, shared prefix pages once)
    instead of the pool — on the HBM-bound decode loop that ratio is
    the tokens/s ratio (docs/performance.md, two-regime roofline).
    Greedy output is token-exact vs the sweep and the dense control
    (tests/test_paged_kernel.py), and the compiled-step count stays
    one per executable across churn. The default ``"xla"`` means the
    live-page walk where the pool allows it (``self.walks``: plain
    rows on one device; ops/lane_walk.py) and the sweep where it does
    not (an int8 pool, ``tp > 1``, the speculative verify).

    ``tp > 1`` (with a committed ``mesh`` carrying a ``tp`` axis of
    that size) shards every compiled step's ATTENTION over the mesh's
    tp (heads) axis (serving/tp.py): qkv column-parallel with
    rank-major columns, O-projection row-parallel with ONE psum per
    layer, and the KV page pool sharded on its KV-head axis — per-chip
    KV bytes/step are the single-chip engine's ÷ tp, which on the
    HBM-bound decode loop is the tokens/s story (docs/parallelism.md
    "Tensor-parallel serving"). GQA shards by KV-head groups (query
    heads follow their group; ``tp`` must divide ``n_kv_heads`` — or
    ``n_heads`` under MHA). Block tables, refcounts, the prefix
    index, and all scheduling stay host-side and replicated — every
    chip walks the same tables over its own head shard, so
    seat/retire/evict/CoW logic is byte-identical to the single-chip
    engine's, and both backends (the sweep and the pallas table walk)
    shard the same way with no kernel changes (at ``tp > 1`` the
    default backend sweeps: the live-page walk is the one-device
    read). Greedy decode is
    token-exact vs tp=1 and vs dense ``jit_generate``; the
    zero-recompile contract holds per executable; the default
    ``tp=1`` builds no shard_map wrapper at all — same compiled
    artifacts, same call signatures.

    **A step's operands** are the params, the pool, ONE packed int32
    buffer and the rng key (``OperandBuffer``, kv_pages.py): every
    small integer the host knows — the tables, the chunk's ids and
    cursors, the modes' lane ids, work lists, branch keys and drafts —
    lives in one preallocated host buffer whose layout is fixed at
    build from the geometry and the modes, crosses to the device as
    one transfer an iteration (``operand_puts`` /
    ``serving_operand_puts_total`` count them) and is sliced apart
    inside the program. The key never leaves the device: each program
    splits it and hands the new key back with its results. Only a
    mode's LARGE operand (the structured legality mask, the tree's
    visibility matrix) rides beside the buffer, as one more transfer.

    **Other models than GPT.** The engine is chosen by the TYPE of
    ``cfg`` (:func:`_served_model`): a model that brings its own layer
    stack (``models/lfm2.py``) gets the same two programs with its
    ``embed`` / ``layers`` / ``head`` in the place of ``_block_core``,
    a pool over the layers its ``cache_spec()`` says attend, and —
    where the spec names slot-indexed states (a short convolution's
    last inputs) — one more donated operand, carried and updated in
    place beside the pool. The chunk program reads a slot's state as
    zeros where its prefill starts at position 0 (a fresh seat, or a
    preempted request's replay), so seating and retiring write
    nothing; the decode program shifts live slots' state and leaves
    the others alone. Features whose bookkeeping takes pages for the
    WHOLE of a sequence refuse such a model at build
    (``NotImplementedError`` naming the feature).

    **Window and full attention layers in one model**
    (``models/afmoe.py``; ``CacheSpec.kv_kinds``): two pools, one a
    kind, both donated to all three programs. The full layers' pool is
    the one the block tables page; a window layer's is a ring of
    ``window / page_size + prefill_chunk_pages + 1`` pages a slot
    (``self.ring``), written at ``(position // page_size) % ring`` and
    read with every row's position recovered from the slot's length
    (``kv_pages.ring_positions``), so ``0 <= q_pos - k_pos < window``
    is the whole of visibility: it hides what a recycled page still
    holds and what the slot's last tenant left, and seating, preempting
    and retiring touch nothing of it. The decode lanes walk a slot's
    ring from the window's lower edge to its newest page, as they walk
    a block table; a chunk walks its slot's ring from the window's
    lower edge for its first query, never its table, for a window
    layer, and its table up to its own position for a full one
    (ops/chunk_walk.py).

    **Linear-attention layers** (``models/qwen3_next.py``): the spec's
    slot states are the convolution's rows and a float32 matrix ``S`` a
    value head, each in its own dtype. The programs hand the model
    ``carry`` (:class:`_Pieces`): the engine loads a named state's rows
    — the chunk's slot, zeros at position 0; every lane — and stores
    what the model's mathematics returns, the lanes' only where a slot
    decodes, in place in the donated array. ``S`` of the lanes is read
    and written by the model's own kernel on the array (live slots
    only, ``ops/delta_lanes.py``).

    **A latent pool beside slot states** (``models/bailing_hybrid.py``:
    KDA layers, whose ``S`` decays each key channel on its own, beside
    latent-attention layers): the spec names both, and the pieces
    compose — the latent ``write`` / ``read`` of the MLA layers' rows
    and the ``carry`` of the KDA layers' states, in the same programs.
    The block tables page the latent pool only; the states are the
    slot's whatever its length, so admission counts pages as for any
    other model.
    """

    def __init__(self, params: dict, cfg: Any, *,
                 page_size: int = 64, n_pages: int = 128,
                 max_slots: int = 8, cache_dtype: Any = None,
                 compute_dtype: Any = jnp.bfloat16,
                 temperature: float = 0.0, top_k: int | None = None,
                 top_p: float | None = None,
                 rng: jax.Array | None = None,
                 prefix_cache: bool = False,
                 prefill_chunk_pages: int = 4,
                 speculative: bool = False,
                 draft_len: int = 4,
                 ngram_min: int = 2,
                 decode_backend: str = "xla",
                 tp: int = 1,
                 mesh: Any = None,
                 parallel_sampling: bool = False,
                 spec_tree: bool = False,
                 tree_width: int = 2,
                 host_spill: bool = False,
                 host_spill_mb: float = 64.0,
                 structured: bool = False,
                 structured_vocab: Any = None,
                 lora_rank: int = 0,
                 lora_max_live: int = 0,
                 prefill_only: bool = False):
        if cfg.seq_len % page_size:
            # a last partial page per slot would shift page_pos math;
            # geometry is static, so fail loudly at construction
            raise ValueError(
                f"page_size ({page_size}) must divide cfg.seq_len "
                f"({cfg.seq_len})")
        if prefill_chunk_pages < 1:
            raise ValueError(
                f"prefill_chunk_pages must be >= 1, got "
                f"{prefill_chunk_pages}")
        if decode_backend not in ("xla", "pallas"):
            raise ValueError(
                f"decode_backend must be 'xla' (the pool sweep) or "
                f"'pallas' (the paged flash-decode kernel), got "
                f"{decode_backend!r}")
        if speculative and not 1 <= draft_len < page_size:
            # the verify step writes 1 + draft_len positions per slot
            # per step; draft_len < page_size bounds the write-ahead
            # to at most ONE page past the cursor's, keeping the
            # grow/preempt pressure of a speculative slot within one
            # page of the non-speculative engine's
            raise ValueError(
                f"speculative decoding needs 1 <= draft_len < "
                f"page_size, got draft_len={draft_len} with "
                f"page_size={page_size}")
        if spec_tree and not speculative:
            raise ValueError(
                "spec_tree=True needs speculative=True: tree "
                "drafting generalizes the draft+verify path, there "
                "is no tree without a verify step")
        if spec_tree and temperature != 0:
            raise ValueError(
                f"spec_tree needs greedy decoding (temperature=0, "
                f"got {temperature}): sampling acceptance across "
                "sibling branches needs without-replacement "
                "residuals the verify rule does not carry")
        if parallel_sampling and speculative:
            raise ValueError(
                "parallel_sampling and speculative are mutually "
                "exclusive: the per-branch PRNG/logprob accounting "
                "rides the plain decode step — serve n-way traffic "
                "on a non-speculative engine")
        if host_spill and not prefix_cache:
            raise ValueError(
                "host_spill=True needs prefix_cache=True: the spill "
                "tier demotes REGISTERED prefix pages at eviction — "
                "without the prefix index there is nothing to demote "
                "or promote")
        if structured_vocab is not None and not structured:
            raise ValueError(
                "structured_vocab without structured=True does "
                "nothing: the token-DFA compiler only runs on a "
                "structured engine")
        if host_spill and tp > 1:
            raise ValueError(
                f"host_spill with tp={tp} is not supported yet: the "
                "promotion executable would need a shard_map wrapper "
                "over the KV-head-sharded pool — run the spill tier "
                "on tp=1 replicas (the fleet path)")
        self.model = _served_model(cfg)
        if self.model is not None:
            # what is not done for a model with its own layer stack:
            # the model's module says why, feature by feature
            # (``UNSERVED``: slot state that pages do not carry, a
            # latent pool with no V half, GPT-shaped layouts)
            refuse_unserved(cfg, {
                "host_spill": host_spill,
                "prefix_cache": prefix_cache,
                "speculative": speculative,
                "disagg (prefill_only)": prefill_only,
                "parallel_sampling (fork)": parallel_sampling,
                "tp": tp > 1,
                "cache_dtype: int8": cache_dtype is not None,
                "decode_backend: pallas": decode_backend != "xla",
                "structured": structured,
                "adapters (lora)": lora_rank > 0 or lora_max_live > 0,
            })
        else:
            # same params/config positional-encoding guard the dense
            # generate() applies — a rope checkpoint served with
            # pos="learned" (or vice versa, or a tp-major-permuted
            # tree) must fail here, not decode garbage quietly
            _check_pos(params, cfg)
        # tensor-parallel serving (serving/tp.py): tp > 1 shards the
        # attention of every compiled step — Q/K/V/O projections and
        # the KV page pool — over the mesh's tp (heads) axis; all
        # host-side tables and scheduling stay replicated. tp == 1 is
        # the single-chip engine, bit-for-bit: no mesh, no permute,
        # no shard_map wrapper, the same jitted call signatures.
        check_tp(tp, cfg, mesh)
        self.tp = int(tp)
        self.mesh = mesh if self.tp > 1 else None
        self._tp_core = ("tp", self.tp) if self.tp > 1 else None
        self.params = params
        self.cfg = cfg
        self.page_size = page_size
        self.n_pages = n_pages
        self.max_slots = max_slots
        self.compute_dtype = compute_dtype
        self.prefix_cache = bool(prefix_cache)
        self.quantized = cache_dtype in ("int8", jnp.int8)
        if not self.quantized and cache_dtype is not None:
            raise ValueError(
                f"cache_dtype must be None or 'int8', got {cache_dtype!r}")
        # copy-on-write parallel sampling (OpenAI n/best_of): fork a
        # prefilled slot into n branches sharing every full page
        # through the refs lanes, per-branch PRNG keys folded by
        # branch id, per-token logprobs for best_of ranking. Off (the
        # default), no key table crosses the jit boundary and the
        # decode step is bit-for-bit the non-parallel engine's — the
        # same collapse contract as n_ref_lanes for the prefix cache.
        self.parallel = bool(parallel_sampling)
        self.tables = BlockTables(cfg, page_size, n_pages, max_slots,
                                  prefix_cache=prefix_cache,
                                  parallel=self.parallel)
        self.prefill_chunk_pages = min(prefill_chunk_pages,
                                       self.tables.max_pages_per_slot)
        self.chunk_tokens = self.prefill_chunk_pages * page_size
        spec = cache_spec(cfg)
        # a head's lanes: the model's own where it states them (a
        # model whose heads are not d_model / n_heads wide)
        self.head_dim = getattr(cfg, "head_dim",
                                cfg.d_model // cfg.n_heads)
        # window layers (CacheSpec.kv_kinds): their cache is a RING a
        # slot — ``ring`` pages of a second pool that slot ``s`` owns
        # for good, position p at ring page (p // page_size) % ring —
        # bounded whatever the sequences' lengths and with no host
        # bookkeeping: pool["k"] / pool["v"] are then {kind: array},
        # donated whole, and the full layers' half is what the block
        # tables page. None where every layer holds every token.
        self.window: int | None = None
        self.ring: int | None = None
        if spec.layers_of("window"):
            self.window = spec.window
            self.ring = ring_pages(spec.window, page_size,
                                   self.prefill_chunk_pages)
        self.pool = make_pool(
            cfg, page_size, n_pages, cache_dtype=cache_dtype,
            compute_dtype=compute_dtype, shards=self.tp,
            ring=None if self.ring is None else (max_slots, self.ring))
        # slot-indexed state (a conv mixer's last inputs), None for a
        # model without: donated and updated in place like the pool
        self.slot_state = make_slot_state(spec, max_slots, compute_dtype)
        # a latent pool (CacheSpec.value_dim): ONE array of rows whose
        # leading lanes are the values; pool["v"] is None and rides
        # through every program as an empty pytree
        self.latent_dim: int | None = spec.value_dim
        # the last decode step's tokens per expert (n_moe_layers,
        # n_experts) of a model that routes, and the registry series
        # fed from it (made at the first step with the registry on)
        self.moe_counts: np.ndarray | None = None
        self.moe_elsewhere: np.ndarray | None = None
        self._moe_inst: dict | None = None
        # rows of a window layer's ring overwritten by a later
        # position, and the registry series of the two kinds' rows
        # (made at the first step with the registry on)
        self.window_rows_recycled = 0
        self._kv_inst: dict | None = None
        # K/V layers of each kind (the pages the lanes' read fetches
        # are counted against what those layers' pools hold), and the
        # registry series (made at the first step with the registry on)
        self._kv_layers = {kind: spec.layers_of(kind) for kind in KINDS
                           if spec.layers_of(kind)}
        self._lane_inst: dict | None = None
        # ... and the pages a chunk's read of its prior context fetches
        self._chunk_inst: dict | None = None
        # the registry series of the slot state's bytes (made at the
        # first step with the registry on)
        self._state_inst = None
        # the host spill tier (PR 16): LRU eviction demotes registered
        # prefix pages to a host-DRAM pool (int8 + scales) and a later
        # seat promotes them back through ONE fixed-shape compiled
        # write over pinned staging buffers — the H2D stream replaces
        # the recompute FLOPs (docs/performance.md "Page spill tier").
        # Off (the default), no staging buffers exist and eviction
        # frees pages exactly as PR 4 shipped it.
        self.host_spill = bool(host_spill)
        self._promote_jit = None
        self._promote_lanes = 0
        self._stage: dict[str, np.ndarray] = {}
        if self.host_spill:
            self.tables.host_pool = HostPagePool(
                max(1, int(host_spill_mb * (1 << 20))))
            self.tables.spill_fetch = self._spill_fetch
            head_dim = cfg.d_model // cfg.n_heads
            lanes = self.prefill_chunk_pages
            self._promote_lanes = lanes
            stage_shape = (lanes, cfg.n_layers, page_size,
                           cfg.kv_heads, head_dim)
            # pinned host staging: fixed shapes so every promotion
            # group rides the same device_put layout and the compiled
            # write never re-specializes; device_put snapshots the
            # buffer, so lane reuse across groups cannot race
            self._stage = {
                "k": np.zeros(stage_shape, np.int8),
                "v": np.zeros(stage_shape, np.int8),
                "k_scale": np.ones(stage_shape[:-1] + (1,), np.float32),
                "v_scale": np.ones(stage_shape[:-1] + (1,), np.float32),
            }
        if self.tp > 1:
            # one-time layout work, never per step: permute the qkv
            # columns rank-major (rank i holds [q_i | k_i | v_i] — a
            # contiguous tp split of the canonical stack would hand
            # rank 0 all of q) and place params + pool on the mesh —
            # qkv column-parallel, O-projection row-parallel, pool
            # sharded on KV heads, everything else replicated
            self.params = qkv_to_tp_major(params, cfg, self.tp)
            self.params, self.pool = _tp_place(self.params, self.pool,
                                               mesh)
        # decode_backend selects HOW the decode/verify steps READ the
        # pool: "xla" (default) is the live-page walk where the pool
        # allows it (below) and the whole-pool sweep where it does
        # not; "pallas" walks the block tables in-kernel
        # (ops/paged_attention.py) over the pool relaid out by heads.
        # Writes, sampling, bookkeeping, and every contract
        # (zero-recompile, token parity, seat/retire/evict, prefix
        # sharing) are backend-independent.
        self.decode_backend = decode_backend
        # whether the decode lanes READ by the live-page walk
        # (kv_pages.walk_attention) and a chunk its prior context by
        # the chunk walk (ops/chunk_walk.py), decided here from what
        # the pool is: the default backend over a pool of plain rows
        # on one device; an int8 pool (rows and scales), ``tp > 1``
        # (the pool sharded on heads) and a latent pool (its own
        # kernel) keep their reads
        self.walks = (decode_backend == "xla" and not self.quantized
                      and self.tp == 1 and spec.value_dim is None)
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self._pick = _make_pick(temperature, top_k, top_p, jnp.int32)
        self._rng = jax.random.PRNGKey(0) if rng is None else rng
        # in-flight chunked prefills, oldest first: dicts of
        # {slot, ids (chunk-padded np), s0, start}
        self._pending: list[dict] = []
        # host-side totals the batcher exports (telemetry counters)
        self.prefill_chunks = 0
        self.mixed_steps = 0     # of them, issued inside mixed_step
        self.prefix_hit_pages = 0
        self.prefix_lookup_pages = 0
        self.spills = 0          # pages demoted HBM -> host
        self.promotions = 0      # pages promoted host -> HBM
        self.host_hit_pages = 0  # seat-time matches served host-tier
        self.promoted_bytes = 0  # measured H2D payload bytes staged
        # prefill-only mode (serving/disagg.py's prefill pool): the
        # engine admits and prefills but its decode entries refuse to
        # run — a disaggregated prefill host exports finished pages
        # over the wire instead of decoding, and a driver bug that
        # would silently decode on the prefill pool must fail loudly
        self.prefill_only = bool(prefill_only)
        self.exported_pages = 0  # pages exported via export_pages
        self.exported_bytes = 0  # their payload bytes (quantized)
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_steps = 0
        self.forks = 0
        self.fork_pages = 0      # pages SHARED into children at fork
        self.cow_copies = 0      # private tail pages copied at fork
        # per-slot branch PRNG state (parallel sampling only): the
        # request's BASE key, the slot's folded branch key (made with
        # the operand buffer below: it rides there), and its branch
        # index — host numpy, rebuilt at admit/fork
        self._base_keys = np.zeros((max_slots, 2), np.uint32)
        self._branch_of = np.zeros(max_slots, np.int32)
        # prefill-final logits + branch-0 logprob stashed per slot so
        # fork() can sample every branch's own first token from the
        # SAME prompt distribution (parallel mode only; popped at
        # fork/retire)
        self._fork_state: dict[int, dict] = {}
        self.step_logprobs: np.ndarray | None = None
        # structured generation (serving/structured/): per-slot
        # automaton cursors fused into ONE fixed-shape (max_slots,
        # vocab) legality mask that rides the decode/verify steps as
        # a trailing VALUE operand — schema churn changes mask BITS,
        # never shapes, so the zero-recompile contract holds; off
        # (the default) no mask operand crosses the jit boundary and
        # every call signature is byte-identical to the pre-feature
        # engine (the same collapse contract as the slot-key table)
        self.structured = bool(structured)
        self._cursors = None
        self._svocab = None
        self._sdfa_cache: dict[str, Any] = {}
        self._smask_verify: np.ndarray | None = None
        self.structured_requests = 0
        if self.structured:
            vocab = (list(structured_vocab)
                     if structured_vocab is not None
                     else bytes_vocab(cfg.vocab))
            if len(vocab) != cfg.vocab:
                raise ValueError(
                    f"structured_vocab has {len(vocab)} entries but "
                    f"the model's vocabulary is {cfg.vocab} — the "
                    "token-DFA mask must cover every logit")
            self._svocab = vocab
            self._cursors = SlotCursors(max_slots, cfg.vocab)
            if speculative:
                # persistent verify-mask buffer: (max_slots,
                # 1 + draft_len, vocab), reset to all-True each
                # spec step and filled per constrained slot
                self._smask_verify = np.ones(
                    (max_slots, 1 + draft_len, cfg.vocab), bool)
        # batched multi-LoRA decode (serving/adapters.py): adapters
        # live STACKED on a device lane axis (lane 0 = the all-zero
        # base adapter) and every compiled step gathers each slot's
        # lane by a traced per-slot id (a slice of the operand
        # buffer) — adapter churn (hot-load/evict/mixed batches)
        # moves VALUES, never shapes, so the zero-recompile contract
        # holds; off (the default) no lora operand crosses the jit
        # boundary and the buffer has no lane slice (the same
        # collapse contract as the structured mask)
        if (lora_rank > 0) != (lora_max_live > 0):
            raise ValueError(
                f"lora_rank={lora_rank} with lora_max_live="
                f"{lora_max_live}: enable batched LoRA with BOTH "
                "positive — rank and lane count are trace SHAPES, "
                "half a configuration cannot compile")
        self.lora = lora_rank > 0
        self.lora_rank = int(lora_rank)
        self.lora_max_live = int(lora_max_live)
        self._lora_buf = None
        self._lora_load_jit = None
        self.adapters = None
        if self.lora:
            lanes = self.lora_max_live + 1
            d = cfg.d_model
            qkv_out = d + 2 * cfg.kv_heads * (d // cfg.n_heads)
            shapes = {
                "a_qkv": (cfg.n_layers, lanes, d, self.lora_rank),
                "b_qkv": (cfg.n_layers, lanes, self.lora_rank,
                          qkv_out),
                "a_proj": (cfg.n_layers, lanes, d, self.lora_rank),
                "b_proj": (cfg.n_layers, lanes, self.lora_rank, d),
            }
            buf = {k: jnp.zeros(s, compute_dtype)
                   for k, s in shapes.items()}
            if self.tp > 1:
                # replicated beside the head-sharded attention they
                # delta: _block_core slices B_qkv's columns and
                # A_proj's rows to each rank's shard in-step, so the
                # qkv delta lands on local columns and the proj delta
                # is a true partial product riding the ONE existing
                # psum — replication adds zero collectives
                from jax.sharding import NamedSharding
                from torchbooster_tpu.serving.tp import REP
                rep_ns = NamedSharding(mesh, REP)
                buf = {k: jax.device_put(v, rep_ns)
                       for k, v in buf.items()}
                self._lora_load_jit = jax.jit(
                    self._lora_write_fn, donate_argnums=(0,),
                    out_shardings=rep_ns)
            else:
                self._lora_load_jit = jax.jit(
                    self._lora_write_fn, donate_argnums=(0,))
            self._lora_buf = buf
            self.adapters = AdapterRegistry(self)
        # the step's host-known operands: ONE int32 buffer, laid out
        # here from the geometry and the modes and never again (no
        # option chooses it). The tables become views of it; the
        # chunk's ids and (start, s0, slot) have their slices in every
        # engine, a mode's small operands where the mode is on.
        # whether a pending chunk rides the decode step as ONE program
        # (mixed_step), decided once from the engine's own mode: the
        # modes whose chunk and decode programs differ in more than
        # their trailing VALUE operands keep two programs an iteration
        # (the class docstring lists them)
        self.mixes = not (speculative or self.parallel or self.lora
                          or self.tp > 1 or self.prefill_only)
        # whether the scheduler may launch a step BEFORE it has read
        # the last one's tokens (one step in flight), decided the same
        # way: the modes that need the token on the host before the
        # next launch (the legality mask follows it; the drafter, the
        # branches' logprobs and forks, and the two-program modes
        # above) land every step at once
        self.looks_ahead = self.mixes and not self.structured
        fields = self.tables.operand_fields()
        fields["chunk_ids"] = (self.chunk_tokens,)
        fields["chunk"] = (3,)
        if self.looks_ahead:
            # per slot: the host knows the slot's last token (the
            # buffer's ``last_ids``); 0: it is still on the device, in
            # the ``tokens`` the last program returned
            fields["known"] = (max_slots,)
        if decode_backend == "pallas":
            n_walk = n_pages - 1
            fields.update(
                work_pages=(n_walk,),
                work_refs=(n_walk, self.tables.n_ref_lanes),
                work_pos=(n_walk,))
        if self.parallel:
            fields["slot_keys"] = (max_slots, 2)
        if self.lora:
            fields["slot_lanes"] = (max_slots,)
        if speculative:
            fields["drafts"] = (max_slots, draft_len)
            if spec_tree:
                fields["parents"] = (max_slots, draft_len)
                fields["depth"] = (max_slots, 1 + draft_len)
        self.operands = OperandBuffer(fields)
        self.tables.bind(self.operands)
        self._op = {name: self.operands.view(name) for name in fields}
        # the slot's folded branch key (its uint32 words as they lie
        # in the buffer) and the slot's adapter lane: views where
        # their mode packs them, plain arrays nobody sends otherwise
        self._slot_keys = (
            self._op["slot_keys"].view(np.uint32) if self.parallel
            else np.zeros((max_slots, 2), np.uint32))
        self._slot_lanes = (
            self._op["slot_lanes"] if self.lora
            else np.zeros(max_slots, np.int32))
        # host-to-device transfers issued for step operands (_put)
        self.operand_puts = 0
        # the last program's ``tokens``, left on the device like the
        # key (a look-ahead engine's lanes read a slot's last token
        # from it where the host does not know it yet), and the newest
        # launched step while it has not landed
        self._tokens = None
        self._flight: _Flight | None = None
        if self.looks_ahead:
            self._op["known"][:] = 1
            self._tokens = jnp.zeros((max_slots,), jnp.int32)
        # where a step's operands go: replicated over the mesh at
        # tp > 1 (the key too: committed like the keys the programs
        # hand back, so the first call is no cache entry of its own)
        self._rep = None
        if self.tp > 1:
            from jax.sharding import NamedSharding
            from torchbooster_tpu.serving.tp import REP
            self._rep = NamedSharding(mesh, REP)
            self._rng = jax.device_put(self._rng, self._rep)
        # the pool crosses the jit boundary EVERY call — donate it so
        # XLA updates the pages in place; an undonated pool would copy
        # pool-sized bytes per step, re-taxing exactly the HBM traffic
        # the pager removes (CPU backends ignore donation — harmless).
        # At tp > 1 the SAME step bodies run under shard_map: pools
        # sharded on KV heads, host tables replicated, outputs
        # replicated post-psum; at tp == 1 the un-wrapped jits below
        # are byte-identical to the single-chip engine's.
        # after the pool every program takes the operand buffer and
        # the rng key, then what rides beside: structured mode's
        # legality mask, lora's four adapter stacks (device-resident)
        n_beside = 2 + (1 if self.structured else 0) \
            + (4 if self.lora else 0)
        # every program hands the new key back first; the per-branch
        # pick path returns one more replicated output (per-slot
        # logprobs), its chunk (token, logprob, final logits)
        n_par = 1 if self.parallel else 0
        self._branch_pick = _make_branch_pick(
            temperature, top_k, top_p, jnp.int32)
        if self.tp > 1:
            pspecs = _tp_param_specs(self.params)
            self._chunk_jit = _shard_engine_fn(
                self._chunk_fn, mesh, pspecs, n_beside,
                4 if self.parallel else 2)
            self._decode_jit = _shard_engine_fn(
                self._decode_fn, mesh, pspecs, n_beside, 2 + n_par)
        else:
            # the slot state rides first among the trailing operands
            # (argument 5 of both programs), donated too
            donate = (1, 2, 5) if self.slot_state is not None else (1, 2)
            self._chunk_jit = jax.jit(self._chunk_fn,
                                      donate_argnums=donate)
            self._decode_jit = jax.jit(self._decode_fn,
                                       donate_argnums=donate)
        # the fork-time copy-on-write page copy (parallel mode only):
        # ONE fixed-shape executable — (max_slots,) src/dst page-id
        # vectors padded with null->null self-copies — compiled once
        # at the first fork; fork churn itself never touches the
        # decode/verify executables (the zero-recompile contract)
        self._cow_jit = None
        if self.parallel:
            if self.tp > 1:
                from jax.sharding import NamedSharding
                from torchbooster_tpu.serving.tp import POOL_SPEC, REP
                pool_ns = NamedSharding(mesh, POOL_SPEC)
                self._cow_jit = jax.jit(
                    jax.shard_map(self._cow_fn, mesh=mesh,
                                  in_specs=(POOL_SPEC, POOL_SPEC, REP,
                                            REP),
                                  out_specs=(POOL_SPEC, POOL_SPEC),
                                  check_vma=False),
                    donate_argnums=(0, 1),
                    out_shardings=(pool_ns, pool_ns))
            else:
                self._cow_jit = jax.jit(self._cow_fn,
                                        donate_argnums=(0, 1))
        # speculative mode (serving/speculative.py): the drafter and
        # the ONE multi-token verify executable exist only when it is
        # on — the cold engine's compiled artifacts and per-step work
        # are BIT-FOR-BIT the non-speculative engine's (the same
        # collapse contract as n_ref_lanes for the prefix cache)
        self.speculative = bool(speculative)
        self.draft_len = draft_len
        # tree speculative decoding: the drafter proposes a TREE of
        # candidate branches and the verify step scores every node in
        # the same single pass through ancestor-only visibility masks
        # (all traced VALUES — adaptive per-step tree shapes cannot
        # recompile); the accepted root-to-leaf path is compacted
        # into contiguous K/V rows by _compact_fn after each step
        self.spec_tree = bool(spec_tree)
        self.tree_width = tree_width
        self._drafter = None
        self._verify_jit = None
        self._compact_jit = None
        if self.speculative:
            if self.spec_tree:
                self._drafter = TreeLookupDrafter(
                    draft_len, ngram_min=ngram_min, width=tree_width)
            else:
                self._drafter = PromptLookupDrafter(
                    draft_len, ngram_min=ngram_min)
            verify_fn = make_verify_fn(self)
            if self.tp > 1:
                # the tree's visibility matrix rides beside too
                self._verify_jit = _shard_engine_fn(
                    verify_fn, mesh, pspecs,
                    n_beside + (1 if self.spec_tree else 0), 3)
            else:
                self._verify_jit = jax.jit(verify_fn,
                                           donate_argnums=(1, 2))
            if self.spec_tree:
                if self.tp > 1:
                    from jax.sharding import NamedSharding
                    from torchbooster_tpu.serving.tp import (
                        POOL_SPEC, REP)
                    pool_ns = NamedSharding(mesh, POOL_SPEC)
                    self._compact_jit = jax.jit(
                        jax.shard_map(self._compact_fn, mesh=mesh,
                                      in_specs=(POOL_SPEC, POOL_SPEC,
                                                REP, REP),
                                      out_specs=(POOL_SPEC, POOL_SPEC),
                                      check_vma=False),
                        donate_argnums=(0, 1),
                        out_shardings=(pool_ns, pool_ns))
                else:
                    self._compact_jit = jax.jit(
                        self._compact_fn, donate_argnums=(0, 1))

    # ---- compiled pieces -----------------------------------------
    def _chunk_fn(self, params, pool_k, pool_v, operands, rng, *extra,
                  lanes=None):
        """ONE prefill chunk: forward the buffer's ``chunk_ids`` as
        ``(1, chunk_tokens)`` at absolute positions ``start + [0, C)``,
        writing each layer's K/V into the slot's pages (row ``slot``
        of the buffer's ``tables``) and attending prior context through
        the pool. Shapes depend only on (chunk size, pool geometry,
        model) — ``start``/``s0``/``slot`` are traced VALUES of the
        buffer, so this compiles exactly once whatever prompt lengths
        arrive (the old ``_prefill_fn`` compiled per page COUNT).
        ``rng`` is the engine's key: split here, the new key goes back
        FIRST among the results.

        Numerics: the chunk's own tokens attend each other in compute
        dtype (the un-quantized intra-prompt attention the dense
        prefill runs) while prior pages are read back from the pool in
        page dtype (what decode reads) — the two flash-style partials
        merge with the standard online-softmax combine. Pad tokens in
        the final chunk write K/V at positions >= ``s0`` (or into the
        reserved null page past the table) which every mask excludes.
        Returns ``(key, picked token, pool_k, pool_v)`` — the pick is
        only meaningful on the chunk containing position ``s0 - 1``
        (the host uses it there; earlier chunks discard it). In
        PARALLEL mode the pick takes the slot's BRANCH KEY (row
        ``slot`` of the buffer's ``slot_keys``, not the step's
        split): the pick key is ``fold_in(key, s0)`` — a
        pure function of (branch key, context length), so a
        preempted-and-refolded branch resumes its sampling stream
        exactly — and the return grows the pick's logprob plus the
        final-position logits ``fork()`` samples sibling branches'
        first tokens from.

        **With ``lanes``** (a dict: structured mode's ``smask`` for
        all slots, the chunk's row among them; a look-ahead engine's
        ``prev``, the last program's ``tokens``; or empty) the
        decode lanes RIDE the chunk, on the SAME buffer: this
        is then the MIXED program, the second and last variant this
        function compiles to (``lanes`` is None or a dict: pytree
        structure, so static). The chunk's ``C`` tokens and the
        ``max_slots`` lanes are one ``(1, C + max_slots)`` token axis
        through every weight product — each weight is read once where
        a chunk program and a decode program would each read it — and
        only what is per sequence stays split (:func:`_ride`): the
        K/V writes and the two attentions, the conv state, the picks.
        Returns ``(key, chunk's token, lanes' tokens, pool_k, pool_v[,
        state])``; where the chunk is its prompt's last, its token
        also stands in its slot's lane of the lanes' tokens."""
        ops = self.operands.unpack(operands)
        start, s0, slot = ops["chunk"]
        # ONE split an iteration, of the key the last program left
        rng, sub = jax.random.split(rng)
        # the adapter stacks ride LAST (appended after every other
        # mode's), so they strip from the end FIRST; the chunk's
        # (1,) lane id is the seating slot's
        lora = None
        if self.lora:
            lora, extra = (extra[-4:], jax.lax.dynamic_slice_in_dim(
                ops["slot_lanes"], slot, 1)), extra[:-4]
        # a model with slot state: the state rides FIRST among the
        # trailing operands
        state = None
        if self.slot_state is not None:
            state, extra = extra[0], extra[1:]
        pieces = self._chunk_pieces(params, ops["chunk_ids"][None], start,
                                    s0, ops["tables"][slot], slot)
        if lanes is not None:
            pieces = _ride(pieces, self._lane_pieces(
                params, ops, lanes.get("prev")))
        x, pool_k, pool_v, state, moe_counts = self._layers(
            params, pieces, pool_k, pool_v, state, lora)
        # the head's one product: the prompt's last real row, and in
        # the mixed program the max_slots lanes under it
        logits = self._logits(params, pieces.rows(x))
        # structured mode: the seating slot's (1, vocab) legality row
        # (all-True when unconstrained — a bitwise no-op, so
        # unconstrained traffic stays token-exact) is the trailing
        # operand, or in the mixed program the slot's row of the
        # lanes' mask. The STASHED logits below stay unmasked: fork()
        # masks them itself with the START-state row so every
        # branch's first pick replays the independent-run
        # distribution.
        smask = smask1 = None
        if self.structured and lanes is not None:
            smask = lanes["smask"]
            smask1 = jax.lax.dynamic_slice_in_dim(smask, slot, 1)
        elif self.structured:
            smask1 = extra[0]
        if lanes is not None:
            # the iteration's ONE split, halved: the chunk's pick and
            # the lanes' each get a half
            sub, sub_lanes = jax.random.split(sub)
            tok = self._pick(sub, _mask_logits(logits[:1], smask1))
            tokens = self._pick(sub_lanes,
                                _mask_logits(logits[1:], smask))
            # a prompt's LAST chunk leaves its token in the slot's
            # lane: the next program's lanes may read it there before
            # the host has (a lane that decoded is never the chunk's)
            ends = start + self.chunk_tokens >= s0
            tokens = jnp.where(
                ends & (jnp.arange(tokens.shape[0]) == slot),
                tok[0], tokens)
            outs = (rng, tok, tokens, pool_k, pool_v)
            return outs if state is None else outs + (state,)
        if self.model is not None:
            return rng, self._pick(sub, logits), pool_k, pool_v, state, \
                moe_counts
        picked = _mask_logits(logits, smask1)
        if self.parallel:
            key = jax.random.fold_in(
                self._branch_keys(ops)[slot], s0)
            tok, lp = self._branch_pick(key[None], picked)
            return rng, tok, lp, logits, pool_k, pool_v
        return rng, self._pick(sub, picked), pool_k, pool_v

    @staticmethod
    def _branch_keys(ops: dict):
        """The slots' branch keys ``(max_slots, 2)`` as the uint32
        words they are (the buffer carries their bit pattern)."""
        return jax.lax.bitcast_convert_type(ops["slot_keys"], jnp.uint32)

    def _decode_fn(self, params, pool_k, pool_v, operands, rng, *extra):
        """One decode step over all slots. Signature shapes depend
        only on pool geometry — never on which slots are live or how
        pages are shared. The tables, and the modes' small operands
        (``work_*`` on the pallas backend: the compacted live-page
        walk from ``kernel_args()``; the slot-key table in
        parallel-sampling mode; the adapter lanes), are slices of the
        ONE operand buffer; ``rng`` is the engine's key, split here,
        and the new key goes back first among the results. The
        trailing operands exist only on their modes: the slot state,
        structured mode's mask, the adapter stacks, and LAST a
        look-ahead engine's ``prev`` (the last program's ``tokens``:
        ``_lane_pieces``)."""
        ops = self.operands.unpack(operands)
        rng, sub = jax.random.split(rng)
        # the adapter stacks strip from the END first (they append
        # last), leaving the earlier modes' front reads untouched
        lora = None
        if self.lora:
            lora, extra = (extra[-4:], ops["slot_lanes"]), extra[:-4]
        state = None
        if self.slot_state is not None:
            state, extra = extra[0], extra[1:]
        # structured: the (max_slots, vocab) legality mask
        smask = extra[0] if self.structured else None
        # a look-ahead engine: the last program's tokens, last of all
        prev = extra[-1] if self.looks_ahead else None
        pieces = self._lane_pieces(params, ops, prev)
        x, pool_k, pool_v, state, moe_counts = self._layers(
            params, pieces, pool_k, pool_v, state, lora)
        logits = self._logits(params, pieces.rows(x))
        if self.model is not None:
            return rng, self._pick(sub, logits), pool_k, pool_v, state, \
                moe_counts
        # constrained slots' rows knock illegal tokens to finfo.min;
        # unconstrained rows are all-True (bitwise no-op — greedy and
        # seeded sampling stay token-identical with the feature on)
        logits = _mask_logits(logits, smask)
        if self.parallel:
            # per-branch keys: fold each slot's branch key with its
            # context length (lengths + 1 — the pending token counts),
            # so branch b's token at depth d is a pure function of
            # (branch key, d, logits): token-exact vs an independent
            # single-slot run with the same key, preemption-invariant
            # (a refolded prompt re-samples with the same context
            # count), and graftlint's prng rule stays green (fold_in
            # is the sanctioned derivation)
            keys = jax.vmap(jax.random.fold_in)(
                self._branch_keys(ops), ops["lengths"] + 1)
            tokens, lps = self._branch_pick(keys, logits)
            return rng, tokens, lps, pool_k, pool_v
        return rng, self._pick(sub, logits), pool_k, pool_v

    def _chunk_pieces(self, params, ids, start, s0, table_row,
                      slot) -> "_Pieces":
        """What is the CHUNK's of a program (:class:`_Pieces`): its
        ``(1, C)`` tokens embedded, the write of their K/V into the
        slot's pages, their attention over the slot's pages and
        themselves, the seating slot's conv state, and the prompt's
        last real row for the head."""
        cfg, ps = self.cfg, self.page_size
        C = ids.shape[1]
        n_cp = C // ps
        mp = table_row.shape[0]
        head_dim = self.head_dim
        # per-shard head count: cfg.n_heads / tp local query heads
        # under the tp shard_map, == cfg.n_heads at tp=1 (python
        # arithmetic — the single-chip jaxpr is unchanged)
        n_heads_l = cfg.n_heads // self.tp
        positions = start + jnp.arange(C)

        if self.model is not None:
            x = self.model.embed(params, ids, dtype=self.compute_dtype)
        else:
            with jax.named_scope("embed"):
                x = L.embedding(params["wte"], ids,
                                dtype=self.compute_dtype)
                if "wpe" in params:
                    x = x + L.embedding(params["wpe"], positions,
                                        dtype=self.compute_dtype)[None]

        # chunk pages: table entries [start/ps, start/ps + n_cp); the
        # final chunk's pad pages (beyond the slot's allocation, or
        # past the table itself) divert to the reserved null page
        pidx = start // ps + jnp.arange(n_cp)
        w_pages = jnp.where(pidx < mp,
                            table_row[jnp.clip(pidx, 0, mp - 1)],
                            NULL_PAGE)
        local = jnp.arange(C)
        vis_chunk = (local[:, None] >= local[None, :])[None, None, None]

        def context(pages):
            # a slot's gathered pages as ONE sequence (1, mp * ps,
            # kv_heads, head_dim) per leaf
            return pool_map(lambda a: a.reshape(1, -1, *a.shape[2:]),
                            self._heads(pages))

        def write(k, v, pk, pv, li):
            # layer ``li`` of the pool: this chunk's K/V written
            g = k.shape[2]
            with jax.named_scope("kv_write"):
                new_k = write_rows(pk, (li, w_pages), self._page_rows(
                    k[0].reshape(n_cp, ps, g, head_dim), pk))
                new_v = write_rows(pv, (li, w_pages), self._page_rows(
                    v[0].reshape(n_cp, ps, g, head_dim), pv))
            return new_k, new_v

        def read(q, k, v, pk, pv, li):
            # the prior context (this slot's already-written pages,
            # masked to < start) and the chunk itself (compute-dtype
            # K/V — parity with the dense prefill's un-quantized
            # intra-prompt attention) are two flash-style partials
            # merged online-softmax style. The default engine WALKS
            # the slot's pages up to the chunk's position in one
            # kernel, the pool read in place (ops/chunk_walk.py); an
            # int8 pool, tp > 1 and the pallas backend gather the
            # slot's mp pages of this layer and attend them by XLA
            # (read after the write, so the update stays in place;
            # the chunk's own pages sit at positions >= start, which
            # the mask hides)
            if self.walks:
                prior = chunk_walk_attention(q, pk, pv, li, table_row, 0,
                                             start, kv_heads=k.shape[2])
            else:
                # the slot's table is sequential: entry i holds
                # positions i*ps + [0, ps)
                seen = jnp.arange(mp * ps) < start
                prior = _grouped_cache_attention(
                    q, context(gather_pages(pk, li, table_row)),
                    context(gather_pages(pv, li, table_row)),
                    seen[None, None, None, None], state=True)
            return _normalised(_merge_partials(
                prior, _grouped_cache_attention(q, k, v, vis_chunk,
                                                state=True)), q)

        if self.latent_dim is not None:
            # a latent pool: ONE row a token written, then the chunk's
            # tokens attend in the absorbed form through the paged
            # kernel, in blocks of LATENT_CHUNK_TOKENS on the seating
            # slot's table — its own rows are in it (read after the
            # write), so one rule (a key's position <= the query's)
            # covers prior context and the chunk alike. A block's walk
            # stops at its own last position: a chunk costs what its
            # prompt so far costs, not what the table could hold
            per_block = math.gcd(C, LATENT_CHUNK_TOKENS)
            n_blocks = C // per_block

            def write(k, v, pk, pv, li):
                with jax.named_scope("kv_write"):
                    pk = write_rows(pk, (li, w_pages), to_rows(
                        k[0].reshape(n_cp, ps, 1, -1), pk.shape[-1]))
                return pk, pv

            def read(q, k, v, pk, pv, li):
                with jax.named_scope("mla_chunk"):
                    o = latent_paged_attention(
                        q[0].reshape(n_blocks, -1, q.shape[-1]), pk, li,
                        jnp.broadcast_to(table_row, (n_blocks, mp)),
                        start + jnp.arange(n_blocks) * per_block,
                        jnp.ones((n_blocks,), bool),
                        n_heads=n_heads_l, value_dim=self.latent_dim)
                return o.reshape(1, C, n_heads_l, -1).astype(q.dtype)

        if self.ring is not None:
            write, read = self._chunk_kinds(write, table_row, slot,
                                            start, C)

        conv = None
        if self.slot_state is not None:
            # the prompt's real tokens in this chunk: the conv state
            # kept is that of the prompt's TRUE end, not of the padded
            # end of a partial last chunk
            n_real = jnp.clip(s0 - start, 0, C)

            def conv(z, w, st, li):
                rows = st["conv"]
                # a prefill from position 0 (a fresh seat, a preempted
                # request's replay) starts from zeros whatever the
                # slot's last tenant left
                prev = jnp.where(start == 0, 0, rows[li, slot])
                c, zz = L.short_conv(z, w, prev[None])
                keep = jax.lax.dynamic_slice_in_dim(
                    zz[0], n_real, w.shape[0] - 1, axis=0)
                return c, {"conv": rows.at[li, slot].set(
                    keep.astype(rows.dtype))}

        def carry(step, xs, st, li, name, scopes, in_place=None):
            # the seating slot's rows, zeros where the prefill starts
            # at position 0 (a fresh seat, a preempted request's
            # replay); the model hands back the state after the
            # prompt's last REAL token of the chunk
            with jax.named_scope(scopes[0]):
                a = st[name]
                mine = jnp.where(start == 0, 0, a[li, slot])[None]
                ys, new = step(xs, mine, (positions < s0)[None])
                return ys, {**st, name: a.at[li, slot].set(
                    new[0].astype(a.dtype))}

        def rows(x):
            # the row that picks: the prompt's position ``s0 - 1``
            # where this chunk holds it, (1, 1, d)
            return jax.lax.dynamic_slice_in_dim(
                x, jnp.clip(s0 - 1 - start, 0, C - 1), 1, axis=1)

        return _Pieces(x, positions[None], (positions < s0)[None],
                       write, read, conv, rows,
                       carry if self.slot_state is not None else None)

    def _lane_pieces(self, params, ops: dict, prev=None) -> "_Pieces":
        """What is the decode LANES' of a program (:class:`_Pieces`):
        every slot's last token embedded at its own depth ``(slots, 1,
        d)``, the write of its K/V at ``lengths``, the slots'
        attention over the pool, the live slots' conv state shifted.
        ``ops``: the operand buffer unpacked — the tables and, on the
        pallas backend, the compacted live-page walk ``work_*``.
        ``prev`` (a look-ahead engine): the ``tokens`` the last
        program returned, where a slot's last token is when the
        buffer's ``known`` says the host had not read it yet."""
        cfg, ps = self.cfg, self.page_size
        tables, lengths, refs, page_pos, last_ids = (
            ops[name] for name in ("tables", "lengths", "refs",
                                   "page_pos", "last_ids"))
        if prev is not None:
            last_ids = jnp.where(ops["known"] != 0, last_ids, prev)
        active = ops["active"] != 0
        n_slots = last_ids.shape[0]
        n_heads_l = cfg.n_heads // self.tp    # local heads (tp shard)

        if self.model is not None:
            x = self.model.embed(params, last_ids[:, None],
                                 dtype=self.compute_dtype)
        else:
            with jax.named_scope("embed"):
                x = L.embedding(params["wte"], last_ids[:, None],
                                dtype=self.compute_dtype)
                if "wpe" in params:
                    x = x + L.embedding(
                        params["wpe"], lengths,
                        dtype=self.compute_dtype)[:, None]

        # the default engine's lanes read by the live-page walk
        # (``self.walks``): each live slot its own table up to
        # ``lengths`` — the token this step writes lands AT
        # ``lengths`` and sees itself — and nothing else, so no
        # bookkeeping is shared by the layers. The pool SWEEP (an int8
        # pool, tp > 1) needs the page -> lane bookkeeping, shared by
        # every layer: each page carries reference LANES (refs row:
        # the slots holding it — prefix-shared pages list every
        # sharer; empty lanes divert to the trash segment n_slots;
        # without the prefix cache the lane axis is 1). A page's token
        # j holds absolute position page_pos*ps + j, visible to a lane
        # iff <= that slot's current length (a sharer mid-prompt never
        # sees past its own depth). The sweep reads ALL n_pages pages:
        # page 0, the reserved null page (dead-slot write target), is
        # never referenced, so its lanes are empty and its partials
        # land in the trash segment like any other empty lane's — a
        # static [1:] would keep the layer's slice of the stacked pool
        # from fusing into the sweep's read
        if self.decode_backend == "xla" and not self.walks:
            n_lanes = refs.shape[1]             # refs (P, R)
            seg = jnp.where(refs >= 0, refs, n_slots).reshape(-1)
            ref_c = jnp.clip(refs, 0, n_slots - 1)
            tok_pos = page_pos[:, None] * ps + jnp.arange(ps)[None, :]
            ref_len = jnp.where(refs >= 0, lengths[ref_c], -1)
            visible = tok_pos[:, None, :] <= ref_len[:, :, None]
            # (P, R, ps): lane r of page p sees token j

        # this step's write target per slot: the page holding position
        # ``lengths`` — ALWAYS private (shared pages are full prompt
        # prefixes and the match is capped before the last prompt
        # token, so the write offset sits past every shared page);
        # dead slots scribble the reserved null page
        w_page = tables[jnp.arange(n_slots), lengths // ps]
        w_page = jnp.where(active, w_page, 0)
        w_off = lengths % ps

        def write(k, v, pk, pv, li):
            # layer ``li`` of the pool: this step's K/V written
            with jax.named_scope("kv_write"):
                new_k = write_rows(pk, (li, w_page, w_off),
                                   self._page_rows(k[:, 0], pk))
                new_v = write_rows(pv, (li, w_page, w_off),
                                   self._page_rows(v[:, 0], pv))
            return new_k, new_v

        def read(q, k, v, pk, pv, li):
            # the slots' queries attended over the layer's pages
            if self.decode_backend == "pallas":
                # the in-kernel block-table walk: the kernel's
                # grid iterates the compacted live-page list and
                # fetches pages by table value, so the HBM stream
                # is the live context (shared pages once), not
                # the pool; (page, lane) partials merge per slot
                # in VMEM scratch with the same online-softmax
                # combine the sweep runs through segment ops
                o = paged_attention(
                    q, self._kernel_pages(pk, li),
                    self._kernel_pages(pv, li), ops["work_pages"],
                    ops["work_refs"], ops["work_pos"], lengths,
                    page_size=ps)
                return o.astype(q.dtype)
            if self.walks:
                # each live slot's pages up to its own position, the
                # stacked pool read in place (kv_pages.walk_attention)
                return walk_attention(q, pk, pv, li, tables,
                                      jnp.zeros_like(lengths), lengths,
                                      active, k.shape[2])
            # the pool sweep: each page attends the queries of ALL
            # its reference lanes (a gather of the TINY q tensor
            # into (P, R, H, Dh) — the layer's pages are read in
            # place, ONCE, in the pool's own layout
            # (kv_pages.sweep_attention); lanes ride the query
            # axis so sharing multiplies only the small-side
            # compute, never the HBM stream), then (page, lane)
            # partials merge per slot via the online-softmax
            # combine
            q_lanes = q[:, 0][ref_c]        # (P, R, H, Dh)
            o_p, m_p, l_p = sweep_attention(
                q_lanes, layer_pages(pk, li), layer_pages(pv, li),
                visible, k.shape[2])
            # o (P, R, g, rep, Dh); m/l (P, g, rep, R): flatten
            # the (page, lane) pairs into one segment axis
            n_pp = o_p.shape[0]
            o_f = o_p.reshape(n_pp * n_lanes, *o_p.shape[2:])
            m_f = jnp.moveaxis(m_p, -1, 1).reshape(
                n_pp * n_lanes, *m_p.shape[1:3])
            l_f = jnp.moveaxis(l_p, -1, 1).reshape(
                n_pp * n_lanes, *l_p.shape[1:3])
            m_s = jax.ops.segment_max(m_f, seg,
                                      num_segments=n_slots + 1)
            w = jnp.exp(m_f - m_s[seg])
            l_s = jax.ops.segment_sum(l_f * w, seg,
                                      num_segments=n_slots + 1)
            o_s = jax.ops.segment_sum(o_f * w[..., None], seg,
                                      num_segments=n_slots + 1)
            o = o_s[:n_slots] / jnp.maximum(
                l_s[:n_slots], 1e-30)[..., None]
            o = o.reshape(n_slots, 1, n_heads_l, self.head_dim)
            return o.astype(q.dtype)

        if self.latent_dim is not None:
            # a latent pool: the slot's one row written, and every
            # slot's own pages attended in the absorbed form by the
            # paged kernel, the pool read in place — a row serves 64
            # query heads, so per-page partials would outweigh the
            # pages (ops/latent_paged_attention.py)
            def write(k, v, pk, pv, li):
                with jax.named_scope("kv_write"):
                    pk = write_rows(pk, (li, w_page, w_off),
                                    to_rows(k[:, 0], pk.shape[-1]))
                return pk, pv

            def read(q, k, v, pk, pv, li):
                with jax.named_scope("mla_sweep"):
                    o = latent_paged_attention(
                        q[:, 0], pk, li, tables, lengths, active,
                        n_heads=n_heads_l, value_dim=self.latent_dim)
                return o[:, None].astype(q.dtype)

        if self.ring is not None:
            write, read = self._lane_kinds(write, read, lengths, active)

        def conv(z, w, st, li):
            # live slots shift their state by this step's input;
            # dead and mid-prefill slots keep theirs
            rows = st["conv"]
            prev = rows[li]                 # (slots, K-1, d)
            c, zz = L.short_conv(z, w, prev)
            new = jnp.where(active[:, None, None],
                            zz[:, 1:].astype(rows.dtype), prev)
            return c, {"conv": rows.at[li].set(new)}

        def carry(step, xs, st, li, name, scopes, in_place=None):
            # only the live slots' rows change — a slot between two of
            # its prompt's chunks, or a dead one, keeps what it had: the
            # model's own step on the array reads and writes those rows
            # alone; else every slot's rows of layer ``li`` are loaded
            # and the live ones stored back
            with jax.named_scope(scopes[1]):
                a = st[name]
                if in_place is not None:
                    ys, a = in_place(xs, a, li, active)
                    return ys, {**st, name: a}
                rows = a[li]
                ys, new = step(xs, rows, active[:, None])
                live = active.reshape(-1, *(1,) * (rows.ndim - 1))
                return ys, {**st, name: a.at[li].set(
                    jnp.where(live, new.astype(a.dtype), rows))}

        stateful = self.slot_state is not None
        return _Pieces(x, lengths[:, None], active[:, None], write,
                       read, conv if stateful else None, lambda x: x,
                       carry if stateful else None)

    def _chunk_kinds(self, write_full, table_row, slot, start, C: int):
        """A chunk's ``write`` / ``read`` over pools of two kinds
        (``pool_k`` / ``pool_v``: ``{kind: array}``; ``kind`` static in
        the model's layer stack). A FULL layer is written as every
        paged layer is (``write_full``, on that kind's pool); a WINDOW
        layer into the slot's ring. Both read their prior context by
        the chunk walk (ops/chunk_walk.py), up to the chunk's own
        position: a full layer its table, a window layer its ring as a
        table in position order from the window's lower edge for the
        chunk's first query — sequence page ``p`` at ring page ``slot *
        ring + p % ring`` (``kv_pages.ring_table``), visible where ``0
        <= q_pos - k_pos < window``. A chunk costs what its prompt so
        far costs (a window layer: at most its window), never what the
        table could hold. Both merge the prior context with the chunk's
        own compute-dtype K/V, as the one-kind chunk read does."""
        ps, ring, window = self.page_size, self.ring, self.window
        n_cp = C // ps
        kv_heads = self.cfg.kv_heads
        local = jnp.arange(C)
        back = local[:, None] - local[None, :]
        # the chunk's own keys: causal, and inside the window
        vis_own = {"full": back >= 0,
                   "window": (back >= 0) & (back < window)}
        w_ring = slot * ring + (start // ps + jnp.arange(n_cp)) % ring
        # the ring's pages from the window's lower edge on, for a query
        # at ``start`` (``ring_table`` lays out slot 0's: moved to ours)
        ring_row, edge = ring_table(start[None], ring, ps, window)
        walked = {"full": (table_row, 0, None),
                  "window": (ring_row[0] + slot * ring, edge[0], window)}

        def write(k, v, pk, pv, li, kind):
            if kind == "full":
                fk, fv = write_full(k, v, pk["full"], pv["full"], li)
                return {**pk, "full": fk}, {**pv, "full": fv}
            rows = lambda t, pool: self._page_rows(
                t[0].reshape(n_cp, ps, kv_heads, -1), pool)
            with jax.named_scope("kv_write"):
                wk = write_rows(pk["window"], (li, w_ring),
                                rows(k, pk["window"]))
                wv = write_rows(pv["window"], (li, w_ring),
                                rows(v, pv["window"]))
            return {**pk, "window": wk}, {**pv, "window": wv}

        def read(q, k, v, pk, pv, li, kind):
            table, base, seen = walked[kind]
            prior = chunk_walk_attention(q, pk[kind], pv[kind], li, table,
                                         base, start, kv_heads=kv_heads,
                                         window=seen)
            return _normalised(_merge_partials(
                prior, _grouped_cache_attention(
                    q, k, v, vis_own[kind][None, None, None], state=True)),
                q)

        return write, read

    def _lane_kinds(self, write_full, read_full, lengths, active):
        """The decode lanes' ``write`` / ``read`` over pools of two
        kinds. A FULL layer is what every paged layer is
        (``write_full`` / ``read_full`` on that kind's pool: the block
        tables' pages, the live-page walk). A WINDOW layer writes the
        slot's row at its ring place — a slot that is not decoding
        writes nowhere: the ring pool has no null page, its index is
        past the pool and the update is dropped — and walks its ring
        as a table in position order: the pages from the window's
        lower edge to the slot's newest, ``slot * ring + p % ring``
        for sequence page ``p`` (``kv_pages.ring_positions``'s
        arithmetic), visible where ``0 <= q_pos - k_pos < window``;
        pages wholly below the edge are not read."""
        ps, ring, window = self.page_size, self.ring, self.window
        n_slots = lengths.shape[0]
        kv_heads = self.cfg.kv_heads
        w_page = jnp.where(
            active, jnp.arange(n_slots) * ring + (lengths // ps) % ring,
            n_slots * ring)
        w_off = lengths % ps
        # the ring's pages from the window's lower edge on
        table, edge = ring_table(lengths, ring, ps, window)

        def write(k, v, pk, pv, li, kind):
            if kind == "full":
                fk, fv = write_full(k, v, pk["full"], pv["full"], li)
                return {**pk, "full": fk}, {**pv, "full": fv}
            put = lambda pool, t: pool.at[li, w_page, w_off].set(
                self._page_rows(t[:, 0], pool).astype(pool.dtype),
                mode="drop")
            with jax.named_scope("kv_write"):
                return ({**pk, "window": put(pk["window"], k)},
                        {**pv, "window": put(pv["window"], v)})

        def read(q, k, v, pk, pv, li, kind):
            if kind == "full":
                return read_full(q, k, v, pk["full"], pv["full"], li)
            return walk_attention(q, pk["window"], pv["window"], li,
                                  table, edge, lengths, active,
                                  kv_heads, window=window)

        return write, read

    def _layers(self, params, pieces: "_Pieces", pool_k, pool_v, state,
                lora=None):
        """The layer stack over ``pieces.x``, the pool (and a model's
        slot state) carried and updated in place: ``_block_core`` over
        the stacked blocks for a GPT, the served model's own
        ``layers`` otherwise. ``lora``: ``(the four adapter stacks,
        lane ids)`` on a lora engine. Returns ``(x, pool_k, pool_v,
        state, tokens per expert or None)``."""
        cfg = self.cfg

        def attend(q, k, v, pk, pv, li, **kind):
            # layer ``li`` of the pool: written, then read (after the
            # write, so the update stays in place). ``kind``: which of
            # two pools, where a model's layers cache in two ways (one
            # whose layers all hold every token has one pool)
            if self.ring is None:
                kind = {}
            pk, pv = pieces.write(k, v, pk, pv, li, **kind)
            return pieces.read(q, k, v, pk, pv, li, **kind), (pk, pv)

        if self.model is not None:
            # the slot-state pieces the model's stack names: LFM2 takes
            # ``conv``, Qwen3-Next ``carry``
            takes = inspect.signature(self.model.layers).parameters
            x, (pool_k, pool_v), state, moe_counts = self.model.layers(
                params, pieces.x, cfg, positions=pieces.positions,
                attend=lambda q, k, v, cache, li, **kind: attend(
                    q, k, v, *cache, li, **kind),
                cache=(pool_k, pool_v), state=state, valid=pieces.valid,
                **{name: getattr(pieces, name) for name in ("conv", "carry")
                   if name in takes})
            return x, pool_k, pool_v, state, moe_counts
        lora_w, lane_ids = lora if lora is not None else (None, None)

        def layer(x, pk, pv, bp, li, lw):
            x, _, (pk, pv) = _block_core(
                bp, x, cfg,
                lambda q, k, v: attend(q, k, v, pk, pv, li),
                capacity_factor=max(cfg.capacity_factor,
                                    float(cfg.n_experts)),
                positions=pieces.positions,     # per-slot rope depth
                tp_attn=self._tp_core,
                lora=(lw, lane_ids) if self.lora else None)
            return x, pk, pv

        x, pool_k, pool_v = scan_layers(layer, pieces.x, pool_k, pool_v,
                                        params["blocks"], lora_w)
        return x, pool_k, pool_v, state, None

    def _logits(self, params, rows):
        """The head over ``rows (n, 1, d)``: logits ``(n, vocab)``."""
        if self.model is not None:
            return self.model.head(params, rows, self.cfg)[:, 0]
        return _lm_head(params, rows)[:, 0]

    # ---- between the model's (..., kv_heads, head_dim) and the
    # pool's rows (kv_pages.make_pool owns the layout) ----------------
    def _page_rows(self, x, pool):
        """New K or V ``(..., kv_heads, head_dim)`` as what
        ``kv_pages.write_rows`` stores in ``pool``:
        rows of the pool's width, or for an int8 pool the quantized
        ``(rows, scales)`` pair (``_quantize_kv``, as the dense
        quantized cache)."""
        if isinstance(pool, tuple):
            return quantized_rows(*_quantize_kv(x), pool[0].shape[-1])
        return to_rows(x, pool.shape[-1])

    def _heads(self, pages):
        """A FEW gathered pages ``(n, page_size, ...)`` per leaf back
        in ``(n, page_size, kv_heads, head_dim)`` (int8: the scales
        get their trailing 1 back): the form ``models/gpt.py``'s
        attention core and the pallas kernel take."""
        head_dim = self.head_dim
        kv_heads = self.cfg.kv_heads // self.tp
        if isinstance(pages, tuple):
            return (from_rows(pages[0], kv_heads, head_dim),
                    pages[1][..., None])
        return from_rows(pages, kv_heads, head_dim)

    def _kernel_pages(self, pool, li):
        """The pallas kernel's operand: one layer's pool split back
        into heads. The kernel's blocks are ``(page_size, kv_heads,
        head_dim)``, so this branch pays a relayout of the layer's
        pool at its own boundary (PERF.md section 7); the default
        backend never calls it."""
        return self._heads(layer_pages(pool, li))

    def _cow_fn(self, pool_k, pool_v, src_pages, dst_pages):
        """The fork-time copy-on-write tail copy: pool page
        ``dst_pages[i]`` becomes a byte-copy of ``src_pages[i]``
        across every layer (both pool halves; int8 pools copy values
        AND scales). Fixed ``(max_slots,)`` id vectors padded with
        null→null self-copies, so one executable serves any fork
        fan-out — fork churn compiles nothing after the first."""

        def copy(pool):
            def one(a):
                return a.at[:, dst_pages].set(a[:, src_pages])
            return (tuple(one(x) for x in pool)
                    if isinstance(pool, tuple) else one(pool))

        return copy(pool_k), copy(pool_v)

    def _compact_fn(self, pool_k, pool_v, operands, src_off):
        """Post-acceptance K/V compaction for TREE speculative
        decoding: the accepted root-to-leaf path's nodes sit at their
        tree STORAGE offsets (``lengths + node_id``), which are not
        contiguous when a side branch won — copy each accepted node's
        rows down to the contiguous positions the advanced ``lengths``
        will expose (``src_off[slot, j]`` = the storage offset whose
        K/V belongs at offset ``j``; identity rows are no-op copies,
        inactive slots divert to the null page). Functional gathers
        read every source before any write lands, so overlapping
        moves (always downward — node ids exceed their path index)
        are safe. ``operands`` is the buffer the verify step took,
        already on the device: the tables as they stood before the
        advance."""
        ops = self.operands.unpack(operands)
        tables, lengths = ops["tables"], ops["lengths"]
        active = ops["active"] != 0
        ps = self.page_size
        n_slots, S = src_off.shape
        mp = tables.shape[1]
        rows = jnp.arange(n_slots)[:, None]

        def locate(pos):
            pidx = pos // ps
            page = tables[rows, jnp.clip(pidx, 0, mp - 1)]
            page = jnp.where((pidx < mp) & active[:, None], page,
                             NULL_PAGE)
            return page, pos % ps

        dst_page, dst_off = locate(lengths[:, None] + jnp.arange(S))
        src_page, src_sub = locate(lengths[:, None] + src_off)

        def copy(pool):
            def one(a):
                moved = a[:, src_page, src_sub]
                return a.at[:, dst_page, dst_off].set(moved)
            return (tuple(one(x) for x in pool)
                    if isinstance(pool, tuple) else one(pool))

        return copy(pool_k), copy(pool_v)

    # ---- the host spill tier -------------------------------------
    def _spill_fetch(self, p: int) -> dict:
        """Demotion payload for pool page ``p``: int8 K/V values plus
        float32 per-(token, head) scales across every layer, as host
        numpy arrays keyed like the staging buffers. This is the
        spill tier's ONE deliberate device->host read, and it runs on
        the ADMISSION cadence (an eviction inside ``seat``), never
        inside a decode step. int8 pools ship their stored payload
        verbatim (a lossless round-trip); wide pools quantize here,
        mirroring ``_quantize_kv``."""
        head_dim = self.cfg.d_model // self.cfg.n_heads

        def page(pool):
            # (n_layers, page_size, ...) per leaf, heads split on the
            # host: the payload's shapes do not follow the pool's
            rows = pool_map(
                lambda a: np.asarray(jax.device_get(a[:, p])), pool)
            if self.quantized:
                return (from_rows(rows[0], self.cfg.kv_heads, head_dim,
                                  self.tp),
                        rows[1][..., None].astype(np.float32))
            return _quantize_page_np(from_rows(
                rows.astype(np.float32), self.cfg.kv_heads, head_dim,
                self.tp))

        (k, ks), (v, vs) = page(self.pool["k"]), page(self.pool["v"])
        self.spills += 1
        return {"k": k, "k_scale": ks, "v": v, "v_scale": vs}

    def _promote_fn(self, pool_k, pool_v, k_q, k_s, v_q, v_s, dst):
        """The host->HBM promotion write: staged pages land at pool
        ids ``dst`` across every layer. Fixed shapes — the ``(lanes,
        n_layers, page_size, kv_heads, head_dim)`` staging block plus
        a ``(lanes,)`` id vector, pad lanes targeting the reserved
        null page (junk on page 0 is masked everywhere — the cow
        pad's contract) — so promotion churn compiles exactly ONE
        executable. The pools are donated and rebound by the caller:
        any chunk or decode step dispatched after a promotion reads
        the rebound arrays, so ordering is a device-side data
        dependency and the host never blocks on the stream."""

        def write(pool, q, s):
            vals = jnp.moveaxis(q, 0, 1)   # (L, lanes, ps, H, D)
            scl = jnp.moveaxis(s, 0, 1)
            if isinstance(pool, tuple):
                rows = quantized_rows(vals, scl, pool[0].shape[-1])
            else:
                rows = to_rows(vals.astype(jnp.float32) * scl,
                               pool.shape[-1])
            return write_rows(pool, (slice(None), dst), rows)

        return write(pool_k, k_q, k_s), write(pool_v, v_q, v_s)

    def issue_promotions(self) -> int:
        """Dispatch every queued host->HBM promotion. The batcher
        calls this right before chunk issue, so a host hit's TTFT
        pays the H2D stream time while the first non-dependent chunk
        overlaps it; ``prefill_step`` also fires it defensively for
        directly-driven engines. Payloads stream through the fixed
        staging buffers in ``lanes``-sized groups — the same compiled
        write every group — and the promoted keys then re-enter the
        HBM prefix index at their seated table positions. Returns the
        number of pages promoted (host integers; the dispatch itself
        is async)."""
        if not self.host_spill:
            return 0
        # lazy ONE-time build (first promotion of the engine's life):
        # fixed staging shapes mean this is the only compile ever
        if self._promote_jit is None:
            self._promote_jit = jax.jit(self._promote_fn,
                                        donate_argnums=(0, 1))
        n = 0
        for p in self._pending:
            work = p.pop("promote", None)
            if not work:
                continue
            keys, payloads = work["keys"], work["payloads"]
            start_idx = work["start_idx"]
            row = self.tables.tables[p["slot"]]
            lanes = self._promote_lanes
            with span("serving_promote"):
                for g in range(0, len(keys), lanes):
                    grp = payloads[g:g + lanes]
                    dst = np.zeros(lanes, np.int32)  # pad -> null
                    for i, pl in enumerate(grp):
                        for name in ("k", "k_scale", "v", "v_scale"):
                            self._stage[name][i] = pl[name]
                        dst[i] = row[start_idx + g + i]
                        self.promoted_bytes += sum(
                            int(a.nbytes) for a in pl.values())
                    pool_k, pool_v = self._promote_jit(
                        self.pool["k"], self.pool["v"],
                        jax.device_put(self._stage["k"]),
                        jax.device_put(self._stage["k_scale"]),
                        jax.device_put(self._stage["v"]),
                        jax.device_put(self._stage["v_scale"]),
                        jnp.asarray(dst))
                    self.pool = {"k": pool_k, "v": pool_v}
            self.tables.promote_keys(p["slot"], keys, start_idx)
            self.promotions += len(keys)
            n += len(keys)
        return n

    def export_pages(self, slot: int,
                     prompt_ids: np.ndarray) -> list[tuple[bytes, dict]]:
        """Read the slot's leading FULL prompt pages out as
        ``(chain_key, payload)`` pairs — the demotion payload
        (:meth:`_spill_fetch`: int8 K/V + fp32 per-(token, head)
        scales, lossless for int8 pools), keyed by the same
        content-hash chain the prefix index and host pool use. This
        is the disaggregation export seam: a prefill host calls it
        once per finished prefill and ships the pairs over the wire;
        the decode host drops them into its ``HostPagePool`` and its
        next ``admit_begin`` seats them through the fixed-shape
        donated promotion lane (zero new compiles). The ``(len - 1)
        // page_size`` cap matches the matcher's — the final token's
        page is never exported, so the importer always re-runs at
        least one prefill chunk and samples the first token itself
        (the spill tier's parity contract). Call it BEFORE
        :meth:`retire` frees the pages. Deliberate device->host
        reads on the per-REQUEST cadence — never inside a decode
        step."""
        prompt = np.ascontiguousarray(prompt_ids,
                                      np.int32).reshape(-1)
        limit = (len(prompt) - 1) // self.page_size
        row = self.tables.tables[slot]
        out: list[tuple[bytes, dict]] = []
        for i in range(limit):
            p = int(row[i])
            if p == NULL_PAGE:
                break
            key = prompt[:(i + 1) * self.page_size].tobytes()
            payload = self._spill_fetch(p)
            self.spills -= 1  # _spill_fetch counts demotions; an
            # export is not a demotion (the page stays seated)
            self.exported_pages += 1
            self.exported_bytes += sum(
                int(a.nbytes) for a in payload.values())
            out.append((key, payload))
        return out

    # ---- host lifecycle ------------------------------------------
    def can_admit(self, prompt_ids: np.ndarray) -> bool:
        """Dry-run of :meth:`admit_begin`'s checks (slot, horizon, and
        pages net of the prefix-cache discount) without seating —
        for external drivers that want to peek before committing.
        Takes the prompt TOKEN ARRAY (matching is content-based); the
        pre-PR-4 scalar prompt_len form is rejected loudly rather
        than silently reinterpreted as a one-token prompt."""
        if np.asarray(prompt_ids).ndim == 0:
            raise TypeError(
                "can_admit takes the prompt token array (prefix "
                "matching is content-based), not its length")
        prompt = np.ascontiguousarray(prompt_ids, np.int32).reshape(-1)
        s0 = len(prompt)
        if self.tables.free_slot() is None \
                or not 0 < s0 < self.cfg.seq_len:
            return False
        return (self.tables.pages_for(s0)
                - len(self.tables.match_pages(prompt))
                <= self.tables.n_available_pages)

    def admit_begin(self, prompt_ids: np.ndarray, seed: int | None = None,
                    branch: int = 0,
                    adapter_lane: int = 0) -> int | None:
        """Seat one request: map cached prefix pages into its block
        table, allocate private pages for the rest, and queue its
        chunked prefill. Returns the slot, or None when no slot or
        not enough pages (the batcher keeps it queued). The request
        decodes only after :meth:`prefill_step` drains its chunks.

        ``seed``/``branch`` matter only in parallel-sampling mode:
        the slot's sampling key becomes ``fold_in(PRNGKey(seed),
        branch)`` — branch 0 for fresh requests, b for a preempted
        fork branch re-seating on its own (its stream must resume
        token-exact), and the contract the parity tests drive: branch
        b of an n-way fork equals an independent run admitted with
        the same seed and ``branch=b``.

        ``adapter_lane`` (lora mode) is the slot's device lane from
        ``AdapterRegistry.acquire`` — 0 (the zero adapter) serves
        base-model traffic; the caller holds the pin until retire."""
        if adapter_lane and not self.lora:
            raise ValueError(
                f"adapter_lane={adapter_lane} on an engine without "
                "lora: build with lora_rank/lora_max_live")
        if not 0 <= adapter_lane <= self.lora_max_live:
            raise ValueError(
                f"adapter_lane {adapter_lane} out of range "
                f"[0, {self.lora_max_live}]")
        prompt = np.ascontiguousarray(prompt_ids, np.int32).reshape(-1)
        s0 = len(prompt)
        slot = self.tables.free_slot()
        if slot is None or not 0 < s0 < self.cfg.seq_len:
            return None
        # hopeless-case bail BEFORE the index walk: even a full
        # prefix hit leaves at least the last page to allocate (the
        # match cap), so with nothing available skip the quadratic
        # prompt-hashing entirely — this is the branch a queue-head
        # request under total pool exhaustion retries every
        # scheduling iteration
        if self.tables.pages_for(s0) - (s0 - 1) // self.page_size \
                > self.tables.n_available_pages:
            return None
        # ONE index walk serves both the capacity check and the
        # seating (the walk hashes prompt-prefix bytes per page —
        # quadratic in prompt length, so never repeated within an
        # attempt; a failed attempt that got past the bail above may
        # re-walk on retry, which only happens when a seat is
        # plausibly one retire away). With the spill tier on, the
        # walk continues past the HBM chain into the host pool —
        # host-tier matches still need pool pages ALLOCATED (only HBM
        # hits discount the capacity math), they just skip the
        # prefill FLOPs: their content arrives over PCIe instead.
        matched, host_keys = self.tables.match_tiered(prompt)
        n_matched = len(matched)
        if self.tables.pages_for(s0) - n_matched \
                > self.tables.n_available_pages:
            return None
        # pop the host payloads BEFORE seating: seat() itself can
        # evict-demote under pressure, and a demotion landing in the
        # host pool could LRU-evict the very pages just matched. Once
        # popped they are promotion-or-bust — re-put on seat failure
        # (below) or on a retire that beats the promotion.
        payloads: list[dict] = []
        for i, key in enumerate(host_keys):
            pl = self.tables.host_pool.pop(key)
            if pl is None:           # defensive: cut the chain at a gap
                host_keys = host_keys[:i]
                break
            payloads.append(pl)
        try:
            self.tables.seat(slot, prompt, matched=matched)
        except RuntimeError:
            # the quick check above counts CACHED matched pages as
            # available capacity, but mapping them makes them
            # un-evictable — under exactly-full pool pressure the
            # private-tail allocation can then come up short. seat()
            # rolled the shares back (the matched pages re-enter the
            # LRU), so the request just stays queued until retires
            # return pages — the same contract as any other
            # not-enough-pages admission.
            for key, pl in zip(host_keys, payloads):
                self.tables.host_pool.put(key, pl)
            return None
        self.prefix_lookup_pages += (s0 - 1) // self.page_size
        self.prefix_hit_pages += n_matched
        n_host = len(host_keys)
        self.host_hit_pages += n_host
        if self.parallel:
            # admission-cadence host jax (never per step): the base
            # key identifies the REQUEST, the folded key its branch
            base = np.asarray(jax.random.PRNGKey(
                0 if seed is None else int(seed) & 0x7fffffff))
            self._base_keys[slot] = base
            self._slot_keys[slot] = np.asarray(
                jax.random.fold_in(base, int(branch)))
            self._branch_of[slot] = int(branch)
        if self._drafter is not None:
            # the prompt seeds the slot's lookup stream — prompt
            # tokens are exactly what prompt-lookup drafting mines
            self._drafter.begin(slot, prompt)
        # chunking starts past BOTH tiers' matches (page-aligned by
        # construction) — the cache hit's whole point is skipping the
        # matched pages' chunks: HBM hits are mapped shares, host
        # hits get filled by the promotion stream before the first
        # chunk issues; pad the tail to a whole chunk
        self._slot_lanes[slot] = int(adapter_lane)
        start = (n_matched + n_host) * self.page_size
        n_chunks = -(-(s0 - start) // self.chunk_tokens)
        padded = np.zeros(start + n_chunks * self.chunk_tokens,
                          np.int32)
        padded[:s0] = prompt
        pend = {"slot": slot, "ids": padded, "s0": s0, "start": start}
        if host_keys:
            pend["promote"] = {"keys": host_keys, "payloads": payloads,
                               "start_idx": n_matched}
        self._pending.append(pend)
        return slot

    @property
    def has_pending(self) -> bool:
        return bool(self._pending)

    @property
    def has_lanes(self) -> bool:
        """Whether a decode step launched now would decode anything:
        a slot is active and not held (:meth:`hold`)."""
        return bool(self.tables.active.any())

    @property
    def pending_chunk_count(self) -> int:
        """Prefill chunks still queued across every in-flight
        admission — the "work ahead of you" term in the front door's
        TTFT slack estimate (host integers only)."""
        return sum(-(-(p["s0"] - p["start"]) // self.chunk_tokens)
                   for p in self._pending)

    @property
    def pending_slots(self) -> list[int]:
        """Slots with an in-flight chunked prefill, oldest first —
        cross-run residue when a driver loop aborts mid-prefill; the
        batcher cancels them before starting a fresh trace."""
        return [p["slot"] for p in self._pending]

    def prefill_step(self) -> tuple[int, int] | None:
        """Run ONE chunk of the oldest queued prefill (no-op None when
        idle). Returns ``(slot, first_token)`` when that request's
        prefill completed — the slot is then activated for decode and
        its full prompt pages registered in the prefix index — else
        None."""
        if not self._pending:
            return None
        p = self._pending[0]
        # the host's work before the program, under its own span, so
        # a traced idle gap in front of a chunk has a name
        with span("prefill_args"):
            self._fill_chunk(p)
            operands = self._put_operands() + self._state_operand()
            if self.structured:
                # the seating slot's legality row masks the
                # first-token pick in-chunk (all-True when the request
                # is unconstrained — exact no-op)
                operands += (self._put(
                    self._cursors.mask[p["slot"]][None]),)
            operands += self._lora_operands()
        # span: host wall time in the event log + the same label on a
        # captured device trace (observability/spans.py); no-op when
        # telemetry is disabled
        with span("serving_prefill_chunk"):
            outs = self._chunk_jit(
                self.params, self.pool["k"], self.pool["v"], *operands)
        lp = logits = None
        if self.parallel:
            self._rng, tok, lp, logits, pool_k, pool_v = outs
        elif self.model is not None:
            # the chunk's expert counts stay on the device: reading
            # them would wait for a program this call only dispatched
            self._rng, tok, pool_k, pool_v, self.slot_state, _ = outs
        else:
            self._rng, tok, pool_k, pool_v = outs
        self.pool = {"k": pool_k, "v": pool_v}
        self._count_rows(None, chunk=p)
        self._count_chunk_pages(p)
        self._count_state(None, chunk=p)
        if not self._chunk_issued(p):
            return None
        self.tables.activate(p["slot"])
        # the prompt's LAST chunk: its token is read back here, which
        # waits for the chunk program the span above only dispatched —
        # a device wait, named so that it is not taken for host work
        with span("prefill_finish"):
            if self.parallel:
                # ONE batched device->host sync; the final-position
                # logits are what fork() samples sibling branches'
                # first tokens from. The stash is consumed at the fork
                # (or by take_first_logprob for requests that never
                # fork), so it lives one scheduling iteration — the
                # one (vocab,)-row host copy per ADMISSION is the
                # price of not threading a will-fork hint through the
                # admission surface.
                tok, lp, logits = jax.device_get((tok, lp, logits))
                self._fork_state[p["slot"]] = {
                    "logits": np.asarray(logits[0]),
                    "logprob": float(np.asarray(lp)[0]),
                    "s0": int(p["s0"])}
            return self._prefill_done(p, int(np.asarray(tok)[0]))

    def _fill_chunk(self, p: dict) -> None:
        """The next chunk of the pending prefill ``p``, written into
        its slices of the operand buffer: the ids and ``(start, s0,
        slot)``. The slot's table row, its adapter lane and (parallel
        mode) its BRANCH KEY are read by ``slot`` inside the program:
        the chunk folds the key with s0, so the first token is a pure
        function of (branch key, prompt length) — never of traffic
        order."""
        if self.host_spill:
            # defensive for directly-driven engines: the batcher
            # already promoted before chunk issue; a chunk must never
            # attend host-matched pages that were not written yet
            self.issue_promotions()
        start = p["start"]
        self._op["chunk_ids"][:] = p["ids"][start:start + self.chunk_tokens]
        self._op["chunk"][:] = (start, p["s0"], p["slot"])

    def _put(self, host: np.ndarray) -> jax.Array:
        """ONE host-to-device transfer of a step operand, counted
        (``operand_puts``, ``serving_operand_puts_total``)."""
        self.operand_puts += 1
        get_registry().counter(
            "serving_operand_puts_total",
            "host-to-device transfers issued for step operands: the "
            "packed buffer, and a mode's large operand beside it").inc()
        return jax.device_put(host, self._rep)

    def _put_operands(self) -> tuple:
        """The iteration's ONE transfer: the packed buffer as it
        stands, and with it the key the last program left on the
        device — every program's two operands after the pool. What
        is put is a snapshot (a memcpy of a few KB): the host writes
        the buffer again before a program that was only dispatched
        (a lone chunk) has run, and the CPU backend aliases an
        aligned numpy array where a device copies it."""
        self.tables.pack()
        return self._put(self.operands.host.copy()), self._rng

    def _state_operand(self) -> tuple:
        """A model's slot state, first among the trailing operands
        (device-resident, donated); empty for a model without."""
        return () if self.slot_state is None else (self.slot_state,)

    def _chunk_issued(self, p: dict) -> bool:
        """Book one issued chunk of the pending prefill ``p``; True
        when it was the prompt's last (``p`` then leaves the queue)."""
        self.prefill_chunks += 1
        p["start"] += self.chunk_tokens
        if p["start"] < p["s0"]:
            return False
        self._pending.pop(0)        # p is the oldest
        return True

    def _prefill_done(self, p: dict, first: int) -> tuple[int, int]:
        """A prompt's last chunk has given its token (its slot was
        activated when the chunk was issued): the host knows the
        slot's last token now, its full prompt pages enter the prefix
        index."""
        self.tables.last_ids[p["slot"]] = first
        self.tables.register_prefix(p["slot"], p["ids"][:p["s0"]])
        if self._drafter is not None:
            self._drafter.observe(p["slot"], [first])
        if self.structured:
            # same hook site as the drafter: the cursor advances
            # on the accepted first token (fork() REBASES
            # children, so a parent about to fork is already
            # correct — branch 0's stream keeps this very token)
            self._cursors.observe(p["slot"], [first])
        return p["slot"], first

    def admit(self, prompt_ids: np.ndarray, seed: int | None = None,
              branch: int = 0) -> tuple[int, int] | None:
        """One-shot admission (tests and simple drivers): seat the
        request and drain prefill chunks until ITS first token lands;
        returns ``(slot, first_token)`` or None. Drains any older
        pending prefills along the way (their slots activate with
        their first tokens recorded in the tables)."""
        slot = self.admit_begin(prompt_ids, seed=seed, branch=branch)
        if slot is None:
            return None
        while True:
            done = self.prefill_step()
            if done is not None and done[0] == slot:
                return done

    # ---- structured generation -----------------------------------
    def structured_compile(self, spec: dict):
        """``response_format`` spec -> token-level DFA over THIS
        engine's vocabulary (None for ``{"type": "text"}``), through
        the per-engine fingerprint cache — a mixed-schema trace
        compiles each distinct schema exactly once, and the batcher
        calls this at SUBMIT time so malformed specs reject before
        queueing and seat-time binding is a dict hit. Raises
        ``ValueError`` on a bad spec or a schema unsatisfiable under
        the vocabulary."""
        if not self.structured:
            raise RuntimeError(
                "structured_compile() needs "
                "PagedEngine(structured=True)")
        return compile_response_format(spec, self._svocab,
                                       cache=self._sdfa_cache)

    def structured_begin(self, slot: int, spec: dict, eos_id: int,
                         prefix_tokens=()) -> bool:
        """Bind a seated slot's automaton cursor (the batcher calls
        this right after ``admit_begin`` succeeds, BEFORE the slot's
        prefill chunks run, so the first-token pick is already
        masked). ``prefix_tokens`` are a preempted request's folded
        generated tokens — replaying them resumes the automaton
        token-exactly. Returns whether the spec actually constrains
        (``{"type": "text"}`` does not)."""
        if not self.structured:
            raise RuntimeError(
                "structured_begin() needs "
                "PagedEngine(structured=True)")
        dfa = self.structured_compile(spec)
        if dfa is None:
            return False
        self._cursors.begin(slot, dfa, eos_id,
                            prefix_tokens=prefix_tokens)
        self.structured_requests += 1
        return True

    @property
    def structured_slot_count(self) -> int:
        """Seated slots currently under an automaton constraint —
        host integers only (the ``/debug/engine`` and
        flight-recorder structured observable)."""
        return (self._cursors.live_count
                if self._cursors is not None else 0)

    @property
    def structured_masked_sum(self) -> float:
        """Cumulative masked-vocabulary fraction over committed
        cursor rows (numerator of the masked_frac gauge)."""
        return (self._cursors.masked_sum
                if self._cursors is not None else 0.0)

    @property
    def structured_masked_rows(self) -> int:
        return (self._cursors.masked_rows
                if self._cursors is not None else 0)

    def fork(self, parent_slot: int, n_branches: int
             ) -> list[tuple[int, int, float]]:
        """Fork a just-prefilled slot into ``n_branches`` sampling
        branches (the copy-on-write heart of OpenAI ``n``/
        ``best_of``): every FULL page of the parent is SHARED into
        each child's block table (one HBM read serves all branches
        through the refs lanes), the partial tail page is copied once
        per child by the fixed-shape ``_cow_fn`` executable, and each
        branch gets its own PRNG key (``fold_in(base, b)``) plus its
        own first token sampled from the SAME prompt-final logits the
        parent's prefill produced — so the branches diverge from
        token one exactly as n independent runs with those keys
        would. Returns ``[(slot, first_token, first_logprob)]`` for
        ALL branches, branch 0 (the parent, already activated by
        ``prefill_step``) first.

        Must be called at the prefill boundary (before the parent's
        first decode step); raises RuntimeError when slots/pages run
        out — the caller preempts and retries. Fork churn adds ZERO
        decode/verify compiles (page sharing is table VALUES; the one
        cow-copy executable compiles at the first fork only)."""
        if not self.parallel:
            raise RuntimeError(
                "fork() needs PagedEngine(parallel_sampling=True)")
        if n_branches < 2:
            raise ValueError(
                f"n_branches must be >= 2, got {n_branches}")
        st = self._fork_state.get(parent_slot)
        if st is None or int(self.tables.lengths[parent_slot]) \
                != int(self.tables.prompt_len[parent_slot]):
            raise RuntimeError(
                f"slot {parent_slot} is not at its prefill boundary: "
                "fork() must run before the parent's first decode "
                "step (branches diverge from token one)")
        if int(self._branch_of[parent_slot]) != 0:
            raise RuntimeError(
                f"slot {parent_slot} is itself branch "
                f"{int(self._branch_of[parent_slot])}: only branch 0 "
                "forks (re-forking a branch would alias keys)")
        # PEEK above, pop only past the fallible part: a pool/slot
        # exhaustion here must leave the stash intact so the batcher
        # can preempt a victim and RETRY the fork
        children = self.tables.fork(parent_slot, n_branches - 1)
        self._fork_state.pop(parent_slot)
        L = int(self.tables.lengths[parent_slot])
        n_full = L // self.page_size
        self.forks += 1
        self.fork_pages += n_full * len(children)
        # the CoW tail copy: one fixed-shape device call per fork,
        # null->null self-copies padding the unused lanes
        if L % self.page_size:
            src = np.zeros(self.max_slots, np.int32)
            dst = np.zeros(self.max_slots, np.int32)
            parent_tail = int(self.tables.tables[parent_slot, n_full])
            for i, child in enumerate(children):
                src[i] = parent_tail
                dst[i] = int(self.tables.tables[child, n_full])
            with span("serving_fork_cow"):
                pool_k, pool_v = self._cow_jit(
                    self.pool["k"], self.pool["v"],
                    jnp.asarray(src), jnp.asarray(dst))
            self.pool = {"k": pool_k, "v": pool_v}
            self.cow_copies += len(children)
        # per-branch keys + first tokens off the stashed prompt-final
        # logits (fork cadence, never per step): branch b's pick key
        # is fold_in(fold_in(base, b), s0) — exactly what an
        # independent run admitted with (seed, branch=b) would use
        base = self._base_keys[parent_slot]
        s0 = st["s0"]
        logits = jnp.asarray(st["logits"])[None]
        constrained = self.structured \
            and self._cursors.active(parent_slot)
        if constrained:
            # the stash is UNMASKED prompt-final logits; mask with
            # the automaton START-state row (every branch's first
            # token re-derives from the start — the cursor rebases
            # below), exactly what an independent constrained run's
            # prefill chunk applies
            logits = _mask_logits(
                logits,
                jnp.asarray(self._cursors.start_row(parent_slot)))
        out = [(parent_slot, int(self.tables.last_ids[parent_slot]),
                st["logprob"])]
        for b, child in enumerate(children, start=1):
            # branches decode through the parent's adapter — the
            # request carries ONE model; the registry's pin is held
            # once per seated request, so no extra acquire here (the
            # batcher releases once at the request's retirement)
            self._slot_lanes[child] = self._slot_lanes[parent_slot]
            self._base_keys[child] = base
            key = jax.random.fold_in(jnp.asarray(base), b)
            self._slot_keys[child] = np.asarray(key)
            self._branch_of[child] = b
            pick_key = jax.random.fold_in(key, s0)
            tok, lp = self._branch_pick(pick_key[None], logits)
            tok = int(np.asarray(tok)[0])
            self.tables.activate(child, tok)
            if constrained:
                # automaton state forks WITH the CoW pages: the
                # child rebases to start and observes its own first
                # token — token-exact vs an independent run with
                # (seed, branch=b)
                self._cursors.fork_child(parent_slot, child)
                self._cursors.observe(child, [tok])
            out.append((child, tok, float(np.asarray(lp)[0])))
        return out

    def take_first_logprob(self, slot: int) -> float:
        """Consume a just-prefilled slot's first-token logprob
        (parallel mode): pops the whole fork stash, so a request that
        will NOT fork (n = 1, or a re-admitted branch) frees its
        stashed prompt logits the moment its first token is
        accounted. Returns 0.0 when nothing is stashed."""
        st = self._fork_state.pop(slot, None)
        return 0.0 if st is None else st["logprob"]

    def grow_slots(self) -> list[int]:
        """Pre-allocate each active slot's upcoming write pages
        (evicting cached prefixes under pressure): one position ahead
        normally, ``1 + draft_len`` in speculative mode (the verify
        step writes every drafted position, accepted or not). Returns
        the slots that could NOT get their pages (pool exhausted —
        the batcher preempts). Call before every :meth:`step` /
        :meth:`spec_step`."""
        ahead = 1 + (self.draft_len if self.speculative else 0)
        starved = []
        for slot in np.flatnonzero(self.tables.active):
            if not self.tables.ensure_write_pages(int(slot), ahead):
                starved.append(int(slot))
        return starved

    def _lora_write_fn(self, buf, lane, a_qkv, b_qkv, a_proj, b_proj):
        """The ONE compiled adapter hot-load: overwrite lane ``lane``
        of all four stacks. The lane index is a traced VALUE
        (dynamic_update_index_in_dim), so any load/evict churn the
        registry produces reuses this single executable — the
        ``_cow_fn``/``_promote_fn`` pattern; the buffer donates, so a
        hot-load is an in-place lane write, never a stack copy."""
        new = {"a_qkv": a_qkv, "b_qkv": b_qkv,
               "a_proj": a_proj, "b_proj": b_proj}
        return {k: jax.lax.dynamic_update_index_in_dim(
            buf[k], new[k].astype(buf[k].dtype), lane, axis=1)
            for k in buf}

    def lora_load(self, lane: int, stacks: dict) -> None:
        """Write one adapter's host stacks into device lane ``lane``
        (AdapterRegistry calls this; direct drivers may too). The
        stacks are lane-less ``(n_layers, ...)`` arrays in the
        registry's convention — already rank-padded and (at tp>1)
        qkv-column-permuted."""
        if not self.lora:
            raise RuntimeError(
                "lora_load() needs a PagedEngine(lora_rank=...,"
                " lora_max_live=...)")
        if not 1 <= lane <= self.lora_max_live:
            raise ValueError(
                f"lane {lane} out of range [1, {self.lora_max_live}]"
                " — lane 0 is the reserved zero adapter")
        with span("lora_load"):
            self._lora_buf = self._lora_load_jit(
                self._lora_buf, jnp.asarray(lane, jnp.int32),
                jnp.asarray(stacks["a_qkv"]),
                jnp.asarray(stacks["b_qkv"]),
                jnp.asarray(stacks["a_proj"]),
                jnp.asarray(stacks["b_proj"]))

    def _lora_operands(self) -> tuple:
        """The lora mode's four trailing step operands: the lane
        stacks, device-resident (the per-slot lane ids ride the
        operand buffer); empty when lora is off."""
        if not self.lora:
            return ()
        b = self._lora_buf
        return (b["a_qkv"], b["b_qkv"], b["a_proj"], b["b_proj"])

    @property
    def lora_load_compiles(self) -> int:
        """Compiled adapter-writer count — exactly ONE whatever
        hot-load/evict churn the registry drives (the lane index is
        traced); 0 until the first load, 0 forever with lora off."""
        return (self._lora_load_jit._cache_size()
                if self._lora_load_jit is not None else 0)

    def _pack_kernel_walk(self) -> None:
        """The pallas backend's compacted live-page walk, written into
        its slices of the operand buffer; nothing on the XLA sweep,
        whose buffer has no such slices."""
        if self.decode_backend == "pallas":
            for name, walk in self.tables.kernel_args().items():
                self._op[name][...] = walk

    def step(self) -> np.ndarray:
        """One decode step over every ACTIVE slot; advances lengths/
        last_ids for those and returns the (max_slots,) token ids
        (garbage at inactive or mid-prefill slots). Synchronous: the
        launch is followed at once by the land (what
        :meth:`step_ahead` keeps an iteration apart), the two in the
        one ``decode_step`` span."""
        return self._launch_and_land(False)[0]

    def mixed_step(self) -> tuple[np.ndarray, tuple[int, int] | None]:
        """ONE program for the oldest pending prefill's next chunk AND
        the decode step over every active slot (the mixed variant of
        ``_chunk_fn``): what :meth:`prefill_step` followed by
        :meth:`step` would do, with one pass over the weights, one
        transfer, one launch, one rng split and one read-back. Needs
        a pending chunk, and an engine whose mode rides
        (``self.mixes``). Synchronous, as :meth:`step` is.
        Returns ``(tokens, done)``: the (max_slots,) token ids as
        :meth:`step` gives them, and ``(slot, first_token)`` when the
        chunk was its prompt's last, else None — that slot joins the
        decode lanes from the NEXT step on (the two-program iteration
        decodes it in the same one)."""
        return self._launch_and_land(True)

    def _launch_and_land(self, mixed: bool) -> tuple:
        """A synchronous step: ``(tokens, done)``."""
        operands = self._launch_operands(mixed)
        with span("decode_step"):
            flight = self._dispatch(mixed, operands)
            got = jax.device_get(flight.fetch)
        return self._landed(flight, got)

    def step_ahead(self, flight: "_Flight | None", mixed: bool | None
                   ) -> tuple["_Flight | None", tuple | None]:
        """The look-ahead loop's iteration (``self.looks_ahead``):
        launch the next program — the mixed one, the plain decode
        step, or with ``mixed`` None nothing — BEHIND ``flight``, the
        step launched by the last call, and only then wait for
        ``flight``'s tokens (None: nothing is in flight). The device
        starts the new program the moment ``flight`` ends, so what the
        host does between two calls runs beside a program, not between
        two. Returns ``(the new flight, flight's (tokens, done))``,
        either None where there was none. The wait and the dispatch in
        front of it are the iteration's ONE ``decode_step`` span.

        What a launch takes for granted of the step in front of it:
        its lanes' lengths (+1 each: bumped at the launch), its tokens
        (read on the device, from the ``tokens`` it returns), and a
        slot whose prompt's last chunk it carried (active from the
        launch on, its first token in its lane of ``tokens``). What
        the host must decide before a launch — a slot whose token in
        flight is its last — it says with :meth:`hold`."""
        operands = None if mixed is None else \
            self._launch_operands(mixed)
        if flight is None:
            return self._dispatch(mixed, operands), None
        with span("decode_step"):
            ahead = None if mixed is None else \
                self._dispatch(mixed, operands)
            # the step's ONE batched device->host sync
            got = jax.device_get(flight.fetch)
        return ahead, self._landed(flight, got)

    def hold(self, slot: int) -> None:
        """Keep a seated slot out of every further launch (its pages
        stay its own): its token in flight is its last, or it has
        stopped and only waits for the step in flight to land before
        :meth:`retire`."""
        self.tables.active[slot] = False

    def _launch_operands(self, mixed: bool) -> tuple:
        """The host's work before a launch, under its own spans: the
        chunk written into the buffer (a mixed step), the ONE
        transfer, and what rides beside the buffer. Returns
        ``(active, pending, args, kwargs)`` for :meth:`_dispatch`."""
        if mixed and not (self.mixes and self._pending):
            raise RuntimeError(
                "mixed_step() needs a pending prefill chunk and an "
                "engine whose mode rides the mixed program "
                "(PagedEngine.mixes)")
        active = self._decoding("mixed_step" if mixed else "step")
        p = self._pending[0] if mixed else None
        if mixed:
            with span("prefill_args"):
                self._fill_chunk(p)
        # the ONE transfer; structured mode's mask for all slots (the
        # chunk's row among them) is the mixed program's ``lanes``
        operands, smask = self._lane_operands()
        if not mixed:
            beside = () if smask is None else (smask,)
            beside += self._lora_operands()
            if self.looks_ahead:
                beside += (self._tokens,)
            return active, p, operands + beside, {}
        lanes = {} if smask is None else {"smask": smask}
        if self.looks_ahead:
            lanes["prev"] = self._tokens
        return active, p, operands, {"lanes": lanes}

    def _dispatch(self, mixed: bool, operands: tuple) -> "_Flight":
        """Launch the program and book what is predictable of it: the
        pool, the key, the slot state and ``tokens`` rebound to its
        (not yet ready) results, the lanes' lengths bumped (the
        program writes at the old length), the chunk counted and, its
        prompt's last, its slot active from the next launch on."""
        active, p, args, kwargs = operands
        jit = self._chunk_jit if mixed else self._decode_jit
        outs = jit(self.params, self.pool["k"], self.pool["v"], *args,
                   **kwargs)
        self._rng, outs = outs[0], outs[1:]
        last = False
        if mixed:
            tok, tokens, pool_k, pool_v = outs[:4]
            if self.slot_state is not None:
                self.slot_state = outs[4]
            fetch = (tokens,)
            self.mixed_steps += 1
        elif self.model is not None:
            tokens, pool_k, pool_v, self.slot_state, counts = outs
            fetch = (tokens, counts)
        elif self.parallel:
            tokens, lps, pool_k, pool_v = outs
            fetch = (tokens, lps)
        else:
            tokens, pool_k, pool_v = outs
            fetch = (tokens,)
        self.pool = {"k": pool_k, "v": pool_v}
        self._count_rows(active, chunk=p)
        self._count_lane_pages(active)
        self._count_state(active, chunk=p)
        self.tables.launched(active)
        if mixed:
            self._count_chunk_pages(p)
            last = self._chunk_issued(p)
            if last:
                # the chunk's token only where it is the prompt's
                # first; the slot rides the next launch
                fetch += (tok,)
                self.tables.activate(p["slot"])
        flight = self._flight = _Flight(active, fetch, p, last)
        if self.looks_ahead:
            self._tokens = tokens
            self._on_device(flight)
        return flight

    def _on_device(self, flight: "_Flight", known: int = 0) -> None:
        """From its launch until it lands, a step's lanes (and the
        slot whose prompt it ended) have their last token on the
        device: the buffer's ``known`` says so to the next launch."""
        self._op["known"][flight.active] = known
        if flight.last:
            self._op["known"][flight.pending["slot"]] = known

    def _landed(self, flight: "_Flight", got: tuple) -> tuple:
        """Book a landed step from what was read back of it: the
        lanes' last tokens, the experts' counts or the branches'
        logprobs, the drafter's and the cursors' view, and the
        prompt's first token where the chunk was its last. Returns
        ``(tokens, done)`` as :meth:`mixed_step` does."""
        active, p = flight.active, flight.pending
        tokens = np.asarray(got[0])
        if p is None and self.model is not None:
            self._count_experts(got[1])
        elif p is None and self.parallel:
            self.step_logprobs = np.asarray(got[1])
        done = None
        if flight.last:
            # no wait left in it: the token came with the lanes'
            with span("prefill_finish"):
                done = self._prefill_done(p, int(np.asarray(got[-1])[0]))
        with span("decode_advance"):
            self.tables.landed(active, tokens)
            if self._drafter is not None or self.structured:
                for slot in np.flatnonzero(active):
                    seen = [int(tokens[slot])]
                    if self._drafter is not None:
                        self._drafter.observe(int(slot), seen)
                    if self.structured:
                        self._cursors.observe(int(slot), seen)
        newer = self._flight
        if newer is flight:
            newer = self._flight = None
        if self.looks_ahead:
            self._on_device(flight, known=1)
            if newer is not None:
                # a step was launched behind this one
                self._on_device(newer)
        return tokens, done

    def _decoding(self, entry: str) -> np.ndarray:
        """The slots a decode program is about to advance (a copy of
        the active mask), after the checks every decode entry makes."""
        if self.prefill_only:
            raise RuntimeError(
                f"{entry}() on a prefill_only engine: the disaggregated "
                "prefill pool exports pages (export_pages) instead "
                "of decoding — route decode to the decode host")
        active = self.tables.active.copy()
        if active.any():
            full = self.tables.lengths[active] >= self.cfg.seq_len
            if full.any():
                raise RuntimeError(
                    "a slot reached cfg.seq_len; the batcher must "
                    "retire sequences at the cache horizon")
        return active

    def _lane_operands(self) -> tuple[tuple, jax.Array | None]:
        """What a program that runs the decode lanes takes after the
        pool: the iteration's ONE transfer of the operand buffer (the
        tables are views of it; the pallas walk is written here), the
        key and the slot state; and structured mode's fused legality
        mask (None without the mode), which rides beside the buffer
        as a VALUE operand (max_slots x vocab: too large to pack) —
        schema churn flips bits, never shapes."""
        with span("decode_args"):
            self._pack_kernel_walk()
            operands = self._put_operands() + self._state_operand()
            smask = self._put(self._cursors.mask) \
                if self.structured else None
            return operands, smask

    def spec_step(self) -> dict[int, list[int]]:
        """One speculative decode step over every ACTIVE slot: draft
        (host-side prompt lookup), verify all ``1 + draft_len``
        positions in the ONE compiled multi-token scoring step, accept
        the longest confirmed prefix, and advance each slot by its
        accepted tokens plus the fallback/bonus pick — between 1 and
        ``draft_len + 1`` tokens per slot per step. Rejected draft
        positions REWIND by simply not being advanced over: their
        poisoned K/V sits past ``lengths`` (invisible to every mask)
        and the next step's writes cover it; their pages are private
        and never enter the prefix index (kv_pages.check()).

        Returns ``{slot: [tokens]}`` in slot order — multi-token
        emission is why this cannot share :meth:`step`'s fixed
        ``(max_slots,)`` return. Requires ``speculative=True``."""
        if not self.speculative:
            raise RuntimeError(
                "spec_step() needs a PagedEngine(speculative=True); "
                "the cold engine decodes through step()")
        active = self._decoding("spec_step")
        k = self.draft_len
        drafts = np.full((self.max_slots, k), -1, np.int32)
        # chain parents by default (node j+1 off node j): slots with
        # no tree draft — and the whole linear mode — verify exactly
        # the PR-5 chain through the same operands
        parents = np.tile(np.arange(k, dtype=np.int32),
                          (self.max_slots, 1))
        vmask = None
        if self.structured:
            vmask = self._smask_verify
            vmask[:] = True
        for slot in np.flatnonzero(active):
            slot = int(slot)
            if self.spec_tree:
                d, parents[slot] = self._drafter.draft_tree(slot)
            else:
                d = self._drafter.draft(slot)
            # horizon cap: drafted position j writes at lengths+1+j,
            # which must stay inside the slot's table — positions
            # past it are sentinelled out (the verify step ALSO
            # diverts overflow writes to the null page, so this is
            # belt and braces, not the only guard)
            room = int(self.cfg.seq_len - self.tables.lengths[slot]) - 1
            if room < k:
                d[max(room, 0):] = -1
            if self.structured and self._cursors.active(slot):
                # draft pre-validation against the automaton: a chain
                # truncates at its first illegal token, a tree prunes
                # the illegal node and (transitively) its subtree —
                # all to the -1 never-accept sentinel, so verify
                # cannot spend an acceptance on an illegal branch;
                # the per-position legality rows mask verify's
                # fallback/bonus picks
                if self.spec_tree:
                    d, rows = self._cursors.tree_rows(
                        slot, d, parents[slot])
                else:
                    d, rows = self._cursors.draft_rows(slot, d)
                vmask[slot] = rows
            drafts[slot] = d
            self.spec_proposed += int((d >= 0).sum())
        with span("decode_args"):
            self._pack_kernel_walk()
            self._op["drafts"][...] = drafts
            beside = ()
            if self.spec_tree:
                # parents and depths pack; the (slots, S, S)
                # visibility matrix rides beside, like the mask
                depth, tvis = tree_masks(parents)
                self._op["parents"][...] = parents
                self._op["depth"][...] = depth
                beside = (self._put(tvis),)
            if self.structured:
                beside = beside + (self._put(vmask),)
            operands = self._put_operands()
        with span("spec_verify_step"):
            self._rng, accept, token, pool_k, pool_v = self._verify_jit(
                self.params, self.pool["k"], self.pool["v"], *operands,
                *beside, *self._lora_operands())
            self.pool = {"k": pool_k, "v": pool_v}
            # ONE batched device->host sync for both results (two
            # np.asarray calls would serialize two round-trips into
            # the decode loop)
            accept, token = jax.device_get((accept, token))
        self.spec_steps += 1
        out: dict[int, list[int]] = {}
        paths: dict[int, list[int]] = {}
        for slot in np.flatnonzero(active):
            slot = int(slot)
            if self.spec_tree:
                path = tree_accept_path(accept[slot], parents[slot])
                a = len(path)
                bonus_at = path[-1] if path else 0
                emitted = [int(drafts[slot, p - 1]) for p in path] \
                    + [int(token[slot, bonus_at])]
                paths[slot] = path
            else:
                a = accept_count(accept[slot])
                emitted = [int(t) for t in drafts[slot, :a]] \
                    + [int(token[slot, a])]
            # a request retiring AT the horizon may accept its way
            # right up to seq_len — never past it
            room = int(self.cfg.seq_len - self.tables.lengths[slot])
            emitted = emitted[:room]
            self.spec_accepted += min(a, len(emitted))
            out[slot] = emitted
        if self.spec_tree:
            # accepted-path K/V compaction BEFORE lengths advance: a
            # side branch's accepted rows move down to the contiguous
            # positions the new lengths will expose (identity rows —
            # chain accepts, idle slots — are no-op copies through
            # the same single executable)
            src_off = np.tile(np.arange(k + 1, dtype=np.int32),
                              (self.max_slots, 1))
            for slot, path in paths.items():
                for i, node in enumerate(path, start=1):
                    src_off[slot, i] = node
            with span("spec_tree_compact"):
                pool_k, pool_v = self._compact_jit(
                    self.pool["k"], self.pool["v"], operands[0],
                    self._put(src_off))
            self.pool = {"k": pool_k, "v": pool_v}
        with span("decode_advance"):
            for slot, emitted in out.items():
                for t in emitted:
                    self.tables.advance(slot, t)
                self._drafter.observe(slot, emitted)
                if self.structured:
                    # the cursor stops at EOS itself; tokens past it
                    # in the burst are the same tail the batcher drops
                    self._cursors.observe(slot, emitted)
        return out

    def _count_experts(self, counts) -> None:
        """File one decode step's tokens per expert ``(n_moe_layers,
        n_experts)``: kept as ``moe_counts`` and, while the registry
        is on, fed to the ``serving_moe_*`` series — experts hit by at
        least one token (summed over the layers: what the step's
        expert weights cost to read) and, per layer, the fullest
        expert's tokens over the mean. A model that holds a SHARE of
        its experts hands ``{"held": (n_moe_layers, n held),
        "elsewhere": (n_moe_layers,)}``: the series above are then
        over the experts held, ``moe_elsewhere`` keeps the pairs routed
        to experts this device does not hold, and
        ``serving_moe_pairs_total{where=here|elsewhere}`` counts
        both."""
        elsewhere = None
        if isinstance(counts, dict):
            counts, elsewhere = counts["held"], counts["elsewhere"]
            self.moe_elsewhere = np.asarray(elsewhere)
        counts = self.moe_counts = np.asarray(counts)
        if not counts.size:
            return
        reg = get_registry()
        if not reg.enabled:
            return
        if self._moe_inst is None:
            self._moe_inst = {
                "hit": reg.histogram(
                    "serving_moe_experts_hit",
                    "experts given at least one token in a decode "
                    "step, summed over the expert layers"),
                "load": reg.histogram(
                    "serving_moe_tokens_per_expert",
                    "per decode step and expert layer: tokens on the "
                    "fullest expert over the mean per expert"),
            }
            if elsewhere is not None:
                self._moe_inst["pairs"] = reg.counter(
                    "serving_moe_pairs_total",
                    "(token, expert) pairs of the decode steps: "
                    "computed on the experts held here, or routed to "
                    "experts another device holds")
        if elsewhere is not None:
            pairs = self._moe_inst["pairs"]
            pairs.inc(int(counts.sum()), where="here")
            pairs.inc(int(self.moe_elsewhere.sum()), where="elsewhere")
        self._moe_inst["hit"].observe(np.count_nonzero(counts))
        mean = counts.mean(axis=1)
        live = mean > 0
        if live.any():
            fullest = counts.max(axis=1)[live] / mean[live]
            self._moe_inst["load"].observe(fullest.mean())

    def _count_rows(self, active: np.ndarray | None,
                    chunk: dict | None = None) -> None:
        """File what the two kinds of cache hold at this step (an
        engine with window layers; host arithmetic on the tables, made
        BEFORE the step's advance): the rows ONE layer of each kind
        keeps for the slots ``active`` once the step has written — a
        full layer every position, a window layer the last ``window``
        — as the gauge ``serving_kv_rows_live{kind}`` and summed over
        the steps in ``serving_kv_rows_read_total{kind}`` (what the
        steps' attention had to read of a layer of that kind), and the
        rows of a ring this step's writes recycled
        (``serving_window_rows_recycled_total``: a position at or past
        the ring's size lands on the row of the position one ring
        before it): the lanes' and the ``chunk``'s real tokens'.
        ``active`` None: a lone chunk, no lane decodes."""
        if self.ring is None:
            return
        at = np.zeros(0, np.int64) if active is None \
            else self.tables.lengths[active].astype(np.int64)
        span = self.ring * self.page_size
        recycled = int(np.count_nonzero(at >= span))
        if chunk is not None:
            recycled += max(0, min(chunk["start"] + self.chunk_tokens,
                                   chunk["s0"])
                            - max(chunk["start"], span))
        self.window_rows_recycled += recycled
        reg = get_registry()
        if not reg.enabled:
            return
        if self._kv_inst is None:
            self._kv_inst = {
                "live": reg.gauge(
                    "serving_kv_rows_live",
                    "cached rows ONE layer of the kind holds for the "
                    "decoding slots at the last step: a window layer "
                    "at most its window a slot"),
                "read": reg.counter(
                    "serving_kv_rows_read_total",
                    "serving_kv_rows_live summed over the steps: the "
                    "rows the steps' attention needed of one layer of "
                    "the kind"),
                "recycled": reg.counter(
                    "serving_window_rows_recycled_total",
                    "rows of a window layer's ring overwritten by a "
                    "later position"),
            }
        self._kv_inst["recycled"].inc(recycled)
        if active is None:
            return
        rows = {"full": int((at + 1).sum()),
                "window": int(np.minimum(at + 1, self.window).sum())}
        for kind, n in rows.items():
            self._kv_inst["live"].set(n, kind=kind)
            self._kv_inst["read"].inc(n, kind=kind)

    def _count_lane_pages(self, active: np.ndarray) -> None:
        """File the pages the decode lanes' read fetches at this step,
        by host arithmetic on the lengths (made BEFORE the step's
        advance) and the pools' geometry, against the pages the same
        layers' pools hold: ``serving_lane_pages_read_total{kind}`` and
        ``serving_lane_pages_pool_total{kind}``, each summed over the
        kind's K/V layers (a page of K, and as many of V). The walk
        fetches a decoding slot's pages through its position — for a
        window layer from the window's lower edge on; the latent kernel
        whole blocks of its table; the sweep every page of the pool."""
        reg = get_registry()
        if not reg.enabled:
            return
        if self._lane_inst is None:
            self._lane_inst = {
                "read": reg.counter(
                    "serving_lane_pages_read_total",
                    "pages the decode lanes' read fetched, summed over "
                    "the K/V layers of the kind"),
                "pool": reg.counter(
                    "serving_lane_pages_pool_total",
                    "pages the same layers' pools hold, summed over the "
                    "steps that counted serving_lane_pages_read_total"),
            }
        ps = self.page_size
        at = self.tables.lengths[active].astype(np.int64)
        top = at // ps
        pool = {"full": self.n_pages}
        if self.ring is not None:
            pool["window"] = self.max_slots * self.ring
        if self.latent_dim is not None:
            mp = self.tables.max_pages_per_slot
            n_pp = min(LATENT_PAGES_PER_STEP, mp)
            blocks = np.minimum(at // (n_pp * ps) + 1, -(-mp // n_pp))
            read = {"full": int(blocks.sum()) * n_pp}
        elif self.walks:
            read = {"full": int((top + 1).sum())}
            if self.ring is not None:
                edge = np.maximum(at - self.window + 1, 0) // ps
                read["window"] = int((top - edge + 1).sum())
        else:
            read = pool
        for kind, layers in self._kv_layers.items():
            self._lane_inst["read"].inc(read[kind] * layers, kind=kind)
            self._lane_inst["pool"].inc(pool[kind] * layers, kind=kind)

    def _count_chunk_pages(self, chunk: dict) -> None:
        """File the pages a prefill chunk's read of its prior context
        fetches, by host arithmetic on its start and the pools'
        geometry, against the pages a gather of its slot's table
        reads: ``serving_chunk_pages_read_total{kind}`` and
        ``serving_chunk_pages_table_total{kind}``, each summed over the
        kind's K/V layers (a page of K, and as many of V). The chunk
        walk fetches the pages before the chunk's start — of a window
        layer's ring, those from the window's lower edge for the
        chunk's first query on; the latent kernel whole blocks of its
        table, each block of the chunk's tokens to its own last
        position; the gather every entry of the table (a window layer:
        the whole ring)."""
        reg = get_registry()
        if not reg.enabled:
            return
        if self._chunk_inst is None:
            self._chunk_inst = {
                "read": reg.counter(
                    "serving_chunk_pages_read_total",
                    "pages a prefill chunk's read of its prior context "
                    "fetched, summed over the K/V layers of the kind"),
                "table": reg.counter(
                    "serving_chunk_pages_table_total",
                    "pages a gather of the chunk's slot's table would "
                    "read (a window layer: its ring), summed over the "
                    "same layers and chunks"),
            }
        ps, start = self.page_size, chunk["start"]
        mp = self.tables.max_pages_per_slot
        table = {"full": mp}
        if self.ring is not None:
            table["window"] = self.ring
        if self.latent_dim is not None:
            n_pp = min(LATENT_PAGES_PER_STEP, mp)
            per_block = math.gcd(self.chunk_tokens, LATENT_CHUNK_TOKENS)
            last = start + np.arange(1, self.chunk_tokens // per_block
                                     + 1) * per_block - 1
            blocks = np.minimum(last // (n_pp * ps) + 1, -(-mp // n_pp))
            read = {"full": int(blocks.sum()) * n_pp}
        elif self.walks:
            before = -(-start // ps)
            read = {"full": before}
            if self.ring is not None:
                read["window"] = before \
                    - max(start - self.window + 1, 0) // ps
        else:
            read = table
        for kind, layers in self._kv_layers.items():
            self._chunk_inst["read"].inc(read[kind] * layers, kind=kind)
            self._chunk_inst["table"].inc(table[kind] * layers, kind=kind)

    def _count_state(self, active: np.ndarray | None,
                     chunk: dict | None = None) -> None:
        """File the bytes of slot state a step reads and writes (a
        model with slot state; host arithmetic where the step is
        packed): ``serving_slot_state_bytes_total{kind=<state>,
        part=lanes|chunk}`` — each live lane's rows of every layer read
        and written once, the chunk's slot's written and, past position
        0, read. ``active`` None: a lone chunk, no lane decodes."""
        if self.slot_state is None:
            return
        reg = get_registry()
        if not reg.enabled:
            return
        if self._state_inst is None:
            self._state_inst = reg.counter(
                "serving_slot_state_bytes_total",
                "bytes of slot state the decode lanes and the prefill "
                "chunk read and wrote, summed over the layers")
        passes = {"lanes": 0 if active is None
                  else 2 * int(np.count_nonzero(active)),
                  "chunk": 0 if chunk is None
                  else 1 + int(chunk["start"] > 0)}
        for name, a in self.slot_state.items():
            row = a.dtype.itemsize * math.prod(a.shape[2:]) * a.shape[0]
            for part, n in passes.items():
                if n:
                    self._state_inst.inc(n * row, kind=name, part=part)

    def retire(self, slot: int) -> None:
        """Release the slot (cancelling any in-flight prefill); shared
        prefix pages stay resident for later hits, everything else
        frees (kv_pages.py refcount/evict lifetime)."""
        for p in self._pending:
            # a retire that beats the promotion: the popped host
            # payloads go back to the host pool instead of vanishing
            # with the cancelled prefill
            if p["slot"] == slot and "promote" in p:
                work = p.pop("promote")
                for key, pl in zip(work["keys"], work["payloads"]):
                    self.tables.host_pool.put(key, pl)
        self._pending = [p for p in self._pending
                         if p["slot"] != slot]
        if self._drafter is not None:
            self._drafter.reset(slot)
        if self.structured:
            self._cursors.reset(slot)
        self._fork_state.pop(slot, None)
        if self.parallel:
            self._base_keys[slot] = 0
            self._slot_keys[slot] = 0
            self._branch_of[slot] = 0
        # lane 0 = zero adapter: a reused slot decodes base-model
        # until its next seat assigns a lane (the registry pin is the
        # BATCHER's to release — the engine only clears the gather id)
        self._slot_lanes[slot] = 0
        self.tables.retire(slot)

    def debug_stats(self) -> dict:
        """Engine introspection snapshot for ``GET /debug/engine``:
        pool occupancy, prefix-cache stats, compile counts, backend —
        host integers only (table bookkeeping and jit cache sizes),
        never a device read, so a debug poll cannot stall the decode
        loop."""
        t = self.tables
        return {
            "backend": self.decode_backend,
            "tp": self.tp,
            "speculative": self.speculative,
            "spec_tree": self.spec_tree,
            "parallel_sampling": self.parallel,
            "quantized": self.quantized,
            "page_size": self.page_size,
            "n_pages": self.n_pages,
            "max_slots": self.max_slots,
            "pages_live": int(t.n_live_pages),
            "pages_free": int(t.n_free_pages),
            "pages_cached": int(t.n_cached_pages),
            "pages_available": int(t.n_available_pages),
            "pending_prefill_chunks": self.pending_chunk_count,
            "prefill_chunks": self.prefill_chunks,
            "prefix_hit_pages": self.prefix_hit_pages,
            "prefix_lookup_pages": self.prefix_lookup_pages,
            "prefix_hit_rate": round(self.prefix_hit_rate, 4),
            "host_spill": self.host_spill,
            "pages_host": int(t.n_host_pages),
            "spills": self.spills,
            "promotions": self.promotions,
            "host_hit_pages": self.host_hit_pages,
            "promoted_bytes": self.promoted_bytes,
            "host_bytes_used": (int(t.host_pool.used_bytes)
                                if t.host_pool is not None else 0),
            "host_evictions": (int(t.host_pool.n_evictions)
                               if t.host_pool is not None else 0),
            "spec_steps": self.spec_steps,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "forks": self.forks,
            "fork_pages": self.fork_pages,
            "cow_copies": self.cow_copies,
            "branch_slots": self.branch_slot_count,
            "structured": self.structured,
            "structured_requests": self.structured_requests,
            "structured_slots": self.structured_slot_count,
            "structured_schemas": len(self._sdfa_cache),
            "window": self.window,
            "ring_pages": self.ring,
            "window_rows_recycled": self.window_rows_recycled,
            "weights_dtype": (_weights_dtype(self.params)
                              if self.model is None else "bf16"),
            # a model with its own layer stack: every stored byte (a
            # dropless expert layer reads only the experts hit)
            "weight_stream_bytes": (
                _weight_stream_bytes(self.params) if self.model is None
                else sum(int(a.nbytes)
                         for a in jax.tree.leaves(self.params))),
            "lora": self.lora,
            "lora_rank": self.lora_rank,
            "lora_max_live": self.lora_max_live,
            "adapters": (self.adapters.debug()
                         if self.adapters is not None else None),
            "compiles": {"decode": self.decode_compiles,
                         "prefill": self.prefill_compiles,
                         "verify": self.verify_compiles,
                         "promote": self.promote_compiles,
                         "lora_load": self.lora_load_compiles},
        }

    @property
    def branch_slot_count(self) -> int:
        """Active slots currently decoding as a fork branch b > 0 —
        host integers only (the ``/debug/engine`` and flight-recorder
        branch-count observable)."""
        if not self.parallel:
            return 0
        return int(np.count_nonzero(
            self.tables.active & (self._branch_of > 0)))

    @property
    def adapter_slot_count(self) -> int:
        """Active slots currently decoding through a non-zero LoRA
        adapter lane — host integers only (the ``/debug/engine`` and
        flight-recorder per-tenant observable). Retire resets a
        slot's lane to 0, so the count is exactly the seated
        adaptered population."""
        if not self.lora:
            return 0
        return int(np.count_nonzero(
            self.tables.active & (self._slot_lanes > 0)))

    def tp_step_traffic(self, s_q: int = 1) -> dict:
        """Modeled per-chip wire bytes of one decode (``s_q=1``) or
        speculative-verify (``s_q = 1 + draft_len``) step's
        decode-output psum — zeros at tp=1 (no collective exists).
        Host arithmetic only; the ``serving_tp_bytes_total`` counter
        reads this model (serving/tp.py ``step_traffic``)."""
        return _tp_step_traffic(self.tp, self.cfg, self.max_slots,
                                self.compute_dtype, s_q=s_q)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of eligible prompt pages served from the cache."""
        return self.prefix_hit_pages / max(self.prefix_lookup_pages, 1)

    @property
    def decode_compiles(self) -> int:
        """Compiled decode-step count — the zero-recompile contract's
        observable (tests assert it stays 1 across seat/retire/evict
        churn; the batcher's RecompileSentinel enforces it at
        runtime)."""
        return self._decode_jit._cache_size()

    @property
    def prefill_compiles(self) -> int:
        """Compiled prefill-chunk count — at most TWO whatever prompt
        lengths arrive (chunk position/length/page-ids are traced
        values, never shapes): the chunk alone, and with the decode
        lanes riding (``mixed_step``)."""
        return self._chunk_jit._cache_size()

    @property
    def verify_compiles(self) -> int:
        """Compiled speculative verify-step count — exactly ONE
        whatever accept lengths, draft availability, and slot churn a
        trace produces (``draft_len`` is fixed at trace time, short
        drafts sentinel-pad); always 0 with ``speculative=False``
        (the verify executable does not exist on the cold engine)."""
        return (self._verify_jit._cache_size()
                if self._verify_jit is not None else 0)

    @property
    def promote_compiles(self) -> int:
        """Compiled promotion-write count — exactly ONE whatever
        group sizes demote/promote churn produces (fixed staging
        shapes, pad lanes hit the null page); always 0 until the
        first host hit, and always 0 with ``host_spill=False`` (the
        executable does not exist on the spill-less engine — the same
        collapse contract as the cow/verify executables)."""
        return (self._promote_jit._cache_size()
                if self._promote_jit is not None else 0)

    @property
    def spec_accept_rate(self) -> float:
        """Fraction of proposed draft tokens the verify step
        accepted."""
        return self.spec_accepted / max(self.spec_proposed, 1)


__all__ = ["PagedEngine"]
