"""Speculative decoding for the paged serving engine: draft →
batched-verify → accept/rewind.

The decode roofline (docs/performance.md) is BYTES-bound: every
non-speculative step streams the whole page pool once to produce ONE
token per slot, leaving the MXU mostly idle. Speculative decoding
converts that idle compute into extra tokens per pool read (Leviathan
et al., *Fast Inference from Transformers via Speculative Decoding*;
Fu et al., *Lookahead Decoding*): propose ``k`` tokens per slot,
score all ``k + 1`` positions in ONE multi-token verify step, keep the
longest model-confirmed prefix, and emit one extra fallback/bonus
token — ``E[accepted] + 1`` tokens per step for roughly one step's
pool bytes.

Three cooperating pieces, all slotting into the existing engine
lifecycle (serving/engine.py drives them from ``spec_step``):

- :class:`PromptLookupDrafter` — MODEL-FREE drafting by prompt lookup
  (n-gram match over the slot's own prompt + emitted tokens, the
  trick behind `prompt-lookup decoding`): host-side numpy over tokens
  the engine already tracks, zero extra HBM, no draft model to load
  or keep resident. Repetitive traffic (code, extraction, few-shot
  continuations, self-repeating chat) drafts well; novel text simply
  drafts nothing and the engine degrades to ordinary one-token decode
  THROUGH THE SAME compiled executable (sentinel padding).
- :func:`make_verify_fn` — the ONE compiled multi-token scoring step:
  the decode pool sweep generalized from one query per (page, lane)
  to ``k + 1`` (the draft positions ride the query axis exactly like
  the PR 4 refs lanes do), with per-position causal visibility
  ``tok_pos <= lengths + j``. All ``k + 1`` tokens' K/V are written
  to the slot's (always private) pages FIRST, then the sweep reads
  them back in pool dtype — so every verified position attends
  bitwise the same bytes the non-speculative engine would have read
  on its own step (including the int8 quantize→dequantize round
  trip), which is what makes greedy parity exact rather than
  approximate. ``k`` is FIXED at trace time and short drafts are
  sentinel-padded, so accept-length churn can never recompile
  (``PagedEngine.verify_compiles`` stays 1 — test- and
  sentinel-guarded).
- acceptance — :func:`accept_count` (host) over the per-position rule
  built by ``models/gpt.py::_make_spec_pick``: longest-prefix under
  greedy (token-for-token identical to the non-speculative engine),
  standard rejection sampling against the point-mass draft under
  ``temperature > 0`` (distribution-exact). The REWIND of rejected
  positions is ``BlockTables`` bookkeeping: the engine only advances
  ``lengths`` over accepted tokens, so the poisoned tail K/V sits
  past the slot's length — invisible to every mask (they all read
  ``tok_pos <= lengths``) and overwritten by the next step's writes,
  which start at the new length and always extend past the old
  draft horizon. Rejected positions' pages are PRIVATE by
  construction (the write cursor never re-enters the copy-on-write
  prefix region) and never enter the prefix index
  (``kv_pages.check()`` asserts both).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from torchbooster_tpu.models import layers as L
from torchbooster_tpu.models.gpt import (
    _block_core,
    _lm_head,
    _make_spec_pick,
    _mask_logits,
)
from torchbooster_tpu.ops.paged_attention import paged_attention
from torchbooster_tpu.serving.kv_pages import (
    NULL_PAGE,
    layer_pages,
    scan_layers,
    sweep_attention,
    write_rows,
)

# "no proposal" marker in a fixed-width draft row: the verify step
# never accepts it (ids are non-negative) and its fallback pick is an
# ordinary sample, so an empty draft IS a plain one-token decode
# through the same executable
NO_DRAFT = -1


class PromptLookupDrafter:
    """Per-slot prompt-lookup drafting state.

    ``begin(slot, prompt)`` seeds a slot's token stream at admission,
    ``observe(slot, tokens)`` appends emitted tokens, ``reset(slot)``
    drops the stream at retirement, ``draft(slot)`` proposes up to
    ``draft_len`` continuation tokens: the longest suffix n-gram of
    the stream (``ngram_max`` down to ``ngram_min`` tokens) is
    searched for an EARLIER occurrence, most recent match wins, and
    the tokens that followed it are the draft. Unfilled positions are
    ``NO_DRAFT`` sentinels. Pure host-side integer matching — the
    "draft model" is the sequence's own history, so drafting costs no
    HBM, no weights, and no device step. The match scans at most the
    last ``lookback`` stream tokens (serving/ is an obs_lint hot
    path: this bounds the per-step host work to O(lookback) however
    long a slot has been generating; matches older than the window —
    none, at the default, for any stream the cache horizon admits —
    are simply not proposed)."""

    def __init__(self, draft_len: int, ngram_min: int = 2,
                 ngram_max: int = 8, lookback: int = 4096):
        if draft_len < 1:
            raise ValueError(
                f"draft_len must be >= 1, got {draft_len}")
        if not 1 <= ngram_min <= ngram_max:
            raise ValueError(
                f"need 1 <= ngram_min <= ngram_max, got "
                f"ngram_min={ngram_min}, ngram_max={ngram_max}")
        if lookback < ngram_max + draft_len:
            raise ValueError(
                f"lookback ({lookback}) shorter than one match + "
                f"continuation (ngram_max={ngram_max} + "
                f"draft_len={draft_len}) can never draft")
        self.draft_len = draft_len
        self.ngram_min = ngram_min
        self.ngram_max = ngram_max
        self.lookback = lookback
        self._streams: dict[int, list[int]] = {}

    def begin(self, slot: int, prompt: np.ndarray) -> None:
        self._streams[slot] = [int(t) for t in np.asarray(prompt)]

    def observe(self, slot: int, tokens) -> None:
        if slot in self._streams:
            self._streams[slot].extend(int(t) for t in tokens)

    def reset(self, slot: int) -> None:
        self._streams.pop(slot, None)

    def draft(self, slot: int) -> np.ndarray:
        """``(draft_len,)`` int32 proposal for the slot's NEXT tokens
        (``NO_DRAFT``-padded)."""
        out = np.full(self.draft_len, NO_DRAFT, np.int32)
        stream = self._streams.get(slot)
        if not stream or len(stream) < self.ngram_min + 1:
            return out
        h = np.asarray(stream[-self.lookback:], np.int32)
        hi = min(self.ngram_max, len(h) - 1)
        for n in range(hi, self.ngram_min - 1, -1):
            # candidate starts 0 .. len-n-1: the window must END
            # before the stream's last token so at least one
            # continuation token exists (and the suffix itself —
            # start len-n — is excluded)
            m = len(h) - n
            if m <= 0:
                continue
            win = np.lib.stride_tricks.sliding_window_view(h, n)[:m]
            hits = np.flatnonzero((win == h[-n:]).all(axis=1))
            if hits.size:
                s = int(hits[-1])
                cont = h[s + n:s + n + self.draft_len]
                out[:len(cont)] = cont
                return out
        return out


def accept_count(accept_row: np.ndarray) -> int:
    """Length of the leading accepted prefix of one slot's verify
    result — the ``a`` of draft → verify → emit ``draft[:a] +
    [token[a]]``."""
    rej = np.flatnonzero(~np.asarray(accept_row, bool))
    return int(rej[0]) if rej.size else len(accept_row)


class TreeLookupDrafter(PromptLookupDrafter):
    """Prompt-lookup drafting over a TREE of candidate branches
    (SpecInfer/Sequoia-shaped): where the linear drafter commits the
    whole ``draft_len`` budget to the single most-recent match's
    continuation, this one groups the history's matches by their
    FIRST continuation token — when the stream is genuinely ambiguous
    (the same suffix n-gram has been followed by different tokens),
    up to ``width`` distinct continuations each get a branch off the
    root, and ONE fused verify pass scores them all (the accepted
    root-to-leaf path replaces the accepted prefix). When history
    shows exactly one continuation the tree degenerates to the linear
    drafter's chain BIT-FOR-BIT (same n-gram, same match, same
    continuation), so tree drafting never proposes worse than linear
    on unambiguous streams and strictly more on ambiguous ones.

    ``draft_tree(slot)`` returns ``(tokens, parents)``: ``tokens``
    the ``(draft_len,)`` NO_DRAFT-padded node tokens and ``parents``
    the ``(draft_len,)`` parent NODE indices — draft node ``j``
    (0-based over the draft row; verify input ``j + 1``) hangs off
    node ``parents[j] ∈ [0, j]``, node 0 being the root/pending
    token. Branches split only at the root and siblings carry
    DISTINCT first tokens (group keys), so at most one child of any
    node can ever be accepted — the accepted path is unique. The
    budget splits primary-heavy: side branches get
    ``max(1, draft_len // (2 * width))`` nodes each, the primary
    (most recent) branch the rest, so the common single-continuation
    regime keeps nearly the full linear depth."""

    def __init__(self, draft_len: int, ngram_min: int = 2,
                 ngram_max: int = 8, lookback: int = 4096,
                 width: int = 2):
        super().__init__(draft_len, ngram_min=ngram_min,
                         ngram_max=ngram_max, lookback=lookback)
        if not 2 <= width <= draft_len:
            raise ValueError(
                f"tree width must satisfy 2 <= width <= draft_len "
                f"({draft_len}), got {width}: one branch is the "
                "linear drafter, and every branch needs a node")
        self.width = width

    def draft_tree(self, slot: int) -> tuple[np.ndarray, np.ndarray]:
        k = self.draft_len
        tokens = np.full(k, NO_DRAFT, np.int32)
        # chain parents by default: node j + 1 hangs off node j — a
        # sentinel-only row still carries a valid topology
        parents = np.arange(k, dtype=np.int32)
        stream = self._streams.get(slot)
        if not stream or len(stream) < self.ngram_min + 1:
            return tokens, parents
        h = np.asarray(stream[-self.lookback:], np.int32)
        hi = min(self.ngram_max, len(h) - 1)
        for n in range(hi, self.ngram_min - 1, -1):
            m = len(h) - n
            if m <= 0:
                continue
            win = np.lib.stride_tricks.sliding_window_view(h, n)[:m]
            hits = np.flatnonzero((win == h[-n:]).all(axis=1))
            if not hits.size:
                continue
            # group matches by first continuation token, most recent
            # occurrence first — the group ORDER is the branch order
            # (primary = the linear drafter's own choice)
            groups: dict[int, int] = {}
            for s_i in hits[::-1]:
                c0 = int(h[int(s_i) + n])
                if c0 not in groups:
                    groups[c0] = int(s_i)
                if len(groups) == self.width:
                    break
            w = len(groups)
            side = max(1, k // (2 * w)) if w > 1 else 0
            node = 1
            for b, (_, s_i) in enumerate(groups.items()):
                depth = (k - side * (w - 1)) if b == 0 else side
                cont = h[s_i + n:s_i + n + depth]
                parent = 0
                for t in cont:
                    tokens[node - 1] = int(t)
                    parents[node - 1] = parent
                    parent = node
                    node += 1
            return tokens, parents
        return tokens, parents


def tree_masks(parents: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Host-side tree bookkeeping for the verify step: from per-slot
    parent vectors ``(n_slots, k)`` (draft node ``j`` hangs off node
    ``parents[:, j] ∈ [0, j]``), build ``depth (n_slots, S)`` — each
    node's distance from the root, its ROPE/embedding position offset
    — and ``vis (n_slots, S, S)`` — the ancestor-or-self matrix the
    visibility masks gather (``vis[s, j, i]``: node i's K/V is
    visible to node j's query). ``S = k + 1``, node 0 the root. The
    chain ``parents[:, j] = j`` yields ``depth = arange`` and
    ``vis[j, i] = i <= j`` — the linear masks bit-for-bit."""
    parents = np.asarray(parents, np.int32)
    n_slots, k = parents.shape
    S = k + 1
    depth = np.zeros((n_slots, S), np.int32)
    vis = np.zeros((n_slots, S, S), bool)
    vis[:, 0, 0] = True
    rows = np.arange(n_slots)
    for j in range(1, S):
        p = parents[:, j - 1]
        depth[:, j] = depth[rows, p] + 1
        vis[:, j] = vis[rows, p]
        vis[rows, j, j] = True
    return depth, vis


def tree_accept_path(accept_row: np.ndarray,
                     parents_row: np.ndarray) -> list[int]:
    """The best (unique) accepted root-to-leaf path of one slot's
    tree verify result, as node indices in root-to-leaf order
    (empty = nothing accepted; the bonus pick then comes from the
    root). ``accept_row[j]`` says draft node ``j + 1``'s token
    matched the model's pick at its parent; siblings carry distinct
    tokens by drafter construction, so at most one child of any node
    accepts and the walk is deterministic — on the chain topology
    this reduces to :func:`accept_count` exactly."""
    accept_row = np.asarray(accept_row, bool)
    parents_row = np.asarray(parents_row, np.int64)
    path: list[int] = []
    cur = 0
    while True:
        nxt = None
        for j in range(len(parents_row)):
            if parents_row[j] == cur and accept_row[j]:
                nxt = j + 1
                break
        if nxt is None:
            return path
        path.append(nxt)
        cur = nxt


def make_verify_fn(engine):
    """Build the engine's ONE compiled multi-token verify step.

    ``fn(params, pool_k, pool_v, operands, rng) -> (rng, accept,
    token, pool_k, pool_v)``: the ``_decode_fn`` convention —
    ``operands`` is the engine's packed buffer (the tables, the
    ``drafts``, the tree's ``parents`` / ``depth`` and the pallas
    backend's ``work_*`` walk are slices of it), ``rng`` its key, split
    here and handed back first. A tree engine's visibility matrix
    rides FIRST in ``extra``, a structured engine's per-position
    legality mask after it, the adapter stacks last. The step's
    ``in_ids`` are ``(max_slots, 1 + draft_len)``: column 0 each slot's
    pending token, columns 1.. the draft (``NO_DRAFT``-padded). Shapes
    depend ONLY on pool geometry, the model config, and the
    trace-time-fixed ``draft_len`` — slot churn, accept-length churn,
    and draft availability all change VALUES, so this compiles exactly
    once (the same zero-recompile contract as the decode step, and
    the engine's ``verify_compiles`` observable).

    Structure: embed every slot's ``k + 1`` inputs at its own depths,
    write all their K/V into the slot's pages (position ``lengths +
    j`` — always past the copy-on-write boundary; horizon-overflow
    and dead-slot writes divert to the reserved null page), then run
    the decode pool sweep with the draft positions riding the query
    axis beside the refs lanes: page × lane × position partials merge
    per (slot, position) with the same online-softmax segment combine,
    and every read comes back in POOL dtype — the intra-draft causal
    part included, which is exactly what a sequence of non-speculative
    steps would have read (greedy parity is therefore exact, int8
    pages included). The per-position pick/accept rule is
    ``_make_spec_pick`` (models/gpt.py) over the final logits.

    With ``engine.spec_tree`` the SAME executable verifies a TREE of
    candidate branches: three extra traced values — per-slot parent
    vectors ``(B, k)``, node depths ``(B, S)``, and the
    ancestor-or-self matrix ``(B, S, S)`` (``tree_masks``) — replace
    the chain's implicit ``arange`` structure. Node j still WRITES at
    storage position ``lengths + j`` (its private row), but ropes/
    embeds at its tree DEPTH and attends prior context plus its
    ancestors only; acceptance tests each node's token against the
    model's pick at its PARENT. All three are VALUES (the chain is
    ``parents = arange``), so adaptive per-step tree shapes recompile
    nothing. The accepted root-to-leaf path is compacted into
    contiguous positions by ``PagedEngine._compact_fn`` afterwards.
    """
    cfg, ps = engine.cfg, engine.page_size
    k = engine.draft_len
    S = k + 1
    head_dim = cfg.d_model // cfg.n_heads
    tree = bool(getattr(engine, "spec_tree", False))
    # per-shard head count under tensor-parallel serving
    # (serving/tp.py): == cfg.n_heads at tp=1, so the single-chip
    # trace is unchanged
    n_heads_l = cfg.n_heads // engine.tp
    spec_pick = _make_spec_pick(engine.temperature, engine.top_k,
                                engine.top_p, jnp.int32)

    def verify_fn(params, pool_k, pool_v, operands, rng, *extra):
        ops = engine.operands.unpack(operands)
        tables, lengths, refs, page_pos = (
            ops[name] for name in ("tables", "lengths", "refs",
                                   "page_pos"))
        active = ops["active"] != 0
        in_ids = jnp.concatenate(
            [ops["last_ids"][:, None], ops["drafts"]], axis=1)
        rng, sub = jax.random.split(rng)
        # None-init every mode operand (the _decode_fn convention):
        # the closures below reference them by name, and a use that
        # ever escaped its mode guard must fail as a loud None error,
        # not a NameError-at-trace trap for the next refactor
        t_parent = t_depth = t_vis = None
        work_pages = work_refs = work_pos = smask = None
        # the adapter stacks append LAST (spec_step), so strip from
        # the end FIRST — the front reads below keep their layout
        lora_w = lane_ids = None
        if engine.lora:
            lora_w, lane_ids = extra[-4:], ops["slot_lanes"]
            extra = extra[:-4]
        if tree:
            t_parent, t_depth = ops["parents"], ops["depth"]
            t_vis, extra = extra[0], extra[1:]
        if engine.decode_backend == "pallas":
            work_pages, work_refs, work_pos = (
                ops[name] for name in ("work_pages", "work_refs",
                                       "work_pos"))
        if engine.structured:
            # (max_slots, S, vocab) per-position legality rows from
            # the slot cursors' draft pre-validation (all-True for
            # unconstrained slots — bitwise no-op)
            smask = extra[0]
        n_slots = in_ids.shape[0]
        mp = tables.shape[1]
        # STORAGE positions (write targets): node j owns row
        # ``lengths + j`` whatever the topology; SEMANTIC positions
        # (rope/embedding): its tree depth — equal on the chain
        positions = lengths[:, None] + jnp.arange(S)     # (B, S)
        sem_pos = (lengths[:, None] + t_depth) if tree else positions
        # clipped twins for table lookups: sentinel ids embed as 0 and
        # horizon-overflow positions rope/embed at the last row — both
        # produce garbage that acceptance (host) and the null-page
        # write diversion below keep out of every live value
        pos_c = jnp.minimum(sem_pos, cfg.seq_len - 1)
        ids_c = jnp.clip(in_ids, 0, cfg.vocab - 1)

        with jax.named_scope("embed"):
            x = L.embedding(params["wte"], ids_c,
                            dtype=engine.compute_dtype)
            if "wpe" in params:
                x = x + L.embedding(params["wpe"], pos_c,
                                    dtype=engine.compute_dtype)

        # write targets per (slot, position): the page holding
        # ``lengths + j`` — private by construction (the cursor sits
        # past every shared prefix page); beyond the table (horizon)
        # or on a dead slot, the reserved null page absorbs the write
        pidx = positions // ps
        w_page = jnp.where(
            (pidx < mp) & active[:, None],
            tables[jnp.arange(n_slots)[:, None],
                   jnp.clip(pidx, 0, mp - 1)],
            NULL_PAGE)
        w_off = positions % ps

        if engine.decode_backend == "xla":
            # sweep bookkeeping, one (page, lane, position) partial
            # per element: exactly decode's (page, lane) routing with
            # the S verify positions riding the query axis — segment
            # ids key (slot, position) so the combine lands each
            # position's output in its own row; empty lanes divert to
            # the trash segment. (The pallas backend carries the same
            # (slot, position) state in kernel scratch — the mask rule
            # below lives in the kernel verbatim.)
            # (all n_pages pages, the never-referenced null page
            # included: engine._decode_fn says why)
            n_lanes = refs.shape[1]                       # refs (P, R)
            ref_c = jnp.clip(refs, 0, n_slots - 1)
            seg = jnp.where(refs[:, :, None] >= 0,
                            ref_c[:, :, None] * S + jnp.arange(S),
                            n_slots * S).reshape(-1)
            tok_pos = page_pos[:, None] * ps + jnp.arange(ps)[None, :]
            ref_len = jnp.where(refs >= 0, lengths[ref_c], -1)
            if not tree:
                # position j's query sees absolute positions <=
                # lengths + j: j = 0 is exactly the decode step's mask
                # (the pending token sees itself), each later draft
                # position one more — the intra-draft causal structure
                # falls out of the same rule
                visible = (tok_pos[:, None, None, :]
                           <= ref_len[:, :, None, None]
                           + jnp.arange(S)[None, None, :, None]
                           ).reshape(-1, n_lanes * S, ps)
            else:
                # tree masks: prior context (offset <= 0 — the root's
                # own write row included) is visible to every node;
                # a draft row at offset i in (0, S) only to nodes it
                # is an ancestor-or-self of (sibling branches never
                # attend each other)
                off = (tok_pos[:, None, :]
                       - ref_len[:, :, None])             # (P, R, ps)
                tvg = t_vis[ref_c]                        # (P,R,S,S)
                offc = jnp.clip(off, 0, S - 1)
                sel = jnp.take_along_axis(
                    tvg, jnp.broadcast_to(
                        offc[:, :, None, :],
                        offc.shape[:2] + (S, offc.shape[-1])),
                    axis=-1)                              # (P,R,S,ps)
                visible = ((off <= 0)[:, :, None, :]
                           | (((off > 0) & (off < S))[:, :, None, :]
                              & sel)).reshape(-1, n_lanes * S, ps)

        def layer(x, pk, pv, bp, li, lora):

            def attend(q, k_new, v_new):
                # q/k_new/v_new (n_slots, S, heads, Dh): write ALL
                # S positions' K/V first, sweep after — every read
                # (prior context AND intra-draft) comes back in pool
                # dtype, byte-identical to what S sequential
                # non-speculative steps would have read
                with jax.named_scope("kv_write"):
                    new_k = write_rows(pk, (li, w_page, w_off),
                                       engine._page_rows(k_new, pk))
                    new_v = write_rows(pv, (li, w_page, w_off),
                                       engine._page_rows(v_new, pv))
                if engine.decode_backend == "pallas":
                    # the fused kernel pass: all S verify positions
                    # ride the kernel's query-block axis, so ONE
                    # in-kernel table walk scores the whole burst —
                    # the mask tok_pos <= lengths + j (or the tree's
                    # ancestor-or-self matrix) and the (slot,
                    # position) state keying are the kernel's own
                    # (ops/paged_attention.py)
                    o = paged_attention(
                        q, engine._kernel_pages(new_k, li),
                        engine._kernel_pages(new_v, li), work_pages,
                        work_refs, work_pos, lengths, page_size=ps,
                        tree_vis=t_vis if tree else None)
                    return o.astype(q.dtype), (new_k, new_v)
                # ONE pool read serves all S positions of every lane:
                # queries gather to (P, R·S, H, Dh) — the small side —
                # while the pool stream stays exactly the decode
                # step's bytes
                q_lanes = q[ref_c].reshape(
                    ref_c.shape[0], n_lanes * S, n_heads_l, head_dim)
                o_p, m_p, l_p = sweep_attention(
                    q_lanes, layer_pages(new_k, li),
                    layer_pages(new_v, li), visible, k_new.shape[2])
                n_pp = o_p.shape[0]
                o_f = o_p.reshape(n_pp * n_lanes * S, *o_p.shape[2:])
                m_f = jnp.moveaxis(m_p, -1, 1).reshape(
                    n_pp * n_lanes * S, *m_p.shape[1:3])
                l_f = jnp.moveaxis(l_p, -1, 1).reshape(
                    n_pp * n_lanes * S, *l_p.shape[1:3])
                m_s = jax.ops.segment_max(
                    m_f, seg, num_segments=n_slots * S + 1)
                w = jnp.exp(m_f - m_s[seg])
                l_s = jax.ops.segment_sum(
                    l_f * w, seg, num_segments=n_slots * S + 1)
                o_s = jax.ops.segment_sum(
                    o_f * w[..., None], seg,
                    num_segments=n_slots * S + 1)
                o = o_s[:n_slots * S] / jnp.maximum(
                    l_s[:n_slots * S], 1e-30)[..., None]
                o = o.reshape(n_slots, S, n_heads_l, head_dim)
                return o.astype(q.dtype), (new_k, new_v)

            x, _, (pk, pv) = _block_core(
                bp, x, cfg, attend,
                capacity_factor=max(cfg.capacity_factor,
                                    float(cfg.n_experts)),
                positions=pos_c,                # per-slot rope depths
                tp_attn=engine._tp_core,
                lora=(lora, lane_ids) if engine.lora else None)
            return x, pk, pv

        # the verify sweep applies the SAME slot lanes the decode
        # step does, so accepted drafts are adapter-consistent
        x, pool_k, pool_v = scan_layers(layer, x, pool_k, pool_v,
                                        params["blocks"], lora_w)
        logits = _lm_head(params, x)            # (n_slots, S, vocab)
        # structured: mask every position's logits with its automaton
        # row BEFORE the pick/accept rule, so fallback and bonus
        # picks are legal by construction (drafts were pre-validated
        # host-side; the -1 sentinel never accepts)
        logits = _mask_logits(logits, smask)
        accept, token = spec_pick(sub, logits, in_ids[:, 1:],
                                  parent=t_parent if tree else None)
        return rng, accept, token, pool_k, pool_v

    return verify_fn


__all__ = ["NO_DRAFT", "PromptLookupDrafter", "TreeLookupDrafter",
           "accept_count", "make_verify_fn", "tree_accept_path",
           "tree_masks"]
