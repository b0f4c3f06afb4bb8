"""Serving engine (torchbooster_tpu/serving) on the CPU mesh:

- paged decode matches the dense ``jit_generate`` path token-for-token
  on decisive-head greedy decode (bf16 AND int8 pages — the acceptance
  parity);
- prefix-cache hits decode IDENTICAL tokens to the cold path (MHA+GQA,
  bf16+int8 pages), including two LIVE slots sharing the same prefix
  pages through the multi-lane decode sweep;
- chunked prefill compiles exactly ONE executable whatever prompt
  lengths arrive, and seat/retire/evict churn causes ZERO decode
  recompiles after warmup (the jit cache-size observables);
- block-table refcount/cache/free invariants hold under randomized
  churn with eviction (refcounts never negative, every page exactly
  one of referenced/cached/free);
- the continuous batcher preserves per-request tokens through
  admission waves, chunk-interleaved prefill, and pool-pressure
  preemption;
- speculative decoding (serving/speculative.py): greedy spec-on
  output is token-for-token identical to the non-speculative paged
  engine AND dense ``generate`` (MHA+GQA, bf16+int8 pages), exactly
  ONE verify-step compile across accept-length/slot churn, zero
  decode recompiles with speculation off, and the rewind invariants
  (length never below the copy-on-write boundary, no cached page past
  a rewound length) hold under randomized accept/reject/rewind churn.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.models.gpt import GPT, GPTConfig


def _decisive_model(n_kv_heads=2, seq_len=32):
    """Tiny GPT with a DECISIVE head (scaled-up tied embeddings widen
    argmax margins so bf16/int8 rounding cannot flip greedy picks —
    the same trick the dense int8 parity test uses)."""
    cfg = GPTConfig(vocab=97, n_layers=2, d_model=32, n_heads=4,
                    seq_len=seq_len, n_kv_heads=n_kv_heads)
    params = GPT.init(jax.random.PRNGKey(0), cfg)
    params = {**params, "wte": {"table": params["wte"]["table"] * 4.0}}
    return params, cfg


def _paged_tokens(engine, prompt, n_new):
    slot, first = engine.admit(prompt)
    toks = [first]
    for _ in range(n_new - 1):
        assert engine.grow_slots() == []
        toks.append(int(engine.step()[slot]))
    engine.retire(slot)
    return toks


@pytest.mark.parametrize("compute_dtype,cache_dtype", [
    (jnp.float32, None),
    (jnp.bfloat16, None),
    (jnp.bfloat16, "int8"),   # the acceptance pair; fp32+int8 adds
])                            # nothing the sharded-params test lacks
def test_paged_decode_matches_dense_jit_generate(compute_dtype,
                                                 cache_dtype):
    """The acceptance parity: paged greedy decode == dense
    ``jit_generate`` token-for-token, bf16 and int8 pages, GQA model."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 5), 0,
                             cfg.vocab)
    n_new = 8
    want = GPT.generate(params, ids, cfg, n_new=n_new, temperature=0.0,
                        compute_dtype=compute_dtype,
                        cache_dtype=cache_dtype)
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, cache_dtype=cache_dtype,
                         compute_dtype=compute_dtype)
    got = _paged_tokens(engine, np.asarray(ids[0]), n_new)
    np.testing.assert_array_equal(np.asarray(want[0, 5:]), got)
    engine.tables.check()


def test_paged_decode_matches_dense_mha():
    """Same parity on the full-MHA cache width (kv_heads == n_heads)."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model(n_kv_heads=0)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 7), 0,
                             cfg.vocab)
    want = GPT.generate(params, ids, cfg, n_new=6, temperature=0.0,
                        compute_dtype=jnp.float32)
    engine = PagedEngine(params, cfg, page_size=8, n_pages=8,
                         max_slots=2, compute_dtype=jnp.float32)
    got = _paged_tokens(engine, np.asarray(ids[0]), 6)
    np.testing.assert_array_equal(np.asarray(want[0, 7:]), got)


@pytest.mark.parametrize("compute_dtype,cache_dtype,kv", [
    (jnp.float32, None, 2),
    (jnp.bfloat16, None, 2),
    (jnp.bfloat16, "int8", 2),     # the acceptance pair
    (jnp.float32, None, 0),        # full-MHA cache width
])
def test_prefix_cache_hit_token_parity(compute_dtype, cache_dtype, kv):
    """The tentpole acceptance parity: with ``prefix_cache`` enabled,
    a request whose prompt prefix is resident (mapped pages, only the
    tail re-prefilled) decodes IDENTICAL tokens to the same request
    served cold — and both match dense ``generate`` — across MHA+GQA
    and bf16+int8 pages. Covers a SECOND request sharing the prefix
    but continuing with a different suffix (the shared-system-prompt
    traffic shape)."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model(n_kv_heads=kv)
    rs = np.random.RandomState(0)
    shared = rs.randint(0, 97, 8).astype(np.int32)     # 2 full pages
    suf_a = rs.randint(0, 97, 3).astype(np.int32)
    suf_b = rs.randint(0, 97, 3).astype(np.int32)
    p_a = np.concatenate([shared, suf_a])
    p_b = np.concatenate([shared, suf_b])
    n_new = 6

    def dense(prompt):
        out = GPT.generate(params, jnp.asarray(prompt)[None], cfg,
                           n_new=n_new, temperature=0.0,
                           compute_dtype=compute_dtype,
                           cache_dtype=cache_dtype)
        return np.asarray(out)[0, len(prompt):]

    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, cache_dtype=cache_dtype,
                         compute_dtype=compute_dtype,
                         prefix_cache=True, prefill_chunk_pages=1)
    cold_a = _paged_tokens(engine, p_a, n_new)     # fills the cache
    assert engine.prefix_hit_pages == 0
    hot_a = _paged_tokens(engine, p_a, n_new)      # full-prefix hit
    assert engine.prefix_hit_pages == 2            # both shared pages
    hot_b = _paged_tokens(engine, p_b, n_new)      # shared-prefix hit
    assert engine.prefix_hit_pages == 4
    np.testing.assert_array_equal(dense(p_a), cold_a)
    np.testing.assert_array_equal(cold_a, hot_a)
    np.testing.assert_array_equal(dense(p_b), hot_b)
    engine.tables.check()
    assert engine.prefill_compiles == 1
    assert engine.decode_compiles == 1


def test_concurrent_prefix_sharing_decode_parity():
    """TWO live slots share the same resident prefix pages DURING
    decode (refcount 2 — the multi-lane sweep must serve one page to
    both queries from the one pool read); each request's greedy
    stream matches its dense reference."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    rs = np.random.RandomState(1)
    shared = rs.randint(0, 97, 8).astype(np.int32)
    p_a = np.concatenate([shared, rs.randint(0, 97, 3).astype(np.int32)])
    p_b = np.concatenate([shared, rs.randint(0, 97, 5).astype(np.int32)])
    n_new = 6

    def dense(prompt):
        out = GPT.generate(params, jnp.asarray(prompt)[None], cfg,
                           n_new=n_new, temperature=0.0,
                           compute_dtype=jnp.float32)
        return np.asarray(out)[0, len(prompt):]

    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, compute_dtype=jnp.float32,
                         prefix_cache=True, prefill_chunk_pages=1)
    prime = _paged_tokens(engine, p_a, 2)          # registers prefix
    del prime
    slot_a, first_a = engine.admit(p_a)
    slot_b, first_b = engine.admit(p_b)
    assert int(engine.tables.refcount.max()) >= 2, (
        "live slots did not share the prefix pages")
    toks_a, toks_b = [first_a], [first_b]
    for _ in range(n_new - 1):
        assert engine.grow_slots() == []
        t = engine.step()
        toks_a.append(int(t[slot_a]))
        toks_b.append(int(t[slot_b]))
    np.testing.assert_array_equal(dense(p_a), toks_a)
    np.testing.assert_array_equal(dense(p_b), toks_b)
    engine.retire(slot_a)
    engine.retire(slot_b)
    engine.tables.check()
    assert engine.decode_compiles == 1


def test_admit_retire_zero_recompiles():
    """The zero-recompile acceptance: after the first decode step
    compiles, slot churn — admits at NEW prompt lengths, retires,
    re-admits into freed slots, crossing page boundaries — leaves the
    decode executable count at exactly 1."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    engine = PagedEngine(params, cfg, page_size=4, n_pages=24,
                         max_slots=3, compute_dtype=jnp.float32)
    rng = np.random.RandomState(0)

    slot_a, _ = engine.admit(rng.randint(0, 97, 5))
    engine.grow_slots()
    engine.step()                       # warmup: the ONE compile
    assert engine.decode_compiles == 1

    # churn: different prompt lengths, staggered admits/retires
    slot_b, _ = engine.admit(rng.randint(0, 97, 9))
    for _ in range(4):
        assert engine.grow_slots() == []
        engine.step()
    engine.retire(slot_a)
    slot_c, _ = engine.admit(rng.randint(0, 97, 3))
    assert slot_c == slot_a             # freed slot reused
    for _ in range(6):                  # crosses page boundaries
        assert engine.grow_slots() == []
        engine.step()
    engine.retire(slot_b)
    engine.retire(slot_c)
    engine.tables.check()
    assert engine.decode_compiles == 1, (
        "slot churn recompiled the decode step")


def test_chunked_prefill_one_compile_and_evict_churn_zero_recompiles():
    """Chunked-prefill acceptance: whatever prompt-length mix arrives
    — crossing chunk boundaries, cache hits starting mid-prompt,
    preemption-style re-admits — the prefill executable count stays
    at exactly 1 (the old page-count-shaped prefill compiled one per
    count), and seat/retire/EVICT churn with the prefix cache on
    leaves the decode executable count at exactly 1."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()                 # seq_len = 32
    rng = np.random.RandomState(3)
    shared = rng.randint(0, 97, 8).astype(np.int32)
    # tight pool: 9 usable pages = 36 tokens; cached prefixes MUST
    # evict to seat the unrelated prompts
    engine = PagedEngine(params, cfg, page_size=4, n_pages=10,
                         max_slots=2, compute_dtype=jnp.float32,
                         prefix_cache=True, prefill_chunk_pages=2)
    saw_cached = saw_evict = False
    for n in (3, 5, 9, 13, 17):       # 1..3 chunks, partial + exact
        prompt = (np.concatenate(
            [shared, rng.randint(0, 97, n - 8).astype(np.int32)])
            if n > 8 else rng.randint(0, 97, n).astype(np.int32))
        slot, _ = engine.admit(prompt)
        for _ in range(3):
            assert engine.grow_slots() == []
            engine.step()
        engine.retire(slot)
        cached = engine.tables.n_cached_pages
        saw_cached |= cached > 0
        engine.tables.check()
    # unrelated full-width prompts force LRU eviction of the cache
    before = engine.tables.n_cached_pages
    slot, _ = engine.admit(rng.randint(0, 97, 17).astype(np.int32))
    slot2, _ = engine.admit(rng.randint(0, 97, 13).astype(np.int32))
    saw_evict = engine.tables.n_cached_pages < before
    for _ in range(3):
        assert engine.grow_slots() == []
        engine.step()
    engine.retire(slot)
    engine.retire(slot2)
    engine.tables.check()
    assert saw_cached, "retire never cached a prefix"
    assert saw_evict, "pool pressure never evicted the cache"
    assert engine.prefill_compiles == 1, (
        "prompt-length mix recompiled the prefill chunk")
    assert engine.decode_compiles == 1, (
        "seat/retire/evict churn recompiled the decode step")


def test_block_tables_churn_invariants():
    """Randomized seat/grow/advance/retire churn (cache off — plain
    alloc/free): structural invariants (page 0 reserved, no
    double-assignment, no leaks, refs/page_pos consistent) hold after
    every operation."""
    from torchbooster_tpu.serving import BlockTables, NULL_PAGE

    cfg = GPTConfig(seq_len=64)
    bt = BlockTables(cfg, page_size=4, n_pages=32, max_slots=4)
    rng = np.random.RandomState(7)
    live = {}
    for op in range(300):
        roll = rng.rand()
        slot = bt.free_slot()
        if roll < 0.35 and slot is not None:
            n = int(rng.randint(1, 12))
            if bt.pages_for(n) <= bt.n_free_pages:
                bt.seat(slot, rng.randint(0, 97, n).astype(np.int32))
                bt.activate(slot, int(rng.randint(0, 97)))
                live[slot] = n
        elif roll < 0.8 and live:
            slot = int(rng.choice(sorted(live)))
            if bt.lengths[slot] < cfg.seq_len and \
                    bt.ensure_next_page(slot):
                bt.advance(slot, int(rng.randint(0, 97)))
        elif live:
            slot = int(rng.choice(sorted(live)))
            bt.retire(slot)
            del live[slot]
        bt.check()
    for slot in list(live):
        bt.retire(slot)
    bt.check()
    assert bt.n_free_pages == bt.n_pages - 1   # everything returned
    assert (bt.tables == NULL_PAGE).all()


def test_block_tables_prefix_refcount_eviction_churn():
    """Randomized churn WITH the prefix cache on (the tentpole's
    page-lifetime acceptance): most prompts share a 3-page prefix, so
    seats hit the index (refcount > 1 on shared pages while several
    sharers are live), retires cache rather than free, and the tight
    pool forces LRU eviction. ``check()`` after every op asserts
    refcounts never go negative, every page is exactly one of
    referenced/cached/free (no leaks), and index/page_pos stay
    consistent."""
    from torchbooster_tpu.serving import BlockTables, NULL_PAGE

    cfg = GPTConfig(seq_len=64)
    bt = BlockTables(cfg, page_size=4, n_pages=24, max_slots=4,
                     prefix_cache=True)
    rng = np.random.RandomState(11)
    shared = rng.randint(0, 97, 12).astype(np.int32)   # 3 full pages
    live = {}
    hits = 0
    saw_shared_live = False
    saw_cached = False
    for op in range(400):
        roll = rng.rand()
        slot = bt.free_slot()
        if roll < 0.4 and slot is not None:
            n_suffix = int(rng.randint(1, 16))
            tail = rng.randint(0, 97, n_suffix).astype(np.int32)
            prompt = (np.concatenate([shared, tail])
                      if rng.rand() < 0.7 else tail)
            if bt.pages_for(len(prompt)) <= bt.n_available_pages:
                _, matched = bt.seat(slot, prompt)
                hits += matched
                bt.activate(slot, int(rng.randint(0, 97)))
                bt.register_prefix(slot, prompt)
                live[slot] = True
        elif roll < 0.8 and live:
            slot = int(rng.choice(sorted(live)))
            if bt.lengths[slot] < cfg.seq_len and \
                    bt.ensure_next_page(slot):
                bt.advance(slot, int(rng.randint(0, 97)))
        elif live:
            slot = int(rng.choice(sorted(live)))
            bt.retire(slot)
            del live[slot]
        saw_shared_live |= bool((bt.refcount > 1).any())
        saw_cached |= bt.n_cached_pages > 0
        bt.check()
    assert hits > 0, "the shared prefix never hit the index"
    assert saw_shared_live, "no page was ever shared by live slots"
    assert saw_cached, "retire never left a cached prefix resident"
    for slot in list(live):
        bt.retire(slot)
    bt.check()
    # everything is reclaimable: free + cached covers the whole pool
    assert bt.n_available_pages == bt.n_pages - 1
    assert (bt.tables == NULL_PAGE).all()
    assert (bt.refcount == 0).all()


def test_block_tables_validation():
    from torchbooster_tpu.serving import BlockTables

    cfg = GPTConfig(seq_len=64)
    bt = BlockTables(cfg, page_size=4, n_pages=8, max_slots=2)
    with pytest.raises(ValueError, match="prompt"):
        bt.seat(0, np.zeros(0, np.int32))
    with pytest.raises(ValueError, match="prompt"):
        bt.seat(0, np.zeros(64, np.int32))
    bt.seat(0, np.arange(5, dtype=np.int32))
    bt.activate(0, 1)
    with pytest.raises(ValueError, match="occupied"):
        bt.seat(0, np.arange(3, dtype=np.int32))
    with pytest.raises(RuntimeError, match="exhausted"):
        bt.seat(1, np.arange(25, dtype=np.int32))  # 7 needed, 5 free
    with pytest.raises(ValueError, match="not seated"):
        bt.activate(1, 1)
    bt.check()


def test_engine_validation():
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    with pytest.raises(ValueError, match="page_size"):
        PagedEngine(params, cfg, page_size=5)   # 5 does not divide 32
    with pytest.raises(ValueError, match="cache_dtype"):
        PagedEngine(params, cfg, page_size=4, cache_dtype="int4")


def test_batcher_end_to_end_and_preemption():
    """Continuous batching over more requests than slots: every
    request decodes the SAME greedy tokens as the single-sequence
    reference, through admission waves AND through pool-pressure
    preemption (the pool below holds ~1.5 sequences, so slots preempt
    and resume via re-prefill — greedy fp32 decode must be exactly
    reproducible across that round trip)."""
    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()
    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 5), 0,
                             cfg.vocab)
    n_new = 8
    want = np.asarray(GPT.generate(params, ids, cfg, n_new=n_new,
                                   temperature=0.0,
                                   compute_dtype=jnp.float32))[0, 5:]

    # ample pool: plain admission waves (5 requests over 2 slots)
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, compute_dtype=jnp.float32)
    reqs = [Request(prompt=np.asarray(ids[0]), max_new_tokens=n_new)
            for _ in range(5)]
    metrics = ContinuousBatcher(engine).run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(want, r.tokens)
    assert metrics["n_requests"] == 5
    assert metrics["new_tokens"] == 5 * n_new
    assert metrics["decode_tok_s"] > 0
    assert engine.decode_compiles == 1
    engine.tables.check()

    # tight pool: (5-1)*4 = 16 tokens for two 13-token sequences —
    # growth starves, the youngest preempts and later resumes
    engine = PagedEngine(params, cfg, page_size=4, n_pages=5,
                         max_slots=2, compute_dtype=jnp.float32)
    reqs = [Request(prompt=np.asarray(ids[0]), max_new_tokens=n_new)
            for _ in range(3)]
    ContinuousBatcher(engine).run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(want, r.tokens)
    engine.tables.check()
    assert engine.tables.n_free_pages == engine.n_pages - 1


def test_batcher_preemption_near_horizon_keeps_full_output():
    """Regression: preemption folds generated tokens into the prompt
    for the re-prefill, and the horizon check must count the ORIGINAL
    prompt + tokens (base_len), not the grown prompt — the grown form
    double-counts and silently truncates requests whose prompt +
    max_new_tokens sits at the cache horizon."""
    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()          # seq_len = 32
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (10,),
                                        0, cfg.vocab))
    n_new = 22                               # 10 + 22 == seq_len exactly
    want = np.asarray(GPT.generate(params, ids[None], cfg, n_new=n_new,
                                   temperature=0.0,
                                   compute_dtype=jnp.float32))[0, 10:]
    # pool fits one 32-token sequence (8 pages) + 1: two concurrent
    # requests MUST preempt while both are mid-generation
    engine = PagedEngine(params, cfg, page_size=4, n_pages=10,
                         max_slots=2, compute_dtype=jnp.float32)
    reqs = [Request(prompt=ids, max_new_tokens=n_new) for _ in range(2)]
    ContinuousBatcher(engine).run(reqs)
    for r in reqs:
        assert len(r.tokens) == n_new, (
            f"request truncated at {len(r.tokens)}/{n_new} tokens")
        np.testing.assert_array_equal(want, r.tokens)
    engine.tables.check()


def test_batcher_repeated_preemption_folds_each_token_once():
    """Regression: a request preempted MORE THAN ONCE must fold only
    the not-yet-folded token suffix into its prompt — re-folding the
    whole cumulative tokens list duplicated context (and inflated the
    prompt past ``base_len + len(tokens)``, eventually past seq_len).
    Three 24-token requests over 8 usable pages (32 tokens) churn
    through repeated preemption rounds; every request must still
    deliver its full output, token-exact vs the dense reference, and
    every prompt must satisfy prompt == original ++ folded-prefix of
    tokens."""
    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()          # seq_len = 32
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (4,),
                                        0, cfg.vocab))
    n_new = 20
    want = np.asarray(GPT.generate(params, ids[None], cfg, n_new=n_new,
                                   temperature=0.0,
                                   compute_dtype=jnp.float32))[0, 4:]
    engine = PagedEngine(params, cfg, page_size=4, n_pages=9,
                         max_slots=3, compute_dtype=jnp.float32)
    reqs = [Request(prompt=ids, max_new_tokens=n_new) for _ in range(3)]
    ContinuousBatcher(engine).run(reqs)
    for r in reqs:
        assert len(r.tokens) == n_new
        np.testing.assert_array_equal(want, r.tokens)
        folded = len(r.prompt) - r.base_len
        assert 0 <= folded <= len(r.tokens), (
            f"prompt grew past base_len + generated ({folded} folded, "
            f"{len(r.tokens)} generated) — tokens folded twice")
        np.testing.assert_array_equal(r.prompt[:r.base_len], ids)
        np.testing.assert_array_equal(r.prompt[r.base_len:],
                                      r.tokens[:folded])
    engine.tables.check()
    assert engine.tables.n_free_pages == engine.n_pages - 1


def test_admit_begin_matched_pages_not_counted_as_capacity():
    """Review regression: the admission quick-check counts CACHED
    matched pages as available capacity, but mapping them makes them
    un-evictable — under an exactly-full pool the private-tail
    allocation then comes up short. admit_begin must return None (the
    request stays queued; seat's rollback re-caches the shares), not
    crash the batcher with RuntimeError."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    rs = np.random.RandomState(4)
    shared = rs.randint(0, 97, 8).astype(np.int32)
    engine = PagedEngine(params, cfg, page_size=4, n_pages=5,
                         max_slots=2, compute_dtype=jnp.float32,
                         prefix_cache=True, prefill_chunk_pages=1)
    # cache the 2-page shared prefix (9-token prompt: 2 full + 1
    # partial page; retire caches the 2 registered, frees the third)
    slot, _ = engine.admit(np.concatenate(
        [shared, rs.randint(0, 97, 1).astype(np.int32)]))
    engine.retire(slot)
    assert engine.tables.n_cached_pages == 2
    # an unrelated live request consumes the remaining 2 free pages
    slot_a, _ = engine.admit(rs.randint(0, 97, 7).astype(np.int32))
    assert engine.tables.n_free_pages == 0
    # 15-token prompt matching the cached prefix: pages_for=4,
    # matched=2, and the other 2 exist neither free nor evictable
    # once the matched pair is mapped
    got = engine.admit_begin(np.concatenate(
        [shared, rs.randint(0, 97, 7).astype(np.int32)]))
    assert got is None
    engine.tables.check()                  # rollback left no damage
    assert engine.tables.n_cached_pages == 2
    engine.retire(slot_a)
    engine.tables.check()
    # the rollback re-cached the shares TAIL-FIRST (like retire):
    # evicting one page must shrink the chain from its tail — a
    # decapitated chain would make the cached remainder unmatchable
    assert engine.tables._evict(1) == 1
    probe = np.concatenate([shared, rs.randint(0, 97, 1).astype(np.int32)])
    assert engine.tables.match_prefix(probe) == 1
    engine.tables.check()


@pytest.mark.slow     # heavy on the 1-cpu rig; coverage kept by cheaper tier-1 tests (870s budget)
def test_batcher_prefix_cache_shared_prompt_end_to_end():
    """Continuous batching with the prefix cache + chunked prefill on,
    over the shared-system-prompt traffic shape (one shared prefix,
    per-request suffixes, more requests than slots): every request
    decodes the SAME greedy tokens as its single-sequence dense
    reference, later admissions hit the cache, and the metrics dict
    reports the hit/chunk stats with its stable key set."""
    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()
    rs = np.random.RandomState(2)
    shared = rs.randint(0, 97, 8).astype(np.int32)
    suffixes = [rs.randint(0, 97, n).astype(np.int32)
                for n in (3, 5, 3, 7)]
    prompts = [np.concatenate([shared, s]) for s in suffixes]
    n_new = 6

    def dense(prompt):
        out = GPT.generate(params, jnp.asarray(prompt)[None], cfg,
                           n_new=n_new, temperature=0.0,
                           compute_dtype=jnp.float32)
        return np.asarray(out)[0, len(prompt):]

    engine = PagedEngine(params, cfg, page_size=4, n_pages=24,
                         max_slots=2, compute_dtype=jnp.float32,
                         prefix_cache=True, prefill_chunk_pages=1)
    reqs = [Request(prompt=p, max_new_tokens=n_new) for p in prompts]
    metrics = ContinuousBatcher(engine).run(reqs)
    for p, r in zip(prompts, reqs):
        np.testing.assert_array_equal(dense(p), r.tokens)
    # the first admission wave (2 slots) is cold — the index fills
    # when the first prefill completes; every later admission hits
    # both shared pages
    assert metrics["prefix_hit_pages"] >= 4
    assert 0 < metrics["prefix_hit_rate"] <= 1
    assert metrics["n_prefill_chunks"] > 0
    # the chunk program alone, and with the decode lanes riding
    assert 1 <= engine.prefill_compiles <= 2
    assert engine.decode_compiles == 1
    engine.tables.check()

    # empty trace keeps the stable key set (incl. the new stats)
    empty = ContinuousBatcher(engine).run([])
    for key in ("n_prefill_chunks", "prefix_hit_pages",
                "prefix_hit_rate"):
        assert key in empty and key in metrics


def test_batcher_cancels_stale_pending_prefills_from_aborted_run():
    """A run() that aborts mid-loop (engine error, interrupt) can
    leave the ENGINE holding half-prefilled slots — cross-run state
    chunked prefill introduced. A fresh run() must cancel them up
    front: their requests belong to the dead trace, and letting
    prefill_step complete a slot this run never seated would KeyError
    the batcher's filling dict (regression)."""
    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()
    rs = np.random.RandomState(4)
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, compute_dtype=jnp.float32,
                         prefill_chunk_pages=1)
    # simulate the aborted run: seat a request and advance its
    # prefill PARTWAY, then abandon it (no batcher bookkeeping)
    stale = rs.randint(0, 97, 9).astype(np.int32)   # 3 chunks
    slot = engine.admit_begin(stale)
    assert slot is not None
    assert engine.prefill_step() is None            # 1 of 3 chunks
    assert engine.has_pending
    free_before = engine.tables.n_free_pages

    prompt = rs.randint(0, 97, 5).astype(np.int32)
    n_new = 4
    want = np.asarray(GPT.generate(params, jnp.asarray(prompt)[None],
                                   cfg, n_new=n_new, temperature=0.0,
                                   compute_dtype=jnp.float32)
                      )[0, len(prompt):]
    req = Request(prompt=prompt, max_new_tokens=n_new)
    ContinuousBatcher(engine).run([req])
    np.testing.assert_array_equal(want, req.tokens)
    assert not engine.has_pending
    # the stale slot's pages were reclaimed, not leaked
    assert engine.tables.n_free_pages > free_before
    assert (engine.tables.lengths == 0).all()
    engine.tables.check()


def test_batcher_eos_and_fit_validation():
    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()
    ids = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (5,), 0, cfg.vocab))
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, compute_dtype=jnp.float32)
    batcher = ContinuousBatcher(engine)

    want = np.asarray(GPT.generate(params, ids[None], cfg, n_new=8,
                                   temperature=0.0,
                                   compute_dtype=jnp.float32))[0, 5:]
    # generation stops AT the eos token, inclusive (the decisive tiny
    # model repeats one token, so the greedy stream hits eos first at
    # position 0); a non-occurring eos never stops early
    req = Request(prompt=ids, max_new_tokens=8, eos_id=int(want[0]))
    batcher.run([req])
    np.testing.assert_array_equal(want[:1], req.tokens)
    absent = int(next(t for t in range(cfg.vocab)
                      if t not in set(want.tolist())))
    req2 = Request(prompt=ids, max_new_tokens=8, eos_id=absent)
    batcher.run([req2])
    np.testing.assert_array_equal(want, req2.tokens)

    with pytest.raises(ValueError, match="seq_len"):
        batcher.run([Request(prompt=ids, max_new_tokens=1000)])
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(prompt=ids, max_new_tokens=0)
    with pytest.raises(ValueError, match="empty"):
        Request(prompt=np.zeros(0, np.int32))


# ---- speculative decoding (serving/speculative.py) -----------------

def _spec_tokens(engine, prompt, n_new):
    """Drive a speculative engine one verify step at a time; returns
    the first ``n_new`` emitted tokens."""
    slot, first = engine.admit(prompt)
    toks = [first]
    while len(toks) < n_new:
        assert engine.grow_slots() == []
        toks.extend(engine.spec_step()[slot])
    engine.retire(slot)
    return toks[:n_new]


def _repetitive_prompt(rs, n_base=3, reps=3):
    return np.tile(rs.randint(0, 97, n_base).astype(np.int32), reps)


@pytest.mark.parametrize("compute_dtype,cache_dtype,kv", [
    # each param compiles a dense generate + two engines (~12s on the
    # CPU rig), so only the widest-coverage pair rides tier-1; the
    # rest keep full MHA/GQA × bf16/int8/fp32 coverage in the slow
    # suite (the PR 1 precedent for the 870s tier-1 budget)
    pytest.param(jnp.float32, None, 2, marks=pytest.mark.slow),
    pytest.param(jnp.bfloat16, None, 2, marks=pytest.mark.slow),
    (jnp.bfloat16, "int8", 2),     # the acceptance pair
    pytest.param(jnp.float32, None, 0,      # full-MHA cache width
                 marks=pytest.mark.slow),
])
def test_spec_greedy_parity(compute_dtype, cache_dtype, kv):
    """The tentpole acceptance parity: speculative greedy decode is
    token-for-token identical to the NON-speculative paged engine and
    the dense control, across MHA+GQA and bf16+int8 pages — the
    verify step reads every byte (prior context AND intra-draft) back
    from the pool in pool dtype, exactly what sequential steps read.
    A repetitive prompt makes prompt-lookup drafts actually accept
    (asserted), so the multi-token path is exercised for real."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model(n_kv_heads=kv)
    prompt = _repetitive_prompt(np.random.RandomState(0))
    n_new = 12
    want = GPT.generate(params, jnp.asarray(prompt)[None], cfg,
                        n_new=n_new, temperature=0.0,
                        compute_dtype=compute_dtype,
                        cache_dtype=cache_dtype)
    want = np.asarray(want)[0, len(prompt):]
    kw = dict(page_size=4, n_pages=16, max_slots=2,
              cache_dtype=cache_dtype, compute_dtype=compute_dtype)
    cold = PagedEngine(params, cfg, **kw)
    got_cold = _paged_tokens(cold, prompt, n_new)
    spec = PagedEngine(params, cfg, speculative=True, draft_len=3,
                       **kw)
    got_spec = _spec_tokens(spec, prompt, n_new)
    np.testing.assert_array_equal(want, got_cold)
    np.testing.assert_array_equal(want, got_spec)
    assert spec.spec_accepted > 0, (
        "the repetitive stream never accepted a draft — the "
        "multi-token path was not exercised")
    assert spec.verify_compiles == 1
    assert spec.decode_compiles == 0    # spec decode never traces it
    assert cold.verify_compiles == 0    # no verify artifact when off
    spec.tables.check()


def test_spec_one_verify_compile_accept_length_churn():
    """The zero-recompile acceptance: one verify executable across a
    randomized trace of admits/retires with wildly varying accept
    lengths (repetitive prompts accept multi-token bursts, random
    prompts draft nothing and sentinel-pad, near-horizon slots cap
    their drafts) — draft_len is a trace-time constant, everything
    else is values."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()                 # seq_len = 32
    rs = np.random.RandomState(5)
    engine = PagedEngine(params, cfg, page_size=4, n_pages=24,
                         max_slots=3, compute_dtype=jnp.float32,
                         speculative=True, draft_len=3)
    accept_lens = set()
    for trial in range(4):
        prompts = [_repetitive_prompt(rs),
                   rs.randint(0, 97, int(rs.randint(3, 9))
                              ).astype(np.int32)]
        slots = {engine.admit(p)[0] for p in prompts}
        for _ in range(5):
            assert engine.grow_slots() == []
            out = engine.spec_step()
            accept_lens.update(len(v) for v in out.values())
            engine.tables.check()
        for slot in slots:
            engine.retire(slot)
        engine.tables.check()
    assert len(accept_lens) > 1, (
        "every step emitted the same burst length — churn too tame "
        "to prove accept-length independence")
    assert engine.verify_compiles == 1, (
        "accept-length/slot churn recompiled the verify step")
    assert engine.decode_compiles == 0


@pytest.mark.slow
def test_spec_near_horizon_caps_draft_and_retires_clean():
    """A slot whose remaining horizon is smaller than draft_len must
    sentinel-cap its draft (the verify step diverts overflow writes
    to the null page) and never advance past seq_len."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()                 # seq_len = 32
    rs = np.random.RandomState(8)
    prompt = np.tile(rs.randint(0, 97, 2).astype(np.int32), 13)  # 26
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=1, compute_dtype=jnp.float32,
                         speculative=True, draft_len=3)
    slot, first = engine.admit(prompt)
    toks = [first]
    while int(engine.tables.lengths[slot]) < cfg.seq_len:
        assert engine.grow_slots() == []
        toks.extend(engine.spec_step()[slot])
        engine.tables.check()
    assert int(engine.tables.lengths[slot]) == cfg.seq_len
    want = np.asarray(GPT.generate(
        params, jnp.asarray(prompt)[None], cfg,
        n_new=cfg.seq_len - len(prompt), temperature=0.0,
        compute_dtype=jnp.float32))[0, len(prompt):]
    np.testing.assert_array_equal(want, toks[:len(want)])
    engine.retire(slot)
    engine.tables.check()
    assert engine.verify_compiles == 1


@pytest.mark.slow
def test_spec_with_prefix_cache_batcher_end_to_end():
    """Speculation composes with the prefix cache: shared-prompt
    requests hit cached pages AND decode speculatively — every
    request matches its dense reference, the rewind never touches a
    shared page (check() asserts the copy-on-write boundary), and
    the metrics dict carries the n_spec_* stable keys with real
    values."""
    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()
    rs = np.random.RandomState(2)
    shared = np.tile(rs.randint(0, 97, 4).astype(np.int32), 2)  # 8
    prompts = [np.concatenate([shared,
                               rs.randint(0, 97, n).astype(np.int32)])
               for n in (3, 5, 3)]
    n_new = 8

    def dense(prompt):
        out = GPT.generate(params, jnp.asarray(prompt)[None], cfg,
                           n_new=n_new, temperature=0.0,
                           compute_dtype=jnp.float32)
        return np.asarray(out)[0, len(prompt):]

    engine = PagedEngine(params, cfg, page_size=4, n_pages=24,
                         max_slots=2, compute_dtype=jnp.float32,
                         prefix_cache=True, prefill_chunk_pages=1,
                         speculative=True, draft_len=3)
    reqs = [Request(prompt=p, max_new_tokens=n_new) for p in prompts]
    metrics = ContinuousBatcher(engine).run(reqs)
    for p, r in zip(prompts, reqs):
        np.testing.assert_array_equal(dense(p), r.tokens)
    assert metrics["n_spec_steps"] > 0
    assert metrics["n_spec_proposed"] >= metrics["n_spec_accepted"] > 0
    assert 0 < metrics["spec_accept_rate"] <= 1
    assert metrics["spec_mean_accepted"] > 0
    assert metrics["prefix_hit_pages"] > 0   # the cache really hit
    assert engine.verify_compiles == 1
    assert engine.decode_compiles == 0
    engine.tables.check()


def test_spec_fit_check_reserves_write_ahead():
    """Admission must reserve the speculative write-ahead:
    ``grow_slots`` demands ``1 + draft_len`` positions past the
    cursor before EVERY step, so a request whose worst-case output
    fits the pool exactly would starve on its last page and
    preempt-thrash itself (one full re-prefill per emitted token).
    ``_check_fits`` rejects it loudly; one page more and the same
    request completes with zero preemptions and greedy parity."""
    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()                 # seq_len = 32
    prompt = _repetitive_prompt(np.random.RandomState(3), reps=4)
    kw = dict(page_size=4, max_slots=1, compute_dtype=jnp.float32,
              speculative=True, draft_len=3)
    # worst = 12 prompt + 4 output = 16 tokens = exactly the 4 usable
    # pages — but the write-ahead peaks at 16 + 3 = 19 positions
    tight = ContinuousBatcher(PagedEngine(params, cfg, n_pages=5,
                                          **kw))
    with pytest.raises(ValueError, match="write-ahead"):
        tight.run([Request(prompt=prompt, max_new_tokens=4)])
    roomy = ContinuousBatcher(PagedEngine(params, cfg, n_pages=6,
                                          **kw))
    req = Request(prompt=prompt, max_new_tokens=4)
    m = roomy.run([req])
    assert m["n_preemptions"] == 0
    want = np.asarray(GPT.generate(
        params, jnp.asarray(prompt)[None], cfg, n_new=4,
        temperature=0.0, compute_dtype=jnp.float32))[0, len(prompt):]
    np.testing.assert_array_equal(want, req.tokens)


def test_batcher_max_new_tokens_1_retires_on_prefill_token():
    """Batcher edge regression: a max_new_tokens=1 request must
    retire on the token the PREFILL produced — the decode sweep (and,
    with speculation on, the drafter and verify step) must never run:
    the compiled-executable counts stay 0. The metrics dict still
    carries the full stable key set including the n_spec_* fields,
    as does the empty trace."""
    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (5,),
                                        0, cfg.vocab))
    want = np.asarray(GPT.generate(params, ids[None], cfg, n_new=1,
                                   temperature=0.0,
                                   compute_dtype=jnp.float32))[0, 5:]
    spec_keys = ("n_spec_steps", "n_spec_proposed", "n_spec_accepted",
                 "spec_accept_rate", "spec_mean_accepted")
    for speculative in (False, True):
        engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                             max_slots=2, compute_dtype=jnp.float32,
                             speculative=speculative, draft_len=3)
        batcher = ContinuousBatcher(engine)
        req = Request(prompt=ids, max_new_tokens=1)
        metrics = batcher.run([req])
        np.testing.assert_array_equal(want, req.tokens)
        assert engine.decode_compiles == 0, (
            "a 1-token request entered the decode sweep")
        assert engine.verify_compiles == 0, (
            "a 1-token request entered the verify step")
        assert engine.spec_proposed == 0, (
            "the drafter ran for a request that never decoded")
        for key in spec_keys:
            assert key in metrics
            assert metrics[key] == 0
        empty = batcher.run([])
        for key in spec_keys:
            assert key in empty and empty[key] == 0
        engine.tables.check()


def test_block_tables_write_ahead_and_rewind():
    """ensure_write_pages allocates every page the verify write-ahead
    needs in one shot; rewind resets the length without freeing the
    draft-ahead pages and refuses to cross the prompt (and with it
    the copy-on-write) floor."""
    from torchbooster_tpu.serving import BlockTables

    cfg = GPTConfig(seq_len=64)
    bt = BlockTables(cfg, page_size=4, n_pages=20, max_slots=2)
    bt.seat(0, np.arange(6, dtype=np.int32))        # 2 pages, len 6
    bt.activate(0, 1)
    # write-ahead of 4 from length 6 covers positions 6..9 -> page 2
    assert bt.ensure_write_pages(0, 4)
    assert bt.tables[0, 2] != 0 and bt.tables[0, 3] == 0
    bt.check()
    n_free = bt.n_free_pages
    for t in (7, 8, 9):
        bt.advance(0, t)                            # accept 3 of 4
    bt.check()
    # dropping positions invalidates last_ids (it points at dropped
    # token 9) — rewind demands the accepted pending token back
    with pytest.raises(ValueError, match="last_id"):
        bt.rewind(0, 7)
    bt.rewind(0, 7, last_id=7)                      # drop 2 of them
    assert bt.lengths[0] == 7 and bt.last_ids[0] == 7
    assert bt.n_free_pages == n_free                # pages kept
    bt.check()
    with pytest.raises(ValueError, match="rewind"):
        bt.rewind(0, 5, last_id=5)                  # below the prompt
    with pytest.raises(ValueError, match="rewind"):
        bt.rewind(0, 8, last_id=8)                  # past the length
    with pytest.raises(ValueError, match="not seated"):
        bt.rewind(1, 1, last_id=1)
    bt.retire(0)
    bt.check()
    # the horizon clamp: write-ahead at the cache edge allocates only
    # the in-range pages and reports success
    bt.seat(1, np.arange(62, dtype=np.int32))
    bt.activate(1, 1)
    assert bt.ensure_write_pages(1, 8)
    assert bt.pages_for(64) == bt.max_pages_per_slot
    bt.check()


def test_block_tables_spec_rewind_churn_invariants():
    """Satellite acceptance: randomized accept/reject/REWIND churn
    with the prefix cache on — speculative write-ahead allocation,
    partial advances, rewinds back to the accept boundary, retires
    and re-seats over a tight pool. check() after every op asserts
    the rewind invariants: slot length never below the copy-on-write
    boundary, draft-ahead pages private and never index-reachable,
    refcounts/partition exact."""
    from torchbooster_tpu.serving import BlockTables, NULL_PAGE

    cfg = GPTConfig(seq_len=64)
    bt = BlockTables(cfg, page_size=4, n_pages=24, max_slots=4,
                     prefix_cache=True)
    rng = np.random.RandomState(13)
    shared = rng.randint(0, 97, 12).astype(np.int32)   # 3 full pages
    K = 3
    live = {}
    saw_rewind = saw_shared = False
    for op in range(400):
        roll = rng.rand()
        slot = bt.free_slot()
        if roll < 0.35 and slot is not None:
            tail = rng.randint(0, 97,
                               int(rng.randint(1, 14))).astype(np.int32)
            prompt = (np.concatenate([shared, tail])
                      if rng.rand() < 0.6 else tail)
            if bt.pages_for(len(prompt)) <= bt.n_available_pages:
                bt.seat(slot, prompt)
                bt.activate(slot, int(rng.randint(0, 97)))
                bt.register_prefix(slot, prompt)
                live[slot] = True
        elif roll < 0.8 and live:
            slot = int(rng.choice(sorted(live)))
            room = cfg.seq_len - int(bt.lengths[slot])
            if room >= 1 and bt.ensure_write_pages(slot,
                                                   min(1 + K, room)):
                # a verify step: up to K+1 written, a+1 advanced —
                # modeled as advance-through-the-draft then rewind
                # to the accept boundary
                n_adv = int(rng.randint(1, min(1 + K, room) + 1))
                for _ in range(n_adv):
                    bt.advance(slot, int(rng.randint(0, 97)))
                back = int(rng.randint(0, n_adv))
                if back and rng.rand() < 0.5:
                    bt.rewind(slot, int(bt.lengths[slot]) - back,
                              last_id=int(rng.randint(0, 97)))
                    saw_rewind = True
        elif live:
            slot = int(rng.choice(sorted(live)))
            bt.retire(slot)
            del live[slot]
        saw_shared |= bool((bt.refcount > 1).any())
        bt.check()
    assert saw_rewind, "churn never exercised a rewind"
    assert saw_shared, "churn never shared a prefix page"
    for slot in list(live):
        bt.retire(slot)
    bt.check()
    assert bt.n_available_pages == bt.n_pages - 1
    assert (bt.tables == NULL_PAGE).all()


def test_prompt_lookup_drafter():
    """Drafting mechanics: longest-suffix n-gram match, most recent
    occurrence wins, sentinel padding when nothing matches (or the
    continuation is short), and loud validation."""
    from torchbooster_tpu.serving import NO_DRAFT, PromptLookupDrafter

    d = PromptLookupDrafter(draft_len=3, ngram_min=2)
    d.begin(0, np.array([1, 2, 3, 4, 1, 2], np.int32))
    # suffix [1, 2] matched at position 0 -> continuation [3, 4, 1]
    np.testing.assert_array_equal(d.draft(0), [3, 4, 1])
    # most recent match wins: a LATER [1, 2] with a different
    # continuation shadows the first
    d.observe(0, [9, 1, 2])
    np.testing.assert_array_equal(d.draft(0), [9, 1, 2])
    # short continuation sentinel-pads
    d.begin(1, np.array([5, 6, 5, 6], np.int32))
    np.testing.assert_array_equal(d.draft(1), [5, 6, NO_DRAFT])
    # no match at ngram_min or above -> all sentinel
    d.begin(2, np.array([1, 2, 3, 4, 5], np.int32))
    assert (d.draft(2) == NO_DRAFT).all()
    # unknown/reset slots never draft
    d.reset(0)
    assert (d.draft(0) == NO_DRAFT).all()
    assert (d.draft(7) == NO_DRAFT).all()
    with pytest.raises(ValueError, match="draft_len"):
        PromptLookupDrafter(draft_len=0)
    with pytest.raises(ValueError, match="ngram_min"):
        PromptLookupDrafter(draft_len=2, ngram_min=3, ngram_max=2)


def test_spec_pick_mechanics():
    """The per-position accept/token rule (_make_spec_pick): greedy
    accepts exactly argmax==draft; sampling accepts with probability
    p(draft) over the FILTERED distribution (certain for a
    near-point-mass, never for a filtered-out token), the rejection
    fallback never re-emits the rejected token, and sentinel
    positions never accept."""
    from torchbooster_tpu.models.gpt import _make_spec_pick

    # greedy: logits with argmax [7, 3, 5] over 3 verify positions
    logits = np.full((1, 3, 10), -5.0, np.float32)
    for j, t in enumerate((7, 3, 5)):
        logits[0, j, t] = 5.0
    verify = _make_spec_pick(0.0, None, None, jnp.int32)
    accept, token = verify(jax.random.PRNGKey(0),
                           jnp.asarray(logits),
                           jnp.asarray([[7, 9]], np.int32))
    np.testing.assert_array_equal(np.asarray(accept), [[True, False]])
    np.testing.assert_array_equal(np.asarray(token), [[7, 3, 5]])
    # sentinel never accepts, even where argmax would continue
    accept, _ = verify(jax.random.PRNGKey(0), jnp.asarray(logits),
                       jnp.asarray([[7, -1]], np.int32))
    np.testing.assert_array_equal(np.asarray(accept), [[True, False]])

    # sampling: position 0's mass is ~all on token 7 -> always
    # accepted; position 1 drafts token 9, which top_k=2 filters out
    # (ranks 3rd) -> never accepted, and the fallback must not be 9
    logits = np.zeros((1, 3, 10), np.float32)
    logits[0, 0, 7] = 50.0
    logits[0, 1, 3] = 5.0
    logits[0, 1, 4] = 4.0
    logits[0, 1, 9] = 3.0
    verify = _make_spec_pick(1.0, 2, None, jnp.int32)
    for seed in range(8):
        accept, token = verify(jax.random.PRNGKey(seed),
                               jnp.asarray(logits),
                               jnp.asarray([[7, 9]], np.int32))
        accept = np.asarray(accept)
        token = np.asarray(token)
        assert accept[0, 0], "p(draft) ~= 1 was rejected"
        assert not accept[0, 1], "a filtered-out draft was accepted"
        assert token[0, 1] in (3, 4), (
            "rejection fallback left the filtered support or "
            "re-emitted the rejected token")


def test_engine_spec_validation():
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    with pytest.raises(ValueError, match="draft_len"):
        PagedEngine(params, cfg, page_size=4, speculative=True,
                    draft_len=4)       # must stay < page_size
    with pytest.raises(ValueError, match="draft_len"):
        PagedEngine(params, cfg, page_size=4, speculative=True,
                    draft_len=0)
    engine = PagedEngine(params, cfg, page_size=4, n_pages=8,
                         max_slots=1, compute_dtype=jnp.float32)
    with pytest.raises(RuntimeError, match="speculative"):
        engine.spec_step()


def test_serving_config_builds_batcher():
    """config.py serving block → engine + batcher from typed YAML
    fields (the ``serving:`` section of docs/config.md)."""
    from torchbooster_tpu.config import ServingConfig
    from torchbooster_tpu.serving import ContinuousBatcher

    params, cfg = _decisive_model()
    sc = ServingConfig(page_size=4, n_pages=16, max_slots=2)
    batcher = sc.make(params, cfg, compute_dtype=jnp.float32)
    assert isinstance(batcher, ContinuousBatcher)
    assert batcher.engine.page_size == 4
    assert batcher.engine.max_slots == 2

    ids = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (5,), 0, cfg.vocab))
    from torchbooster_tpu.serving import Request
    req = Request(prompt=ids, max_new_tokens=4)
    metrics = batcher.run([req])
    assert len(req.tokens) == 4
    assert metrics["new_tokens"] == 4

    sc8 = ServingConfig(page_size=4, n_pages=16, max_slots=2,
                        cache_dtype="int8")
    assert sc8.make(params, cfg).engine.quantized

    # the PR-4 serving keys reach the engine (prefix cache + chunked
    # prefill); chunk size clamps to the slot's page budget
    scp = ServingConfig(page_size=4, n_pages=16, max_slots=2,
                        prefix_cache=True, prefill_chunk_pages=2)
    eng = scp.make(params, cfg, compute_dtype=jnp.float32).engine
    assert eng.prefix_cache and eng.tables.prefix_cache
    assert eng.prefill_chunk_pages == 2
    assert eng.chunk_tokens == 8
    big = ServingConfig(page_size=4, n_pages=16, max_slots=2,
                        prefill_chunk_pages=99)
    assert big.make(params, cfg).engine.prefill_chunk_pages == \
        eng.tables.max_pages_per_slot

    # the YAML observability policy reaches the runtime guard: make()
    # threads on_recompile into the batcher (default stays "warn")
    assert batcher.on_recompile == "warn"
    strict = sc.make(params, cfg, compute_dtype=jnp.float32,
                     on_recompile="raise")
    assert strict.on_recompile == "raise"

    # the speculative keys reach the engine; the default stays off
    # (the cold engine carries NO verify artifact at all)
    assert not batcher.engine.speculative
    scs = ServingConfig(page_size=4, n_pages=16, max_slots=2,
                        speculative=True, draft_len=3, ngram_min=2)
    es = scs.make(params, cfg, compute_dtype=jnp.float32).engine
    assert es.speculative and es.draft_len == 3
    assert es.verify_compiles == 0          # built, never traced yet


# ---- tensor-parallel serving (serving/tp.py) ---------------------


def _tp_mesh(tp):
    from torchbooster_tpu.distributed import make_mesh

    return make_mesh(f"tp:{tp}", n_devices=tp)


@pytest.mark.parametrize("tp,compute_dtype,cache_dtype,kv", [
    (2, jnp.bfloat16, "int8", 2),   # the acceptance pair: GQA + int8
    (2, jnp.float32, None, 0),      # full-MHA cache width
    pytest.param(4, jnp.bfloat16, None, 0, marks=pytest.mark.slow),
    pytest.param(4, jnp.bfloat16, "int8", 0,
                 marks=pytest.mark.slow),
    pytest.param(2, jnp.bfloat16, None, 2, marks=pytest.mark.slow),
])
def test_tp_decode_matches_dense_jit_generate(tp, compute_dtype,
                                              cache_dtype, kv):
    """The headline tp parity: the head-sharded engine (pool sharded
    on KV heads, qkv/proj Megatron-split, one psum per layer) decodes
    the EXACT greedy tokens of the dense ``jit_generate`` control —
    MHA+GQA × bf16+int8 pages, tp ∈ {2, 4} on the forced-8-device CPU
    mesh (tp=1 is the whole pre-existing suite)."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model(n_kv_heads=kv)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 5), 0,
                             cfg.vocab)
    n_new = 8
    want = GPT.generate(params, ids, cfg, n_new=n_new, temperature=0.0,
                        compute_dtype=compute_dtype,
                        cache_dtype=cache_dtype)
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, cache_dtype=cache_dtype,
                         compute_dtype=compute_dtype,
                         tp=tp, mesh=_tp_mesh(tp))
    got = _paged_tokens(engine, np.asarray(ids[0]), n_new)
    np.testing.assert_array_equal(np.asarray(want[0, 5:]), got)
    assert engine.decode_compiles == 1
    assert engine.tp == tp
    engine.tables.check()


def test_tp_prefix_shared_two_slot_parity():
    """Two LIVE slots sharing prefix pages through the multi-lane
    sweep at tp=2 emit exactly the tp=1 engine's tokens — the
    prefix-shared acceptance path: the shared page's one pool read
    serves both sharers on every chip's head shard."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    rs = np.random.RandomState(3)
    shared = rs.randint(0, 97, 8).astype(np.int32)     # 2 full pages
    p_a = np.concatenate([shared, rs.randint(0, 97, 3).astype(np.int32)])
    p_b = np.concatenate([shared, rs.randint(0, 97, 2).astype(np.int32)])
    n_new = 6

    def serve_pair(**kw):
        eng = PagedEngine(params, cfg, page_size=4, n_pages=24,
                          max_slots=2, prefix_cache=True, **kw)
        slot_a, first_a = eng.admit(p_a)
        slot_b, first_b = eng.admit(p_b)
        toks = {slot_a: [first_a], slot_b: [first_b]}
        for _ in range(n_new - 1):
            assert eng.grow_slots() == []
            step = eng.step()
            for s in (slot_a, slot_b):
                toks[s].append(int(step[s]))
        eng.tables.check()
        return toks[slot_a], toks[slot_b], eng

    want_a, want_b, _ = serve_pair()
    got_a, got_b, eng = serve_pair(tp=2, mesh=_tp_mesh(2))
    assert got_a == want_a and got_b == want_b
    assert eng.decode_compiles == 1


@pytest.mark.parametrize("cache_dtype", [
    None, pytest.param("int8", marks=pytest.mark.slow)])
def test_tp_spec_greedy_parity(cache_dtype):
    """Speculative verify at tp=2: the head-sharded multi-token
    verify step emits token-for-token the tp=1 speculative engine's
    greedy stream, through ONE verify compile."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    prompt = _repetitive_prompt(np.random.RandomState(5))
    n_new = 10

    def serve(**kw):
        eng = PagedEngine(params, cfg, page_size=8, n_pages=16,
                          max_slots=2, cache_dtype=cache_dtype,
                          speculative=True, draft_len=3, **kw)
        toks = _spec_tokens(eng, prompt, n_new)
        return toks, eng

    want, _ = serve()
    got, eng = serve(tp=2, mesh=_tp_mesh(2))
    assert got == want
    assert eng.verify_compiles == 1
    assert eng.decode_compiles == 0     # spec engines never decode


def test_tp_zero_recompile_churn():
    """The zero-recompile contract holds at tp>1: exactly one decode
    and one prefill-chunk compile across admit/retire/evict and
    mixed prompt-length churn on the sharded engine."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    rs = np.random.RandomState(7)
    engine = PagedEngine(params, cfg, page_size=4, n_pages=12,
                         max_slots=2, prefix_cache=True,
                         tp=2, mesh=_tp_mesh(2))
    for ln in (3, 9, 5, 13, 7):        # mixed lengths, pool pressure
        prompt = rs.randint(0, 97, ln).astype(np.int32)
        slot, _ = engine.admit(prompt)
        for _ in range(2):
            assert engine.grow_slots() == []
            engine.step()
        engine.retire(slot)
        engine.tables.check()
    assert engine.decode_compiles == 1
    assert engine.prefill_compiles == 1


def test_tp_randomized_churn_check_invariants():
    """Randomized admit/decode/retire churn under tp=2 (prefix cache
    on, eviction pressure): the block-table invariants (``check()``)
    hold after every mutation — the host-side bookkeeping must be
    byte-identical to the single-chip engine's."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    rs = np.random.RandomState(11)
    engine = PagedEngine(params, cfg, page_size=4, n_pages=10,
                         max_slots=2, prefix_cache=True,
                         tp=2, mesh=_tp_mesh(2))
    live: list[int] = []
    for _ in range(24):
        op = rs.randint(3)
        if op == 0 and len(live) < 2:
            prompt = rs.randint(0, 97, rs.randint(2, 11)).astype(
                np.int32)
            if engine.can_admit(prompt):
                got = engine.admit(prompt)
                if got is not None:
                    live.append(got[0])
        elif op == 1 and live:
            if engine.grow_slots() == []:
                engine.step()
        elif op == 2 and live:
            engine.retire(live.pop(rs.randint(len(live))))
        engine.tables.check()
    assert engine.decode_compiles <= 1


def test_tp_validation():
    """The loud-validation satellites: tp must divide the KV-head
    count (numbers in the message), a tp>1 build needs a committed
    mesh, and the mesh's tp axis must exist and match exactly —
    at the engine ctor AND at ServingConfig level."""
    from torchbooster_tpu.config import ServingConfig
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()          # n_heads=4, n_kv_heads=2
    # tp doesn't divide n_kv_heads (GQA): both numbers in the message
    with pytest.raises(ValueError, match=r"tp=4.*n_kv_heads=2"):
        PagedEngine(params, cfg, page_size=4, tp=4, mesh=_tp_mesh(4))
    # tp>1 without a committed mesh
    with pytest.raises(ValueError, match="committed mesh|no mesh"):
        PagedEngine(params, cfg, page_size=4, tp=2)
    # mesh without a tp axis
    from torchbooster_tpu.distributed import make_mesh
    with pytest.raises(ValueError, match="no 'tp' axis"):
        PagedEngine(params, cfg, page_size=4, tp=2,
                    mesh=make_mesh("dp:2", n_devices=2))
    # tp exceeding the mesh's tp axis size: both numbers
    with pytest.raises(ValueError, match=r"tp=2.*size 1"):
        PagedEngine(params, cfg, page_size=4, tp=2,
                    mesh=make_mesh("tp:1", n_devices=1))
    with pytest.raises(ValueError, match=">= 1"):
        PagedEngine(params, cfg, page_size=4, tp=0)
    # the same rejections at YAML level, BEFORE any engine state
    sc = ServingConfig(page_size=4, n_pages=16, max_slots=2, tp=4)
    with pytest.raises(ValueError, match=r"tp=4.*n_kv_heads=2"):
        sc.make(params, cfg, mesh=_tp_mesh(4))
    sc2 = ServingConfig(page_size=4, n_pages=16, max_slots=2, tp=2)
    with pytest.raises(ValueError, match="committed mesh|no mesh"):
        sc2.make(params, cfg)
    # MHA naming: the message blames n_heads when there is no GQA
    _, mha = _decisive_model(n_kv_heads=0)
    with pytest.raises(ValueError, match=r"tp=3.*n_heads=4"):
        PagedEngine(params, mha, page_size=4, tp=3, mesh=_tp_mesh(2))


@pytest.mark.slow     # heavy on the 1-cpu rig; coverage kept by cheaper tier-1 tests (870s budget)
def test_tp_yaml_config_roundtrip_builds_batcher(tmp_path):
    """YAML → ``ServingConfig`` → batcher round-trip at tp=2: the
    typed ``serving.tp`` key reaches the engine, the batcher serves a
    request to the tp=1 config build's exact tokens, the
    ``serving_tp_bytes_total`` counter accumulates the modeled psum
    bytes, and the flight recorder's per-step records carry tp=2."""
    from torchbooster_tpu.config import ServingConfig
    from torchbooster_tpu.observability import get_registry
    from torchbooster_tpu.serving import Request

    params, cfg = _decisive_model()
    path = tmp_path / "serving.yaml"
    path.write_text(
        "page_size: 4\nn_pages: 16\nmax_slots: 2\ntp: 2\n")
    sc = ServingConfig.load(path)
    assert sc.tp == 2
    ids = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (5,), 0, cfg.vocab))

    ref = ServingConfig(page_size=4, n_pages=16, max_slots=2)
    req1 = Request(prompt=ids, max_new_tokens=4)
    ref.make(params, cfg, compute_dtype=jnp.float32).run([req1])

    batcher = sc.make(params, cfg, compute_dtype=jnp.float32,
                      mesh=_tp_mesh(2))
    assert batcher.engine.tp == 2
    reg = get_registry()
    enabled0 = reg.enabled
    reg.enabled = True
    try:
        req2 = Request(prompt=ids, max_new_tokens=4)
        batcher.run([req2])
        total = reg.counter("serving_tp_bytes_total").value()
    finally:
        reg.enabled = enabled0
    assert req2.tokens == req1.tokens
    # the modeled psum counter landed (decode steps ran at tp=2)
    per_step = batcher.engine.tp_step_traffic(1)["wire_bytes"]
    assert total > 0 and total % per_step == 0
    # ... and the flight ring records which topology each step took
    tails = batcher.flight.tail(4)
    assert tails and all(row["tp"] == 2 for row in tails)
    assert batcher.engine.debug_stats()["tp"] == 2


@pytest.mark.parametrize("program", ["chunk", "decode", "verify"])
@pytest.mark.parametrize("cache_dtype", [None, "int8"],
                         ids=["bf16-pool", "int8-pool"])
def test_pool_is_carried_through_the_layer_loop(program, cache_dtype):
    """The three serving programs carry the stacked pool THROUGH their
    layer loop: no scanned input (``xs``) and no stacked output
    (``ys``) of any scan has a pool leaf's shape — those are two
    buffers even under donation, a copy of the pool a step — and the
    pool's leaves are among one loop's carries. Read off the jaxpr,
    so it guards the plumbing where no TPU compiler is installed
    (tests/test_tpu_aot_compile.py holds the compiled program to the
    same)."""
    from torchbooster_tpu.serving import PagedEngine
    from torchbooster_tpu.serving.speculative import make_verify_fn

    params, cfg = _decisive_model()
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, cache_dtype=cache_dtype,
                         speculative=True, draft_len=3)
    pool_k, pool_v = engine.pool["k"], engine.pool["v"]
    pool_shapes = {leaf.shape for leaf in jax.tree.leaves(pool_k)}
    # every program's operands after the pool: the packed buffer
    # (the chunk's ids and cursors, the tables and the drafts are
    # slices of it) and the key
    engine._op["chunk"][:] = (0, 5, 0)
    fn = {"chunk": engine._chunk_fn, "decode": engine._decode_fn,
          "verify": make_verify_fn(engine)}[program]
    jaxpr = jax.make_jaxpr(fn)(
        params, pool_k, pool_v, jnp.asarray(engine.operands.host),
        jax.random.PRNGKey(0))

    def scans(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scans(sub)

    carried = 0
    for eqn in scans(jaxpr.jaxpr):
        n_consts = eqn.params["num_consts"]
        n_carry = eqn.params["num_carry"]
        shape = lambda v: tuple(v.aval.shape)
        xs = eqn.invars[n_consts + n_carry:]
        ys = eqn.outvars[n_carry:]
        assert not pool_shapes & {shape(v) for v in xs + ys}, (
            "a pool-shaped leaf rides a scan's xs / ys")
        carry = eqn.invars[n_consts:n_consts + n_carry]
        carried += sum(shape(v) in pool_shapes for v in carry)
    assert carried == 2 * len(jax.tree.leaves(pool_k))


@pytest.mark.parametrize("kv_heads,rep", [(3, 1), (2, 2)],
                         ids=["mha", "gqa"])
@pytest.mark.parametrize("int8", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("n_q", [1, 3], ids=["decode", "lanes"])
def test_sweep_attention_matches_the_dense_core(kv_heads, rep, int8,
                                                n_q):
    """``kv_pages.sweep_attention`` over the pool's merged rows gives
    the partials ``_grouped_cache_attention(state=True)`` gives over
    the same pages with the heads split out: the paged read has its
    own formulation (block-diagonal queries, the row never split) and
    this is what ties it to the dense path's core, beside the
    token-exact parity tests above."""
    from torchbooster_tpu.models.gpt import (
        _grouped_cache_attention,
        _quantize_kv,
    )
    from torchbooster_tpu.serving import kv_pages

    rs = np.random.RandomState(0)
    n_pages, ps, head_dim = 5, 4, 8
    q = jnp.asarray(rs.randn(n_pages, n_q, kv_heads * rep, head_dim),
                    jnp.float32)
    k, v = (jnp.asarray(rs.randn(n_pages, ps, kv_heads, head_dim),
                        jnp.float32) for _ in range(2))
    visible = jnp.asarray(rs.rand(n_pages, n_q, ps) > 0.3)
    width = kv_pages.kv_width(kv_heads, head_dim)
    assert width % kv_pages.LANES == 0
    if int8:
        k, v = _quantize_kv(k), _quantize_kv(v)
        rows = [kv_pages.quantized_rows(*t, width) for t in (k, v)]
    else:
        rows = [kv_pages.to_rows(t, width) for t in (k, v)]
    want = _grouped_cache_attention(q, k, v, visible[:, None, None],
                                    state=True)
    got = kv_pages.sweep_attention(q, *rows, visible, kv_heads)
    for w, g in zip(want, got):
        assert w.shape == g.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("host", [False, True], ids=["jax", "numpy"])
def test_pool_rows_round_trip(shards, host):
    """``to_rows`` / ``from_rows`` are inverses at the width
    ``make_pool`` gives the pool, per tp shard: each shard's heads sit
    at the start of its own 128-aligned slice of the row, zeros
    behind them."""
    from torchbooster_tpu.serving import kv_pages

    cfg = GPTConfig(vocab=97, n_layers=2, d_model=48, n_heads=4,
                    seq_len=32)
    pool = kv_pages.make_pool(cfg, page_size=4, n_pages=3,
                              shards=shards)
    head_dim = cfg.d_model // cfg.n_heads
    width = pool["k"].shape[-1]
    assert pool["k"].shape == (2, 3, 4, width)
    assert width == kv_pages.kv_width(cfg.kv_heads, head_dim, shards) \
        == shards * kv_pages.LANES
    x = np.random.RandomState(1).randn(3, 4, cfg.kv_heads, head_dim)
    x = x.astype(np.float32) if host else jnp.asarray(x, jnp.float32)
    rows = kv_pages.to_rows(x, width, shards)
    assert isinstance(rows, np.ndarray) == host
    assert rows.shape == (3, 4, width)
    per = cfg.kv_heads // shards * head_dim
    split = np.asarray(rows).reshape(3, 4, shards, width // shards)
    assert not split[..., per:].any()
    back = kv_pages.from_rows(rows, cfg.kv_heads, head_dim, shards)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
