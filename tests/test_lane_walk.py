"""The decode lanes' live-page walk (``ops/lane_walk.py`` through
``kv_pages.walk_attention``) on the CPU, the kernel in interpret mode:

- against a dense float32 reference and against the pool sweep
  (``kv_pages.sweep_attention`` + the per-slot online-softmax merge the
  engine ran before the walk), for MHA with a padded row, GQA at two
  head widths, float32 (exact) and bfloat16 pools, slots dead, at
  position 0, on a page boundary and mid-page, over non-contiguous
  tables, with several pages an item and one;
- over a window layer's ring that has not wrapped and one that has
  wrapped more than once, with a window of 8 so that an off-by-one at
  the window's lower edge fails;
- in the default engine: the decode and mixed programs of a GPT, LFM2
  and the window-and-full family serve the family's float32 reference;
- the counters: the pages the lanes' read fetches, by host arithmetic.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.observability import set_enabled
from torchbooster_tpu.serving import kv_pages
from torchbooster_tpu.serving.kv_pages import (
    kv_width, ring_pages, ring_table, sweep_attention, to_rows,
    walk_attention)

PS = 4                  # rows a page
LAYERS, LAYER = 2, 1    # the stacked pool, and the layer read

# heads, kv heads, head_dim: MHA whose 320 lanes pad to 384, GQA at
# two widths
HEADS = {"mha_padded": (5, 5, 64), "gqa_64": (8, 2, 64),
         "gqa_128": (6, 2, 128)}
# each slot's query position (None: dead): position 0, the last row of
# a page, the first of the next, mid-page, far into a long table
POSITIONS = [0, None, 2 * PS - 1, 2 * PS, 5 * PS + 2, None, 11 * PS + 1]
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


def dense(q, k, v, q_pos, window=None):
    """Float32 attention of one query ``(heads, hd)`` over keys ``(n,
    kv, hd)`` at positions 0 .. n - 1, those at or before ``q_pos`` (and
    less than ``window`` behind it) visible."""
    heads, hd = q.shape
    rep = heads // k.shape[1]
    pos = np.arange(k.shape[0])
    seen = pos <= q_pos
    if window is not None:
        seen &= q_pos - pos < window
    out = np.zeros((heads, hd), np.float32)
    for h in range(heads):
        s = k[seen, h // rep] @ q[h] / np.sqrt(hd)
        p = np.exp(s - s.max())
        out[h] = p @ v[seen, h // rep] / p.sum()
    return out


def geometry(name, dtype, seed=0):
    """Random pools of rows, each slot's own non-contiguous table and
    its sequence's K/V ``(slots, n, kv, hd)`` in float32."""
    heads, kv, hd = HEADS[name]
    width = kv_width(kv, hd)
    n_slots, entries = len(POSITIONS), 12
    n_pages = n_slots * entries + 1
    rng = np.random.default_rng(seed)
    keys = rng.standard_normal((2, LAYERS, n_pages, PS, kv, hd))
    keys = np.asarray(jnp.asarray(keys, dtype).astype(jnp.float32))
    pools = [jnp.asarray(to_rows(keys[i], width), dtype) for i in (0, 1)]
    tables = rng.permutation(np.arange(1, n_pages)).reshape(
        n_slots, entries).astype(np.int32)
    seq = keys[:, LAYER][:, tables].reshape(2, n_slots, entries * PS,
                                            kv, hd)
    q = rng.standard_normal((n_slots, 1, heads, hd)).astype(np.float32)
    q = np.asarray(jnp.asarray(q, dtype).astype(jnp.float32))
    live = np.array([p is not None for p in POSITIONS])
    q_pos = np.array([p or 0 for p in POSITIONS], np.int32)
    return pools, tables, seq, q, q_pos, live, kv


def swept(q, pools, tables, q_pos, live, kv):
    """The pool sweep over the layer, each page's one lane its owner
    slot's query, merged per slot as the engine merged it."""
    n_slots = q.shape[0]
    n_pages = pools[0].shape[1]
    owner = np.full(n_pages, -1)
    page_pos = np.zeros(n_pages, np.int32)
    for s in np.flatnonzero(live):
        for e in range(q_pos[s] // PS + 1):
            owner[tables[s, e]], page_pos[tables[s, e]] = s, e
    tok = page_pos[:, None] * PS + np.arange(PS)
    visible = (tok <= np.where(owner >= 0, q_pos[np.maximum(owner, 0)],
                               -1)[:, None])[:, None]
    o, m, l = sweep_attention(
        jnp.asarray(q[:, 0][np.maximum(owner, 0)])[:, None],
        pools[0][LAYER], pools[1][LAYER], jnp.asarray(visible), kv)
    seg = np.where(owner >= 0, owner, n_slots)
    m_s = jax.ops.segment_max(m[..., 0], seg, num_segments=n_slots + 1)
    w = jnp.exp(m[..., 0] - m_s[seg])
    l_s = jax.ops.segment_sum(l[..., 0] * w, seg,
                              num_segments=n_slots + 1)
    o_s = jax.ops.segment_sum(o[:, 0] * w[..., None], seg,
                              num_segments=n_slots + 1)
    o = o_s[:n_slots] / jnp.maximum(l_s[:n_slots], 1e-30)[..., None]
    return np.asarray(o).reshape(q.shape)


@pytest.fixture(params=[8, 3, 1], ids=lambda n: f"{n}_pages_an_item")
def pages_per_step(request, monkeypatch):
    monkeypatch.setattr(kv_pages, "lane_walk_attention", functools.partial(
        kv_pages.lane_walk_attention, pages_per_step=request.param))
    return request.param


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", sorted(HEADS))
def test_the_walk_is_the_dense_attention_and_the_sweep(heads, dtype,
                                                       pages_per_step):
    pools, tables, seq, q, q_pos, live, kv = geometry(heads, dtype)
    got = walk_attention(jnp.asarray(q, dtype), *pools, LAYER, tables,
                         np.zeros_like(q_pos), q_pos, live, kv)
    assert got.dtype == dtype and got.shape == q.shape
    got = np.asarray(got.astype(jnp.float32))
    assert not got[~live].any()                 # a dead slot reads zeros
    for s in np.flatnonzero(live):
        want = dense(q[s, 0], seq[0, s], seq[1, s], q_pos[s])
        np.testing.assert_allclose(got[s, 0], want, atol=TOL[dtype],
                                   rtol=0)
    sweep = swept(jnp.asarray(q, dtype), pools, tables, q_pos, live, kv)
    np.testing.assert_allclose(got[live], sweep[live], atol=TOL[dtype],
                               rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("q_pos", [5, 8, 45, 47],
                         ids=["unwrapped", "edge_on_a_page", "wrapped_twice",
                              "wrapped_edge_mid_page"])
def test_a_ring_is_walked_from_the_window_s_lower_edge(q_pos, dtype,
                                                       pages_per_step):
    """Window 8, pages of 4, chunks of one page: a ring of 4 pages (16
    positions). One slot's sequence of ``q_pos + 1`` rows is written
    position by position into its ring (later positions over earlier
    ones), the slot beside it holds another tenant's rows; the walk
    sees exactly the 8 positions up to the query's."""
    window, heads, kv, hd = 8, 8, 2, 64
    ring = ring_pages(window, PS, 1)
    width = kv_width(kv, hd)
    rng = np.random.default_rng(q_pos)
    seq = rng.standard_normal((2, q_pos + 1, kv, hd))
    seq = np.asarray(jnp.asarray(seq, dtype).astype(jnp.float32))
    pools = np.asarray(rng.standard_normal((2, LAYERS, 2 * ring, PS, width)),
                       np.float32)
    slot = 1
    for p in range(q_pos + 1):
        at = slot * ring + (p // PS) % ring
        pools[:, LAYER, at, p % PS] = to_rows(seq[:, p], width)
    pools = [jnp.asarray(pools[i], dtype) for i in (0, 1)]
    q = rng.standard_normal((2, 1, heads, hd)).astype(np.float32)
    q = np.asarray(jnp.asarray(q, dtype).astype(jnp.float32))
    lengths = np.array([3, q_pos], np.int32)
    live = np.array([False, True])
    table, base = ring_table(lengths, ring, PS, window)
    got = walk_attention(jnp.asarray(q, dtype), *pools, LAYER, table, base,
                         lengths, live, kv, window=window)
    want = dense(q[slot, 0], seq[0], seq[1], q_pos, window)
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32))[slot, 0],
                               want, atol=TOL[dtype], rtol=0)
    # one position more or less at the lower edge is another answer
    for wrong in (window - 1, window + 1):
        other = dense(q[slot, 0], seq[0], seq[1], q_pos, wrong)
        if q_pos >= window:
            assert np.abs(other - want).max() > 10 * TOL[dtype]


def test_a_ring_table_lists_the_window_s_pages_in_position_order():
    """Window 8, pages of 4, ring of 4: at position 45 the window is
    38 .. 45, sequence pages 9 .. 11 at ring pages 1, 2, 3 of slot 1
    (ring pages 4 .. 7); at position 3, pages 0 .. 2 of slot 0, of
    which the walk reads the first (the entries past the newest page
    are not read)."""
    table, base = ring_table(np.array([3, 45]), 4, PS, 8)
    assert np.asarray(base).tolist() == [0, 9]
    assert np.asarray(table).tolist() == [[0, 1, 2], [5, 6, 7]]


# ---- the default engine -----------------------------------------------

@functools.lru_cache(maxsize=None)
def family(name):
    return Family(name)


class Family:
    """A toy engine of each family whose pools the walk reads, and what
    its served streams are held to: ``GPT.generate`` for a GPT with a
    decisive head, the float32 references on logits for LFM2 and the
    window-and-full family (random weights flip an argmax on rounding)."""

    def __init__(self, name):
        self.name = name
        if name == "afmoe":
            from tests import test_afmoe as fam
            self.cfg, self.flat, self.params = fam.built(fam.TOY)
            self.raw, self.page, self.vocab = fam.TOY, fam.PAGE, 128
            self.chunk_pages, self.tol = fam.CHUNK_PAGES, fam.TOL
            from reference import afmoe as reference
            self.reference = reference
        else:
            from tests import test_mixed_step as mixed
            fam = mixed.Family(name)
            self.cfg, self.params = fam.cfg, fam.params
            self.page, self.vocab = fam.page, fam.vocab
            self.chunk_pages, self.check_gpt = mixed.CHUNK_PAGES, fam.check
            if name == "lfm2":
                from tests.test_lfm2 import TOL, TOY
                from reference import lfm2 as reference
                self.flat, self.raw, self.tol = fam.flat, TOY, TOL
                self.reference = reference

    def check(self, req):
        if self.name == "gpt":
            return self.check_gpt(req)
        prompt = req.prompt[:req.base_len]
        gaps = self.reference.served_gaps(self.flat, prompt, req.tokens,
                                          self.raw)
        assert float(gaps.max()) < self.tol


@pytest.mark.parametrize("name", ["gpt", "lfm2", "afmoe"])
def test_the_default_engine_s_lanes_walk_and_serve_the_reference(name):
    from torchbooster_tpu.serving import (ContinuousBatcher, PagedEngine,
                                          Request)

    fam = family(name)
    engine = PagedEngine(fam.params, fam.cfg, page_size=fam.page,
                         n_pages=64, max_slots=3,
                         prefill_chunk_pages=fam.chunk_pages,
                         compute_dtype=jnp.float32)
    assert engine.walks and engine.mixes
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(0, fam.vocab, n).astype(np.int32),
                    max_new_tokens=m)
            for n, m in ((2 * fam.page - 1, 14), (5 * fam.page + 3, 9),
                         (fam.page, 11), (3 * fam.page + 1, 6))]
    ContinuousBatcher(engine).run(reqs)
    assert engine.mixed_steps > 0 and engine.decode_compiles == 1
    for req in reqs:
        assert len(req.tokens) == req.max_new_tokens
        fam.check(req)


def test_int8_pools_and_tensor_parallel_engines_keep_the_sweep():
    from torchbooster_tpu.models.gpt import GPT, GPTConfig
    from torchbooster_tpu.serving import PagedEngine

    cfg = GPTConfig(vocab=64, n_layers=1, d_model=32, n_heads=4, seq_len=32)
    params = GPT.init(jax.random.PRNGKey(0), cfg)
    build = functools.partial(PagedEngine, params, cfg, page_size=PS,
                              n_pages=16, max_slots=2)
    assert build().walks
    assert not build(cache_dtype="int8").walks
    assert not build(decode_backend="pallas").walks


# ---- the counters -----------------------------------------------------

def lane_pages(snap, kind):
    return (snap.get(f"serving_lane_pages_read_total{{kind={kind}}}"),
            snap.get(f"serving_lane_pages_pool_total{{kind={kind}}}"))


@pytest.mark.parametrize("name", ["gpt", "gpt_int8", "afmoe"])
def test_the_lanes_pages_are_counted_by_host_arithmetic(name):
    """Two slots seated by hand at positions 5 and 45 (pages of 4),
    one decode step: the walk fetches a full layer's pages 0 .. 1 and
    0 .. 11 (2 + 12), a window layer's (window 8) pages 0 .. 1 and
    9 .. 11 (2 + 3), each kind's count times its layers; the sweep
    (an int8 pool) fetches the pool."""
    from torchbooster_tpu.models.gpt import GPT, GPTConfig
    from torchbooster_tpu.serving import PagedEngine

    if name == "afmoe":
        fam = family("afmoe")
        cfg, params = fam.cfg, fam.params
        layers = {"full": 1, "window": 4}
    else:
        cfg = GPTConfig(vocab=64, n_layers=3, d_model=32, n_heads=4,
                        seq_len=64)
        params = GPT.init(jax.random.PRNGKey(0), cfg)
        layers = {"full": 3}
    engine = PagedEngine(params, cfg, page_size=PS, n_pages=40,
                         max_slots=3, prefill_chunk_pages=2,
                         cache_dtype="int8" if name == "gpt_int8" else None,
                         compute_dtype=jnp.float32)
    tables = engine.tables
    for slot, pages, length in ((0, [7, 3], 5), (2, list(range(8, 20)), 45)):
        tables.tables[slot, :len(pages)] = pages
        tables.lengths[slot] = length
        tables.active[slot] = True
    reg = set_enabled(True)
    reg.reset()
    try:
        engine._count_lane_pages(tables.active.copy())
        snap = reg.snapshot()
    finally:
        reg.reset()
        set_enabled(False)
    pool = {"full": 40, "window": 3 * engine.ring if engine.ring else 0}
    read = {"full": 2 + 12, "window": 2 + 3}
    if name == "gpt_int8":
        read = pool
    for kind, n in layers.items():
        assert lane_pages(snap, kind) == (read[kind] * n, pool[kind] * n)
