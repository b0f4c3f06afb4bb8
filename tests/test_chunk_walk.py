"""A prefill chunk's walk of its prior context (``ops/chunk_walk.py``)
on the CPU, the kernel in interpret mode:

- its partial merged with the chunk's own causal block, against the
  gather of the slot's table the engine ran before (the pages taken
  out of the pool and attended by XLA, masked to the chunk's start)
  and against a dense float32 reference, for a paged pool and a
  window layer's ring, MHA at GPT-2 XL's width (heads of 64, a row of
  1,664 lanes: thirteen lane blocks of two heads, the last with a pad
  head) and GQA at the window-and-full family's (48 / 8 heads of 128),
  float32 (exact) and bfloat16 pools; the chunk at position 0, in the
  middle of its table, as its table's last with its pad pages on the
  null page, and a window whose lower edge falls inside a page;
- the counters: the pages a chunk's read fetches, by host arithmetic,
  and the benchmark's reader of them.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.models.gpt import _grouped_cache_attention
from torchbooster_tpu.observability import set_enabled
from torchbooster_tpu.ops.chunk_walk import chunk_walk_attention
from torchbooster_tpu.serving.engine import _merge_partials, _normalised
from torchbooster_tpu.serving.kv_pages import (
    NULL_PAGE, from_rows, kv_width, ring_pages, ring_positions, ring_table,
    to_rows)

PS, C = 4, 8            # rows a page; the chunk: two pages
LAYERS, LAYER = 2, 1    # the stacked pool, and the layer read
ENTRIES = 8             # a block table's entries (32 positions)
WINDOW = 8
RING = ring_pages(WINDOW, PS, C // PS)      # 5 pages = 20 positions
SLOT = 1                # the chunk's slot in a ring pool
# heads, kv heads, head_dim
HEADS = {"mha_gpt2_xl": (25, 25, 64), "gqa_trinity": (48, 8, 128)}
# (pool, the chunk's start): at position 0; in the middle of its
# table; its table's last chunk (its second page past the slot's
# allocation: the null page); a ring whose window's lower edge (start
# - 7) falls inside a page, once unwrapped and once wrapped twice
CASES = {
    "paged_at_0": ("paged", 0),
    "paged_mid_table": ("paged", 12),
    "paged_last_chunk_pad_on_null": ("paged", (ENTRIES - 2) * PS),
    "ring_edge_in_a_page": ("ring", 12),
    "ring_wrapped_twice": ("ring", 48),
}
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


def dense(q, k, v, start, window=None):
    """Float32 attention of the chunk's queries ``(C, heads, hd)`` over
    keys ``(n, kv, hd)`` at positions 0 .. n - 1 (its own at ``start
    ..``): causal, and with ``window`` less than that far behind."""
    n_q, heads, hd = q.shape
    rep = heads // k.shape[1]
    pos = np.arange(k.shape[0])
    out = np.zeros_like(q)
    for c in range(n_q):
        seen = pos <= start + c
        if window is not None:
            seen &= start + c - pos < window
        for h in range(heads):
            s = k[seen, h // rep] @ q[c, h] / np.sqrt(hd)
            p = np.exp(s - s.max())
            out[c, h] = p @ v[seen, h // rep] / p.sum()
    return out


def chunk_case(case, heads, dtype, seed=0):
    """A slot's sequence ``(2, start + C, kv, hd)`` float32 written into
    a stacked pool (the other pages random: another tenant's rows) —
    by its block table, or position by position into its ring, later
    positions over earlier ones — and the chunk's queries."""
    pool_kind, start = CASES[case]
    n_heads, kv, hd = HEADS[heads]
    width = kv_width(kv, hd)
    rng = np.random.default_rng(seed)
    rounded = lambda x: np.asarray(
        jnp.asarray(x, dtype).astype(jnp.float32))
    seq = rounded(rng.standard_normal((2, start + C, kv, hd)))
    q = rounded(rng.standard_normal((C, n_heads, hd)))
    if pool_kind == "paged":
        n_pages = 2 * ENTRIES + 1
        pools = rng.standard_normal((2, LAYERS, n_pages, PS, width))
        # the slot's pages, non-contiguous; entries past its
        # allocation name the null page
        table = rng.permutation(np.arange(1, n_pages))[:ENTRIES]
        held = -(-min(start + C, (ENTRIES - 1) * PS) // PS)
        table[held:] = NULL_PAGE
        for p in range(min(start + C, held * PS)):
            pools[:, LAYER, table[p // PS], p % PS] = to_rows(seq[:, p],
                                                              width)
        walk = (table, 0, None)
    else:
        pools = rng.standard_normal((2, LAYERS, 3 * RING, PS, width))
        for p in range(start + C):
            at = SLOT * RING + (p // PS) % RING
            pools[:, LAYER, at, p % PS] = to_rows(seq[:, p], width)
        ring_row, edge = ring_table(np.array([start]), RING, PS, WINDOW)
        walk = (np.asarray(ring_row)[0] + SLOT * RING,
                int(np.asarray(edge)[0]), WINDOW)
    pools = [jnp.asarray(pools[i], dtype) for i in (0, 1)]
    return pools, walk, seq, q, start, kv


def gathered(q, pools, walk, start, kv):
    """The prior partial the engine took before the walk: every entry
    of the slot's block table out of the pool, or the slot's whole
    ring with each row's position recovered (``ring_positions``),
    attended by XLA and masked to the chunk's start (and the
    window)."""
    table, _, window = walk
    width = pools[0].shape[-1]
    hd = q.shape[-1]
    if window is None:
        k_pos = np.arange(len(table) * PS)
    else:
        table = SLOT * RING + np.arange(RING)
        k_pos = np.asarray(ring_positions(start // PS + C // PS - 1, RING,
                                          PS)).reshape(-1)
    k, v = (jnp.asarray(from_rows(np.asarray(p[LAYER])[np.asarray(table)]
                                  .reshape(1, -1, width), kv, hd))
            for p in pools)
    q_pos = start + np.arange(C)
    seen = (k_pos[None] >= 0) & (k_pos[None] < start)
    if window is not None:
        seen = seen & (q_pos[:, None] - k_pos[None] < window)
    return _grouped_cache_attention(
        q, k, v, jnp.asarray(seen)[None, None, None], state=True)


def merged(prior, q, seq, start, window):
    """``prior`` merged with the chunk's own causal block, as the
    engine's read merges them: ``(C, heads, hd)`` float32."""
    own = jnp.asarray(seq[:, start:start + C])[:, None]
    back = np.arange(C)[:, None] - np.arange(C)[None]
    seen = back >= 0
    if window is not None:
        seen &= back < window
    o = _normalised(_merge_partials(prior, _grouped_cache_attention(
        q, own[0].astype(q.dtype), own[1].astype(q.dtype),
        jnp.asarray(seen)[None, None, None], state=True)), q)
    return np.asarray(o[0].astype(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_walk_is_the_gather_and_the_dense_attention(case, heads,
                                                        dtype):
    pools, walk, seq, q, start, kv = chunk_case(case, heads, dtype)
    table, base, window = walk
    qd = jnp.asarray(q, dtype)[None]
    prior = chunk_walk_attention(qd, *pools, LAYER, jnp.asarray(table),
                                 base, start, kv_heads=kv, window=window)
    o, m, l = prior
    rep = q.shape[1] // kv
    assert o.shape == (1, C, kv, rep, q.shape[2]) and o.dtype == jnp.float32
    assert m.shape == l.shape == (1, kv, rep, C)
    if start == 0:
        # nothing before the chunk: nothing seen, nothing weighed
        assert not np.asarray(o).any() and not np.asarray(l).any()
    got = merged(prior, qd, seq, start, window)
    want = dense(q, seq[0], seq[1], start, window)
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=0)
    gather = merged(gathered(qd, pools, walk, start, kv), qd, seq, start,
                    window)
    np.testing.assert_allclose(got, gather, atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("pages_per_item", [1, 3])
def test_the_walk_does_not_depend_on_its_items(pages_per_item):
    """Items of one page and of three (the last item of a table short):
    the same answer as the default's one item."""
    pools, walk, seq, q, start, kv = chunk_case(
        "ring_wrapped_twice", "gqa_trinity", jnp.float32)
    table, base, window = walk
    args = (jnp.asarray(q)[None], *pools, LAYER, jnp.asarray(table), base,
            start)
    want = chunk_walk_attention(*args, kv_heads=kv, window=window)
    got = chunk_walk_attention(*args, kv_heads=kv, window=window,
                               pages_per_item=pages_per_item)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6,
                                   rtol=0)


# ---- the counters -----------------------------------------------------

def chunk_pages(snap, kind):
    return (snap.get(f"serving_chunk_pages_read_total{{kind={kind}}}"),
            snap.get(f"serving_chunk_pages_table_total{{kind={kind}}}"))


@pytest.mark.parametrize("name", ["gpt", "gpt_int8", "afmoe"])
def test_the_chunk_s_pages_are_counted_by_host_arithmetic(name):
    """Pages of 4, chunks of 8: a chunk at 0 reads nothing, one at 44
    a full layer's pages 0 .. 10 (11) and a window layer's (window 8)
    from the edge at 37 on, pages 9 .. 10 (2); the gather (an int8
    pool) every entry of the table and a window layer its ring. Each
    kind's count times its layers."""
    from torchbooster_tpu.models.gpt import GPT, GPTConfig
    from torchbooster_tpu.serving import PagedEngine

    if name == "afmoe":
        from tests import test_afmoe as fam
        from torchbooster_tpu.models.afmoe import Afmoe as model
        cfg = fam.program.model_config(fam.TOY)
        layers = {"full": 1, "window": 4}
    else:
        model = GPT
        cfg = GPTConfig(vocab=64, n_layers=3, d_model=32, n_heads=4,
                        seq_len=64)
        layers = {"full": 3}
    # the counters are host arithmetic: the weights' shapes will do
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg))
    engine = PagedEngine(params, cfg, page_size=PS, n_pages=40,
                         max_slots=3, prefill_chunk_pages=2,
                         cache_dtype="int8" if name == "gpt_int8" else None,
                         compute_dtype=jnp.float32)
    mp = engine.tables.max_pages_per_slot
    reg = set_enabled(True)
    reg.reset()
    try:
        for start in (0, 44):
            engine._count_chunk_pages({"start": start})
        snap = reg.snapshot()
    finally:
        reg.reset()
        set_enabled(False)
    table = {"full": 2 * mp, "window": 2 * (engine.ring or 0)}
    read = {"full": 11, "window": 2}
    if name == "gpt_int8":
        read = table
    for kind, n in layers.items():
        assert chunk_pages(snap, kind) == (read[kind] * n, table[kind] * n)


def test_the_reader_divides_the_two_counters_summed_over_the_kinds():
    bench = Path(__file__).resolve().parent.parent / "benchmark"
    for path in (bench, bench / "layer_metrics"):
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))
    import chunk_read_share

    read = "serving_chunk_pages_read_total{kind=%s}"
    table = "serving_chunk_pages_table_total{kind=%s}"
    layers = {
        "registry_open": {read % "full": 10.0, table % "full": 100.0},
        "registry_close": {read % "full": 40.0, table % "full": 200.0,
                           read % "window": 20.0, table % "window": 100.0},
    }
    assert chunk_read_share.read("chunk_read_share.lat", layers) \
        == pytest.approx(100.0 * (30 + 20) / (100 + 100))
    # a program without the counters (the parent of the walk)
    assert chunk_read_share.read(
        "chunk_read_share.lat", {"registry_open": {},
                                 "registry_close": {"other": 1.0}}) is None


@pytest.mark.parametrize("mode", ["default", "int8", "pallas"])
def test_the_walk_is_chosen_where_the_lanes_walk(mode):
    """The default engine's chunk program reads its prior context by
    the chunk walk; an int8 pool and the pallas backend (the engine's
    ``walks`` false) keep the gather of the slot's table."""
    from torchbooster_tpu.models.gpt import GPT, GPTConfig
    from torchbooster_tpu.serving import PagedEngine

    cfg = GPTConfig(vocab=64, n_layers=1, d_model=32, n_heads=4, seq_len=32)
    params = GPT.init(jax.random.PRNGKey(0), cfg)
    engine = PagedEngine(
        params, cfg, page_size=PS, n_pages=16, max_slots=2,
        cache_dtype="int8" if mode == "int8" else None,
        decode_backend="pallas" if mode == "pallas" else "xla")
    jaxpr = str(jax.make_jaxpr(engine._chunk_fn)(
        engine.params, engine.pool["k"], engine.pool["v"],
        engine.operands.host, engine._rng))
    assert engine.walks == (mode == "default")
    assert ("chunk_walk" in jaxpr) == engine.walks
