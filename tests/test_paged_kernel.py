"""The pallas paged flash-decode backend (ops/paged_attention.py +
``PagedEngine(decode_backend="pallas")``) on the CPU mesh, kernel in
interpret mode:

- decisive-head token-parity MATRIX: the pallas backend's greedy
  stream equals BOTH the XLA pool sweep's and the dense
  ``jit_generate`` control's — MHA+GQA × bf16+int8 pages × {plain
  decode, prefix-shared two-slot decode, fused speculative verify}
  (heavy combos ride the ``slow`` mark; the acceptance pairs stay
  tier-1);
- exactly ONE decode compile (and ONE verify compile in speculative
  mode) across admit/retire/evict churn on the kernel backend — the
  zero-recompile contract transfers to the kernel path unchanged;
- ``BlockTables.kernel_args()``: fixed shapes under churn, live
  entries first (each referenced page exactly once, refs/page_pos
  aligned), padding pinned to the null page with empty lanes;
- the shared pallas plumbing (ops/_pallas_util.py): interpret-on-CPU
  default, and BOTH kernels (flash + paged) build and run on this
  image's jax through it;
- the engine/config surface: bad backend names rejected loudly,
  ``decode_backend: xla`` stays the default.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.models.gpt import GPT, GPTConfig
from tests.test_serving import _decisive_model, _paged_tokens


def _dense(params, cfg, prompt, n_new, compute_dtype, cache_dtype):
    out = GPT.generate(params, jnp.asarray(prompt)[None], cfg,
                       n_new=n_new, temperature=0.0,
                       compute_dtype=compute_dtype,
                       cache_dtype=cache_dtype)
    return np.asarray(out)[0, len(prompt):]


def _spec_tokens(engine, prompt, n_new):
    slot, first = engine.admit(prompt)
    toks = [first]
    while len(toks) < n_new:
        assert engine.grow_slots() == []
        toks.extend(engine.spec_step()[slot])
    engine.retire(slot)
    return toks[:n_new]


@pytest.mark.parametrize("compute_dtype,cache_dtype,kv", [
    (jnp.float32, None, 2),
    (jnp.bfloat16, "int8", 2),     # the acceptance pair (int8 + GQA)
    (jnp.float32, None, 0),        # full-MHA cache width
    pytest.param(jnp.bfloat16, None, 2, marks=pytest.mark.slow),
    pytest.param(jnp.bfloat16, "int8", 0, marks=pytest.mark.slow),
])
def test_kernel_decode_parity_matrix(compute_dtype, cache_dtype, kv):
    """The acceptance parity: pallas greedy decode == the XLA sweep ==
    dense ``jit_generate``, token for token, with exactly one decode
    compile on the kernel path."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model(n_kv_heads=kv)
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (1, 5), 0, cfg.vocab)[0])
    n_new = 8
    streams = {}
    for backend in ("xla", "pallas"):
        engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                             max_slots=2, cache_dtype=cache_dtype,
                             compute_dtype=compute_dtype,
                             decode_backend=backend)
        streams[backend] = _paged_tokens(engine, ids, n_new)
        engine.tables.check()
        assert engine.decode_compiles == 1
    np.testing.assert_array_equal(
        _dense(params, cfg, ids, n_new, compute_dtype, cache_dtype),
        streams["pallas"])
    assert streams["pallas"] == streams["xla"]


@pytest.mark.parametrize("cache_dtype", [
    None, pytest.param("int8", marks=pytest.mark.slow)])
def test_kernel_prefix_shared_two_slot_parity(cache_dtype):
    """TWO live slots sharing resident prefix pages decode through the
    kernel's ref lanes — the shared page is one work entry serving
    both sharers — and each stream matches its dense reference."""
    from torchbooster_tpu.serving import PagedEngine

    compute_dtype = jnp.bfloat16 if cache_dtype else jnp.float32
    params, cfg = _decisive_model()
    rs = np.random.RandomState(1)
    shared = rs.randint(0, 97, 8).astype(np.int32)     # 2 full pages
    p_a = np.concatenate([shared, rs.randint(0, 97, 3).astype(np.int32)])
    p_b = np.concatenate([shared, rs.randint(0, 97, 5).astype(np.int32)])
    n_new = 6

    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, compute_dtype=compute_dtype,
                         cache_dtype=cache_dtype, prefix_cache=True,
                         prefill_chunk_pages=1,
                         decode_backend="pallas")
    _paged_tokens(engine, p_a, 2)          # registers the prefix
    slot_a, first_a = engine.admit(p_a)
    slot_b, first_b = engine.admit(p_b)
    assert int(engine.tables.refcount.max()) >= 2, (
        "live slots did not share the prefix pages")
    # the shared page appears ONCE in the kernel work list, with both
    # sharers on its lanes — the one-HBM-read sharing claim
    ka = engine.tables.kernel_args()
    wr = np.asarray(ka["work_refs"])
    wp = np.asarray(ka["work_pages"])
    live = wp[wp != 0]
    assert len(set(live.tolist())) == len(live), "work list duplicates"
    assert ((wr >= 0).sum(axis=1) >= 2).any(), (
        "no work entry carries both sharers")
    toks_a, toks_b = [first_a], [first_b]
    for _ in range(n_new - 1):
        assert engine.grow_slots() == []
        t = engine.step()
        toks_a.append(int(t[slot_a]))
        toks_b.append(int(t[slot_b]))
    np.testing.assert_array_equal(
        _dense(params, cfg, p_a, n_new, compute_dtype, cache_dtype),
        toks_a)
    np.testing.assert_array_equal(
        _dense(params, cfg, p_b, n_new, compute_dtype, cache_dtype),
        toks_b)
    engine.retire(slot_a)
    engine.retire(slot_b)
    engine.tables.check()
    assert engine.decode_compiles == 1


@pytest.mark.parametrize("compute_dtype,cache_dtype,kv", [
    (jnp.float32, None, 2),
    pytest.param(jnp.bfloat16, "int8", 2, marks=pytest.mark.slow),
    pytest.param(jnp.float32, None, 0, marks=pytest.mark.slow),
])
def test_kernel_spec_verify_parity(compute_dtype, cache_dtype, kv):
    """The fused verify pass: speculative decode on the pallas backend
    — all 1 + draft_len positions in ONE kernel walk — emits exactly
    the XLA verify sweep's tokens AND the dense control's, with one
    verify compile and zero decode compiles."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model(n_kv_heads=kv)
    rs = np.random.RandomState(2)
    prompt = np.tile(rs.randint(0, 97, 4).astype(np.int32), 3)
    n_new = 10
    streams = {}
    engines = {}
    for backend in ("xla", "pallas"):
        engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                             max_slots=2, cache_dtype=cache_dtype,
                             compute_dtype=compute_dtype,
                             speculative=True, draft_len=3,
                             decode_backend=backend)
        streams[backend] = _spec_tokens(engine, prompt, n_new)
        engines[backend] = engine
    np.testing.assert_array_equal(
        _dense(params, cfg, prompt, n_new, compute_dtype, cache_dtype),
        streams["pallas"])
    assert streams["pallas"] == streams["xla"]
    assert engines["pallas"].verify_compiles == 1
    assert engines["pallas"].decode_compiles == 0
    engines["pallas"].tables.check()


def test_kernel_churn_one_compile_each():
    """Zero-recompile acceptance on the kernel backend: admit/retire/
    re-admit churn across page boundaries — with the prefix cache ON
    so retires cache pages and later seats evict them — leaves the
    decode executable count at exactly 1 (the kernel work-list
    operands are fixed-shape values, never shapes)."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    engine = PagedEngine(params, cfg, page_size=4, n_pages=12,
                         max_slots=3, compute_dtype=jnp.float32,
                         prefix_cache=True, prefill_chunk_pages=1,
                         decode_backend="pallas")
    rng = np.random.RandomState(0)
    slot_a, _ = engine.admit(rng.randint(0, 97, 5))
    engine.grow_slots()
    engine.step()                       # warmup: the ONE compile
    assert engine.decode_compiles == 1
    slot_b, _ = engine.admit(rng.randint(0, 97, 9))
    for _ in range(4):
        assert engine.grow_slots() == []
        engine.step()
    engine.retire(slot_a)               # pages cached (prefix index)
    # a fat admit forces eviction of the cached prefix under pressure
    slot_c, _ = engine.admit(rng.randint(0, 97, 11))
    for _ in range(6):                  # crosses page boundaries
        assert engine.grow_slots() == []
        engine.step()
    engine.retire(slot_b)
    engine.retire(slot_c)
    engine.tables.check()
    assert engine.decode_compiles == 1, (
        "slot/evict churn recompiled the kernel decode step")


def test_kernel_spec_one_verify_compile_accept_churn():
    """Accept-length churn (full accepts, partial accepts, empty
    drafts) through the kernel verify path stays at ONE verify
    compile."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    rs = np.random.RandomState(3)
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, compute_dtype=jnp.float32,
                         speculative=True, draft_len=3,
                         decode_backend="pallas")
    # repetitive prompt drafts well; random prompt drafts nothing —
    # both shapes must ride the same executable
    for prompt in (np.tile(rs.randint(0, 97, 4).astype(np.int32), 3),
                   rs.randint(0, 97, 7).astype(np.int32)):
        _spec_tokens(engine, prompt, 8)
    assert engine.verify_compiles == 1
    assert engine.decode_compiles == 0
    engine.tables.check()


def test_kernel_args_export_shapes_and_compaction():
    """``kernel_args()``: geometry-fixed shapes under churn; live
    entries first (every referenced page exactly once, lanes and
    page_pos aligned with the tables); padding = null page + empty
    lanes; cached refcount-0 prefix pages excluded."""
    from torchbooster_tpu.serving.kv_pages import BlockTables

    cfg = GPTConfig(vocab=97, n_layers=1, d_model=16, n_heads=2,
                    seq_len=32)
    t = BlockTables(cfg, page_size=4, n_pages=10, max_slots=3,
                    prefix_cache=True)
    rs = np.random.RandomState(0)

    def check_export():
        ka = t.kernel_args()
        wp = np.asarray(ka["work_pages"])
        wr = np.asarray(ka["work_refs"])
        wpos = np.asarray(ka["work_pos"])
        assert wp.shape == (t.n_pages - 1,)
        assert wr.shape == (t.n_pages - 1, t.n_ref_lanes)
        assert wpos.shape == (t.n_pages - 1,)
        live = set(np.flatnonzero(t.refcount > 0).tolist())
        n = len(live)
        assert set(wp[:n].tolist()) == live
        assert (wp[n:] == 0).all(), "padding not pinned to null page"
        assert (wr[n:] == -1).all(), "padding lanes not empty"
        assert t.n_live_pages == n
        for i in range(n):
            p = int(wp[i])
            np.testing.assert_array_equal(wr[i], t.refs[p])
            assert wpos[i] == t.page_pos[p]
        return n

    assert check_export() == 0
    t.seat(0, rs.randint(0, 97, 9))
    t.activate(0, 1)
    t.register_prefix(0, np.arange(9, dtype=np.int32))
    t.seat(1, rs.randint(0, 97, 5))
    t.activate(1, 2)
    assert check_export() == 3 + 2
    t.retire(0)                       # full pages cached, tail freed
    assert t.n_cached_pages == 2
    assert check_export() == 2        # cached pages NOT in the walk
    t.check()


def test_default_interpret_and_both_kernels_build():
    """The shared pallas plumbing regression: on the CPU backend
    ``default_interpret()`` is True, and BOTH kernels build and run
    through it — flash with an unspecified ``interpret`` and the paged
    kernel end to end."""
    from torchbooster_tpu.ops._pallas_util import (
        default_interpret, resolve_interpret)
    from torchbooster_tpu.ops.attention import mha_reference
    from torchbooster_tpu.ops.flash_attention import flash_attention
    from torchbooster_tpu.ops.paged_attention import paged_attention

    assert jax.default_backend() == "cpu"
    assert default_interpret() is True
    assert resolve_interpret(None) is True
    assert resolve_interpret(False) is False

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(2, 16, 8), jnp.float32)
    k = jnp.asarray(rs.randn(2, 16, 8), jnp.float32)
    v = jnp.asarray(rs.randn(2, 16, 8), jnp.float32)
    got = flash_attention(q, k, v, causal=True)    # interpret=None
    want = mha_reference(q[:, :, None, :], k[:, :, None, :],
                         v[:, :, None, :])         # (B, S, H=1, D)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want)[:, :, 0], rtol=2e-5,
        atol=2e-5)

    pool_k = jnp.asarray(rs.randn(4, 4, 2, 8), jnp.float32)
    pool_v = jnp.asarray(rs.randn(4, 4, 2, 8), jnp.float32)
    q4 = jnp.asarray(rs.randn(2, 1, 2, 8), jnp.float32)
    out = paged_attention(
        q4, pool_k, pool_v,
        work_pages=np.array([1, 2, 0], np.int32),
        work_refs=np.array([[0], [1], [-1]], np.int32),
        work_pos=np.array([0, 0, 0], np.int32),
        lengths=np.array([2, 3], np.int32), page_size=4)
    assert out.shape == (2, 1, 2, 8)
    assert np.isfinite(np.asarray(out)).all()


def test_engine_and_config_backend_validation():
    """Bad backend names fail loudly at construction; the config
    default stays the XLA sweep (the bit-for-bit-unchanged control)."""
    from torchbooster_tpu.config import ServingConfig
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    with pytest.raises(ValueError, match="decode_backend"):
        PagedEngine(params, cfg, page_size=4, n_pages=8, max_slots=1,
                    decode_backend="cuda")
    assert ServingConfig().decode_backend == "xla"
    batcher = ServingConfig(
        page_size=4, n_pages=8, max_slots=1,
        decode_backend="pallas").make(params, cfg,
                                      compute_dtype=jnp.float32)
    assert batcher.engine.decode_backend == "pallas"


# ---- tensor-parallel kernel path (serving/tp.py) -----------------


def _tp_mesh(tp):
    from torchbooster_tpu.distributed import make_mesh

    return make_mesh(f"tp:{tp}", n_devices=tp)


@pytest.mark.parametrize("tp,compute_dtype,cache_dtype,kv", [
    (2, jnp.bfloat16, "int8", 2),   # the acceptance pair (GQA+int8)
    pytest.param(2, jnp.float32, None, 0,      # full-MHA cache width
                 marks=pytest.mark.slow),      # tier-1 time budget
    pytest.param(4, jnp.bfloat16, None, 0, marks=pytest.mark.slow),
    pytest.param(4, jnp.bfloat16, "int8", 0,
                 marks=pytest.mark.slow),
])
def test_kernel_tp_decode_parity(tp, compute_dtype, cache_dtype, kv):
    """The kernel path at tp>1: the in-kernel block-table walk runs
    per-shard over the heads-sliced pool UNMODIFIED (the work lists
    are sharding-oblivious host values) and the greedy stream equals
    the tp-sharded XLA sweep's AND the dense control's, with one
    decode compile."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model(n_kv_heads=kv)
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (1, 5), 0, cfg.vocab)[0])
    n_new = 8
    mesh = _tp_mesh(tp)
    streams = {}
    for backend in ("xla", "pallas"):
        engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                             max_slots=2, cache_dtype=cache_dtype,
                             compute_dtype=compute_dtype,
                             decode_backend=backend, tp=tp, mesh=mesh)
        streams[backend] = _paged_tokens(engine, ids, n_new)
        engine.tables.check()
        assert engine.decode_compiles == 1
    np.testing.assert_array_equal(
        _dense(params, cfg, ids, n_new, compute_dtype, cache_dtype),
        streams["pallas"])
    assert streams["pallas"] == streams["xla"]


@pytest.mark.parametrize("cache_dtype", [
    None, pytest.param("int8", marks=pytest.mark.slow)])
def test_kernel_tp_spec_verify_parity(cache_dtype):
    """The fused speculative verify through the kernel at tp=2: one
    head-sharded kernel walk scores the whole draft burst, emitting
    token-for-token the single-chip pallas spec engine's stream, with
    exactly one verify compile."""
    from torchbooster_tpu.serving import PagedEngine

    compute_dtype = jnp.bfloat16 if cache_dtype else jnp.float32
    params, cfg = _decisive_model()
    rs = np.random.RandomState(2)
    prompt = np.tile(rs.randint(0, 97, 3).astype(np.int32), 3)
    n_new = 10
    kw = dict(page_size=8, n_pages=16, max_slots=2,
              compute_dtype=compute_dtype, cache_dtype=cache_dtype,
              speculative=True, draft_len=3, decode_backend="pallas")

    ref = PagedEngine(params, cfg, **kw)
    want = _spec_tokens(ref, prompt, n_new)
    eng = PagedEngine(params, cfg, tp=2, mesh=_tp_mesh(2), **kw)
    got = _spec_tokens(eng, prompt, n_new)
    assert got == want
    assert eng.verify_compiles == 1
    assert eng.spec_accepted > 0, (
        "the repetitive stream never accepted a draft — the fused "
        "multi-token path was not exercised at tp=2")


def test_kernel_tp_prefix_shared_and_churn_one_compile():
    """Prefix-shared decode through the kernel at tp=2 (the shared
    page is one work entry serving both sharers on every chip's head
    shard), then admit/retire churn: exactly one decode compile
    end to end."""
    from torchbooster_tpu.serving import PagedEngine

    params, cfg = _decisive_model()
    rs = np.random.RandomState(4)
    shared = rs.randint(0, 97, 8).astype(np.int32)     # 2 full pages
    p_a = np.concatenate([shared, rs.randint(0, 97, 3).astype(np.int32)])
    p_b = np.concatenate([shared, rs.randint(0, 97, 5).astype(np.int32)])
    n_new = 5

    def serve_pair(**kw):
        eng = PagedEngine(params, cfg, page_size=4, n_pages=16,
                          max_slots=2, prefix_cache=True,
                          prefill_chunk_pages=1,
                          decode_backend="pallas", **kw)
        slot_a, first_a = eng.admit(p_a)
        slot_b, first_b = eng.admit(p_b)
        assert int(eng.tables.refcount.max()) >= 2
        toks = {slot_a: [first_a], slot_b: [first_b]}
        for _ in range(n_new - 1):
            assert eng.grow_slots() == []
            t = eng.step()
            toks[slot_a].append(int(t[slot_a]))
            toks[slot_b].append(int(t[slot_b]))
        eng.retire(slot_a)
        eng.retire(slot_b)
        # churn: a fresh admission decodes through the SAME executable
        slot_c, _ = eng.admit(rs.randint(0, 97, 6).astype(np.int32))
        assert eng.grow_slots() == []
        eng.step()
        eng.retire(slot_c)
        eng.tables.check()
        return toks[slot_a], toks[slot_b], eng

    want_a, want_b, _ = serve_pair()
    got_a, got_b, eng = serve_pair(tp=2, mesh=_tp_mesh(2))
    assert got_a == want_a and got_b == want_b
    assert eng.decode_compiles == 1
    assert eng.prefill_compiles == 1
