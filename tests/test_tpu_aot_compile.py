"""Ask the chip's compiler, without the chip.

The TPU compiler is installed beside the CPU backend and compiles for
a chip that is *described*, not attached. These tests hand it the
kernels on ``chip_smoke.py``'s path at the smoke's real shapes, and
the GPT-2-small train step: what Mosaic or XLA:TPU would refuse on
first chip contact (a slice off the tiling, too much VMEM, a program
past 16 GB) fails here, at no chip time. Nothing runs, so this says
nothing about results or speed.

All of it lives in this one file, behind one module-scoped fixture:
only one process may hold the TPU library, and under xdist only the
worker that is handed this file must load it.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host. The persistent compilation cache is
    off while it is in use: a compile for a described chip can be
    written to it but not read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Lower + compile for the described chip; returns (lowered text,
    compiled)."""
    lowered = jax.jit(fn).lower(*args)
    return lowered.as_text(), lowered.compile()


# the serve phase's geometry: 8 slots, GPT-2 heads, page 64, a pool
# that holds every slot at S=1024 (chip_smoke.yml)
SLOTS, HEADS, HEAD_DIM, PAGE, N_PAGES = 8, 12, 64, 64, 129


@pytest.mark.parametrize("s_q,int8_pool", [
    pytest.param(1, False, id="decode-bf16-pool"),
    pytest.param(1, True, id="decode-int8-pool"),
    pytest.param(5, False, id="verify-1+draft_len"),
])
def test_paged_attention_compiles_for_v5e(one_chip, s_q, int8_pool):
    from torchbooster_tpu.ops.paged_attention import paged_attention

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool_shape = (N_PAGES, PAGE, HEADS, HEAD_DIM)
    if int8_pool:
        pool = (arg(pool_shape, jnp.int8),
                arg(pool_shape[:-1] + (1,), jnp.bfloat16))
    else:
        pool = arg(pool_shape, jnp.bfloat16)
    n_work = N_PAGES - 1
    text, _ = _compile(
        functools.partial(paged_attention, page_size=PAGE,
                          interpret=False),
        arg((SLOTS, s_q, HEADS, HEAD_DIM), jnp.bfloat16), pool, pool,
        arg((n_work,), jnp.int32), arg((n_work, 1), jnp.int32),
        arg((n_work,), jnp.int32), arg((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("q_rows,kv_rows,seq,head_dim", [
    pytest.param(12, 12, 8192, 64, id="s8192-mha-d64"),
    pytest.param(16, 8, 8192, 48, id="s8192-gpt-long-gqa-d48"),
    # gpt2-small.train-s1024: batch 16 x 12 heads folded into rows
    pytest.param(16 * 12, 16 * 12, 1024, 64, id="s1024-train-cell"),
])
def test_flash_attention_compiles_for_v5e(
        one_chip, q_rows, kv_rows, seq, head_dim, backward):
    from torchbooster_tpu.ops.flash_attention import flash_attention

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def grads(q, k, v):
        return jax.value_and_grad(
            lambda *qkv: flash(*qkv).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    q = jax.ShapeDtypeStruct((q_rows, seq, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((kv_rows, seq, head_dim), jnp.bfloat16,
                              sharding=one_chip)
    text, _ = _compile(grads if backward else flash, q, kv, kv)
    assert text.count("tpu_custom_call") >= (3 if backward else 1)


def test_gpt_gradient_with_the_kernel_keeps_no_score_matrix(one_chip):
    """One GPT-2-small layer at the train cell's batch, remat and bf16
    compute with ``attn_impl="flash"``: three kernels (forward, dQ,
    dK/dV — the forward is not run again inside the backward) and no
    (.., S, S) tensor of any dtype left in the optimised program."""
    import re

    from torchbooster_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig(n_layers=1)

    def loss(params, ids):
        logits = GPT.apply(params, ids, cfg=cfg,
                           compute_dtype=jnp.bfloat16, remat=True,
                           attn_impl="flash")
        return logits.astype(jnp.float32).mean()

    def arg(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(
        arg, jax.eval_shape(lambda: GPT.init(jax.random.PRNGKey(0), cfg)))
    ids = jax.ShapeDtypeStruct((16, cfg.seq_len), jnp.int32,
                               sharding=one_chip)
    _, compiled = _compile(jax.grad(loss), params, ids)
    text = compiled.as_text()
    s = cfg.seq_len
    assert not re.findall(rf"\b(?:f32|bf16)\[[0-9,]*{s},{s}\]", text)
    kernels = re.findall(r'custom_call_target="tpu_custom_call"', text)
    assert len(kernels) == 3, len(kernels)


def test_gpt2_small_train_step_compiles_and_fits_v5e(one_chip):
    """The smoke's train phase as one program: GPT-2 small, batch 16,
    S=1024, bf16 compute, remat, AdamW, through ``make_step``."""
    import optax

    from torchbooster_tpu.models.gpt import GPT, GPTConfig
    from torchbooster_tpu.ops.losses import cross_entropy
    from torchbooster_tpu.utils import TrainState, make_step

    cfg = GPTConfig()
    tx = optax.adamw(3e-4)

    def loss_fn(params, batch, rng):
        del rng
        logits = GPT.apply(params, batch["ids"], cfg=cfg,
                           compute_dtype=jnp.bfloat16, remat=True)
        return cross_entropy(logits, batch["labels"]), {}

    state = jax.eval_shape(
        lambda: TrainState.create(
            GPT.init(jax.random.PRNGKey(0), cfg), tx, rng=0))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip), state)
    tokens = jax.ShapeDtypeStruct((16, cfg.seq_len), jnp.int32,
                                  sharding=one_chip)
    compiled = make_step(loss_fn, tx, clip=1.0).lower(
        state, {"ids": tokens, "labels": tokens}).compile()
    memory = compiled.memory_analysis()
    need = (memory.temp_size_in_bytes + memory.argument_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert need < V5E_HBM_BYTES, f"{need / 2**30:.1f} GiB on a 16 GiB chip"


# ---- the serve cell's two programs: the pool stays where it is --------
# GPT-2 XL widths at the benchmark cell's pool geometry (256 pages x 64,
# 32 slots, 4-page chunks), 4 layers so that a compile takes seconds

XL = dict(vocab=50257, seq_len=1024, d_model=1600, n_heads=25)
XL_LAYERS, XL_PAGES, XL_PAGE, XL_SLOTS = 4, 256, 64, 32


def _top_level_results(text):
    """(name, opcode, [(dtype, dims, layout)]) of every instruction
    OUTSIDE fused computations: those are the buffers a program
    writes."""
    import re

    shape = re.compile(r"(pred|s4|s8|s32|u8|u32|bf16|f16|f32)"
                       r"\[([0-9,]*)\](\{[^}]*\})?")
    fused = False
    for line in text.splitlines():
        line = line.strip()
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            fused = "fused_computation" in head.group(2)
            continue
        inst = re.match(r"(ROOT )?%?([\w.\-]+) = (.*)", line)
        if fused or not inst:
            continue
        rest = inst.group(3)
        op = re.search(r"\)?\s([a-z][\w\-]*)\(", rest)
        if op:
            yield (inst.group(2), op.group(1),
                   shape.findall(rest[:op.start() + 1]))


def _pool_sized_writes(compiled, layer_elems: int, pool_k, vocab: int):
    """Top-level instructions of a compiled serving program that write
    a buffer as large as one layer's pool (``layer_elems`` elements),
    other than the pool's own update — a scatter fusion whose result
    IS the stacked pool, in place under donation (the callers'
    aliasing assertions hold it to that) — and the head's relayout of
    the tied embedding (``vocab`` among its dims: the model's)."""
    import math

    pool_shapes = {tuple(leaf.shape) for leaf in jax.tree.leaves(pool_k)}
    # a pool of ONE layer is updated under its shape without that axis
    pool_shapes |= {s[1:] for s in pool_shapes if s[0] == 1}
    moved = []
    for name, op, results in _top_level_results(compiled.as_text()):
        if op in ("parameter", "get-tuple-element", "tuple", "bitcast",
                  "while", "constant"):
            continue
        for dtype, dims, _ in results:
            dims = tuple(int(d) for d in dims.split(",") if d)
            if math.prod(dims) < layer_elems or vocab in dims:
                continue
            if op == "fusion" and dims in pool_shapes:
                continue
            moved.append((name, op, dtype, dims))
    return moved


def _step_args(arg, engine) -> tuple:
    """What every serve program takes after the pool, described: the
    engine's packed operand buffer (the tables, the chunk's ids and
    cursors: one int32 array, sliced apart inside the program) and
    the rng key."""
    return arg(engine.operands.host.shape), arg((2,), jnp.uint32)


def _serve_program(engine, program: str, arg):
    """``(function, trailing operands, keyword arguments)`` of one of
    an engine's three serve programs: decode, the chunk alone, and the
    chunk with the decode lanes riding (MIXED: ``lanes`` a dict). The
    programs with lanes take the last program's ``tokens`` (an engine
    that looks ahead): last of all in decode, in ``lanes`` in the
    mixed one."""
    prev = arg((engine.max_slots,)) if engine.looks_ahead else None
    if program == "decode":
        return engine._decode_fn, () if prev is None else (prev,), {}
    if program == "chunk":
        return engine._chunk_fn, (), {}
    return engine._chunk_fn, (), {
        "lanes": {} if prev is None else {"prev": prev}}


@pytest.mark.parametrize("int8_pool", [False, True],
                         ids=["bf16-pool", "int8-pool"])
@pytest.mark.parametrize("program", ["decode", "chunk", "mixed"])
def test_serve_programs_keep_the_pool_in_place_on_v5e(
        one_chip, program, int8_pool):
    """``PagedEngine._decode_fn`` / ``_chunk_fn`` (alone, and MIXED:
    the decode lanes riding the chunk, both K/V writes and both reads
    of a layer in one program) compiled for the
    described v5e with the pools donated: no instruction writes a
    buffer the size of a layer's pool other than the in-place update
    of the pool itself, the pool comes back in the layout it went in
    with, that layout pads at most 5 %, and the program's scratch is
    smaller than one layer's K + V (the head's relayout of the tied
    embedding, vocabulary x d_model, is the model's and excepted by
    its shape)."""
    import math

    import torchbooster_tpu.serving.engine as engine_mod
    from torchbooster_tpu.models.gpt import GPT, GPTConfig
    from torchbooster_tpu.serving.engine import PagedEngine

    cfg = GPTConfig(n_layers=XL_LAYERS, **XL)
    abstract = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip), tree)
    params = abstract(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        GPT.init(jax.random.PRNGKey(0), cfg))))
    # the engine's own constructor, with the pool described, not held
    make_pool = engine_mod.make_pool
    engine_mod.make_pool = lambda *a, **kw: jax.eval_shape(
        lambda: make_pool(*a, **kw))
    try:
        engine = PagedEngine(
            params, cfg, page_size=XL_PAGE, n_pages=XL_PAGES,
            max_slots=XL_SLOTS, prefill_chunk_pages=4,
            cache_dtype="int8" if int8_pool else None)
    finally:
        engine_mod.make_pool = make_pool
    pool_k, pool_v = abstract(engine.pool["k"]), abstract(engine.pool["v"])

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, tail, kw = _serve_program(engine, program, arg)
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, pool_k, pool_v, *_step_args(arg, engine), *tail,
        **kw).compile()

    head_dim = cfg.d_model // cfg.n_heads
    layer_elems = XL_PAGES * XL_PAGE * cfg.kv_heads * head_dim
    moved = _pool_sized_writes(compiled, layer_elems, pool_k, cfg.vocab)
    assert not moved, f"pool-sized buffers written: {moved}"

    # the pool goes out in the layout it came in with, aliased
    n_pool = len(jax.tree.leaves((pool_k, pool_v)))
    formats_in = jax.tree.leaves(compiled.input_formats[0][1:3])
    formats_out = jax.tree.leaves(compiled.output_formats)[-n_pool:]
    assert [f.layout for f in formats_in] \
        == [f.layout for f in formats_out]
    memory = compiled.memory_analysis()
    pool_bytes = sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves((pool_k, pool_v)))
    assert memory.alias_size_in_bytes >= pool_bytes

    # what the pool weighs on the device against what it holds
    params_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    held = 2 * XL_LAYERS * layer_elems * (1 if int8_pool else 2)
    if int8_pool:       # + one bf16 scale per (token, head)
        held += 2 * XL_LAYERS * layer_elems // head_dim * 2
    on_device = memory.argument_size_in_bytes - params_bytes
    assert on_device <= 1.05 * held + 2**20, (on_device, held)

    head_relayout = cfg.vocab * cfg.d_model * 2
    layer_kv = 2 * layer_elems * 2
    assert memory.temp_size_in_bytes - head_relayout < layer_kv, (
        memory.temp_size_in_bytes, layer_kv)


@pytest.mark.parametrize("program", ["decode", "chunk", "mixed"])
def test_lfm2_serve_programs_fit_and_stay_in_place_on_v5e(
        one_chip, program, monkeypatch):
    """The programs of the ``lfm2-8b-a1b.serve-rag-r80`` cell (decode,
    the chunk alone, and the chunk with the decode lanes riding) at
    its own size (14 layers at published widths, 2048 pages of 64, 64
    slots, chunks of 256), compiled for the described v5e with the
    pool AND the conv mixers' slot state donated: both come back
    aliased, no instruction writes a second buffer the size of a
    layer's pool, the experts run as the pallas grouped product the
    TPU path chooses (no buffer of experts x tokens, and none of
    XLA's own ``ragged-dot`` kernels, whose time no scope names), and
    weights, pool, state and scratch together fit the chip."""
    import json
    import math
    import sys
    from pathlib import Path

    import torchbooster_tpu.models.moe as moe_mod
    import torchbooster_tpu.serving.engine as engine_mod
    from torchbooster_tpu.models.lfm2 import LFM2

    # the process is pinned to the CPU; the program compiled is the
    # chip's
    monkeypatch.setattr(moe_mod, "_on_tpu", lambda: True)
    bench = Path(__file__).resolve().parent.parent / "benchmark"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    import program_lfm2

    raw = json.loads((bench / "configs" / "lfm2-8b-a1b.json").read_text())
    cfg = program_lfm2.model_config(raw, seq_len=4864)
    slots, pages, page = 64, 2048, 64
    abstract = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip), tree)
    params = abstract(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        LFM2.init(jax.random.PRNGKey(0), cfg))))
    # the engine's own constructor, with pool and state described
    real = engine_mod.make_pool, engine_mod.make_slot_state
    described = lambda make: lambda *a, **kw: jax.eval_shape(
        lambda: make(*a, **kw))
    engine_mod.make_pool = described(real[0])
    engine_mod.make_slot_state = described(real[1])
    try:
        engine = engine_mod.PagedEngine(
            params, cfg, page_size=page, n_pages=pages, max_slots=slots,
            prefill_chunk_pages=4)
    finally:
        engine_mod.make_pool, engine_mod.make_slot_state = real
    pool_k, pool_v = abstract(engine.pool["k"]), abstract(engine.pool["v"])
    state = abstract(engine.slot_state)
    assert pool_k.shape == (3, pages, page, 512)
    assert state["conv"].shape == (11, slots, 2, 2048)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # the slot state rides right behind the buffer and the key
    fn, tail, kw = _serve_program(engine, program, arg)
    compiled = jax.jit(fn, donate_argnums=(1, 2, 5)).lower(
        params, pool_k, pool_v, *_step_args(arg, engine), state, *tail,
        **kw).compile()

    layer_elems = pages * page * 512
    moved = _pool_sized_writes(compiled, layer_elems, pool_k, cfg.vocab)
    assert not moved, f"pool-sized buffers written: {moved}"
    nbytes = lambda tree: sum(math.prod(x.shape) * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= nbytes((pool_k, pool_v, state))
    # the experts' three matrices of ONE layer, were they applied to
    # every pair: the scratch stays far under it
    pairs = {"decode": slots, "chunk": engine.chunk_tokens,
             "mixed": engine.chunk_tokens + slots}[program] * cfg.top_k
    assert memory.temp_size_in_bytes < cfg.n_experts * pairs \
        * cfg.expert_width * 2 + cfg.vocab * cfg.d_model * 2 + 2**26
    assert "ragged-dot" not in compiled.as_text()
    # no copy as large as ONE layer's stack of one expert matrix: the
    # grouped products read the experts where they lie
    stack = cfg.n_experts * cfg.d_model * cfg.expert_width
    copies = [(name, dims) for name, op, results
              in _top_level_results(compiled.as_text()) if op == "copy"
              for _, dims, _ in results
              if math.prod(int(d) for d in dims.split(",") if d) >= stack]
    assert not copies, f"expert-stack-sized copies: {copies}"
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert nbytes(params) > 9.3e9 and total < V5E_HBM_BYTES, total
    if program == "mixed":
        # beside the weights: what the cell's two programs took
        assert total < 10.6e9, total


# sha256 of the StableHLO text each accepted serve cell's programs
# lower to for the described v5e (Mosaic kernels' bodies included, call
# sites' line numbers left out), taken at PR 35's tree. What PR 35
# changed of PR 33's text (the operand list of params, pool, ONE
# packed buffer and the key, and the in-program rng split): the buffer
# is ``max_slots`` words longer (``known``), the programs with lanes
# take the last program's ``tokens`` and select each lane's last token
# between it and the buffer's ``last_ids``, and the mixed program
# writes a prompt's first token into its slot's lane of ``tokens``.
# The trinity cell's three programs joined the table then (PR 34 had
# left them out)
ACCEPTED_SERVE_TEXT = {
    ("gpt2-xl.serve-chat-r80", "decode"):
        "55d734b80a7726add900c461f55048d94d1d173c46c8654c91a750f876013797",
    ("gpt2-xl.serve-chat-r80", "chunk"):
        "64fb3db321c03d436b92c9a5f2d424f1b3c17e7e16b2fb93f451d24a86d70d7a",
    ("gpt2-xl.serve-chat-r80", "mixed"):
        "46e99daf4361c62ba336bef86ddeb4dcee2faa61cbd3e7cb36edff6def1f917a",
    ("lfm2-8b-a1b.serve-rag-r80", "decode"):
        "667964cc8b1ff468f11ed6fe625e3df854fef1d6b5a12e9bb0d97189df2daf5f",
    ("lfm2-8b-a1b.serve-rag-r80", "chunk"):
        "c831ff5a47ec94c4d65b200c0c9d22c8172dd67f7eccb4ec6c6aba9cdaed2b4e",
    ("lfm2-8b-a1b.serve-rag-r80", "mixed"):
        "6fdf46d7f00b3b859be1638437554e90467aa9fe1f11292a553cdd7b34653808",
    ("sarvam-105b.serve-longdoc-r80", "decode"):
        "e413f24ac7a58e487cab29e22063c0eaf5a90a0321596c3c15c059cafcfd216f",
    ("sarvam-105b.serve-longdoc-r80", "chunk"):
        "349bd382cff460975a610b141516fd44d45c119181e61ff7eff5ee17c15e976d",
    ("sarvam-105b.serve-longdoc-r80", "mixed"):
        "95e76e2e57330ce59b8ab9c91ea1dcc578b9b0cf31597185a53a1a29f36cc000",
    ("trinity-large-preview.serve-longctx-r80", "decode"):
        "412e1d4b14527e41f40412f1484931835ab82e86fe5f84e179334e928477ae8a",
    ("trinity-large-preview.serve-longctx-r80", "chunk"):
        "f0d921aec04ed36d50b60966100d84d23786845efa9d7eb149feaebca45e0532",
    ("trinity-large-preview.serve-longctx-r80", "mixed"):
        "3e3a3b44fe1c6efccded848559e08908d58083cc446d5e9c22ff49cce5bfe069",
}


def _accepted_cell_lowered(one_chip, cell: str, program: str):
    """One serve program of an accepted cell, lowered (not compiled)
    for the described v5e at the cell's OWN geometry: the
    configuration file's model at full depth, the traffic file's
    ``serving:`` block and positions, weights in bfloat16."""
    import json
    import sys
    from pathlib import Path

    import torchbooster_tpu.serving.engine as engine_mod

    bench = Path(__file__).resolve().parent.parent / "benchmark"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    manifest = json.loads((bench.parent / "BENCHMARK.json").read_text())
    entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    raw = json.loads((bench.parent / next(
        c["file"] for c in manifest["configs"]
        if c["name"] == entry["config"])).read_text())
    traffic = json.loads(
        (bench / "traffic" / f"{entry['traffic']}.json").read_text())
    serving = traffic["serving"]
    init = lambda: jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                                model.init(jax.random.PRNGKey(0), cfg))
    if traffic["job"] == "serve_lfm2":
        import program_lfm2
        from torchbooster_tpu.models.lfm2 import LFM2 as model

        cfg = program_lfm2.model_config(raw, traffic["max_positions"])
    elif traffic["job"] == "serve_sarvam_mla":
        import program_sarvam_mla
        from torchbooster_tpu.models.mla_moe import MLAMoE as model

        cfg = program_sarvam_mla.model_config(
            raw, traffic["max_positions"])
        # made in bfloat16; the router's float32 bias stays as served
        init = lambda: model.init(jax.random.PRNGKey(0), cfg,
                                  jnp.bfloat16)
    elif traffic["job"] == "serve_afmoe":
        import program_afmoe
        from torchbooster_tpu.models.afmoe import Afmoe as model

        cfg = program_afmoe.model_config(raw, traffic["max_positions"])
        init = lambda: model.init(jax.random.PRNGKey(0), cfg,
                                  jnp.bfloat16)
    else:
        import program as program_gpt
        from torchbooster_tpu.models.gpt import GPT as model

        cfg = program_gpt.gpt_config(raw)
    abstract = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip), tree)
    params = abstract(jax.eval_shape(init))
    real = engine_mod.make_pool, engine_mod.make_slot_state
    described = lambda make: lambda *a, **kw: jax.eval_shape(
        lambda: make(*a, **kw))
    engine_mod.make_pool = described(real[0])
    engine_mod.make_slot_state = described(real[1])
    try:
        engine = engine_mod.PagedEngine(
            params, cfg, page_size=serving["page_size"],
            n_pages=serving["n_pages"], max_slots=serving["max_slots"],
            prefill_chunk_pages=serving["prefill_chunk_pages"])
    finally:
        engine_mod.make_pool, engine_mod.make_slot_state = real

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = () if engine.slot_state is None \
        else (abstract(engine.slot_state),)
    fn, tail, kw = _serve_program(engine, program, arg)
    return jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, abstract(engine.pool["k"]), abstract(engine.pool["v"]),
        *_step_args(arg, engine), *state, *tail, **kw)


@pytest.mark.parametrize("cell,program", sorted(ACCEPTED_SERVE_TEXT))
def test_accepted_serve_cells_lower_to_the_text_pr33_left(
        one_chip, cell, program, monkeypatch):
    """The decode, chunk and mixed programs of the four serve cells
    (GPT-2 XL, LFM2, the latent-attention family and the window-and-
    full one, each at its cell's own geometry and full depth) lower to
    the StableHLO text they lowered to at PR 35's tree: PR 33's (the
    packed operand buffer and the device-carried key) with the last
    tokens kept on the device for the lanes. A PR that MEANS
    to change one of these programs replaces its hash here and says so
    in CHANGES.md; one that does not and fails here has moved a cell
    it did not measure."""
    import hashlib

    import torchbooster_tpu.models.moe as moe_mod

    # the process is pinned to the CPU; the program lowered is the
    # chip's (the pallas grouped product)
    monkeypatch.setattr(moe_mod, "_on_tpu", lambda: True)
    # a Mosaic kernel's serialized body carries its call sites' files
    # and line numbers: with no frames kept, a line added above a call
    # does not change the text
    frames = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = _accepted_cell_lowered(one_chip, cell, program).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", frames)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == ACCEPTED_SERVE_TEXT[cell, program]


@pytest.mark.parametrize("program", ["decode", "chunk", "mixed"])
def test_sarvam_mla_serve_programs_fit_and_stay_in_place_on_v5e(
        one_chip, program, monkeypatch):
    """The programs of the ``sarvam-105b.serve-longdoc-r80`` cell
    (decode, the chunk alone, and the chunk with the decode lanes
    riding) at its own size (6 layers at published widths, 32 of 128
    experts a layer, 4096 pages of 64, 32 slots, chunks of 256 over an
    8,960-position table), compiled for the described v5e with the
    pool donated: the pool is ONE array of 640-lane rows with no V
    half and comes back aliased, no instruction writes a second buffer
    the size of a layer's pool, the experts run as the pallas grouped
    product with no copy of an expert stack, both attentions run as
    the latent paged kernel (Mosaic compiles it; a chunk's float32
    scores over its table never reach HBM: the scratch stays far under
    them), and weights, pool and scratch together fit the chip."""
    import json
    import math
    import sys
    from pathlib import Path

    import torchbooster_tpu.models.moe as moe_mod
    import torchbooster_tpu.serving.engine as engine_mod
    from torchbooster_tpu.models.mla_moe import MLAMoE

    monkeypatch.setattr(moe_mod, "_on_tpu", lambda: True)
    bench = Path(__file__).resolve().parent.parent / "benchmark"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    import program_sarvam_mla

    raw = json.loads((bench / "configs" / "sarvam-105b.json").read_text())
    traffic = json.loads(
        (bench / "traffic" / "serve-longdoc-r80.json").read_text())
    serving = traffic["serving"]
    cfg = program_sarvam_mla.model_config(raw, traffic["max_positions"])
    slots, pages, page = (serving["max_slots"], serving["n_pages"],
                          serving["page_size"])
    abstract = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip), tree)
    params = abstract(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if x.dtype == jnp.float32 and x.ndim > 1 else x,
        MLAMoE.init(jax.random.PRNGKey(0), cfg, jnp.bfloat16))))
    real = engine_mod.make_pool
    engine_mod.make_pool = lambda *a, **kw: jax.eval_shape(
        lambda: real(*a, **kw))
    try:
        engine = engine_mod.PagedEngine(
            params, cfg, page_size=page, n_pages=pages, max_slots=slots,
            prefill_chunk_pages=serving["prefill_chunk_pages"])
    finally:
        engine_mod.make_pool = real
    assert engine.pool["v"] is None and engine.slot_state is None
    assert engine.chunk_tokens == 256
    pool_k = abstract(engine.pool["k"])
    assert pool_k.shape == (6, pages, page, 640)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tables = engine.tables
    fn, tail, kw = _serve_program(engine, program, arg)
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, pool_k, None, *_step_args(arg, engine), *tail,
        **kw).compile()

    layer_elems = pages * page * 640
    moved = _pool_sized_writes(compiled, layer_elems, pool_k, cfg.vocab)
    assert not moved, f"pool-sized buffers written: {moved}"
    nbytes = lambda tree: sum(math.prod(x.shape) * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= nbytes(pool_k)
    assert "ragged-dot" not in compiled.as_text()
    # no copy as large as ONE layer's stack of one expert matrix
    stack = cfg.experts_held[1] * cfg.d_model * cfg.expert_width
    copies = [(name, dims) for name, op, results
              in _top_level_results(compiled.as_text()) if op == "copy"
              for _, dims, _ in results
              if math.prod(int(d) for d in dims.split(",") if d) >= stack]
    assert not copies, f"expert-stack-sized copies: {copies}"
    # a chunk's float32 scores of all heads over the table would be
    # chunk x heads x table x 4 B (587 MB), the decode lanes' per-page
    # partials 671 MB: the kernel keeps both on the chip
    scores = engine.chunk_tokens * cfg.n_heads \
        * tables.max_pages_per_slot * page * 4
    assert memory.temp_size_in_bytes < scores // 2, \
        memory.temp_size_in_bytes
    assert "tpu_custom_call" in compiled.as_text()
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    print(f"sarvam-mla {program}: args "
          f"{memory.argument_size_in_bytes / 1e9:.3f} GB, temp "
          f"{memory.temp_size_in_bytes / 1e9:.3f} GB, total "
          f"{total / 1e9:.3f} GB")
    assert nbytes(params) > 10.9e9 and total < V5E_HBM_BYTES, total


@pytest.mark.parametrize("program", ["decode", "chunk", "mixed"])
def test_afmoe_serve_programs_fit_and_keep_both_pools_in_place_on_v5e(
        one_chip, program, monkeypatch):
    """The programs of the ``trinity-large-preview.serve-longctx-r80``
    cell (decode, the chunk alone, and the chunk with the decode lanes
    riding) at its own size (5 layers at published widths — four
    sliding, one full —, 32 of 256 experts a layer, 32 slots, chunks
    of 256, a full-layer pool of 4096 pages of 64 under a
    17,152-position table and a window pool of 32 x 69 ring pages),
    compiled for the described v5e with BOTH pools donated: each
    comes back aliased (no copy of either: the pool-sized writes are
    the two scatters), no instruction writes another buffer the size
    of a layer of either pool, the experts run as the pallas grouped
    product with no copy of an expert stack, a chunk's float32 scores
    over its whole table (843 MB) never exist — the full layer is
    walked in blocks — and weights, both pools and scratch fit the
    chip."""
    import json
    import math
    import sys
    from pathlib import Path

    import torchbooster_tpu.models.moe as moe_mod
    import torchbooster_tpu.serving.engine as engine_mod
    from torchbooster_tpu.models.afmoe import Afmoe

    monkeypatch.setattr(moe_mod, "_on_tpu", lambda: True)
    bench = Path(__file__).resolve().parent.parent / "benchmark"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    import program_afmoe

    raw = json.loads(
        (bench / "configs" / "trinity-large-preview.json").read_text())
    traffic = json.loads(
        (bench / "traffic" / "serve-longctx-r80.json").read_text())
    serving = traffic["serving"]
    cfg = program_afmoe.model_config(raw, traffic["max_positions"])
    slots, pages, page = (serving["max_slots"], serving["n_pages"],
                          serving["page_size"])
    abstract = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip), tree)
    params = abstract(jax.eval_shape(
        lambda: Afmoe.init(jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    real = engine_mod.make_pool
    engine_mod.make_pool = lambda *a, **kw: jax.eval_shape(
        lambda: real(*a, **kw))
    try:
        engine = engine_mod.PagedEngine(
            params, cfg, page_size=page, n_pages=pages, max_slots=slots,
            prefill_chunk_pages=serving["prefill_chunk_pages"])
    finally:
        engine_mod.make_pool = real
    assert engine.ring == 69 and engine.chunk_tokens == 256
    assert engine.tables.max_pages_per_slot == 268
    pool_k, pool_v = (abstract(engine.pool[h]) for h in "kv")
    assert pool_k["full"].shape == (1, pages, page, 1024)
    assert pool_k["window"].shape == (4, slots * 69, page, 1024)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, tail, kw = _serve_program(engine, program, arg)
    compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, pool_k, pool_v, *_step_args(arg, engine), *tail,
        **kw).compile()

    nbytes = lambda tree: sum(math.prod(x.shape) * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= nbytes(pool_k) + nbytes(pool_v)
    # nothing the size of the smaller pool's layer is written but the
    # pools' own updates
    layer_elems = min(pages, slots * 69) * page * 1024
    moved = _pool_sized_writes(compiled, layer_elems, pool_k, cfg.vocab)
    assert not moved, f"pool-sized buffers written: {moved}"
    assert "ragged-dot" not in compiled.as_text()
    stack = cfg.experts_held[1] * cfg.d_model * cfg.expert_width
    copies = [(name, dims) for name, op, results
              in _top_level_results(compiled.as_text()) if op == "copy"
              for _, dims, _ in results
              if math.prod(int(d) for d in dims.split(",") if d) >= stack]
    assert not copies, f"expert-stack-sized copies: {copies}"
    scores = engine.chunk_tokens * cfg.n_heads \
        * engine.tables.max_pages_per_slot * page * 4
    assert memory.temp_size_in_bytes < scores, memory.temp_size_in_bytes
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    print(f"afmoe {program}: args "
          f"{memory.argument_size_in_bytes / 1e9:.3f} GB, temp "
          f"{memory.temp_size_in_bytes / 1e9:.3f} GB, total "
          f"{total / 1e9:.3f} GB")
    assert nbytes(params) > 8.6e9 and total < V5E_HBM_BYTES, total
