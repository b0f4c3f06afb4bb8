"""Bailing hybrid (Ling-3.0-flash) — Kimi-delta-attention layers whose
float32 state decays each key channel on its own, beside a head-gated
latent-attention layer, a dense leading layer and sigmoid-routed
experts selected by group — against its plain float32 reference, at a
toy size on the CPU: the chunked rule against the recurrence (gates at
their bound included), the decode lanes' kernel against the step, the
paged engine's chunk, decode and mixed programs carrying the latent
pool and both slot states, the group-limited routing, the share of the
experts and the interleaved rotary lanes. Every comparison of the
model is on LOGITS, against ``benchmark/reference/bailing_hybrid.py``
— which imports nothing of the program, keeps no cache, runs KDA token
by token and latent attention expanded with interleaved RoPE.

Toy geometry: published layers ``[0, 3, 4, 5]`` of a pattern of three
(KDA, KDA, MLA) with two dense leading layers — a dense KDA layer,
then one whole period —, 4 heads of 16, a latent of 32 and rotary
lanes of 8, pages of 4 and chunks of 8, 16 experts in 4 groups, the 2
best groups kept, top-4.

Tolerances. Both sides run in float32, so what differs is the order of
sums (the chunked form's blocks, the absorbed attention's page
partials, tokens sorted by expert) over toy widths with logits of size
~1: a few 1e-7 at a time; 2e-4 leaves room for it and is two orders
under what a bfloat16 side shows (``test_bfloat16_would_fail``).
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import program_bailing_hybrid as program  # noqa: E402
import weights_bailing_hybrid as weights  # noqa: E402
from reference import bailing_hybrid as reference  # noqa: E402

from torchbooster_tpu.config import ServingConfig  # noqa: E402
from torchbooster_tpu.models import bailing_hybrid  # noqa: E402
from torchbooster_tpu.models.bailing_hybrid import (  # noqa: E402
    BailingHybrid, kda_chunked, kda_step)
from torchbooster_tpu.models.gpt import _rope  # noqa: E402
from torchbooster_tpu.models.moe import moe_dropless, moe_route  # noqa: E402
from torchbooster_tpu.observability import set_enabled  # noqa: E402
from torchbooster_tpu.serving import PagedEngine, Request  # noqa: E402

TOL = 2e-4
PAGE, CHUNK_PAGES = 4, 2                       # chunks of 8 tokens
K, M = bailing_hybrid.KDA, bailing_hybrid.MLA
NO_CLAMP = [0] * 12

TOY = {
    "vocab_size": 128, "hidden_size": 64, "num_attention_heads": 4,
    "head_dim": 16, "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "kda_safe_gate": True, "use_kda_lora": False,
    "num_kv_heads_for_linear_attn": 0, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "kv_lora_rank": 32, "v_head_dim": 16,
    "q_lora_rank": None, "use_qk_norm": True, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 100,
    "gated_attention_proj_granularity_type": "head_wise",
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "moe_shared_expert_intermediate_size": 16, "num_shared_experts": 1,
    "num_experts": 16, "experts_held": {"first": 0, "count": 16},
    "published": {"num_experts": 16, "vocab_size": 256,
                  "num_hidden_layers": 12},
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "score_function": "sigmoid", "topk_method": "noaux_tc",
    "first_k_dense_replace": 2, "layer_group_size": 3,
    "num_hidden_layers": 4, "layers_held": [0, 3, 4, 5],
    "expert_swiglu_limit_list": NO_CLAMP,
    "share_expert_swiglu_limit_list": NO_CLAMP,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "max_position_embeddings": 256, "model_type": "bailing_hybrid",
}


def share(first, count):
    return {**TOY, "num_experts": count,
            "experts_held": {"first": first, "count": count}}


def tokens(seed, n, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


class Recorder:
    """The engine's own logits, recorded where they are produced: the
    head of the chunk, decode and mixed programs."""

    def __init__(self, patch):
        self.rows = []
        real = bailing_hybrid.head

        def head(params, x, cfg):
            out = real(params, x, cfg)
            jax.debug.callback(lambda a: self.rows.append(np.asarray(a)),
                               out)
            return out

        patch.setattr(bailing_hybrid, "head", head)


@pytest.fixture(scope="module")
def model():
    """(config dict, model config, flat float32 weights, the program's
    tree, a recorder of the served logits)."""
    with pytest.MonkeyPatch.context() as patch:
        rec = Recorder(patch)
        flat = weights.generate(TOY, 11, jnp.float32)
        tree = weights.generate(TOY, 11, jnp.float32,
                                arrange=program.arranger(TOY))
        yield TOY, program.model_config(TOY), flat, tree, rec


@pytest.fixture(scope="module")
def engine(model):
    """ONE compiled toy engine for the module's serving tests: three
    slots, a pool of 64 pages, the registry on from its first step."""
    _, mcfg, _, tree, _ = model
    reg = set_enabled(True)
    reg.reset()
    try:
        yield PagedEngine(tree, mcfg, page_size=PAGE, n_pages=64,
                          max_slots=3, prefill_chunk_pages=CHUNK_PAGES,
                          compute_dtype=jnp.float32), reg
    finally:
        reg.reset()
        set_enabled(False)


def test_apply_matches_the_reference(model):
    cfg, mcfg, flat, tree, _ = model
    ids = tokens(0, 70)
    got, counts = BailingHybrid.apply(tree, jnp.asarray(ids)[None], mcfg,
                                      return_counts=True)
    want = reference.logits(flat, ids, cfg)
    assert float(jnp.abs(got[0] - want).max()) < TOL
    # pairs here + elsewhere = tokens x top-k, in each expert layer
    assert counts["held"].shape == (3, 16)
    assert (counts["held"].sum(1) + counts["elsewhere"] == 70 * 4).all()
    assert mcfg.plan == ((K,), (K, K, M), 1)
    shape = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)
    assert shape(BailingHybrid.init(jax.random.PRNGKey(0), mcfg)) \
        == shape(tree)


def test_bfloat16_would_fail(model):
    """The tolerance is tight enough: the same forward in bfloat16 lies
    far outside it."""
    cfg, mcfg, flat, tree, _ = model
    ids = tokens(0, 70)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.dtype == jnp.float32 and a.ndim > 1 else a, tree)
    got = BailingHybrid.apply(low, jnp.asarray(ids)[None], mcfg,
                              compute_dtype=jnp.bfloat16)[0]
    assert float(jnp.abs(got - reference.logits(flat, ids, cfg)).max()) \
        > 10 * TOL


@pytest.mark.parametrize("case", ["spread", "at-the-bound",
                                  "aligned-keys"])
def test_the_chunked_rule_is_the_recurrence_across_calls(case):
    """The chunked form (blocks of 64 in sub-blocks of 16) over calls
    of 37, 90 and 13 tokens, the state carried from call to call,
    against the recurrence one token at a time: the outputs and the
    final state. With every gate at -5 a block's cumulative gate spans
    e^-320 and no inf or NaN appears; with keys that point the same way
    (as SiLU'd, normed keys do) ``A``'s entries near 1 would cancel
    terms of ~1e4 in a product series for ``(I + A)^-1``; a pad
    (``beta`` 0, ``g`` 0) leaves the state as it was."""
    rng = np.random.default_rng(3)
    b, n, h, d = 2, 140, 3, 16
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q, k, v = unit(f32(b, n, h, d)) * d ** -0.5, unit(f32(b, n, h, d)), \
        f32(b, n, h, d)
    if case == "aligned-keys":
        k = unit(jnp.abs(f32(1, 1, h, d)) + 0.1 * jnp.abs(f32(b, n, h, d)))
    g = jnp.full((b, n, h, d), -5.0) if case == "at-the-bound" else \
        -jnp.asarray(rng.uniform(0.0, 0.3, (b, n, h, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (b, n, h)), jnp.float32)
    s0 = 0.1 * f32(b, h, d, d)
    state, want = s0, []
    for t in range(n):
        o, state = kda_step(state, q[:, t], k[:, t], v[:, t], g[:, t],
                            beta[:, t])
        want.append(o)
    got, carried = [], s0
    for lo, hi in ((0, 37), (37, 127), (127, 140)):
        o, carried = kda_chunked(carried, q[:, lo:hi], k[:, lo:hi],
                                 v[:, lo:hi], g[:, lo:hi], beta[:, lo:hi])
        got.append(o)
    got = jnp.concatenate(got, 1)
    assert bool(jnp.isfinite(got).all() and jnp.isfinite(carried).all())
    assert float(jnp.abs(got - jnp.stack(want, 1)).max()) < 1e-5
    assert float(jnp.abs(carried - state).max()) < 1e-5
    zero = jnp.zeros_like(g[:, :5])
    _, same = kda_chunked(carried, q[:, :5], k[:, :5], v[:, :5], zero,
                          zero[..., 0])
    assert jnp.array_equal(same, carried)


@pytest.mark.parametrize("live", [
    pytest.param([1, 0, 1, 1, 0], id="some"),
    pytest.param([0, 0, 0, 0, 0], id="none")])
def test_the_lanes_kernel_steps_the_live_slots_in_place(live):
    """``ops/delta_lanes.kda_lanes`` on a stacked state of 3 layers:
    layer 1's live slots advance one token exactly as ``kda_step``
    advances them, their outputs are its outputs; every other layer and
    every slot that is not live comes back bit for bit, with a zero
    output."""
    from torchbooster_tpu.ops.delta_lanes import kda_lanes

    rng = np.random.default_rng(5)
    b, h, dk, dv = 5, 4, 16, 8
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    state = f32(3, b, h, dk, dv)
    q, k, v = f32(b, h, dk), f32(b, h, dk), f32(b, h, dv)
    g = -jnp.asarray(rng.uniform(0.0, 5.0, (b, h, dk)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (b, h)), jnp.float32)
    live = jnp.asarray(live, bool)
    o, got = jax.jit(kda_lanes)(state, 1, q, k, v, g, beta, live)
    want_o, want_s = kda_step(state[1], q, k, v, g, beta)
    on = np.asarray(live)
    assert jnp.array_equal(got[0], state[0])
    assert jnp.array_equal(got[2], state[2])
    assert jnp.array_equal(got[1][~on], state[1][~on])
    assert not np.asarray(o)[~on].any()
    assert float(jnp.abs(got[1][on] - want_s[on]).max(initial=0)) < 1e-5
    assert float(jnp.abs(o[on] - want_o[on]).max(initial=0)) < 1e-5


def test_chunks_then_decode_for_slots_of_three_lengths(model, engine):
    """Three prompts of 5, 19 and 29 tokens (one, three and four chunks
    of 8: the KDA states and the MLA layer's latent rows cross chunk
    boundaries), seated one after another, then 10 decode steps of all
    three together: the logits behind every served token equal the
    reference's full forward over that slot's served stream. Both
    states are counted (``serving_slot_state_bytes_total{kind=kda}``),
    and one program of each kind was compiled."""
    cfg, _, flat, _, rec = model
    eng, reg = engine
    prompts = [tokens(20 + i, n) for i, n in enumerate((5, 19, 29))]
    slots, served, got = [], [], []
    for p in prompts:
        before = len(rec.rows)
        slot, first = eng.admit(p)
        jax.effects_barrier()
        slots.append(slot)
        served.append([first])
        got.append([rec.rows[-1][0, 0]])
        assert len(rec.rows) - before == -(-len(p) // 8)
    for _ in range(10):
        assert not eng.grow_slots()
        out = eng.step()
        jax.effects_barrier()
        for i, slot in enumerate(slots):
            served[i].append(int(out[slot]))
            got[i].append(rec.rows[-1][slot, 0])
    for p, s, g in zip(prompts, served, got):
        seq = list(p) + s
        want = reference.logits(flat, seq, cfg,
                                positions=range(len(p) - 1, len(seq) - 1))
        assert float(np.abs(np.stack(g) - np.asarray(want)).max()) < TOL
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1
    assert eng.pool["v"] is None
    assert eng.slot_state["kda"].shape == (3, 3, 4, 16, 16)
    assert eng.slot_state["kda"].dtype == jnp.float32
    live = eng.moe_counts.sum(axis=1) + eng.moe_elsewhere
    assert (live == 3 * cfg["num_experts_per_tok"]).all()
    snap = reg.snapshot()
    key = "serving_slot_state_bytes_total{kind=kda}{part=%s}"
    row = 3 * 4 * 16 * 16 * 4
    # chunks: 1 + 3 + 4 written, 0 + 2 + 3 read past position 0
    assert snap[key % "chunk"] == (8 + 5) * row
    # lanes: 10 steps of 3 live slots, each read and written
    assert snap[key % "lanes"] == 2 * 30 * row
    for slot in slots:
        eng.retire(slot)


@pytest.mark.parametrize("kind", ["kda", "conv"])
def test_zeroing_the_carried_state_fails_the_comparison(model, engine,
                                                         kind):
    """The comparison can see a state that is not carried: a prompt of
    four chunks whose ``kind`` rows are zeroed after its first chunk
    serves tokens far outside the tolerance (the decays are not ~0:
    what the first chunk wrote is still there three chunks later),
    where the same engine untouched matches the reference."""
    _, _, flat, _, _ = model
    eng, _ = engine
    prompt = tokens(40, 30)
    for zero in (False, True):
        slot = eng.admit_begin(prompt)
        eng.prefill_step()
        if zero:
            state = dict(eng.slot_state)
            state[kind] = jnp.zeros_like(state[kind])
            eng.slot_state = state
        while (done := eng.prefill_step()) is None:
            pass
        served = [done[1]]
        for _ in range(6):
            eng.grow_slots()
            served.append(int(eng.step()[slot]))
        eng.retire(slot)
        gaps = reference.served_gaps(flat, prompt, served, TOY)
        assert (float(gaps.max()) < TOL) != zero, (zero, float(gaps.max()))


def test_batching_preemption_and_mixed_steps(model):
    """Four requests over two slots and a pool too small for them:
    slots are seated, retired and reused, the pool's pressure preempts
    (the request re-seated from position 0: both states from zeros,
    the latent rows rewritten), and pending chunks ride the decode step
    as the mixed program. Every served token's logit equals the
    reference's best within the tolerance."""
    _, mcfg, flat, tree, _ = model
    serving = dict(page_size=PAGE, n_pages=14, max_slots=2,
                   prefill_chunk_pages=CHUNK_PAGES)
    reqs = [Request(prompt=tokens(10 + i, p), max_new_tokens=n)
            for i, (p, n) in enumerate([(21, 24), (30, 12), (9, 20),
                                        (17, 10)])]
    batcher = ServingConfig(**serving).make(tree, mcfg,
                                            compute_dtype=jnp.float32)
    assert batcher.run(reqs)["n_preemptions"] > 0
    assert batcher.engine.mixed_steps > 0
    for r in reqs:
        assert len(r.tokens) == r.max_new_tokens
        # a preempted request's prompt has its tokens folded in
        gaps = reference.served_gaps(flat, r.prompt[:r.base_len], r.tokens,
                                     TOY)
        assert float(gaps.max()) < TOL
    assert batcher.engine.decode_compiles == 1
    assert batcher.engine.prefill_compiles <= 2


def expert_layer(cfg, seed=11):
    """The first expert layer's feed-forward alone: (the flat leaves'
    rows as the reference reads them, the program's tree of the same
    numbers)."""
    names = ("mo_gate", "mo_bias", "mo_w1", "mo_w3", "mo_w2", "mo_s1",
             "mo_s3", "mo_s2")
    lw = jax.jit(lambda key: {n: weights.taker(cfg, key)(n, [0])[0]
                              for n in names})(weights.seed_key(seed))
    mat = lambda n: {"kernel": lw[n]}
    lp = {"moe_gate": mat("mo_gate"), "moe_bias": lw["mo_bias"],
          "moe_fc1": mat("mo_w1"), "moe_fc3": mat("mo_w3"),
          "moe_fc2": mat("mo_w2")}
    return lw, lp


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Model-configs guide, section 4: the four shares of one layer
    (experts 0-3, 4-7, 8-11 and 12-15 of 16: a routing group each),
    the routed parts summed and the shared expert counted once, equal
    the UNCUT reference layer; each share's pairs here plus pairs
    elsewhere are T x k."""
    uncut = share(0, 16)
    lw, _ = expert_layer(uncut)
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 29, 64), jnp.float32)
    st = lambda cfg: dict(reference.static(cfg))
    shared = reference._swiglu(u[0], lw["mo_s1"], lw["mo_s3"], lw["mo_s2"],
                               None)
    whole = shared + reference.routed(u[0], lw, st(uncut))
    total = shared
    for first in (0, 4, 8, 12):
        cfg = share(first, 4)
        part, lp = expert_layer(cfg)
        assert jnp.array_equal(part["mo_w1"], lw["mo_w1"][first:first + 4])
        out, held, away = moe_dropless(
            lp, u, 4, 2.5, held=(first, 4), route_eps=0.0, n_group=4,
            topk_group=2)
        assert int(held.sum() + away) == 29 * 4
        assert float(jnp.abs(out[0] - reference.routed(
            u[0], part, st(cfg))).max()) < 1e-5
        total = total + out[0]
    assert float(jnp.abs(total - whole).max()) < 1e-5


def test_group_limited_routing_is_a_masked_top_k():
    """``moe_route`` with ``n_group`` 8 / ``topk_group`` 4 over 64
    experts: the selection is the top-k of ``s + b`` with every expert
    outside the 4 groups of the highest top-2 sums masked, weights
    renormalised and scaled — the reference's selection and weights —
    and never an expert of a group not kept; with one group it is
    today's routing bit for bit."""
    gate = jax.random.normal(jax.random.PRNGKey(1), (64, 64)) * 0.3
    bias = jax.random.normal(jax.random.PRNGKey(3), (64,)) * 0.05
    u = jax.random.normal(jax.random.PRNGKey(2), (37, 64))
    params = {"moe_gate": {"kernel": gate}, "moe_bias": bias}
    sel, w = moe_route(params, u, top_k=8, scaling=2.5, eps=0.0,
                       n_group=8, topk_group=4)
    scores = jax.nn.sigmoid(jnp.dot(u, gate, precision="highest"))
    biased = np.asarray(scores + bias).reshape(37, 8, 8)
    top2 = np.sort(biased, -1)[..., -2:].sum(-1)
    kept = np.argsort(-top2, -1)[:, :4]
    masked = np.full((37, 8, 8), -np.inf)
    np.put_along_axis(masked, kept[..., None],
                      np.take_along_axis(biased, kept[..., None], 1), 1)
    want = np.argsort(-masked.reshape(37, 64), -1)[:, :8]
    assert np.array_equal(np.sort(sel, -1), np.sort(want, -1))
    assert np.isin(np.asarray(sel) // 8, kept).all(-1).all()
    full, ref_sel = reference.route(
        u, {"mo_gate": gate, "mo_bias": bias},
        {"num_experts_per_tok": 8, "norm_topk_prob": True, "n_group": 8,
         "topk_group": 4, "routed_scaling_factor": 2.5})
    assert np.array_equal(np.sort(sel, -1), np.sort(ref_sel, -1))
    got = np.zeros((37, 64))
    np.put_along_axis(got, np.asarray(sel), np.asarray(w), axis=-1)
    assert np.abs(got - np.asarray(full)).max() < 1e-6
    one = jax.jit(lambda p, t: moe_route(p, t, 8, 2.5))
    grouped = jax.jit(lambda p, t: moe_route(p, t, 8, 2.5, n_group=1,
                                             topk_group=1))
    assert one.lower(params, u).as_text() \
        == grouped.lower(params, u).as_text()
    with pytest.raises(ValueError, match="sigmoid"):
        moe_route(params, u, 8, scoring="softmax", n_group=8, topk_group=4)


def test_interleaved_rope_is_the_permuted_rotate_half():
    """The published rotary lanes turn in interleaved pairs; the program
    turns rotate-half pairs over lanes permuted by
    ``rope_permutation``: the two rotations agree lane for lane, so the
    scores (products of rotated queries and keys) are the same."""
    r, theta = 64, 6e6
    x = jax.random.normal(jax.random.PRNGKey(0), (300, 3, r))
    perm = bailing_hybrid.rope_permutation(r)
    freqs = theta ** (-2.0 * np.arange(r // 2) / r)
    half = _rope(x[None, :, :, perm], jnp.arange(300),
                 freqs=jnp.asarray(freqs, jnp.float32))[0]
    inter = reference._rope(x, theta)
    assert float(jnp.abs(half - inter[..., perm]).max()) < 1e-5
    assert sorted(perm) == list(range(r))


UNSUPPORTED = {
    "prefix_cache": {"prefix_cache": True},
    "speculative": {"speculative": True},
    "tp": {"tp": 2},
    "cache_dtype": {"cache_dtype": "int8"},
    "decode_backend": {"decode_backend": "pallas"},
}


@pytest.mark.parametrize("feature", sorted(UNSUPPORTED))
def test_unsupported_feature_raises_at_build(model, feature):
    """By name, with the reason the model's module gives."""
    from torchbooster_tpu.config import resolve_types

    _, mcfg, _, tree, _ = model
    block = {"page_size": 8, "n_pages": 32, "max_slots": 2,
             **UNSUPPORTED[feature]}
    conf = ServingConfig(**resolve_types(ServingConfig, block))
    mesh = jax.make_mesh((2,), ("tp",)) if feature == "tp" else None
    with pytest.raises(NotImplementedError,
                       match=feature.split("_")[0]) as err:
        conf.make(tree, mcfg, compute_dtype=jnp.float32, mesh=mesh)
    key = next(k for k in bailing_hybrid.UNSERVED
               if k.startswith(feature.split("_")[0]))
    assert bailing_hybrid.UNSERVED[key] in str(err.value)


def test_a_clamped_swiglu_is_refused():
    """The published layers 34-41 clamp their SwiGLUs
    (``expert_swiglu_limit_list``): a configuration that holds one is
    refused at build with the reason, and the cut's layers hold none."""
    clamped = {**TOY, "expert_swiglu_limit_list": [0] * 4 + [4] * 8}
    with pytest.raises(NotImplementedError, match="clamped SwiGLU"):
        program.model_config(clamped)
    assert program.swiglu_limit(TOY) == 0.0


READERS = ("kda_lanes_roofline", "kda_chunk_roofline",
           "ling_moe_experts_roofline", "ling_serve_mfu",
           "ling_mla_decode_attn_roofline", "ling_mla_chunk_attn_roofline")


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_with_nothing_to_read_returns_none(reader):
    """What the benchmark's traced runs of the PARENT and of the other
    families' cells rest on: a reader returns None, never 0 and never
    an exception, for a configuration of another family and for a run
    of this family whose registry and trace hold nothing."""
    import run as harness
    import trace_reduce

    if str(BENCH / "layer_metrics") not in sys.path:
        sys.path.append(str(BENCH / "layer_metrics"))
    module = harness.load_module(BENCH / "layer_metrics" / f"{reader}.py")
    empty = trace_reduce.Trace(ops={"chip": []}, modules={"chip": []})
    window = {"decode_tokens": 0, "prefill_tokens": 0, "context_read": 0.0,
              "ttfts": [], "gaps": []}
    for cfg in ({"n_layer": 2, "n_embd": 64},
                {"kv_lora_rank": 32, "num_hidden_layers": 2}, TOY):
        layers = {"cfg": cfg, "window": window, "seconds": 1.0, "chips": 1,
                  "peaks": {"bf16_flops_per_s": 1e12,
                            "hbm_bytes_per_s": 1e11},
                  "registry_open": {}, "registry_close": {},
                  "prefill_pairs": 0.0, "trace_path": None, "trace": empty}
        assert module.read(reader + ".lat", layers) is None


def test_the_latent_readers_count_one_mla_layer_in_seven(monkeypatch):
    """The two latent-attention rooflines of this family by hand at the
    published widths, with the device time put in the trace's place:
    the lanes' kernel reads a live row of the ONE MLA layer (1,280 B)
    or does 32 heads x (576 + 512) x 2 operations on it, the chunk's
    visible pairs cost 32 heads x (192 + 128) x 2 in the expanded
    form."""
    import json

    import run as harness

    if str(BENCH / "layer_metrics") not in sys.path:
        sys.path.append(str(BENCH / "layer_metrics"))
    cfg = json.loads((BENCH / "configs" / "ling-3.0-flash.json")
                     .read_text())
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    layers = {"cfg": cfg, "peaks": peaks, "traced_context_read": 4.0e6,
              "prefill_pairs": 3.0e7,
              "registry_open": {"serving_prefill_chunks_total": 10.0},
              "registry_close": {"serving_prefill_chunks_total": 110.0}}
    lanes = harness.load_module(
        BENCH / "layer_metrics" / "ling_mla_decode_attn_roofline.py")
    monkeypatch.setattr(lanes, "seconds_by_run", lambda *a: [0.004, 0.006])
    least = max(4.0e6 * 2 * 1088 * 32 / 197e12, 4.0e6 * 1280 / 819e9)
    assert lanes.read("x.lat", layers) == pytest.approx(
        100 * least / 0.010)
    chunk = harness.load_module(
        BENCH / "layer_metrics" / "ling_mla_chunk_attn_roofline.py")
    monkeypatch.setattr(chunk, "scope_ms", lambda *a: 2.0)
    assert chunk.read("x.lat", layers) == pytest.approx(
        100 * 3.0e5 * 2 * 320 * 32 / (197e12 * 2.0e-3))


def test_the_operation_counts_by_hand():
    """``flops_bailing_hybrid`` at the published widths: the parameter
    arithmetic (a KDA mixer 63 M, the MLA mixer 32 M, an expert 5.90 M,
    5.23 B in all for the cut), the state a slot (2 MiB of ``S`` and
    72 KiB of conv rows a KDA layer), the latent row (1,280 B) and the
    chunked form's operations for one block."""
    import json

    import flops_bailing_hybrid as fl

    cfg = json.loads((BENCH / "configs" / "ling-3.0-flash.json")
                     .read_text())
    assert fl.layer_counts(cfg) == {"layers": 7, "kda": 6, "mla": 1,
                                    "dense": 1, "moe": 6}
    assert fl.kda_params(cfg) == pytest.approx(63.0e6, rel=2e-3)
    assert fl.mla_params(cfg) == pytest.approx(31.96e6, rel=1e-3)
    assert fl.expert_params(cfg) == pytest.approx(5.90e6, rel=1e-3)
    assert fl.n_params(cfg) == pytest.approx(5.23e9, rel=2e-3)
    assert fl.state_bytes(cfg) == {"kda": 2 * 2**20, "conv": 72 * 2**10}
    assert fl.row_bytes(cfg) == 1280
    one = 6 * 64 * 128 * 128 + 2 * 64 ** 2 * 384 + 64 ** 2 * 256
    assert fl.kda_chunk_flops(cfg, 256) == 4 * 32 * one


def test_the_benchmark_job_rehearses_at_toy_size():
    """``benchmark/run.execute`` on the toy root beside the others
    (benchmark/tests/tiny_bailing_hybrid): the ``serve_bailing_hybrid``
    job end to end — weights from the seed, the stack as a user's YAML
    builds it, HTTP traffic from the load generator's process with
    prompts of several chunks, the served streams against the float32
    reference. The limit is a bfloat16 program's against a float32
    reference at toy widths; nothing here is a measurement."""
    import flops
    import run as harness

    root = BENCH / "tests" / "tiny_bailing_hybrid"
    out = harness.execute("ling-tiny.serve-longout-tiny", 2**31 + 7, 3.0,
                          False, root=root, devices=jax.devices()[:1],
                          peaks=flops.peaks_of("TPU v5 lite"))
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert line["compared"]["bad_streams"]["value"] == 0
    # the sample always holds the longest request that finished: a
    # prompt of several chunks of 16
    assert out["checks"]["chunk_boundaries_checked"] >= 1
    assert out["log"]["compiles_in_window"] == 0
    assert len(out["log"]["occupancy_at_window"]) == 2
