"""Qwen3-Next — gated delta-rule linear-attention layers with a float32
state a slot, a gated full-attention layer, softmax-routed experts and
a gated shared expert — against its plain float32 reference, at a toy
size on the CPU: the model file, the chunked delta rule against the
recurrence, the paged engine's chunk, decode and mixed programs
carrying both slot states (the convolution's rows and ``S``) across
chunks and steps, the batcher's seat / retire / preempt cycle, the
share of the experts, softmax routing and the counters. Every
comparison is on LOGITS, against ``benchmark/reference/qwen3_next.py``
— which imports nothing of the program, keeps no cache and runs the
delta rule token by token.

Toy geometry: one period (three linear layers, one full), 2 key heads
and 4 value heads of 16 (the published 2:1), heads of 32 with RoPE on
8 lanes (the published quarter), pages of 4 and chunks of 8, 8 of 16
experts held, top-4.

Tolerances. Both sides run in float32, so what differs is the order of
sums (the chunked form's blocks, page partials merged by an online
softmax, tokens sorted by expert) over toy widths with logits of size
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

import program_qwen3_next as program  # noqa: E402
import weights_qwen3_next as weights  # noqa: E402
from reference import qwen3_next as reference  # noqa: E402

from torchbooster_tpu.config import ServingConfig  # noqa: E402
from torchbooster_tpu.models import lfm2, qwen3_next  # noqa: E402
from torchbooster_tpu.models.moe import moe_dropless, moe_route  # noqa: E402
from torchbooster_tpu.models.qwen3_next import (  # noqa: E402
    Qwen3Next, delta_chunked, delta_step)
from torchbooster_tpu.observability import set_enabled  # noqa: E402
from torchbooster_tpu.serving import PagedEngine, Request  # noqa: E402
from torchbooster_tpu.serving.kv_pages import make_slot_state  # noqa: E402

TOL = 2e-4
PAGE, CHUNK_PAGES = 4, 2                       # chunks of 8 tokens
L, F = qwen3_next.LINEAR, qwen3_next.FULL

TOY = {
    "vocab_size": 128, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 8,
    "experts_held": {"first": 0, "count": 8},
    "published": {"num_experts": 16, "vocab_size": 256,
                  "num_hidden_layers": 48},
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "rope_theta": 100,
    "rope_scaling": None, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "max_position_embeddings": 256, "model_type": "qwen3_next",
}


def share(first, count):
    return {**TOY, "num_experts": count,
            "experts_held": {"first": first, "count": count}}


def built(cfg, seed=11):
    """(model config, flat float32 weights, the program's tree)."""
    flat = weights.generate(cfg, seed, jnp.float32)
    tree = weights.generate(cfg, seed, jnp.float32,
                            arrange=program.arranger(cfg))
    return program.model_config(cfg), flat, tree


@pytest.fixture(scope="module")
def model():
    return (TOY, *built(TOY))


def engine_of(mcfg, tree, **kw):
    kw = {"page_size": PAGE, "n_pages": 64, "max_slots": 3,
          "prefill_chunk_pages": CHUNK_PAGES,
          "compute_dtype": jnp.float32, **kw}
    return PagedEngine(tree, mcfg, **kw)


def tokens(seed, n, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def gap_max(flat, prompt, served):
    return float(reference.served_gaps(flat, prompt, served, TOY).max())


def test_apply_matches_the_reference(model):
    cfg, mcfg, flat, tree = model
    ids = tokens(0, 70)
    got, counts = Qwen3Next.apply(tree, jnp.asarray(ids)[None], mcfg,
                                  return_counts=True)
    want = reference.logits(flat, ids, cfg)
    assert float(jnp.abs(got[0] - want).max()) < TOL
    # pairs here + elsewhere = tokens x top-k, in each layer
    assert counts["held"].shape == (4, 8)
    assert (counts["held"].sum(1) + counts["elsewhere"] == 70 * 4).all()
    assert mcfg.plan == ((), (L, L, L, F), 1)
    shape = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shape(Qwen3Next.init(jax.random.PRNGKey(0), mcfg)) == shape(tree)


def test_bfloat16_would_fail(model):
    """The tolerance is tight enough: the same forward in bfloat16 lies
    far outside it."""
    cfg, mcfg, flat, tree = model
    ids = tokens(0, 70)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.dtype == jnp.float32 and a.ndim > 1 else a, tree)
    got = Qwen3Next.apply(low, jnp.asarray(ids)[None], mcfg,
                          compute_dtype=jnp.bfloat16)[0]
    assert float(jnp.abs(got - reference.logits(flat, ids, cfg)).max()) \
        > 10 * TOL


@pytest.mark.parametrize("block", [16, 64])
def test_the_chunked_rule_is_the_recurrence_across_calls(block):
    """The chunked form over calls of 37, 50 and 13 tokens (none a
    multiple of the block), the state carried from call to call,
    against the recurrence one token at a time: the outputs and the
    final state; a pad (``beta`` 0, ``g`` 0) leaves the state as it
    was."""
    rng = np.random.default_rng(3)
    b, n, h, d = 2, 100, 3, 16
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q, k, v = unit(f32(b, n, h, d)) * d ** -0.5, unit(f32(b, n, h, d)), \
        f32(b, n, h, d)
    g = -jnp.asarray(rng.uniform(0.0, 0.1, (b, n, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (b, n, h)), jnp.float32)
    s0 = 0.1 * f32(b, h, d, d)
    state, want = s0, []
    for t in range(n):
        o, state = delta_step(state, q[:, t], k[:, t], v[:, t], g[:, t],
                              beta[:, t])
        want.append(o)
    got, carried = [], s0
    for lo, hi in ((0, 37), (37, 87), (87, 100)):
        o, carried = delta_chunked(carried, q[:, lo:hi], k[:, lo:hi],
                                   v[:, lo:hi], g[:, lo:hi], beta[:, lo:hi],
                                   block=block)
        got.append(o)
    assert float(jnp.abs(jnp.concatenate(got, 1)
                         - jnp.stack(want, 1)).max()) < 1e-5
    assert float(jnp.abs(carried - state).max()) < 1e-5
    zero = jnp.zeros_like(g[:, :5])
    _, same = delta_chunked(carried, q[:, :5], k[:, :5], v[:, :5], zero,
                            zero, block=block)
    assert jnp.array_equal(same, carried)


@pytest.mark.parametrize("live", [
    pytest.param([1, 0, 1, 1, 0], id="some"),
    pytest.param([1, 1, 1, 1, 1], id="all"),
    pytest.param([0, 0, 0, 0, 1], id="last-only"),
    pytest.param([0, 0, 0, 0, 0], id="none")])
def test_the_lanes_kernel_steps_the_live_slots_in_place(live):
    """``ops/delta_lanes.py`` on a stacked state of 3 layers: layer 1's
    live slots advance one token exactly as ``delta_step`` advances
    them, their outputs are its outputs; every other layer and every
    slot that is not live comes back bit for bit, with a zero output."""
    from torchbooster_tpu.ops.delta_lanes import delta_lanes

    rng = np.random.default_rng(5)
    b, h, dk, dv = 5, 4, 16, 8
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    state = f32(3, b, h, dk, dv)
    q, k, v = f32(b, h, dk), f32(b, h, dk), f32(b, h, dv)
    g = -jnp.asarray(rng.uniform(0.0, 0.1, (b, h)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (b, h)), jnp.float32)
    live = jnp.asarray(live, bool)
    o, got = jax.jit(delta_lanes)(state, 1, q, k, v, g, beta, live)
    want_o, want_s = delta_step(state[1], q, k, v, g, beta)
    on = np.asarray(live)
    assert jnp.array_equal(got[0], state[0])
    assert jnp.array_equal(got[2], state[2])
    assert jnp.array_equal(got[1][~on], state[1][~on])
    assert not np.asarray(o)[~on].any()
    assert float(jnp.abs(got[1][on] - want_s[on]).max(initial=0)) < 1e-5
    assert float(jnp.abs(o[on] - want_o[on]).max(initial=0)) < 1e-5


class Recorder:
    """The engine's own logits, recorded where they are produced: the
    head of the chunk, decode and mixed programs."""

    def __init__(self, monkeypatch):
        self.rows = []
        real = qwen3_next.head

        def head(params, x, cfg):
            out = real(params, x, cfg)
            jax.debug.callback(lambda a: self.rows.append(np.asarray(a)),
                               out)
            return out

        monkeypatch.setattr(qwen3_next, "head", head)


def test_chunks_then_decode_for_slots_of_three_lengths(model, monkeypatch):
    """Three prompts of 5, 19 and 29 tokens (one, three and four
    chunks of 8: both states cross chunk boundaries), seated one after
    another, then 12 decode steps of all three together: the logits
    behind every served token equal the reference's full forward over
    that slot's served stream."""
    cfg, mcfg, flat, tree = model
    rec = Recorder(monkeypatch)
    eng = engine_of(mcfg, tree)
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
    for _ in range(12):
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
    live = eng.moe_counts.sum(axis=1) + eng.moe_elsewhere
    assert (live == 3 * cfg["num_experts_per_tok"]).all()


def _rows(eng, slot):
    return {k: np.asarray(a[:, slot]) for k, a in eng.slot_state.items()}


def test_a_slot_between_its_chunks_keeps_its_state(model):
    """A decoding slot beside a prompt half prefilled: a plain decode
    step moves the decoding slot's rows of both states and leaves the
    other slot's — the lanes update live slots only — and the prompt's
    served stream, finished afterwards, matches the reference."""
    cfg, mcfg, flat, tree = model
    eng = engine_of(mcfg, tree)
    a, _ = eng.admit(tokens(30, 6))
    b_prompt = tokens(31, 27)
    b = eng.admit_begin(b_prompt)
    eng.prefill_step()                      # B's first chunk of four
    before_a, before_b = _rows(eng, a), _rows(eng, b)
    assert np.abs(before_b["delta"]).sum() > 0
    eng.grow_slots()
    eng.step()
    after_a, after_b = _rows(eng, a), _rows(eng, b)
    for kind in ("conv", "delta"):
        assert np.array_equal(after_b[kind], before_b[kind])
        assert not np.array_equal(after_a[kind], before_a[kind])
    while (done := eng.prefill_step()) is None:
        eng.grow_slots()
        eng.step()
    served = [done[1]]
    for _ in range(6):
        eng.grow_slots()
        served.append(int(eng.step()[b]))
    assert gap_max(flat, b_prompt, served) < TOL


@pytest.mark.parametrize("kind", ["delta", "conv"])
def test_zeroing_the_carried_state_fails_the_comparison(model, kind):
    """The comparison can see a state that is not carried: a prompt of
    four chunks whose ``kind`` rows are zeroed after its first chunk
    serves tokens far outside the tolerance (the decays are not ~0:
    what the first chunk wrote is still there three chunks later),
    where the same engine untouched matches the reference."""
    _, mcfg, flat, tree = model
    prompt = tokens(40, 30)
    for zero in (False, True):
        eng = engine_of(mcfg, tree, max_slots=1)
        slot = eng.admit_begin(prompt)
        eng.prefill_step()
        if zero:
            state = dict(eng.slot_state)
            state[kind] = jnp.zeros_like(state[kind])
            eng.slot_state = state
        while (done := eng.prefill_step()) is None:
            pass
        served = [done[1]]
        for _ in range(8):
            eng.grow_slots()
            served.append(int(eng.step()[slot]))
        seq = list(prompt) + served
        got = reference.logits(flat, seq, TOY,
                               positions=range(len(prompt) - 1,
                                               len(seq) - 1))
        gaps = reference.served_gaps(flat, prompt, served, TOY)
        assert (float(gaps.max()) < TOL) != zero, (zero, float(gaps.max()))
        assert got.shape[0] == 9


def test_batching_reuse_preemption_and_mixed_steps(model):
    """Five requests over two slots and a pool too small for them:
    slots are seated and retired at different steps and reused, the
    pool's pressure preempts (the request re-seated from position 0:
    both states start from zeros, whatever the slot's last tenant
    left), and pending chunks ride the decode step as the mixed
    program. Every stream equals a fresh single run of its own, and
    every served token's logit equals the reference's best within the
    tolerance."""
    _, mcfg, flat, tree = model
    serving = dict(page_size=PAGE, n_pages=24, max_slots=2,
                   prefill_chunk_pages=CHUNK_PAGES)
    lens = [(21, 40), (37, 30), (9, 40), (50, 9), (17, 25)]

    def requests():
        return [Request(prompt=tokens(10 + i, p), max_new_tokens=n)
                for i, (p, n) in enumerate(lens)]

    batcher = ServingConfig(**serving).make(tree, mcfg,
                                            compute_dtype=jnp.float32)
    reqs = requests()
    assert batcher.run(reqs)["n_preemptions"] > 0
    assert batcher.engine.mixed_steps > 0
    alone = ServingConfig(**{**serving, "n_pages": 64}).make(
        tree, mcfg, compute_dtype=jnp.float32)
    for crowded, fresh in zip(reqs, requests()):
        alone.run([fresh])
        assert list(crowded.tokens) == list(fresh.tokens)
        assert gap_max(flat, fresh.prompt, fresh.tokens) < TOL
    assert batcher.engine.decode_compiles == 1
    assert batcher.engine.prefill_compiles <= 2


def test_each_state_keeps_its_own_dtype(model):
    """``CacheSpec.slot_states`` names a dtype a state: ``S`` float32
    whatever the engine computes in, the convolution's rows in the
    compute dtype; LFM2's conv state stays in the compute dtype."""
    _, mcfg, _, _ = model
    spec = mcfg.cache_spec()
    for compute in (jnp.bfloat16, jnp.float32):
        state = jax.eval_shape(lambda: make_slot_state(spec, 5, compute))
        assert state["delta"].dtype == jnp.float32
        assert state["delta"].shape == (3, 5, 4, 16, 16)
        assert state["conv"].dtype == compute
        assert state["conv"].shape == (3, 5, 3, 2 * 32 + 64)
    lf = lfm2.LFM2Config(d_model=64, n_heads=4, n_kv_heads=2,
                         layer_types=(lfm2.CONV, lfm2.ATTENTION),
                         n_dense_layers=2)
    conv = jax.eval_shape(lambda: make_slot_state(
        lf.cache_spec(), 5, jnp.bfloat16))["conv"]
    assert conv.dtype == jnp.bfloat16 and conv.shape == (1, 5, 2, 64)
    # the cell's geometry: 3 linear layers x 128 slots = 0.82 GB
    big = program.model_config(
        {**TOY, "hidden_size": 2048, "linear_num_key_heads": 16,
         "linear_num_value_heads": 32, "linear_key_head_dim": 128,
         "linear_value_head_dim": 128})
    state = jax.eval_shape(lambda: make_slot_state(big.cache_spec(), 128))
    nbytes = sum(a.dtype.itemsize * np.prod(a.shape)
                 for a in state.values())
    assert nbytes == pytest.approx(0.824e9, rel=1e-3)


def test_the_state_bytes_are_counted(model):
    """``serving_slot_state_bytes_total{kind, part}``: a live lane's
    rows of every linear layer read and written once a step; a chunk's
    slot written, and read past position 0."""
    _, mcfg, _, tree = model
    reg = set_enabled(True)
    reg.reset()
    try:
        eng = engine_of(mcfg, tree)
        eng.admit(tokens(8, 13))                  # two chunks
        eng.grow_slots()
        eng.step()
        snap = reg.snapshot()
        key = "serving_slot_state_bytes_total{kind=%s}{part=%s}"
        row = {"delta": 3 * 4 * 16 * 16 * 4, "conv": 3 * 3 * 128 * 4}
        for kind, n in row.items():
            assert snap[key % (kind, "chunk")] == (1 + 2) * n
            assert snap[key % (kind, "lanes")] == 2 * n
    finally:
        reg.reset()
        set_enabled(False)


def expert_layer(cfg, seed=11):
    """The first layer's feed-forward alone: (the flat leaves' rows as
    the reference reads them, the program's tree of the same
    numbers)."""
    names = ("mo_gate", "mo_w1", "mo_w3", "mo_w2", "mo_s1", "mo_s3",
             "mo_s2", "mo_sg")
    lw = jax.jit(lambda key: {n: weights.taker(cfg, key)(n, [0])[0]
                              for n in names})(weights.seed_key(seed))
    mat = lambda n: {"kernel": lw[n]}
    lp = {"moe_gate": mat("mo_gate"), "moe_fc1": mat("mo_w1"),
          "moe_fc3": mat("mo_w3"), "moe_fc2": mat("mo_w2")}
    return lw, lp


def test_two_halves_and_the_shared_expert_once_are_the_uncut_layer():
    """Model-configs guide, section 4: the two shares of one layer
    (experts 0-7 and 8-15 of 16), the routed parts summed and the gated
    shared expert counted once, equal the UNCUT reference layer; each
    share's pairs here plus pairs elsewhere are T x k."""
    uncut = share(0, 16)
    lw, _ = expert_layer(uncut)
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 29, 64), jnp.float32)
    st = lambda cfg: dict(reference.static(cfg))
    whole = reference.shared(u[0], lw) + reference.routed(u[0], lw,
                                                          st(uncut))
    total = reference.shared(u[0], lw)
    for first in (0, 8):
        cfg = share(first, 8)
        part, lp = expert_layer(cfg)
        assert jnp.array_equal(part["mo_w1"], lw["mo_w1"][first:first + 8])
        out, held, away = moe_dropless(
            lp, u, 4, 1.0, held=(first, 8), route_eps=0.0,
            scoring="softmax")
        assert int(held.sum() + away) == 29 * 4
        assert float(jnp.abs(out[0] - reference.routed(
            u[0], part, st(cfg))).max()) < 1e-5
        total = total + out[0]
    assert float(jnp.abs(total - whole).max()) < 1e-5


def test_each_expert_is_drawn_from_its_own_key():
    """The weight program folds the expert ids into each layer's key in
    one vectorised step: every held expert's three matrices are the
    draw from (seed, leaf, layer, expert id) made one expert at a time,
    the form the generator had before (to the last bits of the
    normal's transform, which one jitted program and single draws
    round alike up to an ulp)."""
    cfg = share(4, 8)
    got = weights.generate(cfg, 11, jnp.float32)
    key = weights.seed_key(11)
    order = sorted(weights.shapes(cfg))
    for name in weights.EXPERT:
        leaf = jax.random.fold_in(key, order.index(name))
        for layer in range(weights.counts(cfg)[name[:2]]):
            row = jax.random.fold_in(leaf, layer)
            for e in range(8):
                want = weights._draw(name, jax.random.fold_in(row, 4 + e),
                                     weights.shapes(cfg)[name])
                assert float(jnp.abs(got[name][layer, e] - want).max()) \
                    < 1e-8


def test_softmax_routing_is_the_reference_s():
    """Softmax over all the router's experts, the top-k by probability
    (no bias: a ``moe_bias`` present is not read), renormalised over
    the chosen: the selection and weights of ``moe_route`` equal the
    reference's; the sigmoid scoring, the default, still reads the
    bias."""
    gate = jax.random.normal(jax.random.PRNGKey(1), (64, 16)) * 0.3
    u = jax.random.normal(jax.random.PRNGKey(2), (37, 64))
    params = {"moe_gate": {"kernel": gate},
              "moe_bias": jnp.full((16,), 5.0).at[0].set(0.0)}
    sel, w = moe_route(params, u, top_k=4, eps=0.0, scoring="softmax")
    full, ref_sel = reference.route(u, {"mo_gate": gate},
                                    {"num_experts_per_tok": 4,
                                     "norm_topk_prob": True})
    assert np.array_equal(np.sort(sel, -1), np.sort(ref_sel, -1))
    got = np.zeros((37, 16)).copy()
    np.put_along_axis(got, np.asarray(sel), np.asarray(w), axis=-1)
    assert np.abs(got - np.asarray(full)).max() < 1e-6
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    # the bias steers sigmoid selection only: expert 0 is never chosen
    sel_sig, _ = moe_route(params, u, top_k=4)
    assert not (np.asarray(sel_sig) == 0).any()
    with pytest.raises(ValueError, match="unknown scoring"):
        moe_route(params, u, top_k=4, scoring="softplus")


UNSUPPORTED = {
    "prefix_cache": {"prefix_cache": True},
    "speculative": {"speculative": True},
    "host_spill": {"prefix_cache": True, "host_spill": {"enabled": True}},
    "disagg": {"disagg": {"enabled": True}},
    "tp": {"tp": 2},
    "cache_dtype": {"cache_dtype": "int8"},
    "decode_backend": {"decode_backend": "pallas"},
    "parallel_sampling": {"parallel_sampling": True},
    "structured": {"structured": {"enabled": True}},
    "adapters": {"adapters": {"rank": 4, "max_live": 2}},
    "weights": {"weights": {"dtype": "int8"}},
}
STATE_REASON = ("prefix_cache", "speculative", "host_spill", "disagg",
                "parallel_sampling")


@pytest.mark.parametrize("feature", sorted(UNSUPPORTED))
def test_unsupported_feature_raises_at_build(model, feature):
    """By name, with the reason the model's module gives: the five that
    need pages to be the whole of a sequence say why (one state a slot
    for its whole past), the others are GPT-shaped code."""
    from torchbooster_tpu.config import resolve_types

    _, mcfg, _, tree = model
    block = {"page_size": 8, "n_pages": 32, "max_slots": 2,
             **UNSUPPORTED[feature]}
    conf = ServingConfig(**resolve_types(ServingConfig, block))
    mesh = jax.make_mesh((2,), ("tp",)) if feature == "tp" else None
    with pytest.raises(NotImplementedError,
                       match=feature.split("_")[0]) as err:
        conf.make(tree, mcfg, compute_dtype=jnp.float32, mesh=mesh)
    key = next(k for k in qwen3_next.UNSERVED
               if k.startswith(feature.split("_")[0]))
    assert qwen3_next.UNSERVED[key] in str(err.value)
    assert ("one matrix a slot" in str(err.value)) \
        == (feature in STATE_REASON)


READERS = ("delta_lanes_roofline", "delta_chunk_roofline",
           "qwen3next_mixed_roofline", "qwen3next_serve_mfu",
           "qwen3next_moe_experts_roofline")


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_with_nothing_to_read_returns_none(reader):
    """What the driver's traced runs of the PARENT and of the other
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


def test_the_experts_roofline_reads_the_traced_stretch():
    """``qwen3next_moe_experts_roofline`` takes the experts hit and the
    pairs computed here from the registry at the traced stretch's edges
    (not the window's): 10 plain steps that hit 40 experts and computed
    120 pairs here each, against 1 ms under ``moe_experts`` a run."""
    import flops_qwen3_next as fl
    import run as harness

    if str(BENCH / "layer_metrics") not in sys.path:
        sys.path.append(str(BENCH / "layer_metrics"))
    module = harness.load_module(
        BENCH / "layer_metrics" / "qwen3next_moe_experts_roofline.py")
    module.seconds_by_run = lambda layers, program, name: \
        [1e-3, 1e-3] if (program, name) == ("decode_fn", "moe_experts") \
        else []
    edge = lambda n: {"serving_moe_experts_hit_count": n,
                      "serving_moe_experts_hit_sum": 40 * n,
                      "serving_moe_pairs_total{where=here}": 120 * n}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    layers = {"cfg": TOY, "peaks": peaks, "registry_open": edge(0),
              "registry_close": edge(1000), "registry_trace_open": edge(5),
              "registry_trace_close": edge(15)}
    least = max(fl.experts_flops(TOY, 120) / 1e12,
                fl.experts_bytes(TOY, 40, 120) / 1e11)
    assert module.read("", layers) == pytest.approx(100 * least / 1e-3)


def test_the_operation_counts_by_hand():
    """``flops_qwen3_next`` at the published widths: the issue's
    parameter arithmetic (33.72 M a linear layer's mixer, 27.26 M the
    full layer's, 6.29 MB an expert in bfloat16, 3,677.6 M in all for
    the cut) and the chunked form's operations for one block."""
    import json

    import flops_qwen3_next as fl

    cfg = json.loads((BENCH / "configs" / "qwen3-next-80b-a3b.json")
                     .read_text())
    assert fl.linear_params(cfg) + 4 * 8192 == pytest.approx(33.72e6,
                                                             rel=1e-3)
    assert fl.full_params(cfg) == pytest.approx(27.26e6, rel=1e-3)
    assert 2 * fl.expert_params(cfg) == pytest.approx(6.29e6, rel=1e-3)
    assert fl.n_params(cfg) == pytest.approx(3677.6e6, rel=1e-3)
    assert fl.state_bytes(cfg) == {"delta": 2 * 2**20, "conv": 48 * 2**10}
    # one block of 64: 6 T dk dv + 2 T^2 (3 dk + 2 dv) + 5 x 4 T^3
    one = 6 * 64 * 128 * 128 + 2 * 64 ** 2 * 640 + 20 * 64 ** 3
    assert fl.delta_chunk_flops(cfg, 64) == 32 * one
    assert fl.delta_chunk_flops(cfg, 65) == 2 * 32 * one
    assert fl.row_bytes(cfg) == 2048


def test_the_benchmark_job_rehearses_at_toy_size():
    """``benchmark/run.execute`` on the toy root beside the others
    (benchmark/tests/tiny_qwen3_next): the ``serve_qwen3_next`` job end
    to end — weights from the seed, the stack as a user's YAML builds
    it, HTTP traffic from the load generator's process with ids from
    the vocabulary slice and prompts of several chunks, the served
    streams against the float32 reference's recurrence. The limit is a
    bfloat16 program's against a float32 reference at toy widths;
    nothing here is a measurement."""
    import flops
    import run as harness

    root = BENCH / "tests" / "tiny_qwen3_next"
    out = harness.execute("qwen3-next-tiny.serve-longgen-tiny", 2**31 + 7,
                          1.5, False, root=root, devices=jax.devices()[:1],
                          peaks=flops.peaks_of("TPU v5 lite"))
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert line["compared"]["bad_streams"]["value"] == 0
    assert 0 <= line["compared"]["served_gap_p99"]["value"] \
        <= line["compared"]["served_gap_max"]["value"] < 0.1
    assert out["checks"]["chunk_boundaries_checked"] >= 4
    assert out["log"]["compiles_in_window"] == 0
    assert out["log"]["stream_variety"]["distinct"] > 1
