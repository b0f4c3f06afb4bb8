"""AFMoE — window and full attention layers in one model, gated
attention, sandwich norms, a shared expert and a SHARE of the routed
experts — against its plain float32 reference, at a toy size on the
CPU: the model file, the paged engine's chunk, decode and mixed
programs over TWO pools (a ring of ``window + chunk + page`` positions
a slot for the window layers, growing pages for the full one), the
batcher's seat / retire / preempt cycle, the share of the experts and
the counters. Every comparison is on LOGITS (random weights flip an
argmax on rounding), against ``benchmark/reference/afmoe.py`` — which
imports nothing of the program and keeps no cache: its window is a
mask over the whole sequence.

Toy geometry: window 8, pages of 4, chunks of 8 (2 pages), so a ring
is 2 + 2 + 1 = 5 pages = 20 positions and a prompt of 40 wraps it
twice; heads of 32 lanes where ``d_model / n_heads`` is 16.

Tolerances. Everything here runs in float32 on both sides, so what
differs is the order of sums (page partials merged by an online
softmax, tokens sorted by expert, one fused ``[q | k | v | g]``
product) over toy widths of 32-128 and logits of size ~1: a few 1e-7
at a time; 2e-4 leaves room for 5 layers of it and is two orders under
what a bfloat16 side would show (``test_bfloat16_would_fail``).
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

import program_afmoe as program  # noqa: E402
import weights_afmoe as weights  # noqa: E402
from reference import afmoe as reference  # noqa: E402

from torchbooster_tpu.config import ServingConfig  # noqa: E402
from torchbooster_tpu.models import afmoe  # noqa: E402
from torchbooster_tpu.models.afmoe import Afmoe  # noqa: E402
from torchbooster_tpu.models.moe import moe_dropless, moe_route  # noqa: E402
from torchbooster_tpu.observability import get_registry, set_enabled  # noqa: E402
from torchbooster_tpu.serving import PagedEngine, Request  # noqa: E402
from torchbooster_tpu.serving.kv_pages import (  # noqa: E402
    make_pool, ring_pages, ring_positions)

TOL = 2e-4
PAGE, CHUNK_PAGES, WINDOW = 4, 2, 8            # chunks of 8 tokens
RING = WINDOW // PAGE + CHUNK_PAGES + 1        # 5 pages = 20 positions
S, F = afmoe.SLIDING, afmoe.FULL

# 1 dense (sliding) layer + one period (sliding x 3, full) of expert
# layers; 4 of 32 experts held, top-4: eight shares make a layer
TOY = {
    "vocab_size": 128, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_shared_experts": 1,
    "num_experts": 4, "experts_held": {"first": 0, "count": 4},
    "published": {"num_experts": 32, "vocab_size": 1024,
                  "num_hidden_layers": 60, "num_dense_layers": 6},
    "num_experts_per_tok": 4, "num_hidden_layers": 5,
    "num_dense_layers": 1, "layer_types": [S, S, S, S, F],
    "sliding_window": WINDOW, "rope_theta": 100, "rope_scaling": None,
    "rms_norm_eps": 1e-5, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "mup_enabled": True, "model_type": "afmoe",
    "max_position_embeddings": 256,
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


class Recorder:
    """The engine's own logits, recorded where they are produced: the
    head of the chunk, decode and mixed programs."""

    def __init__(self, monkeypatch):
        self.rows = []
        real = afmoe.head

        def head(params, x, cfg):
            out = real(params, x, cfg)
            jax.debug.callback(lambda a: self.rows.append(np.asarray(a)),
                               out)
            return out

        monkeypatch.setattr(afmoe, "head", head)


def serve(eng, prompt, n_new):
    """Admit, then ``n_new`` plain decode steps: the served tokens."""
    slot, first = eng.admit(prompt)
    served = [first]
    for _ in range(n_new):
        assert not eng.grow_slots()
        served.append(int(eng.step()[slot]))
    return slot, served


def test_apply_matches_the_reference(model):
    cfg, mcfg, flat, tree = model
    ids = tokens(0, 70)
    got, counts = Afmoe.apply(tree, jnp.asarray(ids)[None], mcfg,
                              return_counts=True)
    want = reference.logits(flat, ids, cfg)
    assert float(jnp.abs(got[0] - want).max()) < TOL
    # pairs here + elsewhere = tokens x top-k, in each expert layer
    assert counts["held"].shape == (4, 4)
    assert (counts["held"].sum(1) + counts["elsewhere"] == 70 * 4).all()


def test_bfloat16_would_fail(model):
    """The tolerance is tight enough: the same forward in bfloat16
    lies far outside it."""
    cfg, mcfg, flat, tree = model
    ids = tokens(0, 70)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.dtype == jnp.float32 and a.ndim > 1 else a, tree)
    got = Afmoe.apply(low, jnp.asarray(ids)[None], mcfg,
                      compute_dtype=jnp.bfloat16)[0]
    want = reference.logits(flat, ids, cfg)
    assert float(jnp.abs(got - want).max()) > 10 * TOL


def test_init_builds_the_tree_the_arranger_builds(model):
    _, mcfg, _, tree = model
    own = Afmoe.init(jax.random.PRNGKey(0), mcfg)
    shape = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shape(own) == shape(tree)
    assert mcfg.plan == ((S,), (S, S, S, F), 1)
    # the published model: six leading dense layers, 54 expert layers
    lead, period, n_periods = afmoe.AfmoeConfig().plan
    assert lead == (S, S, S, F, S, S) and len(period) * n_periods == 54


@pytest.mark.parametrize("what", ["rope_on_full", "no_gate", "window_off_by_one",
                                  "no_embed_scale"])
def test_the_reference_tells_each_mechanism_apart(model, what):
    """What the chip's ``correct`` must be able to catch, caught here
    at float32: RoPE applied on the full layer, the gate left out, a
    window one position too wide, the embedding's scale left out —
    each moves the logits far outside the tolerance."""
    cfg, mcfg, flat, tree = model
    ids = tokens(2, 48)
    want = reference.logits(flat, ids, cfg)
    if what == "rope_on_full":
        # with a window no sequence reaches, a sliding layer IS a full
        # layer with RoPE: the two differ by the last layer's rotation
        wide = {**cfg, "sliding_window": 10 ** 6}
        other = reference.logits(flat, ids, {**wide, "layer_types": [S] * 5})
        want = reference.logits(flat, ids, wide)
    elif what == "no_gate":
        wrong_flat = {**flat, "at_g": jnp.zeros_like(flat["at_g"])}
        other = reference.logits(wrong_flat, ids, cfg)   # gate = 1/2
    elif what == "window_off_by_one":
        other = reference.logits(flat, ids, {**cfg, "sliding_window": 9})
    else:
        other = reference.logits(flat, ids, {**cfg, "mup_enabled": False})
    assert float(jnp.abs(other - want).max()) > 10 * TOL


def one_layer(kind):
    """A model of ONE layer of ``kind`` (dense feed-forward)."""
    cfg = {**TOY, "num_hidden_layers": 1, "layer_types": [kind]}
    return (cfg, *built(cfg))


@pytest.mark.parametrize("kind", [S, F])
def test_a_key_a_window_back_reaches_a_full_layer_only(kind, monkeypatch):
    """Two prompts that differ in their FIRST token only. In a model
    of one sliding layer no position from ``window`` on can tell them
    apart — through ``Afmoe.apply`` and through the engine's ring
    alike, to the bit: a masked key's weight is exactly 0 — and in a
    model of one full layer every position can."""
    cfg, mcfg, flat, tree = one_layer(kind)
    a = tokens(4, 30)
    b = a.copy()
    b[0] = (b[0] + 1) % 128
    la, lb = (Afmoe.apply(tree, jnp.asarray(t)[None], mcfg)[0]
              for t in (a, b))
    rec = Recorder(monkeypatch)
    eng = engine_of(mcfg, tree)
    served = []
    for prompt in (a, b):
        eng.retire(0)
        # the same continuation forced on both: what is compared is
        # logits at equal inputs
        slot, first = eng.admit(prompt)
        for t in (5, 6, 7):
            eng.tables.last_ids[slot] = t
            eng.grow_slots()
            eng.step()
        jax.effects_barrier()
        served.append(np.stack([r[0, 0] if r.shape[0] == 1 else r[slot, 0]
                                for r in rec.rows[-4:]]))
    if kind == S:
        assert jnp.array_equal(la[WINDOW:], lb[WINDOW:])
        assert not jnp.array_equal(la[:WINDOW], lb[:WINDOW])
        assert np.array_equal(served[0], served[1])
    else:
        assert float(jnp.abs(la[WINDOW:] - lb[WINDOW:]).max()) > 1e-4
        assert float(np.abs(served[0] - served[1]).max()) > 1e-4


def test_engine_prefill_and_decode_wrap_the_ring_and_match(model,
                                                          monkeypatch):
    """A prompt of five chunks and a partial sixth (8 x 5 + 3 = 43:
    the 20-position ring wraps twice in the prefill), then 30 decode
    steps that wrap it again and cross seven ring pages' recycling:
    the logits behind every served token equal the reference's full
    forward over the served stream, whose window is a mask."""
    cfg, mcfg, flat, tree = model
    rec = Recorder(monkeypatch)
    eng = engine_of(mcfg, tree)
    prompt = tokens(1, 43)
    slot, served = serve(eng, prompt, 30)
    jax.effects_barrier()
    got = [rec.rows[5][0, 0]] + [r[slot, 0] for r in rec.rows[6:]]
    seq = list(prompt) + served
    want = reference.logits(flat, seq, cfg,
                            positions=range(len(prompt) - 1, len(seq) - 1))
    assert len(got) == 31
    assert float(np.abs(np.stack(got) - np.asarray(want)).max()) < TOL
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1
    # a decode step's pairs: held here + routed elsewhere = slots x k
    live = eng.moe_counts.sum(axis=1) + eng.moe_elsewhere
    assert (live == 1 * cfg["num_experts_per_tok"]).all()
    # positions 20 .. 72 each landed on the row of the one a ring
    # before it: 23 of the prompt's in its chunks, 30 decoded
    assert eng.window_rows_recycled == 23 + 30


def test_a_reseated_slot_sees_nothing_of_its_last_tenant(model):
    """One slot: a long tenant fills the ring and several pages of the
    full pool, is retired, and a SHORT prompt is seated in its place —
    no clearing pass ran over either pool, and every logit of the new
    tenant equals the reference's (what the ring still holds of the
    last tenant reads as positions the mask hides)."""
    cfg, mcfg, flat, tree = model
    eng = engine_of(mcfg, tree, max_slots=1)
    slot, _ = serve(eng, tokens(5, 47), 9)
    ring_before = np.asarray(eng.pool["k"]["window"])
    assert np.abs(ring_before).sum() > 0
    eng.retire(slot)
    for seed, n in ((6, 5), (7, 13)):
        prompt = tokens(seed, n)
        slot, served = serve(eng, prompt, 12)
        gaps = reference.served_gaps(flat, prompt, served, cfg)
        assert float(gaps.max()) < TOL
        eng.retire(slot)
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1


def test_batching_reuse_preemption_and_mixed_steps(model):
    """Five requests over two slots and a full-layer pool too small
    for them: slots are seated and retired at different steps and
    reused, the pool's pressure preempts (fold and replay: both kinds
    re-prefilled), and pending chunks ride the decode step as the
    mixed program — over rings that wrap. Every stream equals a fresh
    single run of its own, and every served token's logit equals the
    reference's best to within the tolerance."""
    cfg, mcfg, flat, tree = model
    serving = dict(page_size=PAGE, n_pages=24, max_slots=2,
                   prefill_chunk_pages=CHUNK_PAGES)
    lens = [(21, 40), (37, 30), (9, 40), (50, 9), (17, 25)]

    def requests():
        return [Request(prompt=tokens(10 + i, p), max_new_tokens=n)
                for i, (p, n) in enumerate(lens)]

    batcher = ServingConfig(**serving).make(
        tree, mcfg, compute_dtype=jnp.float32)
    reqs = requests()
    assert batcher.run(reqs)["n_preemptions"] > 0
    assert batcher.engine.mixed_steps > 0
    alone = ServingConfig(**{**serving, "n_pages": 64}).make(
        tree, mcfg, compute_dtype=jnp.float32)
    for crowded, fresh in zip(reqs, requests()):
        alone.run([fresh])
        assert list(crowded.tokens) == list(fresh.tokens)
        gaps = reference.served_gaps(flat, fresh.prompt, fresh.tokens,
                                     cfg)
        assert float(gaps.max()) < TOL
    assert batcher.engine.decode_compiles == 1
    assert batcher.engine.prefill_compiles <= 2


def test_the_window_pool_is_bounded_whatever_the_positions(model):
    """Two pools, one a kind: the full layer's pages are ``n_pages``,
    the window layers' are ``max_slots x ring`` — the ring follows
    from the window, the page and the chunk, and neither ``n_pages``
    nor the positions a sequence may reach move it. At the cell's
    geometry that is 4 x 32 x 69 pages (2.3 GB) where every layer
    holding every token of 32 sequences of 17,152 would be 11.2 GB."""
    cfg, _, _, tree = model
    shapes = []
    for positions, n_pages in ((64, 16), (256, 64)):
        mcfg = program.model_config(cfg, positions)
        eng = engine_of(mcfg, tree, n_pages=n_pages)
        assert eng.ring == RING and eng.slot_state is None
        assert eng.pool["k"]["full"].shape == (1, n_pages, PAGE, 128)
        shapes.append(eng.pool["k"]["window"].shape)
        assert eng.tables.max_pages_per_slot == positions // PAGE
    assert shapes[0] == shapes[1] == (4, 3 * RING, PAGE, 128)  # 64 -> 128
    assert ring_pages(4096, 64, 4) == 69
    big = program.model_config({**TOY, "sliding_window": 4096,
                                "num_key_value_heads": 8, "head_dim": 128,
                                "num_attention_heads": 48})
    pool = jax.eval_shape(lambda: make_pool(big, 64, 4096, ring=(32, 69)))
    nbytes = lambda a: a.dtype.itemsize * np.prod(a.shape)
    assert pool["k"]["window"].shape == (4, 32 * 69, 64, 1024)
    assert 2 * nbytes(pool["k"]["window"]) == pytest.approx(2.32e9, rel=0.01)
    assert 2 * nbytes(pool["k"]["full"]) == pytest.approx(1.07e9, rel=0.01)
    with pytest.raises(ValueError, match="must divide the attention window"):
        ring_pages(10, 4, 2)


def test_ring_positions_recover_what_a_slot_wrote():
    """Ring page r of a sequence whose newest page is ``top`` holds the
    newest page ``a <= top`` with ``a % ring == r``; pages the
    sequence has not reached read negative."""
    pos = np.asarray(ring_positions(jnp.asarray([0, 3, 7]), 5, 4))
    assert pos.shape == (3, 5, 4)
    assert pos[0, :, 0].tolist() == [0, -16, -12, -8, -4]
    assert pos[1, :, 0].tolist() == [0, 4, 8, 12, -4]
    assert pos[2, :, 0].tolist() == [20, 24, 28, 12, 16]    # pages 5,6,7,3,4
    assert pos[2, 2].tolist() == [28, 29, 30, 31]


def expert_layer(cfg, seed=11):
    """The first expert layer alone: (the flat leaves' rows as the
    reference reads them, the program's layer tree of the same
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


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4: over the 8 shares of one expert layer, the
    routed parts summed and the shared expert counted once equal the
    UNCUT reference's whole layer (all 32 experts held); each share's
    pairs here plus pairs elsewhere are T x k."""
    uncut = share(0, 32)
    lw, _ = expert_layer(uncut)
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 29, 64), jnp.float32)
    whole = reference.shared(u[0], lw) + reference.routed(
        u[0], lw, dict(reference.static(uncut)))
    total = reference.shared(u[0], lw)
    for first in range(0, 32, 4):
        cfg = share(first, 4)
        part, lp = expert_layer(cfg)
        # a share's experts ARE the uncut layer's
        assert jnp.array_equal(part["mo_w1"], lw["mo_w1"][first:first + 4])
        out, held, away = moe_dropless(
            lp, u, cfg["num_experts_per_tok"], cfg["route_scale"],
            held=(first, 4), route_eps=1e-20)
        assert int(held.sum() + away) == 29 * 4
        ref_part = reference.routed(u[0], part,
                                    dict(reference.static(cfg)))
        assert float(jnp.abs(out[0] - ref_part).max()) < 1e-5
        total = total + out[0]
    assert float(jnp.abs(total - whole).max()) < 1e-5


def test_router_renormalises_with_the_family_s_eps_and_scale():
    """Scores s = (.9, .8, .6, .5), bias (0, 0, .5, 0): the top-2 by
    s + b is {2, 0} and the weights are 2.448 x s[2], s[0] over their
    sum + 1e-20, the bias nowhere in them; the reference agrees."""
    s = np.array([0.9, 0.8, 0.6, 0.5], np.float32)
    gate = np.log(s / (1 - s))[None]            # u = [1] -> logits
    bias = np.array([0.0, 0.0, 0.5, 0.0], np.float32)
    sel, w = moe_route({"moe_gate": {"kernel": jnp.asarray(gate)},
                        "moe_bias": jnp.asarray(bias)},
                       jnp.ones((1, 1), jnp.float32), top_k=2,
                       scaling=2.448, eps=1e-20)
    by_expert = dict(zip(np.asarray(sel[0]).tolist(),
                         np.asarray(w[0]).tolist()))
    assert sorted(by_expert) == [0, 2]
    assert by_expert[0] == pytest.approx(2.448 * 0.9 / 1.5, rel=1e-6)
    assert by_expert[2] == pytest.approx(2.448 * 0.6 / 1.5, rel=1e-6)
    full, ref_sel = reference.route(
        jnp.ones((1, 1)), {"mo_gate": jnp.asarray(gate),
                           "mo_bias": jnp.asarray(bias)},
        {"num_experts_per_tok": 2, "route_scale": 2.448,
         "route_norm": True})
    assert sorted(np.asarray(ref_sel[0]).tolist()) == [0, 2]
    assert np.asarray(full[0]) == pytest.approx(
        [2.448 * 0.9 / 1.5, 0.0, 2.448 * 0.6 / 1.5, 0.0], rel=1e-6)


def test_the_two_kinds_rows_are_counted_apart(model):
    """``serving_kv_rows_live{kind}``: a full layer's rows grow with
    the sequence, a window layer's stop at the window;
    ``serving_kv_rows_read_total`` sums them over the steps and
    ``serving_window_rows_recycled_total`` counts the rows a ring
    wrote over."""
    _, mcfg, _, tree = model
    reg = set_enabled(True)
    reg.reset()
    try:
        eng = engine_of(mcfg, tree)
        slot, _ = serve(eng, tokens(8, 5), 2)     # rows 6, 7 (< window)
        snap = reg.snapshot()
        assert snap["serving_kv_rows_live{kind=full}"] == 7
        assert snap["serving_kv_rows_live{kind=window}"] == 7
        for _ in range(20):                        # ... rows 8 .. 27
            eng.grow_slots()
            eng.step()
        snap = reg.snapshot()
        assert snap["serving_kv_rows_live{kind=full}"] == 27
        assert snap["serving_kv_rows_live{kind=window}"] == WINDOW
        assert snap["serving_kv_rows_read_total{kind=full}"] \
            == sum(range(6, 28))
        assert snap["serving_kv_rows_read_total{kind=window}"] \
            == 6 + 7 + 20 * WINDOW
        # positions 20 .. 26 were written over ring rows 0 .. 6
        assert snap["serving_window_rows_recycled_total"] == 7
        assert eng.debug_stats()["ring_pages"] == RING
    finally:
        reg.reset()
        set_enabled(False)
    assert get_registry() is reg


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
RING_REASON = ("prefix_cache", "speculative", "host_spill", "disagg",
               "parallel_sampling")


@pytest.mark.parametrize("feature", sorted(UNSUPPORTED))
def test_unsupported_feature_raises_at_build(model, feature):
    """By name, with the reason the model's module gives: the five
    that need a window layer's pages to be the whole of a sequence say
    so (the ring), the others are GPT-shaped code."""
    from torchbooster_tpu.config import resolve_types

    _, mcfg, _, tree = model
    # pages of 8: the speculative check wants draft_len < page_size
    block = {"page_size": 8, "n_pages": 32, "max_slots": 2,
             **UNSUPPORTED[feature]}
    conf = ServingConfig(**resolve_types(ServingConfig, block))
    mesh = jax.make_mesh((2,), ("tp",)) if feature == "tp" else None
    with pytest.raises(NotImplementedError,
                       match=feature.split("_")[0]) as err:
        conf.make(tree, mcfg, compute_dtype=jnp.float32, mesh=mesh)
    key = next(k for k in afmoe.UNSERVED
               if k.startswith(feature.split("_")[0]))
    assert afmoe.UNSERVED[key] in str(err.value)
    assert ("a ring of its slot's last positions" in str(err.value)) \
        == (feature in RING_REASON)


def test_a_window_the_pages_do_not_divide_is_refused(model):
    _, _, _, tree = model
    mcfg = program.model_config({**TOY, "sliding_window": 10})
    with pytest.raises(ValueError, match="must divide the attention window"):
        engine_of(mcfg, tree)


READERS = ("trinity_serve_mfu", "trinity_mixed_roofline", "win_attn_ms",
           "full_attn_ms", "kv_window_saving")


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
    module = harness.load_module(
        BENCH / "layer_metrics" / f"{reader}.py")

    empty = trace_reduce.Trace(ops={"chip": []}, modules={"chip": []})
    window = {"decode_tokens": 0, "prefill_tokens": 0, "context_read": 0.0,
              "ttfts": [], "gaps": []}
    for cfg in ({"n_layer": 2, "n_embd": 64},
                {"kv_lora_rank": 32, "num_hidden_layers": 2}, TOY):
        layers = {"cfg": cfg, "window": window, "seconds": 1.0, "chips": 1,
                  "peaks": {"bf16_flops_per_s": 1e12,
                            "hbm_bytes_per_s": 1e11},
                  "registry_open": {}, "registry_close": {},
                  "trace_path": None, "trace": empty}
        assert module.read(reader + ".lat", layers) is None


def test_the_mfu_reader_counts_what_the_window_needs():
    """``trinity_serve_mfu`` on a hand-made window: 100 decoded tokens
    at context 1,000 (a window layer sees 8 of them) and one prompt of
    1,000 tokens, an eighth of the routed pairs here — against
    ``flops_afmoe`` by hand."""
    import flops_afmoe as fl
    import run as harness

    if str(BENCH / "layer_metrics") not in sys.path:
        sys.path.append(str(BENCH / "layer_metrics"))
    module = harness.load_module(
        BENCH / "layer_metrics" / "trinity_serve_mfu.py")
    pairs = "serving_moe_pairs_total{where=%s}"
    full_pairs = 100 * 1000.0 + 1000 * 1001 / 2
    win_pairs = 100 * 8.0 + (8 * 9 / 2 + 992 * 8)
    layers = {
        "cfg": TOY, "seconds": 2.0, "chips": 1,
        "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9},
        "window": {"decode_tokens": 100, "prefill_tokens": 1000,
                   "context_read": 100 * 1000.0, "ttfts": [0.1]},
        "attn_pairs": {"full": full_pairs, "window": win_pairs},
        "registry_open": {pairs % "here": 10.0, pairs % "elsewhere": 70.0},
        "registry_close": {pairs % "here": 110.0,
                           pairs % "elsewhere": 770.0},
    }
    n_tokens = 1100
    want = (2.0 * fl.token_matmul_params(TOY) * n_tokens
            + fl.attention_flops(TOY, win_pairs, full_pairs)
            + 2.0 * fl.expert_params(TOY) * n_tokens * 4 * 4 * 0.125
            + 2.0 * fl.head_params(TOY) * 101)
    got = module.read("trinity_serve_mfu.lat", layers)
    assert got == pytest.approx(100.0 * want / (2.0 * 1e9), rel=1e-9)
    assert module.read("trinity_serve_mfu.lat", {
        **layers, "registry_open": {}, "registry_close": {}}) is None
    # by hand: 4 heads x (32 + 32) x 2 a pair and layer; 4 window
    # layers and 1 full; a cached row is K and V of 2 x 32 -> 128 lanes
    assert fl.attention_flops(TOY, 1.0, 0.0) == 4 * 64 * 2 * 4
    assert fl.attention_flops(TOY, 0.0, 1.0) == 4 * 64 * 2 * 1
    assert fl.row_bytes(TOY) == 2 * 128 * 2
    assert fl.row_bytes({"num_key_value_heads": 8, "head_dim": 128}) == 4096


def test_the_window_saving_reader_divides_the_two_kinds():
    import run as harness

    if str(BENCH / "layer_metrics") not in sys.path:
        sys.path.append(str(BENCH / "layer_metrics"))
    module = harness.load_module(
        BENCH / "layer_metrics" / "kv_window_saving.py")
    rows = "serving_kv_rows_read_total{kind=%s}"
    layers = {"cfg": TOY,
              "registry_open": {rows % "full": 100.0, rows % "window": 80.0},
              "registry_close": {rows % "full": 1100.0,
                                 rows % "window": 330.0}}
    assert module.read("kv_window_saving.lat", layers) \
        == pytest.approx(0.25)


def test_the_benchmark_job_rehearses_at_toy_size():
    """``benchmark/run.execute`` on the toy root beside the others
    (benchmark/tests/tiny_afmoe): the ``serve_afmoe`` job end to end —
    weights from the seed, the stack as a user's YAML builds it, HTTP
    traffic from the load generator's process with ids from the
    vocabulary slice and prompts of several windows, the served
    streams against the float32 reference, a wrapped ring among the
    compared requests. The limit is a bfloat16 program's against a
    float32 reference at toy widths; nothing here is a measurement."""
    import flops
    import run as harness

    root = BENCH / "tests" / "tiny_afmoe"
    out = harness.execute("afmoe-tiny.serve-longctx-tiny", 2**31 + 7,
                          1.5, False, root=root, devices=jax.devices()[:1],
                          peaks=flops.peaks_of("TPU v5 lite"))
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert line["compared"]["bad_streams"]["value"] == 0
    assert 0 <= line["compared"]["served_gap_p99"]["value"] \
        <= line["compared"]["served_gap_max"]["value"] < 0.1
    assert out["checks"]["wrapped_rings_checked"] >= 1
    assert out["checks"]["recycling_decodes_checked"] >= 1
    assert out["log"]["compiles_in_window"] == 0
    assert out["log"]["stream_variety"]["distinct"] > 1
