"""Latent attention (MLA) with a shared expert and a SHARE of the
routed experts against its plain float32 reference, at a toy size on
the CPU: the model file in both forms, the paged engine's chunk and
decode programs over the one-row latent pool, the batcher's seat /
retire / preempt cycle, the share of the experts and the router's
semantics. Every comparison is on LOGITS (random weights flip an
argmax on rounding), against ``benchmark/reference/sarvam_mla.py`` —
which imports nothing of the program and never computes the absorbed
form the engine decodes in.

Tolerances. Everything here runs in float32 on both sides, so what
differs is the order of sums: float32 matrix products reassociated
(the program folds ``W_uk`` into the query and sums latents where the
reference up-projects every head's keys and values, sorts tokens by
expert, splits attention into page partials with an online softmax),
over toy widths of 32-128 and logits of size ~1. That is a few 1e-7
at a time; 2e-4 leaves room for 4 layers of it and is two orders
under what a bfloat16 side would show (``test_bfloat16_would_fail``).
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

import program_sarvam_mla as program  # noqa: E402
import weights_sarvam_mla as weights  # noqa: E402
from reference import sarvam_mla as reference  # noqa: E402

from torchbooster_tpu.config import ServingConfig  # noqa: E402
from torchbooster_tpu.models import mla_moe  # noqa: E402
from torchbooster_tpu.models.mla_moe import MLAMoE  # noqa: E402
from torchbooster_tpu.models.moe import moe_dropless, moe_route  # noqa: E402
from torchbooster_tpu.ops.attention import mha_reference  # noqa: E402
from torchbooster_tpu.serving import PagedEngine, Request  # noqa: E402
from torchbooster_tpu.serving.kv_pages import make_pool  # noqa: E402

TOL = 2e-4
PAGE, CHUNK_PAGES = 8, 2                       # chunks of 16 tokens

# 1 dense layer + 3 expert layers; 4 of 16 experts held, top-4; YaRN
# factor 4 over 32 positions with theta 100: the ramp is 0, .5, 1, 1
TOY = {
    "vocab_size": 128, "hidden_size": 64, "num_attention_heads": 4,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "kv_lora_rank": 32,
    "v_head_dim": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_shared_experts": 1,
    "num_experts": 4, "experts_held": {"first": 0, "count": 4},
    "published": {"num_experts": 16, "vocab_size": 512,
                  "num_hidden_layers": 4},
    "num_experts_per_tok": 4, "num_hidden_layers": 4,
    "first_k_dense_replace": 1, "rope_theta": 100,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32,
                     "type": "deepseek_yarn"},
    "rms_norm_eps": 1e-6, "routed_scaling_factor": 2.5,
    "moe_router_enable_expert_bias": True,
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
    kw = {"page_size": PAGE, "n_pages": 64, "max_slots": 4,
          "prefill_chunk_pages": CHUNK_PAGES,
          "compute_dtype": jnp.float32, **kw}
    return PagedEngine(tree, mcfg, **kw)


def tokens(seed, n, vocab=128):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


class Recorder:
    """The engine's own logits, recorded where they are produced: the
    head of the chunk and of the decode program."""

    def __init__(self, monkeypatch):
        self.rows = []
        real = mla_moe.head

        def head(params, x, cfg):
            out = real(params, x, cfg)
            jax.debug.callback(lambda a: self.rows.append(np.asarray(a)),
                               out)
            return out

        monkeypatch.setattr(mla_moe, "head", head)


def test_yarn_ramp_is_exercised_and_agrees(model):
    """The toy's frequencies interpolate in part: dimension 0 keeps
    its frequency, 1 is half way, 2 and 3 are divided by the factor;
    program and reference agree on them and on the softmax scale."""
    cfg, mcfg, _, _ = model
    f = 100.0 ** (-2.0 * np.arange(4) / 8)
    want = f * np.array([1, 0.625, 0.25, 0.25])
    assert mla_moe.yarn_frequencies(mcfg) == pytest.approx(want, rel=1e-6)
    assert reference.yarn(cfg) == pytest.approx(want, rel=1e-6)
    m = 0.1 * np.log(4.0) + 1.0
    assert mcfg.softmax_scale == pytest.approx(24 ** -0.5 * m * m)
    assert reference.softmax_scale(cfg) == pytest.approx(mcfg.softmax_scale)


def test_apply_matches_the_reference(model):
    cfg, mcfg, flat, tree = model
    ids = tokens(0, 70)
    got = MLAMoE.apply(tree, jnp.asarray(ids)[None], mcfg)[0]
    want = reference.logits(flat, ids, cfg)
    assert float(jnp.abs(got - want).max()) < TOL


def test_absorbed_attention_equals_expanded_on_one_layer(model):
    """One layer's attention block in its two forms, on the same
    normed input: ``W_uk`` folded into the query and ``W_uv`` applied
    after the sum of latents give what per-head keys and values give —
    and both equal the reference's block."""
    cfg, mcfg, flat, tree = model
    u = jax.random.normal(jax.random.PRNGKey(3), (1, 37, 64), jnp.float32)

    def attend(q, k, v, cache, li):
        if v is None:
            v = k[..., :mcfg.latent_dim]
        return mha_reference(q, k, v, causal=True, sm_scale=1.0), cache

    out = {form: mla_moe.attention(
        tree["lead"][0], u, mcfg, positions=jnp.arange(37), attend=attend,
        cache=None, li=0, form=form)[0] for form in mla_moe.FORMS}
    assert float(jnp.abs(out["absorbed"] - out["expanded"]).max()) < 1e-5
    lw, _ = reference.layer_weights(flat, cfg, 0)
    want = reference.attention(u[0], lw, dict(reference.static(cfg))
                               | {"rope_scaling": cfg["rope_scaling"]})
    assert float(jnp.abs(out["absorbed"][0] - want).max()) < 1e-5


def test_bfloat16_would_fail(model):
    """The tolerance is tight enough: the same forward in bfloat16
    lies far outside it."""
    cfg, mcfg, flat, tree = model
    ids = tokens(0, 70)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.dtype == jnp.float32 and a.ndim > 1 else a, tree)
    got = MLAMoE.apply(low, jnp.asarray(ids)[None], mcfg,
                       compute_dtype=jnp.bfloat16)[0]
    want = reference.logits(flat, ids, cfg)
    assert float(jnp.abs(got - want).max()) > 10 * TOL


def test_init_builds_the_tree_the_arranger_builds(model):
    _, mcfg, _, tree = model
    own = MLAMoE.init(jax.random.PRNGKey(0), mcfg)
    shape = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shape(own) == shape(tree)


def test_engine_prefill_and_decode_match_the_reference(model, monkeypatch):
    """A prompt of three chunks and a partial fourth (16 x 3 + 5), then
    20 decode steps: the prefill writes latents in chunks, the decode
    reads them in the absorbed form, and the logits behind every served
    token equal the reference's full (expanded) forward over the served
    stream."""
    cfg, mcfg, flat, tree = model
    rec = Recorder(monkeypatch)
    eng = engine_of(mcfg, tree)
    prompt = tokens(1, 53)
    slot, first = eng.admit(prompt)
    served = [first]
    for _ in range(20):
        assert not eng.grow_slots()
        served.append(int(eng.step()[slot]))
    jax.effects_barrier()
    got = [rec.rows[3][0, 0]] + [r[slot, 0] for r in rec.rows[4:]]
    seq = list(prompt) + served
    want = reference.logits(flat, seq, cfg,
                            positions=range(len(prompt) - 1, len(seq) - 1))
    assert len(got) == 21
    assert float(np.abs(np.stack(got) - np.asarray(want)).max()) < TOL
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1
    # a decode step's pairs: held here + routed elsewhere = slots x k
    live = eng.moe_counts.sum(axis=1) + eng.moe_elsewhere
    assert (live == 1 * cfg["num_experts_per_tok"]).all()


def test_batching_reuse_preemption_and_mixed_steps(model):
    """Five requests over two slots and a pool too small for them:
    slots are seated and retired at different steps and reused, the
    pool's pressure preempts (fold and replay), and pending chunks
    ride the decode step as the mixed program. Every stream equals a
    fresh single run of its own, and every served token's logit equals
    the reference's best to within the tolerance."""
    cfg, mcfg, flat, tree = model
    serving = dict(page_size=PAGE, n_pages=11, max_slots=2,
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
    """Guide section 4: over the 4 shares of one expert layer, the
    routed parts summed and the shared expert counted once equal the
    UNCUT reference's whole layer (all 16 experts held); each share's
    pairs here plus pairs elsewhere are T x k."""
    uncut = share(0, 16)
    lw, _ = expert_layer(uncut)
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 29, 64), jnp.float32)
    whole = reference.shared(u[0], lw) + reference.routed(
        u[0], lw, dict(reference.static(uncut)))
    total = reference.shared(u[0], lw)
    for first in (0, 4, 8, 12):
        cfg = share(first, 4)
        part, lp = expert_layer(cfg)
        # a share's experts ARE the uncut layer's
        assert jnp.array_equal(part["mo_w1"], lw["mo_w1"][first:first + 4])
        out, held, away = moe_dropless(
            lp, u, cfg["num_experts_per_tok"],
            cfg["routed_scaling_factor"], held=(first, 4))
        assert int(held.sum() + away) == 29 * 4
        ref_part = reference.routed(u[0], part,
                                    dict(reference.static(cfg)))
        assert float(jnp.abs(out[0] - ref_part).max()) < 1e-5
        total = total + out[0]
    assert float(jnp.abs(total - whole).max()) < 1e-5


def test_router_selects_by_biased_scores_and_weighs_by_unbiased():
    """Scores s = (.9, .8, .6, .5), bias (0, 0, .5, 0): the top-2 by
    s + b is {2, 0} — by s alone it would be {0, 1} — and the weights
    are 2.5 x s[2], s[0] renormalised, the bias nowhere in them. The
    reference's router agrees."""
    s = np.array([0.9, 0.8, 0.6, 0.5], np.float32)
    gate = np.log(s / (1 - s))[None]            # u = [1] -> logits
    bias = np.array([0.0, 0.0, 0.5, 0.0], np.float32)
    sel, w = moe_route({"moe_gate": {"kernel": jnp.asarray(gate)},
                        "moe_bias": jnp.asarray(bias)},
                       jnp.ones((1, 1), jnp.float32), top_k=2, scaling=2.5)
    by_expert = dict(zip(np.asarray(sel[0]).tolist(),
                         np.asarray(w[0]).tolist()))
    total = 0.9 + 0.6 + 1e-6
    assert sorted(by_expert) == [0, 2]
    assert by_expert[0] == pytest.approx(2.5 * 0.9 / total, abs=1e-6)
    assert by_expert[2] == pytest.approx(2.5 * 0.6 / total, abs=1e-6)
    ref_cfg = {"num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
               "moe_router_enable_expert_bias": True}
    full, ref_sel = reference.route(
        jnp.ones((1, 1)), {"mo_gate": jnp.asarray(gate),
                           "mo_bias": jnp.asarray(bias)}, ref_cfg)
    assert sorted(np.asarray(ref_sel[0]).tolist()) == [0, 2]
    assert np.asarray(full[0]) == pytest.approx(
        [2.5 * 0.9 / total, 0.0, 2.5 * 0.6 / total, 0.0], abs=1e-6)


@pytest.mark.parametrize("to", ["held", "absent"])
def test_a_bias_that_sends_every_pair_here_or_none(model, to):
    """A selection bias that sends every token's top-4 to the 4 held
    experts (a capacity-dropping layer would drop most pairs; the
    dropless one computes them all), and one that sends every pair to
    absent experts (only the shared expert is left): through the
    engine, counts and logits follow the reference."""
    cfg, mcfg, flat, tree = model
    bias = np.zeros((3, 16), np.float32)
    bias[:, :4] = 10.0 if to == "held" else -10.0
    flat = {**flat, "mo_bias": jnp.asarray(bias)}
    tree = {**tree, "stack": {**tree["stack"],
                              "moe_bias": jnp.asarray(bias)}}
    eng = engine_of(mcfg, tree)
    prompt = tokens(3, 40)
    slot, first = eng.admit(prompt)
    served = [first]
    for _ in range(6):
        eng.grow_slots()
        served.append(int(eng.step()[slot]))
    if to == "held":
        assert (eng.moe_counts == 1).all() and not eng.moe_elsewhere.any()
    else:
        assert not eng.moe_counts.any() and (eng.moe_elsewhere == 4).all()
    gaps = reference.served_gaps(flat, prompt, served, cfg)
    assert float(gaps.max()) < TOL


def test_the_pool_is_one_leaf_of_latent_rows(model):
    """One row a token: the pool is ONE array ``(layers, pages, page,
    row lanes padded to 128s)``, no V half is allocated, and a token
    costs ``row bytes x layers``."""
    _, mcfg, _, tree = model
    eng = engine_of(mcfg, tree)
    assert eng.pool["v"] is None and eng.slot_state is None
    assert len(jax.tree.leaves(eng.pool)) == 1
    assert eng.pool["k"].shape == (4, 64, PAGE, 128)    # 32 + 8 -> 128
    full = program.model_config(
        {**TOY, "kv_lora_rank": 512, "qk_rope_head_dim": 64,
         "num_hidden_layers": 6})
    pool = jax.eval_shape(lambda: make_pool(full, 64, 16))
    assert pool["v"] is None
    assert pool["k"].shape == (6, 16, 64, 640)          # 576 -> 5 x 128
    per_token = pool["k"].dtype.itemsize * pool["k"].shape[-1] * 6
    assert per_token == 1280 * 6


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


@pytest.mark.parametrize("feature", sorted(UNSUPPORTED))
def test_unsupported_feature_raises_at_build(model, feature):
    """By name, with the reason the model's module gives — none of
    them slot-indexed state, which this model has not."""
    from torchbooster_tpu.config import resolve_types

    _, mcfg, _, tree = model
    block = {"page_size": PAGE, "n_pages": 32, "max_slots": 2,
             **UNSUPPORTED[feature]}
    conf = ServingConfig(**resolve_types(ServingConfig, block))
    mesh = jax.make_mesh((2,), ("tp",)) if feature == "tp" else None
    with pytest.raises(NotImplementedError,
                       match=feature.split("_")[0]) as err:
        conf.make(tree, mcfg, compute_dtype=jnp.float32, mesh=mesh)
    key = next(k for k in mla_moe.UNSERVED
               if k.startswith(feature.split("_")[0]))
    assert mla_moe.UNSERVED[key] in str(err.value)
    assert "slot-indexed" not in str(err.value)


READERS = ("sarvam_serve_mfu", "sarvam_mixed_roofline",
           "mla_decode_attn_roofline", "mla_chunk_attn_roofline",
           "mla_absorb_ms", "moe_shared_ms", "sarvam_moe_experts_roofline",
           "sarvam_decode_roofline")


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
    for cfg in ({"n_layer": 2, "n_embd": 64}, TOY):
        layers = {"cfg": cfg, "window": window, "seconds": 1.0, "chips": 1,
                  "peaks": {"bf16_flops_per_s": 1e12,
                            "hbm_bytes_per_s": 1e11},
                  "registry_open": {}, "registry_close": {},
                  "trace_path": None, "trace": empty}
        assert module.read(reader + ".lat", layers) is None


def test_the_mfu_reader_counts_what_the_window_needs():
    """``sarvam_serve_mfu`` on a hand-made window: 100 decoded tokens
    at context 1,000 and one prompt of 1,000 tokens, a quarter of the
    routed pairs here, against ``flops_sarvam_mla`` by hand."""
    import flops_sarvam_mla as fl
    import run as harness

    if str(BENCH / "layer_metrics") not in sys.path:
        sys.path.append(str(BENCH / "layer_metrics"))
    module = harness.load_module(
        BENCH / "layer_metrics" / "sarvam_serve_mfu.py")
    pairs = "serving_moe_pairs_total{where=%s}"
    layers = {
        "cfg": TOY, "seconds": 2.0, "chips": 1,
        "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9},
        "window": {"decode_tokens": 100, "prefill_tokens": 1000,
                   "context_read": 100 * 1000.0, "ttfts": [0.1]},
        "prefill_pairs": 1000 * 1001 / 2,
        "registry_open": {pairs % "here": 10.0, pairs % "elsewhere": 30.0},
        "registry_close": {pairs % "here": 110.0,
                           pairs % "elsewhere": 330.0},
    }
    tokens = 1100
    want = (2.0 * fl.token_matmul_params(TOY) * tokens
            + fl.attention_flops(TOY, 100_000 + 500_500)
            + 2.0 * fl.expert_params(TOY) * tokens * 4 * 3 * 0.25
            + 2.0 * fl.head_params(TOY) * 101)
    got = module.read("sarvam_serve_mfu.lat", layers)
    assert got == pytest.approx(100.0 * want / (2.0 * 1e9), rel=1e-9)
    # a count that was not made is not guessed: without the program's
    # pair counters the reader has nothing to read
    assert module.read("sarvam_serve_mfu.lat", {
        **layers, "registry_open": {}, "registry_close": {}}) is None
    # by hand: 4 heads x (24 + 16) x 2 a pair and layer, 4 layers
    assert fl.attention_flops(TOY, 1.0) == 4 * 40 * 2 * 4
    assert fl.row_bytes({"kv_lora_rank": 512, "qk_rope_head_dim": 64}) \
        == 1280


def test_the_decode_roofline_reads_the_stretch_it_times():
    """``sarvam_decode_roofline`` on a hand-made traced stretch: four
    plain steps of 2 ms that filed 6 experts hit a step and 24 routed
    pairs (6 of them here), so 2 sequences a step (top-4 x 3 expert
    layers), each token decoded in the stretch having read 50 rows —
    against ``flops_sarvam_mla.step_bytes`` by hand, at a bandwidth
    that makes bytes the bound. The WINDOW's registry (ten times the
    pairs) is not what it reads; a stretch without a plain step reads
    None."""
    import flops_sarvam_mla as fl
    import run as harness
    import trace_reduce

    if str(BENCH / "layer_metrics") not in sys.path:
        sys.path.append(str(BENCH / "layer_metrics"))
    module = harness.load_module(
        BENCH / "layer_metrics" / "sarvam_decode_roofline.py")
    pairs = "serving_moe_pairs_total{where=%s}"
    hit = "serving_moe_experts_hit_%s"
    runs = [(i * 0.01, i * 0.01 + 0.002, "jit__decode_fn(1)")
            for i in range(4)]
    layers = {
        "cfg": TOY, "peaks": {"bf16_flops_per_s": 1e15,
                              "hbm_bytes_per_s": 1e9},
        "trace": trace_reduce.Trace(ops={"chip": []},
                                    modules={"chip": runs}),
        "registry_open": {}, "registry_close": {
            pairs % "here": 600.0, pairs % "elsewhere": 1800.0},
        "registry_trace_open": {
            pairs % "here": 100.0, pairs % "elsewhere": 300.0,
            hit % "sum": 60.0, hit % "count": 10.0},
        "registry_trace_close": {
            pairs % "here": 124.0, pairs % "elsewhere": 372.0,
            hit % "sum": 84.0, hit % "count": 14.0},
        "traced_decode_tokens": 20, "traced_context_read": 1000.0,
    }
    n = fl.layer_counts(TOY)
    fixed = fl.n_params(TOY) - fl.head_params(TOY) \
        - n["moe"] * TOY["num_experts"] * fl.expert_params(TOY)
    want = ((fixed + 6 * fl.expert_params(TOY)) * 2
            + 2 * 50 * n["attention"] * fl.row_bytes(TOY)) / 1e9
    got = module.read("sarvam_decode_roofline.lat", layers)
    assert got == pytest.approx(100.0 * want / 0.002, rel=1e-9)
    assert module.read("sarvam_decode_roofline.lat", {
        **layers, "registry_trace_close":
        layers["registry_trace_open"]}) is None


def test_every_window_is_offered_the_same_work():
    """The job plans the pre-roll and the window apart: whatever the
    seed, the WINDOW holds the same multiset of (prompt, output)
    lengths and of arrival gaps (the seed orders them and draws the
    ids), the pre-roll likewise, and no request of one falls into the
    other. Planned as one horizon, which requests the window holds is
    the seed's, and the cell's p95 followed it."""
    import json

    from jobs import serve_sarvam_mla as job

    traffic = json.loads(
        (BENCH / "traffic" / "serve-longdoc-r80.json").read_text())
    pre, seconds, rate = traffic["preroll_s"], 40.0, traffic["rate"]

    def shape(seed):
        reqs = job.plan_requests(traffic, seed, pre, seconds, 65536)
        assert sorted(r["id"] for r in reqs) == list(range(len(reqs)))
        before = [r for r in reqs if r["due"] < pre]
        inside = [r for r in reqs if r["due"] >= pre]
        assert len(before) == round(rate * pre)
        assert len(inside) == round(rate * seconds)
        assert inside[0]["due"] == pre and reqs[-1]["due"] < pre + seconds
        due = [r["due"] for r in inside] + [pre + seconds]
        return (sorted((len(r["prompt"]), r["max_tokens"]) for r in inside),
                sorted(np.round(np.diff(due), 9)),
                sorted((len(r["prompt"]), r["max_tokens"]) for r in before),
                [len(r["prompt"]) for r in inside])

    a, b = shape(3), shape(2**31 + 12345)
    assert a[:3] == b[:3] and a[3] != b[3]


def test_the_benchmark_job_rehearses_at_toy_size():
    """``benchmark/run.execute`` on the toy root beside the others
    (benchmark/tests/tiny_sarvam_mla): the ``serve_sarvam_mla`` job end
    to end — weights from the seed, the stack as a user's YAML builds
    it, HTTP traffic from the load generator's process with ids from
    the vocabulary slice, the served streams against the float32
    reference. The limit is a bfloat16 program's against a float32
    reference at toy widths; nothing here is a measurement."""
    import flops
    import run as harness

    root = BENCH / "tests" / "tiny_sarvam_mla"
    out = harness.execute("sarvam-mla-tiny.serve-longdoc-tiny", 2**31 + 7,
                          1.5, False, root=root, devices=jax.devices()[:1],
                          peaks=flops.peaks_of("TPU v5 lite"))
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert line["compared"]["bad_streams"]["value"] == 0
    assert 0 <= line["compared"]["served_gap_p99"]["value"] \
        <= line["compared"]["served_gap_max"]["value"] < 0.05
    assert out["log"]["compiles_in_window"] == 0
    assert out["log"]["stream_variety"]["distinct"] > 1
