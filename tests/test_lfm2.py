"""LFM2-MoE against its plain float32 reference, at a toy size on the
CPU: the model file, the paged engine's chunk and decode programs with
the conv mixers' slot state, the batcher's seat / retire / preempt
cycle, the dropless expert layer and the router's semantics. Every
comparison is on LOGITS (random weights flip an argmax on rounding),
against ``benchmark/reference/lfm2.py`` — which imports nothing of the
program.

Tolerances. Everything here runs in float32 on both sides, so what
differs is the order of sums: float32 matrix products reassociated
(the program fuses q/k/v, sorts tokens by expert, splits attention
into page partials with an online softmax), over toy widths of 64-128
and logits of size ~1. That is a few 1e-6 at a time; 2e-4 leaves room
for 14 layers of it and is two orders under what a bfloat16 side
would show (``test_bfloat16_would_fail``: ~1e-2).
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

import program_lfm2  # noqa: E402
import weights_lfm2  # noqa: E402
from reference import lfm2 as reference  # noqa: E402

from torchbooster_tpu.config import ServingConfig  # noqa: E402
from torchbooster_tpu.models.lfm2 import LFM2  # noqa: E402
from torchbooster_tpu.models.moe import moe_route  # noqa: E402
from torchbooster_tpu.serving import PagedEngine, Request  # noqa: E402

TOL = 2e-4
PAGE, CHUNK_PAGES = 8, 2                       # chunks of 16 tokens

# 2 dense conv layers + 2 periods of (attention, conv, conv, conv)
TOY = {
    "vocab_size": 128, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "num_dense_layers": 2,
    "num_hidden_layers": 10,
    "layer_types": ["conv", "conv"]
    + ["full_attention", "conv", "conv", "conv"] * 2,
    "conv_L_cache": 3, "rope_theta": 1000000, "norm_eps": 1e-05,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "max_position_embeddings": 256,
}


@pytest.fixture(scope="module")
def model():
    """(config dict, model config, flat float32 weights, the program's
    tree of the same numbers)."""
    flat = weights_lfm2.generate(TOY, 11, jnp.float32)
    tree = weights_lfm2.generate(TOY, 11, jnp.float32,
                                 arrange=program_lfm2.arranger(TOY))
    return TOY, program_lfm2.model_config(TOY), flat, tree


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
        import torchbooster_tpu.models.lfm2 as prog

        self.rows = []
        real = prog.head

        def head(params, x, cfg):
            out = real(params, x, cfg)
            jax.debug.callback(lambda a: self.rows.append(np.asarray(a)),
                               out)
            return out

        monkeypatch.setattr(prog, "head", head)


def test_apply_matches_the_reference(model):
    cfg, mcfg, flat, tree = model
    ids = tokens(0, 70)
    got = LFM2.apply(tree, jnp.asarray(ids)[None], mcfg)[0]
    want = reference.logits(flat, ids, cfg)
    assert float(jnp.abs(got - want).max()) < TOL


def test_bfloat16_would_fail(model):
    """The tolerance is tight enough: the same forward in bfloat16
    lies far outside it."""
    cfg, mcfg, flat, tree = model
    ids = tokens(0, 70)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                       if a.dtype == jnp.float32 and a.ndim > 1 else a, tree)
    got = LFM2.apply(low, jnp.asarray(ids)[None], mcfg,
                     compute_dtype=jnp.bfloat16)[0]
    want = reference.logits(flat, ids, cfg)
    assert float(jnp.abs(got - want).max()) > 10 * TOL


def test_init_builds_the_tree_the_arranger_builds(model):
    _, mcfg, _, tree = model
    own = LFM2.init(jax.random.PRNGKey(0), mcfg)
    shape = lambda t: jax.tree.map(lambda a: a.shape, t)
    assert shape(own) == shape(tree)


def test_engine_prefill_and_decode_match_the_reference(model, monkeypatch):
    """A prompt of three chunks and a partial fourth (16 x 3 + 5: the
    conv state must be that of token 52, not of the padded end), then
    20 decode steps: the logits behind every served token equal the
    reference's full forward over the served stream."""
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
    # chunk heads: the last chunk's is the prompt's last position;
    # decode heads: (slots, 1, vocab), the live slot's row
    got = [rec.rows[3][0, 0]] + [r[slot, 0] for r in rec.rows[4:]]
    seq = list(prompt) + served
    want = reference.logits(flat, seq, cfg,
                            positions=range(len(prompt) - 1, len(seq) - 1))
    assert len(got) == 21
    assert float(np.abs(np.stack(got) - np.asarray(want)).max()) < TOL
    assert eng.decode_compiles == 1 and eng.prefill_compiles == 1


def test_batching_reuse_and_preemption_leave_no_state_behind(model):
    """Five requests over two slots and a pool too small for them:
    slots are seated and retired at different steps and reused, and the
    pool's pressure preempts (fold and replay). Every stream equals a
    fresh single run of its own, and every served token's logit equals
    the reference's best to within the tolerance — a stale or leaked
    conv state, or one kept from the padded end of a replayed chunk,
    would show in both."""
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
    alone = ServingConfig(**{**serving, "n_pages": 64}).make(
        tree, mcfg, compute_dtype=jnp.float32)
    for crowded, fresh in zip(reqs, requests()):
        alone.run([fresh])
        assert list(crowded.tokens) == list(fresh.tokens)
        gaps = reference.served_gaps(flat, fresh.prompt, fresh.tokens,
                                     cfg)
        assert float(gaps.max()) < TOL
    assert batcher.engine.decode_compiles == 1


def test_dropless_when_every_token_takes_the_same_experts(model):
    """A selection bias that sends every token to experts 0 and 1: a
    capacity-dropping layer would drop most pairs; the dropless one
    still equals the reference, through the engine."""
    cfg, mcfg, flat, tree = model
    bias = np.zeros((8, 8), np.float32)
    bias[:, :2] = 10.0
    flat = {**flat, "mo_bias": jnp.asarray(bias)}
    skew = lambda lp: {**lp, "moe_bias": jnp.broadcast_to(
        jnp.asarray(bias[0]), lp["moe_bias"].shape)}
    tree = {**tree, "periods": [skew(lp) for lp in tree["periods"]]}
    eng = engine_of(mcfg, tree)
    prompt = tokens(3, 40)
    slot, first = eng.admit(prompt)
    served = [first]
    for _ in range(6):
        eng.grow_slots()
        served.append(int(eng.step()[slot]))
    # one live slot, top-2: both pairs of every layer on experts 0, 1
    assert (eng.moe_counts[:, :2] == 1).all()
    assert eng.moe_counts[:, 2:].sum() == 0
    gaps = reference.served_gaps(flat, prompt, served, cfg)
    assert float(gaps.max()) < TOL


def test_router_selects_by_biased_scores_and_weighs_by_unbiased():
    """Scores s = sigmoid(logits) = (.9, .8, .6, .5), bias (0, 0, .5,
    0): the top-2 by s + b is {2, 0} — by s alone it would be {0, 1} —
    and the weights are s[2], s[0] renormalised, the bias nowhere in
    them. The reference's router agrees."""
    s = np.array([0.9, 0.8, 0.6, 0.5], np.float32)
    gate = np.log(s / (1 - s))[None]            # u = [1] -> logits
    bias = np.array([0.0, 0.0, 0.5, 0.0], np.float32)
    sel, w = moe_route({"moe_gate": {"kernel": jnp.asarray(gate)},
                        "moe_bias": jnp.asarray(bias)},
                       jnp.ones((1, 1), jnp.float32), top_k=2)
    assert sorted(np.asarray(sel[0]).tolist()) == [0, 2]
    by_expert = dict(zip(np.asarray(sel[0]).tolist(),
                         np.asarray(w[0]).tolist()))
    total = 0.9 + 0.6 + 1e-6
    assert by_expert[0] == pytest.approx(0.9 / total, abs=1e-6)
    assert by_expert[2] == pytest.approx(0.6 / total, abs=1e-6)
    ref_cfg = {"num_experts_per_tok": 2, "use_expert_bias": True,
               "norm_topk_prob": True, "routed_scaling_factor": 1}
    full, ref_sel = reference.route(
        jnp.ones((1, 1)), {"mo_gate": jnp.asarray(gate),
                           "mo_bias": jnp.asarray(bias)}, ref_cfg)
    assert sorted(np.asarray(ref_sel[0]).tolist()) == [0, 2]
    assert np.asarray(full[0]) == pytest.approx(
        [0.9 / total, 0.0, 0.6 / total, 0.0], abs=1e-6)


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
    from torchbooster_tpu.config import resolve_types

    _, mcfg, _, tree = model
    block = {"page_size": PAGE, "n_pages": 32, "max_slots": 2,
             **UNSUPPORTED[feature]}
    conf = ServingConfig(**resolve_types(ServingConfig, block))
    mesh = jax.make_mesh((2,), ("tp",)) if feature == "tp" else None
    with pytest.raises(NotImplementedError, match=feature.split("_")[0]):
        conf.make(tree, mcfg, compute_dtype=jnp.float32, mesh=mesh)


def test_cache_spec_sizes_the_pool_and_the_slot_state(model):
    """The pool holds the attention layers only; the conv layers'
    state is indexed by slot."""
    _, mcfg, _, tree = model
    eng = engine_of(mcfg, tree)
    assert eng.pool["k"].shape == (2, 64, PAGE, 128)   # 2 x 16 -> 128
    assert eng.slot_state["conv"].shape == (8, 4, 2, 64)
    from torchbooster_tpu.models.gpt import GPTConfig
    from torchbooster_tpu.serving.kv_pages import cache_spec

    spec = cache_spec(GPTConfig(n_layers=3, d_model=64, n_heads=4))
    assert (spec.kv_layers, spec.kv_heads, spec.head_dim) == (3, 4, 16)
    assert not spec.slot_states


def test_the_benchmark_job_rehearses_at_toy_size():
    """``benchmark/run.execute`` on the toy root beside the GPT-2 one
    (benchmark/tests/tiny_lfm2): the ``serve_lfm2`` job end to end —
    weights from the seed, the stack as a user's YAML builds it, HTTP
    traffic from the load generator's process, the served streams
    against the float32 reference. The limit is a bfloat16 program's
    against a float32 reference at toy widths (readings ~0.01); nothing
    here is a measurement."""
    import flops
    import run as harness

    root = BENCH / "tests" / "tiny_lfm2"
    out = harness.execute("lfm2-tiny.serve-rag-tiny", 2**31 + 7, 1.5,
                          False, root=root, devices=jax.devices()[:1],
                          peaks=flops.peaks_of("TPU v5 lite"))
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert line["compared"]["bad_streams"]["value"] == 0
    assert 0 <= line["compared"]["served_gap_p99"]["value"] \
        <= line["compared"]["served_gap_max"]["value"] < 0.05
    assert out["log"]["compiles_in_window"] == 0
    assert out["log"]["stream_variety"]["distinct"] > 1
