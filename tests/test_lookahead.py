"""One step in flight: an engine that looks ahead
(``PagedEngine.looks_ahead``) lets ``ContinuousBatcher`` launch step
N+1 before it has read step N — the last tokens stay on the device,
the lengths are bumped at the launch, the host's work runs beside a
program. On the CPU, for the four served families at toy size, the
streams equal the synchronous loop's (the same engine with
``looks_ahead`` off) token for token; a stop by EOS costs one lane, a
stop by length none; a cancel, a preemption, a drain and the session's
end land the step in flight first; the modes that need the token on
the host keep depth 0.
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

import torchbooster_tpu.observability as obs  # noqa: E402
from tests import test_afmoe, test_lfm2, test_sarvam_mla  # noqa: E402
from torchbooster_tpu.models.gpt import GPT, GPTConfig  # noqa: E402
from torchbooster_tpu.serving import (ContinuousBatcher,  # noqa: E402
                                      PagedEngine, Request)


def gpt_model(head=4.0):
    """``head`` 4: a decisive head (rounding cannot flip a greedy
    pick, the streams soon repeat one token); a quarter: streams that wander."""
    cfg = GPTConfig(vocab=97, n_layers=2, d_model=32, n_heads=4,
                    seq_len=64, n_kv_heads=2)
    params = GPT.init(jax.random.PRNGKey(0), cfg)
    return cfg, {**params,
                 "wte": {"table": params["wte"]["table"] * head}}


def gpt_batcher(ahead=True, head=4.0, **kw):
    cfg, params = gpt_model(head)
    kw = {"page_size": 4, "n_pages": 64, "max_slots": 4,
          "prefill_chunk_pages": 2, "compute_dtype": jnp.float32, **kw}
    engine = PagedEngine(params, cfg, **kw)
    assert engine.looks_ahead            # the defaults look ahead
    engine.looks_ahead = ahead           # off: the synchronous loop
    return ContinuousBatcher(engine)


# family -> (model config, the program's tree, page size, vocabulary,
# longest context): the suites' own toy configurations
def _family(name):
    if name == "gpt":
        cfg, params = gpt_model()
        return cfg, params, 4, 97, 64
    if name == "lfm2":
        mcfg = test_lfm2.program_lfm2.model_config(test_lfm2.TOY)
        tree = test_lfm2.weights_lfm2.generate(
            test_lfm2.TOY, 11, jnp.float32,
            arrange=test_lfm2.program_lfm2.arranger(test_lfm2.TOY))
        return mcfg, tree, test_lfm2.PAGE, 128, 256
    suite = {"sarvam_mla": test_sarvam_mla, "afmoe": test_afmoe}[name]
    mcfg, _, tree = suite.built(suite.TOY)
    return mcfg, tree, suite.PAGE, 128, 256


@pytest.fixture(scope="module", params=["gpt", "lfm2", "sarvam_mla",
                                        "afmoe"])
def pair(request):
    """(look-ahead batcher, synchronous batcher, draw) of one family:
    the same weights and geometry, three slots for six requests."""
    mcfg, tree, page, vocab, longest = _family(request.param)

    def batcher(ahead):
        engine = PagedEngine(tree, mcfg, page_size=page, n_pages=96,
                             max_slots=3, prefill_chunk_pages=2,
                             compute_dtype=jnp.float32)
        assert engine.looks_ahead and engine.mixes
        engine.looks_ahead = ahead
        return ContinuousBatcher(engine)

    def draw(seed):
        rng = np.random.default_rng(seed)
        chunk = 2 * page
        # the first finds no slot decoding: its prompt ends in a LONE
        # chunk; the others' chunks ride decode steps, one of them a
        # whole number of chunks long, one shorter than a chunk
        lens = [int(rng.integers(1, 3 * chunk)), 2 * chunk, 3,
                *(int(n) for n in rng.integers(1, 4 * chunk, 3))]
        return [Request(
            prompt=rng.integers(0, vocab, n).astype(np.int32),
            max_new_tokens=int(rng.integers(1, min(24, longest - n))))
            for n in lens]

    return batcher(True), batcher(False), draw


@pytest.fixture()
def registry():
    reg = obs.get_registry()
    was = reg.enabled
    reg.reset()
    reg.enabled = True
    try:
        yield reg
    finally:
        reg.enabled = was
        reg.reset()


def _count(snap, name, **labels):
    if labels:
        name += "{" + ",".join(f"{k}={v}" for k, v in labels.items()) + "}"
    return snap.get(name, 0.0)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lookahead_streams_equal_the_synchronous_loops(pair, registry,
                                                       seed):
    ahead, sync, draw = pair
    served = {}
    for name, batcher in (("ahead", ahead), ("sync", sync)):
        engine = batcher.engine
        mixed0, chunks0 = engine.mixed_steps, engine.prefill_chunks
        registry.reset()
        reqs = draw(seed)
        metrics = batcher.run(reqs)
        snap = registry.snapshot()
        served[name] = [list(r.tokens) for r in reqs]
        assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
        assert metrics["n_preemptions"] == 0
        # mixed steps, lone chunks and plain steps all ran
        mixed = engine.mixed_steps - mixed0
        assert 0 < mixed < engine.prefill_chunks - chunks0
        steps = _count(snap, "span_seconds{name=decode_step}_count")
        assert steps > mixed
        launched = _count(snap, "serving_lookahead_steps_total")
        lands = {reason: _count(snap, "serving_sync_lands_total",
                                reason=reason)
                 for reason in ("mode", "preempt", "drain", "idle")}
        assert _count(snap, "serving_wasted_lanes_total") == 0
        if name == "ahead":
            # every step but the first after an idle point was
            # launched behind another, and nothing else forced a wait
            assert lands["mode"] == lands["preempt"] == 0
            assert lands["drain"] == 0 and lands["idle"] >= 1
            assert launched == steps - lands["idle"] > 0
        else:
            assert launched == 0 and lands["mode"] == steps
        assert engine.decode_compiles == 1
        assert engine.prefill_compiles <= 2
        assert engine._flight is None
        engine.tables.check()
        assert engine.tables.n_free_pages == engine.n_pages - 1
    assert served["ahead"] == served["sync"]


def test_sampled_streams_equal_where_the_schedule_does():
    """One rng split a program, in the same order: with every request
    seated at once the two loops issue the same programs, so sampled
    streams are equal too (where admissions wait for a slot the
    look-ahead loop seats one step later and the key stream shifts)."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, n).astype(np.int32)
               for n in (5, 17, 9, 26, 3)]
    served = []
    for ahead in (True, False):
        batcher = gpt_batcher(ahead, max_slots=8, temperature=0.9)
        reqs = [Request(prompt=p, max_new_tokens=6 + i)
                for i, p in enumerate(prompts)]
        batcher.run(reqs)
        served.append([list(r.tokens) for r in reqs])
    assert served[0] == served[1]
    assert len({tuple(s) for s in served[0]}) == len(prompts)


def _free_run(prompt, n_new, **kw):
    batcher = gpt_batcher(False, **kw)
    req = Request(prompt=prompt, max_new_tokens=n_new)
    batcher.run([req])
    return list(req.tokens)


def test_an_eos_stop_lands_one_step_late(registry):
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 97, n).astype(np.int32) for n in (9, 13)]
    free = _free_run(prompts[0], 12, head=0.25)
    # a token the stream first shows at its fourth place or later
    at = next(i for i in range(3, 12) if free[i] not in free[:i])
    eos = free[at]
    batcher = gpt_batcher(head=0.25, prefix_cache=True)
    engine, tables = batcher.engine, batcher.engine.tables
    stopper = Request(prompt=prompts[0], max_new_tokens=12, eos_id=eos)
    other = Request(prompt=prompts[1], max_new_tokens=16)
    batcher.start_session()
    try:
        batcher.submit(stopper)
        batcher.submit(other)
        while stopper.finished_at is None:
            batcher.step()
            tables.check()
        # delivered exactly up to the EOS, and the request is done...
        assert stopper.tokens == free[:at + 1]
        assert stopper.finish_reason == "stop"
        # ...while its slot rides the step in flight to its end: the
        # pages are still its own, one row past its final length
        final = len(prompts[0]) + at + 1
        slot = batcher._s.stopped[0]
        assert not tables.active[slot]
        assert tables.lengths[slot] == final
        held = tables.n_free_pages
        assert _count(registry.snapshot(),
                      "serving_wasted_lanes_total") == 0
        batcher.step()                  # that step lands
        assert batcher._s.stopped == [] and tables.lengths[slot] == 0
        assert tables.n_free_pages + tables.n_cached_pages > held
        assert stopper.tokens == free[:at + 1]     # nothing delivered
        tables.check()
        while batcher.has_work:
            batcher.step()
    finally:
        batcher.finish_session()
    assert _count(registry.snapshot(), "serving_wasted_lanes_total") == 1
    assert other.tokens == _free_run(prompts[1], 16, head=0.25)
    tables.check()
    # the prefix index holds whole prompt pages only: no row past a
    # prompt, let alone past a final length
    assert tables._index
    for key in tables._index:
        assert len(key) // 4 <= max(len(p) for p in prompts)
    assert tables.n_free_pages + tables.n_cached_pages \
        == engine.n_pages - 1


def test_a_length_stop_costs_no_lane(registry):
    """``max_new_tokens`` (1 and 2 among them) and the ``seq_len``
    horizon are counted with the token in flight: every lane a
    program decodes is a token delivered."""
    rng = np.random.default_rng(7)
    # the last one ends AT the horizon (seq_len 64)
    asks = [(rng.integers(0, 97, n).astype(np.int32), m)
            for n, m in [(5, 1), (11, 2), (7, 9), (20, 3), (50, 14)]]
    served, lanes = [], 0
    for ahead in (True, False):
        batcher = gpt_batcher(ahead)
        engine = batcher.engine
        if ahead:
            real = engine.step_ahead

            def counting(flight, mixed):
                nonlocal lanes
                new, landed = real(flight, mixed)
                if new is not None:
                    lanes += int(new.active.sum())
                return new, landed

            engine.step_ahead = counting
        reqs = [Request(prompt=p, max_new_tokens=m) for p, m in asks]
        batcher.run(reqs)
        served.append([(list(r.tokens), r.finish_reason) for r in reqs])
        engine.tables.check()
        assert engine.tables.n_free_pages == engine.n_pages - 1
    assert served[0] == served[1]
    assert [len(t) for t, _ in served[0]] == [1, 2, 9, 3, 14]
    assert {why for _, why in served[0]} == {"length"}
    assert lanes == sum(len(t) - 1 for t, _ in served[0])
    assert _count(registry.snapshot(), "serving_wasted_lanes_total") == 0


def _decoding(batcher, reqs):
    """Submit ``reqs`` and step until all decode with a step in flight."""
    batcher.start_session()
    for req in reqs:
        batcher.submit(req)
    while not all(len(r.tokens) >= 2 for r in reqs):
        batcher.step()
    assert batcher._s.flight is not None


def test_a_cancel_lands_the_step_in_flight_first(registry):
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 97, n).astype(np.int32) for n in (6, 10)]
    batcher = gpt_batcher()
    gone = Request(prompt=prompts[0], max_new_tokens=30)
    stays = Request(prompt=prompts[1], max_new_tokens=12)
    try:
        _decoding(batcher, [gone, stays])
        had = len(gone.tokens)
        batcher.cancel(gone)
        events = batcher.step()
        # the step in flight landed with nothing behind it, its token
        # for the cancelled slot was dropped, the seat went back
        snap = registry.snapshot()
        assert _count(snap, "serving_sync_lands_total", reason="drain") == 1
        assert gone.cancelled and len(gone.tokens) == had
        assert [toks for req, toks in events if req is gone] == [[]]
        assert batcher.engine.tables.n_free_slots() == 3
        batcher.engine.tables.check()
        while batcher.has_work:
            batcher.step()
    finally:
        batcher.finish_session()
    assert stays.tokens == _free_run(prompts[1], 12)
    assert batcher.engine.tables.n_free_pages == batcher.engine.n_pages - 1


def test_a_preemption_lands_the_step_in_flight_first(registry):
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 97, n).astype(np.int32)
               for n in (9, 14, 5, 21, 11)]
    served, preempted = [], []
    for ahead in (True, False):
        batcher = gpt_batcher(ahead, n_pages=16)   # ~2 sequences' worth
        reqs = [Request(prompt=p, max_new_tokens=20) for p in prompts]
        registry.reset()
        preempted.append(batcher.run(reqs)["n_preemptions"])
        served.append([list(r.tokens) for r in reqs])
        batcher.engine.tables.check()
        assert batcher.engine.tables.n_free_pages == 15
        lands = _count(registry.snapshot(), "serving_sync_lands_total",
                       reason="preempt")
        assert (lands > 0) == ahead
    assert preempted[0] == preempted[1] > 0
    assert served[0] == served[1]


@pytest.mark.parametrize("how", ["drain_unfinished", "finish_session"])
def test_a_drain_and_the_sessions_end_land_what_is_in_flight(registry,
                                                             how):
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, 97, n).astype(np.int32) for n in (6, 10)]
    batcher = gpt_batcher()
    engine = batcher.engine
    reqs = [Request(prompt=p, max_new_tokens=30) for p in prompts]
    try:
        _decoding(batcher, reqs)
        had = [list(r.tokens) for r in reqs]
        if how == "drain_unfinished":
            out = batcher.drain_unfinished()
            # the step landed, its tokens dropped: every request
            # leaves with exactly what was delivered, folded
            assert [list(r.tokens) for r in out] == had
            for req, prompt in zip(out, prompts):
                assert list(req.prompt) == [*prompt, *req.tokens]
            assert engine.tables.n_free_pages == engine.n_pages - 1
            assert not batcher.has_work
    finally:
        batcher.finish_session()
    assert engine._flight is None
    assert [list(r.tokens) for r in reqs] == had
    assert _count(registry.snapshot(), "serving_sync_lands_total",
                  reason="drain") == 1
    engine.tables.check()
    # the engine is as a synchronous one: the next launch knows every
    # slot's last token
    assert engine._op["known"].all()


@pytest.mark.parametrize("mode", [
    {"structured": True}, {"speculative": True, "draft_len": 2},
    {"parallel_sampling": True}, {"lora_rank": 2, "lora_max_live": 2},
    {"prefill_only": True}])
def test_modes_that_need_the_token_keep_depth_zero(registry, mode):
    cfg, params = gpt_model()
    engine = PagedEngine(params, cfg, page_size=4, n_pages=32,
                         max_slots=2, compute_dtype=jnp.float32, **mode)
    assert not engine.looks_ahead
    assert "known" not in engine.operands.fields and engine._tokens is None
    if engine.prefill_only:
        return                          # nothing decodes there
    batcher = ContinuousBatcher(engine)
    req = Request(prompt=np.arange(1, 8, dtype=np.int32), max_new_tokens=5)
    batcher.start_session()
    try:
        batcher.submit(req)
        while batcher.has_work:
            batcher.step()
            assert batcher._s.flight is None
    finally:
        batcher.finish_session()
    assert len(req.tokens) == 5
    snap = registry.snapshot()
    assert _count(snap, "serving_lookahead_steps_total") == 0
    assert _count(snap, "serving_sync_lands_total", reason="mode") > 0
