"""A pending prefill chunk rides the decode step as ONE program
(``PagedEngine.mixed_step``, the mixed variant of ``_chunk_fn``): for
both served families, float32 and greedy on the CPU, the tokens of
every request under mixed iterations equal those of the two-program
iteration (the same engine with ``mixes`` off) and of the family's
float32 reference — ``GPT.generate`` for a GPT with a decisive head,
``benchmark/reference/lfm2.py`` (on logits: random weights flip an
argmax on rounding) for LFM2.
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

import torchbooster_tpu.observability as obs  # noqa: E402
from tests.test_lfm2 import TOL, TOY  # noqa: E402
from torchbooster_tpu.models.gpt import GPT, GPTConfig  # noqa: E402
from torchbooster_tpu.serving import (ContinuousBatcher,  # noqa: E402
                                      PagedEngine, Request)

CHUNK_PAGES = 2


class Family:
    """One served family at a toy size: its engine, and what a served
    stream is held to."""

    def __init__(self, name):
        self.name = name
        if name == "gpt":
            self.page, self.vocab = 4, 97
            self.cfg = GPTConfig(vocab=97, n_layers=2, d_model=32,
                                 n_heads=4, seq_len=64, n_kv_heads=2)
            params = GPT.init(jax.random.PRNGKey(0), self.cfg)
            # a decisive head: rounding cannot flip a greedy pick
            self.params = {**params, "wte": {
                "table": params["wte"]["table"] * 4.0}}
        else:
            self.page, self.vocab = 8, 128
            self.flat = weights_lfm2.generate(TOY, 11, jnp.float32)
            self.params = weights_lfm2.generate(
                TOY, 11, jnp.float32, arrange=program_lfm2.arranger(TOY))
            self.cfg = program_lfm2.model_config(TOY)
        self.chunk = CHUNK_PAGES * self.page

    def engine(self, mixes=True, **kw):
        kw = {"page_size": self.page, "n_pages": 64, "max_slots": 4,
              "prefill_chunk_pages": CHUNK_PAGES,
              "compute_dtype": jnp.float32, **kw}
        engine = PagedEngine(self.params, self.cfg, **kw)
        assert engine.mixes         # the defaults ride, a step ahead
        # off: two programs an iteration, each read back at once
        engine.mixes = engine.looks_ahead = mixes
        return engine

    def check(self, req):
        """The stream against the family's float32 reference."""
        prompt = req.prompt[:req.base_len]
        if self.name == "gpt":
            out = GPT.generate(self.params, jnp.asarray(prompt)[None],
                               self.cfg, n_new=len(req.tokens),
                               temperature=0.0,
                               compute_dtype=jnp.float32)
            np.testing.assert_array_equal(
                np.asarray(out)[0, len(prompt):], req.tokens)
        else:
            gaps = reference.served_gaps(self.flat, prompt, req.tokens,
                                         TOY)
            assert float(gaps.max()) < TOL


@pytest.fixture(scope="module", params=["gpt", "lfm2"])
def family(request):
    return Family(request.param)


def drive(batcher, first, rest):
    """``first`` decode before ``rest`` arrive; then to the end. One
    row an iteration: (chunks that rode it, requests it finished)."""
    engine, rows = batcher.engine, []
    batcher.start_session()
    try:
        for req in first:
            batcher.submit(req)
        while not all(req.tokens for req in first):
            batcher.step()
        for req in rest:
            batcher.submit(req)
        while batcher.has_work:
            rode = engine.mixed_steps
            open_ = [r for r in (*first, *rest) if r.finished_at is None]
            batcher.step()
            rows.append((engine.mixed_steps - rode,
                         [r for r in open_ if r.finished_at is not None]))
    finally:
        metrics = batcher.finish_session()
    return rows, metrics


def prompt(seed, n, vocab):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


# what arrives behind two decoding requests, in chunks of C tokens and
# pages of P: (prompt length, chunks that must ride, engine geometry)
SCENARIOS = {
    "first_chunk": lambda C, P: (C, 1, {}),
    "middle_chunk": lambda C, P: (3 * C, 3, {}),
    "padded_last_chunk": lambda C, P: (2 * C + 3, 3, {}),
    "shorter_than_a_chunk": lambda C, P: (3, 1, {}),
    # the pool holds both prompts and not one page more: the decoding
    # request's next page preempts the seat that is filling
    "preempted_mid_prefill": lambda C, P: (3 * C, None, {"n_pages": 9}),
    "retiring_in_the_step": lambda C, P: (3 * C, None, {}),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_mixed_iterations_serve_the_two_program_tokens(family, scenario):
    C, P = family.chunk, family.page
    n_prompt, must_ride, geometry = SCENARIOS[scenario](C, P)
    lone = scenario in ("preempted_mid_prefill", "retiring_in_the_step")

    def traffic():
        # decoding when the prompt arrives: its second page nearly full
        first = [Request(prompt=prompt(1, 2 * P - 2, family.vocab),
                         max_new_tokens=(4 if scenario
                                         == "retiring_in_the_step" else 12))]
        if not lone:
            first.append(Request(prompt=prompt(2, P + 1, family.vocab),
                                 max_new_tokens=9))
        return first, [Request(prompt=prompt(3, n_prompt, family.vocab),
                               max_new_tokens=6)]

    runs = {}
    for mixes in (True, False):
        first, rest = traffic()
        batcher = ContinuousBatcher(family.engine(mixes, **geometry))
        rows, metrics = drive(batcher, first, rest)
        runs[mixes] = (first + rest, rows, metrics, batcher.engine)
    reqs, rows, metrics, engine = runs[True]
    for mixed, plain in zip(reqs, runs[False][0]):
        assert len(mixed.tokens) == mixed.max_new_tokens
        assert list(mixed.tokens) == list(plain.tokens)
        family.check(mixed)
    assert runs[False][3].mixed_steps == 0
    assert 1 <= runs[False][3].prefill_compiles <= engine.prefill_compiles
    # the chunk program alone (nothing decoded beside the first
    # prompts) and with the lanes riding; the plain step untouched
    assert engine.prefill_compiles == 2 and engine.decode_compiles == 1
    engine.tables.check()
    rode = sum(n for n, _ in rows)      # since the late prompt arrived
    if must_ride is not None:
        assert rode == must_ride
        assert metrics["n_preemptions"] == 0
    late = reqs[-1]
    if scenario == "preempted_mid_prefill":
        # one chunk rode, the next iteration's grow took the seat: the
        # victim had no token to fold, and replayed from position 0
        assert metrics["n_preemptions"] == 1 and rode == 1
        assert len(late.prompt) == late.base_len
    if scenario == "retiring_in_the_step":
        # its last token was a mixed step's, landed (and the request
        # retired) under the next iteration's launch
        at, = [i for i, (_, done) in enumerate(rows) if reqs[0] in done]
        assert rows[at - 1][0] == 1


@pytest.mark.parametrize("last", [False, True],
                         ids=["middle_chunk", "padded_last_chunk"])
def test_lfm2_conv_state_after_a_mixed_step_is_the_two_programs(last):
    """The conv mixers' slot state after ONE mixed step equals, bit for
    bit, that after the chunk program and the decode program (the
    decode first where the chunk is the prompt's last: the two-program
    iteration would decode the new slot in the same step, the mixed
    one does from the next), and so do the tokens."""
    fam = Family("lfm2")
    C = fam.chunk

    def engine():
        eng = fam.engine()
        for seed, n in ((1, 21), (2, 9)):
            eng.admit(prompt(seed, n, fam.vocab))
        for _ in range(3):
            assert not eng.grow_slots()
            eng.step()
        assert eng.admit_begin(prompt(3, C + 5, fam.vocab)) == 2
        assert not eng.grow_slots()
        if last:
            assert eng.prefill_step() is None
        return eng

    mixed, plain = engine(), engine()
    tokens, done = mixed.mixed_step()
    want = plain.step()
    want_done = plain.prefill_step()
    assert (done is not None) == last and done == want_done
    live = [0, 1]
    np.testing.assert_array_equal(tokens[live], want[live])
    np.testing.assert_array_equal(np.asarray(mixed.slot_state["conv"]),
                                  np.asarray(plain.slot_state["conv"]))
    np.testing.assert_array_equal(mixed.tables.lengths,
                                  plain.tables.lengths)


def test_mixed_steps_counter_is_the_chunks_issued_beside_a_live_slot():
    """``serving_mixed_steps_total`` over ``serving_prefill_chunks_
    total`` is the share of chunks that rode: the first counts the
    chunks issued while a slot was live, the second every chunk, both
    as they are issued; the experts' histograms see plain steps only."""
    fam = Family("lfm2")
    reg = obs.get_registry()
    was = reg.enabled
    reg.reset()
    reg.enabled = True
    try:
        engine = fam.engine()
        beside_live = {"prefill_step": 0, "mixed_step": 0}
        for name in beside_live:
            def wrapped(real=getattr(engine, name), name=name):
                beside_live[name] += bool(engine.tables.active.any())
                return real()
            setattr(engine, name, wrapped)

        # the look-ahead loop launches the mixed step through here
        def ahead(flight, mixed, real=engine.step_ahead):
            beside_live["mixed_step"] += bool(
                mixed and engine.tables.active.any())
            return real(flight, mixed)
        engine.step_ahead = ahead
        batcher = ContinuousBatcher(engine)
        reqs = [Request(prompt=prompt(i, n, fam.vocab), max_new_tokens=m)
                for i, (n, m) in enumerate(
                    [(21, 10), (40, 6), (5, 12), (33, 4), (16, 8)])]
        batcher.start_session()
        for req in reqs[:2]:
            batcher.submit(req)
        mid = None
        while batcher.has_work:
            batcher.step()
            if mid is None and engine.mixed_steps:
                # the counters land per chunk, not at the session's end
                mid = reg.snapshot()
                for req in reqs[2:]:
                    batcher.submit(req)
        batcher.finish_session()
        snap = reg.snapshot()
    finally:
        reg.enabled = was
        reg.reset()
    assert mid["serving_mixed_steps_total"] == 1
    assert mid["serving_prefill_chunks_total"] >= 2
    assert beside_live["prefill_step"] == 0
    assert snap["serving_mixed_steps_total"] == engine.mixed_steps \
        == beside_live["mixed_step"] > 3
    assert snap["serving_prefill_chunks_total"] == engine.prefill_chunks \
        > engine.mixed_steps
    # one decode_step span an iteration that decoded, mixed or plain
    steps = sum("decode" in r["kind"] for r in batcher.flight.tail(10_000))
    assert snap["span_seconds{name=decode_step}_count"] == steps
    plain = steps - engine.mixed_steps
    assert snap["serving_moe_experts_hit_count"] == plain > 0
    assert all(len(r.tokens) == r.max_new_tokens for r in reqs)
    assert engine.prefill_compiles == 2 and engine.decode_compiles == 1
