"""Named time (docs/observability.md "Named scopes", "The span tree of
one scheduler iteration"):

- every ``jax.named_scope`` of the ten-name vocabulary reaches the
  ``op_name`` metadata of the compiled train step (with and without
  remat, plain and chunked head; forward, backward and recomputed), of
  the engine's decode and chunk programs and of ``jit_generate``;
- the module names of the engine's decode and chunk programs contain
  ``decode_fn`` / ``chunk_fn`` (what the benchmark's readers match in
  ``jit_<function>(<hash>)``), at ``tp`` 1 and through
  ``shard_engine_fn``;
- with the registry on, one ``ContinuousBatcher.step()`` closes the
  span tree once, each name once, children inside the parent;
- ``serving_queue_wait_seconds`` + ``serving_prefill_seconds`` equal
  ``serving_ttft_seconds`` per request, each observed once a request,
  under preemption and re-admission too;
- ``enable_compile_cache`` ends in the metadata version's subdirectory
  (placement itself: tests/test_chip_smoke.py).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import torchbooster_tpu.observability as obs
from torchbooster_tpu import utils
from torchbooster_tpu.models.gpt import GPT, GPTConfig, jit_generate
from torchbooster_tpu.ops.losses import (cross_entropy,
                                         lm_head_cross_entropy)

SCOPES = ("embed", "attn_qkv", "attn_core", "attn_out", "mlp", "head",
          "loss", "optimizer", "kv_write", "sample")
BLOCK = ("attn_qkv", "attn_core", "attn_out", "mlp")
WRAPPER = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*\((.*)\)$")


def _model(seq_len=32):
    cfg = GPTConfig(vocab=97, n_layers=2, d_model=32, n_heads=4,
                    seq_len=seq_len, n_kv_heads=2)
    return GPT.init(jax.random.PRNGKey(0), cfg), cfg


def _op_names(lowered) -> set[str]:
    return set(re.findall(r'op_name="([^"]+)"',
                          lowered.compile().as_text()))


def _found(op_names: set[str]) -> set[tuple[str, str]]:
    """(scope, phase) pairs met as a path component of any name stack,
    wrappers stripped: phase ``bwd`` under a ``transpose(``, ``remat``
    under ``rematted_computation``, else ``fwd``."""
    out = set()
    for op_name in op_names:
        phase = "fwd"
        for part in op_name.split("/"):
            if part == "rematted_computation":
                phase = "remat"
            while (m := WRAPPER.match(part)):
                if part.startswith("transpose(") and phase == "fwd":
                    phase = "bwd"
                part = m.group(1)
            if part in SCOPES:
                out.add((part, phase))
    return out


# =====================================================================
# the train step
# =====================================================================

@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
@pytest.mark.parametrize("chunked", [False, True],
                         ids=["full_head", "chunked_head"])
def test_train_step_carries_its_scopes(remat, chunked):
    params, cfg = _model()

    def loss_fn(p, batch, rng):
        ids, labels = batch
        out = GPT.apply(p, ids, cfg, remat=remat, return_hidden=chunked)
        if chunked:
            return lm_head_cross_entropy(out, GPT.head_table(p), labels,
                                         chunk_size=16), {}
        return cross_entropy(out, labels), {}

    tx = optax.adamw(1e-3)
    step = utils.make_step(loss_fn, tx, clip=1.0,
                           compute_dtype=jnp.bfloat16, ema_decay=0.99)
    state = utils.TrainState.create(params, tx, jax.random.PRNGKey(1),
                                    ema=True)
    ids = jnp.zeros((2, 16), jnp.int32)
    found = _found(_op_names(step.lower(state, (ids, ids))))
    scopes = {scope for scope, _ in found}
    assert scopes == {"embed", *BLOCK, "head", "loss", "optimizer"}
    for scope in BLOCK:
        # the scope survives scan, jvp and transpose into the backward
        assert (scope, "bwd") in found or (scope, "remat") in found
        assert ((scope, "remat") in found) == remat or scope != "mlp"
    assert ("head", "bwd") in found and ("loss", "fwd") in found
    assert {p for s, p in found if s == "optimizer"} == {"fwd"}


# =====================================================================
# the engine's programs and jit_generate
# =====================================================================

def _tp_mesh(tp):
    from torchbooster_tpu.distributed import make_mesh

    return make_mesh(f"tp:{tp}", n_devices=tp)


def _lowered_engine_programs(tp: int) -> dict:
    """Run one request through an engine and lower each program with
    the operands its first call got (lowered BEFORE the call: the call
    donates the pool)."""
    from torchbooster_tpu.serving import (ContinuousBatcher, PagedEngine,
                                          Request)

    params, cfg = _model()
    kw = {"tp": tp, "mesh": _tp_mesh(tp)} if tp > 1 else {}
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, compute_dtype=jnp.float32, **kw)
    lowered = {}

    def lowering(name, jitted):
        def call(*args):
            if name not in lowered:
                lowered[name] = jitted.lower(*args)
            return jitted(*args)
        call._cache_size = jitted._cache_size    # the compile counters
        return call

    engine._chunk_jit = lowering("chunk", engine._chunk_jit)
    engine._decode_jit = lowering("decode", engine._decode_jit)
    ContinuousBatcher(engine).run(
        [Request(prompt=np.arange(1, 6), max_new_tokens=3)])
    return lowered


@pytest.mark.parametrize("tp", [1, 2], ids=["tp1", "shard_engine_fn"])
def test_engine_programs_keep_their_names_and_scopes(tp):
    lowered = _lowered_engine_programs(tp)
    serving = {"embed", *BLOCK, "head", "kv_write", "sample"}
    for name, needle in (("decode", "decode_fn"), ("chunk", "chunk_fn")):
        text = lowered[name].as_text()
        module = re.search(r"module @(\S+)", text).group(1)
        # decode_roofline.lat / prefill_chunk_ms.lat and the scope
        # readers find the program by this substring of its name
        assert needle in module, module
        op_names = _op_names(lowered[name])
        assert {s for s, _ in _found(op_names)} == serving
        # the K/V write is named INSIDE the attention's scope
        assert any("attn_core/kv_write/" in n for n in op_names)


def test_jit_generate_carries_its_scopes():
    params, cfg = _model()
    fn = jit_generate(cfg, n_new=4, temperature=0.8, top_k=5,
                      compute_dtype=jnp.float32)
    ids = jnp.ones((1, 6), jnp.int32)
    found = _found(_op_names(fn.lower(params, ids, jax.random.PRNGKey(0))))
    assert {s for s, _ in found} == \
        {"embed", *BLOCK, "head", "kv_write", "sample"}


def test_the_vocabulary_is_the_documented_one():
    """The ten names, letter for letter, in the docs' table (the
    benchmark's readers and ``PROGRAM_METADATA_VERSION`` hang on it)."""
    from pathlib import Path

    doc = (Path(__file__).resolve().parent.parent / "docs"
           / "observability.md").read_text()
    table = doc.split("## Named scopes on the device timeline")[1] \
        .split("\n## ")[0]
    assert tuple(re.findall(r"^\| `(\w+)` \|", table, re.M)) == SCOPES
    assert "m<PROGRAM_METADATA_VERSION>" in table
    assert isinstance(utils.PROGRAM_METADATA_VERSION, int)


# =====================================================================
# the span tree of one iteration
# =====================================================================

TREE = ("sched_admit", "prefill_args", "serving_prefill_chunk",
        "prefill_finish", "sched_grow", "decode_args", "decode_step",
        "decode_advance", "sched_deliver")
# the chunk rides the decode step as one program: grow first, no
# chunk-program span, the token read back inside decode_step
MIXED_TREE = ("sched_admit", "sched_grow", "prefill_args", "decode_args",
              "decode_step", "prefill_finish", "decode_advance",
              "sched_deliver")
# one step in flight: the iteration that LAUNCHES the mixed step lands
# the plain step before it (decode_step: the dispatch and the wait),
# and the prompt's first token is the next iteration's to book
AHEAD_TREE = ("sched_admit", "sched_grow", "prefill_args", "decode_args",
              "decode_step", "decode_advance", "sched_deliver")
AHEAD_NEXT = ("sched_admit", "sched_grow", "decode_args", "decode_step",
              "prefill_finish", "decode_advance", "sched_deliver")


class _Tick:
    """Deterministic self-advancing clock (tests/test_tracing.py)."""

    def __init__(self, dt=0.0005):
        self.t, self.dt = 0.0, dt

    def __call__(self):
        self.t += self.dt
        return self.t


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


def _engine(params, cfg, **kw):
    from torchbooster_tpu.serving import PagedEngine

    kw.setdefault("page_size", 4)
    kw.setdefault("n_pages", 16)
    kw.setdefault("max_slots", 2)
    return PagedEngine(params, cfg, compute_dtype=jnp.float32, **kw)


@pytest.mark.parametrize("mixes,ahead", [
    (False, False), (True, False), (True, True)],
    ids=["two_programs", "mixed", "lookahead"])
def test_one_step_closes_the_span_tree_once(registry, mixes, ahead):
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _model()
    b = ContinuousBatcher(_engine(params, cfg), clock=_Tick())
    assert b.engine.mixes and b.engine.looks_ahead
    # off: the synchronous loop the other modes keep
    b.engine.mixes, b.engine.looks_ahead = mixes, ahead
    tree = AHEAD_TREE if ahead else MIXED_TREE if mixes else TREE
    events: list[dict] = []
    b.start_session()
    try:
        b.submit(Request(prompt=np.arange(1, 6), max_new_tokens=8))
        while not b._s.live:            # until the first one decodes
            b.step()
        b.submit(Request(prompt=np.arange(2, 8), max_new_tokens=8))
        chunks0, unsubscribe = b.engine.prefill_chunks, \
            obs.span_events_subscribe(events.append)
        try:
            # ONE iteration that seats, prefills the prompt's one and
            # LAST chunk (so prefill_finish closes too) AND decodes
            b.step()
        finally:
            unsubscribe()
        assert b.engine.prefill_chunks == chunks0 + 1
        if ahead:
            # the prompt's token lands an iteration later, under the
            # next launch: the same tree but for who finishes
            assert len(b._s.live) == 1 and b._s.flight is not None
            after: list[dict] = []
            unsubscribe = obs.span_events_subscribe(after.append)
            try:
                b.step()
            finally:
                unsubscribe()
            assert [e["name"] for e in sorted(
                after, key=lambda e: e["ts"])
                if e["name"] != "sched_step"] == list(AHEAD_NEXT)
        assert len(b._s.live) == 2
        while b.has_work:               # nothing in flight at the end
            b.step()
    finally:
        b.finish_session()
    names = [e["name"] for e in events]
    assert sorted(names) == sorted((*tree, "sched_step"))    # each once
    by_name = {e["name"]: e for e in events}
    whole = by_name["sched_step"]
    assert whole["depth"] == 0 and whole["path"] == "sched_step"
    for name in tree:
        child = by_name[name]
        assert child["path"] == f"sched_step/{name}", child
        assert whole["ts"] <= child["ts"]
    # in the order the iteration runs them, none overlapping
    assert [e["name"] for e in sorted(events, key=lambda e: e["ts"])
            if e["name"] != "sched_step"] == list(tree)
    assert whole["dur_s"] >= sum(by_name[n]["dur_s"] for n in tree) - 1e-5
    snap = registry.snapshot()
    # the accepted readers count these two: one per program call (a
    # mixed step is a decode_step, and no chunk program's call)
    assert b.engine.mixed_steps == (1 if mixes else 0)
    assert snap["span_seconds{name=serving_prefill_chunk}_count"] == \
        b.engine.prefill_chunks - b.engine.mixed_steps
    steps = sum(1 for r in b.flight.tail() if "decode" in r["kind"])
    assert snap["span_seconds{name=decode_step}_count"] == steps > 0
    assert snap["span_seconds{name=sched_step}_count"] == \
        b.flight.n_recorded


def test_frontend_fanout_span_closes_per_pumped_step(registry):
    import asyncio

    from tests.test_frontend import _unary
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import ServingFrontend

    params, cfg = _model()
    fe = ServingFrontend(ContinuousBatcher(_engine(params, cfg)), port=0)

    async def run():
        await fe.start()
        status, _, body = await _unary(
            fe.port, "/v1/completions",
            {"prompt": [1, 2, 3, 4], "max_tokens": 4})
        await fe.stop()
        return status, body

    status, body = asyncio.run(run())
    assert status == 200 and body["usage"]["completion_tokens"] == 4
    snap = registry.snapshot()
    assert snap["span_seconds{name=frontend_fanout}_count"] == \
        snap["span_seconds{name=sched_step}_count"] > 0


# =====================================================================
# TTFT = queue wait + prefill, per request
# =====================================================================

@pytest.mark.parametrize("n_pages", [16, 5], ids=["roomy", "preempting"])
def test_queue_wait_plus_prefill_is_ttft_per_request(registry, n_pages):
    from torchbooster_tpu.observability.tracing import RequestTracer
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _model()
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (5,), 0, cfg.vocab))
    tracer = RequestTracer(enabled=True)
    b = ContinuousBatcher(_engine(params, cfg, n_pages=n_pages),
                          clock=_Tick(), tracer=tracer)
    reqs = [Request(prompt=ids, max_new_tokens=8) for _ in range(3)]
    out = b.run(reqs)
    assert (out["n_preemptions"] > 0) == (n_pages == 5)
    assert out["n_admissions"] == 3 + out["n_preemptions"]
    series = {k: registry.histogram(f"serving_{k}_seconds")
              for k in ("ttft", "queue_wait", "prefill")}
    snap = registry.snapshot()
    for k in series:        # once a request, re-admitted or not
        assert snap[f"serving_{k}_seconds_count"] == len(reqs)
    for r in reqs:
        wait, fill = r.admitted_at - r.arrival, \
            r.first_token_at - r.admitted_at
        assert wait >= 0 and fill > 0
        assert wait + fill == pytest.approx(r.first_token_at - r.arrival,
                                            abs=1e-12)
    assert snap["serving_queue_wait_seconds_sum"] \
        + snap["serving_prefill_seconds_sum"] == pytest.approx(
            snap["serving_ttft_seconds_sum"], abs=1e-9)
    assert snap["serving_queue_wait_seconds_sum"] == pytest.approx(
        sum(r.admitted_at - r.arrival for r in reqs), abs=1e-9)
    # the tracer's seated event says the same, and a re-admission's
    # says the FIRST seat's wait again
    for r in reqs:
        seated = [e for e in tracer.events(r.request_id)
                  if e["kind"] == "seated"]
        assert {e["queue_wait_s"] for e in seated} == \
            {round(r.admitted_at - r.arrival, 6)}
        assert [e["readmission"] for e in seated] == \
            [False] + [True] * (len(seated) - 1)


def test_tokens_carry_the_step_that_made_them():
    from torchbooster_tpu.observability.tracing import RequestTracer
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _model()
    tracer = RequestTracer(enabled=True)
    b = ContinuousBatcher(_engine(params, cfg), clock=_Tick(),
                          tracer=tracer)
    reqs = [Request(prompt=np.arange(1, 6), max_new_tokens=5),
            Request(prompt=np.arange(2, 7), max_new_tokens=3)]
    b.run(reqs)
    steps = [e["step"] for e in tracer.events(None)
             if e["kind"] == "decode_step"]
    assert steps == sorted(set(steps)) and steps[0] >= 1
    for r in reqs:
        toks = [e["step"] for e in tracer.events(r.request_id)
                if e["kind"] == "tokens"]
        # one decode token an iteration, each in a step that decoded
        assert toks == sorted(set(toks)) and set(toks) <= set(steps)
        assert len(toks) == len(r.tokens) - 1    # the first is prefill's
    # every decode step delivered to the slots it reports
    by_step = {}
    for r in reqs:
        for e in tracer.events(r.request_id):
            if e["kind"] == "tokens":
                by_step[e["step"]] = by_step.get(e["step"], 0) + 1
    slots = {e["step"]: e["slots"] for e in tracer.events(None)
             if e["kind"] == "decode_step"}
    assert by_step == slots
