"""Serving front door (torchbooster_tpu/serving/frontend) on CPU:

- a REAL asyncio HTTP client streams a greedy completion over SSE
  from the running server and the streamed tokens are token-exact vs
  dense ``jit_generate`` (the PR acceptance), with exactly one decode
  compile;
- externally-driven cancellation — mid-prefill (the PR 4
  pending-slot abort from OUTSIDE run()), mid-decode, and
  mid-spec-burst — reclaims every pool page, keeps
  ``kv_pages.check()`` green, and never recompiles the decode/verify
  executables;
- ``Request`` keeps its pre-frontend construction surface (the
  regression satellite) and validates the new SLO fields loudly;
- FCFS remains the default policy with its metric keys stable
  (now including the SLO keys on every return path); the SLO policy
  admits earliest-slack-first, sheds unmeetable deadlines with HTTP
  429 + Retry-After, and picks preemption victims by re-admission
  cost;
- the ``serving.frontend`` YAML block builds the policy + server.

The full-server soak (concurrent mixed-priority clients +
cancellations + shedding) is ``slow``-marked; a short localhost smoke
rides tier-1.
"""
import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.models.gpt import GPT, GPTConfig


def _decisive_model(n_kv_heads=2, seq_len=32):
    """Tiny GPT with a DECISIVE head (scaled-up tied embeddings widen
    argmax margins so bf16 rounding cannot flip greedy picks — the
    test_serving trick)."""
    cfg = GPTConfig(vocab=97, n_layers=2, d_model=32, n_heads=4,
                    seq_len=seq_len, n_kv_heads=n_kv_heads)
    params = GPT.init(jax.random.PRNGKey(0), cfg)
    params = {**params, "wte": {"table": params["wte"]["table"] * 4.0}}
    return params, cfg


def _engine(params, cfg, **kw):
    from torchbooster_tpu.serving import PagedEngine

    kw.setdefault("page_size", 4)
    kw.setdefault("n_pages", 16)
    kw.setdefault("max_slots", 2)
    kw.setdefault("compute_dtype", jnp.float32)
    return PagedEngine(params, cfg, **kw)


# ---- HTTP plumbing helpers ------------------------------------------

async def _post(port, path, payload, headers=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    body = json.dumps(payload).encode()
    extra = "".join(f"{k}: {v}\r\n"
                    for k, v in (headers or {}).items())
    writer.write(
        f"POST {path} HTTP/1.1\r\nHost: t\r\n{extra}"
        f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    await writer.drain()
    return reader, writer


async def _read_head(reader):
    head = await reader.readuntil(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    headers = {}
    for line in head.decode().split("\r\n")[1:]:
        name, sep, value = line.partition(":")
        if sep:
            headers[name.strip().lower()] = value.strip()
    return status, headers


async def _stream_completion(port, payload):
    """POST /v1/completions with stream=true; returns (status,
    headers, events) where events are the decoded SSE payloads."""
    reader, writer = await _post(port, "/v1/completions",
                                 {**payload, "stream": True})
    status, headers = await _read_head(reader)
    events = []
    if status == 200:
        while True:
            line = await reader.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            if line == b"data: [DONE]":
                break
            events.append(json.loads(line[6:]))
    else:
        events.append(json.loads(await reader.read()))
    writer.close()
    return status, headers, events


async def _unary(port, path, payload, headers=None):
    reader, writer = await _post(port, path, payload, headers)
    status, hdrs = await _read_head(reader)
    body = json.loads(await reader.read())
    writer.close()
    return status, hdrs, body


async def _get(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    status, headers = await _read_head(reader)
    body = await reader.read()
    writer.close()
    return status, body


# ---- the acceptance smoke: SSE token-exact vs jit_generate ----------

def test_http_sse_stream_token_exact_vs_jit_generate():
    """A real asyncio HTTP client streams a greedy completion over
    SSE from the running server; the streamed token sequence is
    TOKEN-EXACT vs dense ``jit_generate`` for the same prompt, the
    unary (non-streaming) response agrees, and the engine compiled
    its decode step exactly once. /healthz and /metrics answer."""
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import ServingFrontend

    params, cfg = _decisive_model()
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (5,), 0, cfg.vocab))
    n_new = 8
    want = [int(t) for t in np.asarray(GPT.generate(
        params, jnp.asarray(prompt)[None], cfg, n_new=n_new,
        temperature=0.0, compute_dtype=jnp.float32))[0, 5:]]
    engine = _engine(params, cfg)
    fe = ServingFrontend(ContinuousBatcher(engine))

    async def scenario():
        await fe.start()
        payload = {"prompt": [int(t) for t in prompt],
                   "max_tokens": n_new}
        status, headers, events = await _stream_completion(
            fe.port, payload)
        assert status == 200
        streamed = [t for e in events
                    for t in e["choices"][0]["token_ids"]]
        # one SSE event per token on the non-speculative engine
        assert len(events) == n_new
        assert events[-1]["choices"][0]["finish_reason"] == "length"
        assert events[0]["id"].startswith("cmpl-")
        status, _, body = await _unary(fe.port, "/v1/completions",
                                       payload)
        assert status == 200
        assert body["usage"] == {"prompt_tokens": 5,
                                 "completion_tokens": n_new,
                                 "total_tokens": 5 + n_new}
        hstatus, hbody = await _get(fe.port, "/healthz")
        mstatus, mbody = await _get(fe.port, "/metrics")
        metrics = await fe.stop()
        return (streamed, body["choices"][0]["token_ids"],
                hstatus, json.loads(hbody), mstatus,
                mbody.decode(), metrics)

    streamed, unary_toks, hstatus, health, mstatus, prom, metrics = \
        asyncio.run(scenario())
    assert streamed == want
    assert unary_toks == want
    assert hstatus == 200 and health["status"] == "ok"
    assert mstatus == 200 and "serving_ttft_seconds" in prom
    assert engine.decode_compiles == 1
    assert engine.prefill_compiles == 1
    assert metrics["n_requests"] == 2
    assert metrics["n_shed"] == 0 and metrics["n_cancelled"] == 0
    engine.tables.check()
    assert engine.tables.n_free_pages == engine.n_pages - 1


def test_http_chat_completions_and_errors():
    """The chat surface shares the pipeline (messages concatenate
    through the codec); malformed requests get structured 4xx."""
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import ServingFrontend

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    fe = ServingFrontend(ContinuousBatcher(engine))

    async def scenario():
        await fe.start()
        status, _, body = await _unary(
            fe.port, "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "1 2 3 4"}],
             "max_tokens": 3})
        assert status == 200
        assert body["object"] == "chat.completion"
        assert body["choices"][0]["message"]["role"] == "assistant"
        # bad JSON body -> 400 with the OpenAI error envelope
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", fe.port)
        writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                     b"Content-Length: 3\r\n\r\nnop")
        await writer.drain()
        s400, _ = await _read_head(reader)
        err = json.loads(await reader.read())
        writer.close()
        # unknown route -> 404; text prompt that isn't ids -> 400
        s404, _ = await _get(fe.port, "/nope")
        sbad, _, ebad = await _unary(
            fe.port, "/v1/completions",
            {"prompt": "not token ids", "max_tokens": 2})
        await fe.stop()
        return status, s400, err, s404, sbad, ebad

    status, s400, err, s404, sbad, ebad = asyncio.run(scenario())
    assert s400 == 400 and "error" in err
    assert s404 == 404
    assert sbad == 400 and "codec" in ebad["error"]["message"]


# ---- externally-driven cancellation ---------------------------------

def test_cancel_mid_prefill_mid_decode_reclaims_pages():
    """Cancellation from OUTSIDE run(): mid-prefill (the PR 4
    admit_begin/pending-slot abort path) and mid-decode. Pool pages
    are reclaimed, check() holds, and the decode executable never
    recompiles across the cancel churn."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    engine = _engine(params, cfg, prefill_chunk_pages=1)
    b = ContinuousBatcher(engine)
    rs = np.random.RandomState(0)
    b.start_session()
    # ---- mid-prefill: a 14-token prompt needs 4 one-page chunks ----
    req = Request(prompt=rs.randint(0, 97, 14), max_new_tokens=4)
    b.submit(req)
    b.step()
    assert engine.has_pending          # seated, prefill in flight
    b.cancel(req)
    events = b.step()
    assert req.cancelled and req.finish_reason == "cancelled"
    assert any(r is req for r, toks in events)
    assert not engine.has_pending
    engine.tables.check()
    assert engine.tables.n_free_pages == engine.n_pages - 1
    # ---- mid-decode: let it emit a couple of tokens first ----
    req2 = Request(prompt=rs.randint(0, 97, 5), max_new_tokens=20)
    b.submit(req2)
    while len(req2.tokens) < 2:
        b.step()
    b.cancel(req2)
    b.step()
    assert req2.cancelled and len(req2.tokens) >= 2
    engine.tables.check()
    assert engine.tables.n_free_pages == engine.n_pages - 1
    # ---- a queued (never seated) cancel is a pure queue removal ----
    req3 = Request(prompt=rs.randint(0, 97, 5), max_new_tokens=4,
                   arrival=1e9)
    b.submit(req3, arrival=1e9)
    b.cancel(req3)
    b.step()
    assert req3.cancelled and req3.tokens == []
    m = b.finish_session()
    assert m["n_cancelled"] == 3
    assert engine.decode_compiles == 1          # zero RE-compiles
    assert engine.prefill_compiles == 1


def test_cancel_mid_spec_burst_drops_tail():
    """Cancelling a speculatively-decoding request: the slot retires
    through the same abort path, the rest of its accepted burst is
    dropped (never delivered), pages reclaim, and the verify
    executable never recompiles."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    engine = _engine(params, cfg, n_pages=24, speculative=True,
                     draft_len=3)
    b = ContinuousBatcher(engine)
    rs = np.random.RandomState(1)
    pattern = rs.randint(0, 97, 4)
    prompt = np.tile(pattern, 3)       # repetitive: drafting fires
    b.start_session()
    req = Request(prompt=prompt, max_new_tokens=16)
    b.submit(req)
    while not req.tokens:
        b.step()
    n_before = len(req.tokens)
    b.cancel(req)
    b.step()
    assert req.cancelled
    # nothing delivered after the cancel landed
    assert len(req.tokens) == n_before or req.finished_at is not None
    engine.tables.check()
    assert engine.tables.n_free_pages == engine.n_pages - 1
    m = b.finish_session()
    assert m["n_cancelled"] == 1
    assert engine.verify_compiles == 1
    assert engine.decode_compiles == 0  # spec engine never decodes


# ---- Request surface regression -------------------------------------

def test_request_pre_frontend_construction_unchanged():
    """The pre-PR-7 construction surface works untouched, and the new
    SLO fields validate loudly."""
    from torchbooster_tpu.serving import Request

    # the exact pre-frontend shapes (positional prompt, old kwargs)
    r = Request(prompt=np.arange(1, 5), max_new_tokens=3,
                eos_id=7, arrival=0.25)
    assert r.base_len == 4 and r.tokens == []
    assert r.priority == "" and r.deadline_ms is None
    assert r.arrival_time is None
    assert not r.shed and not r.cancelled
    r2 = Request(np.ones(2, np.int32))
    assert r2.max_new_tokens == 32
    # new fields validate in __post_init__
    with pytest.raises(ValueError, match="deadline_ms"):
        Request(prompt=np.arange(3), deadline_ms=-5)
    with pytest.raises(ValueError, match="deadline_ms"):
        Request(prompt=np.arange(3), deadline_ms=0)
    with pytest.raises(ValueError, match="arrival_time"):
        Request(prompt=np.arange(3), arrival_time=-1.0)
    with pytest.raises(TypeError, match="priority"):
        Request(prompt=np.arange(3), priority=2)
    # identity semantics: scheduling queues/cancels by object
    assert Request(np.arange(3)) != Request(np.arange(3))


# ---- scheduler policies ---------------------------------------------

def test_parse_classes_and_policy_validation():
    from torchbooster_tpu.serving.frontend import (
        SLOPolicy, parse_classes)

    classes = parse_classes("interactive:250:60,batch:5000:0")
    assert classes["interactive"].ttft_ms == 250
    assert classes["interactive"].rank == 0
    assert classes["batch"].rank == 1
    assert classes["batch"].tpot_ms == 0
    with pytest.raises(ValueError, match="name:ttft_ms:tpot_ms"):
        parse_classes("oops:1")
    with pytest.raises(ValueError, match="duplicate"):
        parse_classes("a:1:1,a:2:2")
    with pytest.raises(ValueError, match="numbers"):
        parse_classes("a:fast:1")
    with pytest.raises(ValueError, match="at least one"):
        SLOPolicy({})
    with pytest.raises(ValueError, match="default class"):
        SLOPolicy(classes, default="nope")
    with pytest.raises(ValueError, match="shed_grace"):
        SLOPolicy(classes, shed_grace=0)


def test_unknown_priority_class_raises_at_submit():
    """ISSUE satellite: unknown-class values raise loudly — at
    submit/run time, the one place the class table is known."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request
    from torchbooster_tpu.serving.frontend import (
        SLOPolicy, parse_classes)

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    pol = SLOPolicy(parse_classes("rt:200:50,batch:0:0"))
    b = ContinuousBatcher(engine, policy=pol)
    bad = Request(prompt=np.arange(1, 4), max_new_tokens=2,
                  priority="vip")
    with pytest.raises(ValueError, match="unknown priority class"):
        b.run([bad])
    # the FCFS path IGNORES the field entirely (satellite contract)
    fcfs = ContinuousBatcher(engine)
    fcfs.policy.validate(bad)          # no raise


def test_slo_admission_earliest_slack_first():
    """Deadline-driven admission: an interactive request overtakes
    earlier-arrived no-deadline batch requests in the queue."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request
    from torchbooster_tpu.serving.frontend import (
        FCFSPolicy, SLOPolicy, parse_classes)

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    pol = SLOPolicy(parse_classes("rt:200:0,batch:0:0"),
                    default="batch")
    b = ContinuousBatcher(engine, policy=pol)
    b1 = Request(prompt=np.arange(1, 4), arrival=0.0)
    b2 = Request(prompt=np.arange(2, 5), arrival=0.01)
    rt = Request(prompt=np.arange(3, 6), arrival=0.02, priority="rt")
    queue = [b1, b2, rt]
    assert pol.next_admission(queue, now=1.0, batcher=b) is rt
    # FCFS on the same queue keeps strict arrival order
    assert FCFSPolicy().next_admission(queue, 1.0, b) is b1
    # rank orders the no-deadline tail deterministically
    assert pol.next_admission([b1, b2], 1.0, b) is b1


def test_slo_victim_by_readmission_cost():
    """Preemption victims: a DECODING slot whose prompt pages are
    registered in the prefix cache re-admits nearly for free (retire
    caches them; re-seat maps them back), while a mid-prefill
    long-prompt slot — nothing registered yet — would redo its whole
    prefill. The SLO policy evicts the cheap one, even though FCFS
    would have picked the younger (expensive) victim."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request
    from torchbooster_tpu.serving.frontend import (
        FCFSPolicy, SLOPolicy, parse_classes)

    params, cfg = _decisive_model()
    engine = _engine(params, cfg, prefix_cache=True,
                     prefill_chunk_pages=1)
    pol = SLOPolicy(parse_classes("std:0:0"))
    b = ContinuousBatcher(engine, policy=pol)
    rs = np.random.RandomState(0)
    shared = rs.randint(0, 97, 8)      # 2 full pages
    long_cold = rs.randint(0, 97, 14)  # 4 chunks of prefill
    b.start_session()
    hot_req = Request(prompt=shared, max_new_tokens=8)
    b.submit(hot_req)
    while not hot_req.tokens:          # decode-live, pages registered
        b.step()
    cold_req = Request(prompt=long_cold, max_new_tokens=8)
    b.submit(cold_req)
    b.step()                           # seats + first chunk only
    assert cold_req in list(b._s.filling.values())  # mid-prefill
    seated = {**b._s.filling, **b._s.live}
    assert len(seated) == 2
    hot_slot = next(s for s, r in seated.items() if r is hot_req)
    # the registered 2-page prompt makes the decoding slot the cheap
    # re-admission; the mid-prefill slot re-prefills everything
    assert b.readmission_cost(hot_req) < b.readmission_cost(cold_req)
    assert pol.select_victim(b._s.admit_order, seated, b) == hot_slot
    # FCFS would have evicted the YOUNGEST — the expensive one
    assert FCFSPolicy().select_victim(
        b._s.admit_order, seated, b) != hot_slot
    b.finish_session()


def test_slo_shed_unmeetable_deadline_and_metrics():
    """A queued request whose TTFT deadline is already unmeetable is
    shed (not served late): n_shed counts it, the request is marked,
    and the per-class serving_slo_* shed/deadline series land in the
    Prometheus export (the acceptance's dashboard contract)."""
    import torchbooster_tpu.observability as obs
    from torchbooster_tpu.observability.export import prometheus_text
    from torchbooster_tpu.serving import ContinuousBatcher, Request
    from torchbooster_tpu.serving.frontend import (
        SLOPolicy, parse_classes)

    registry = obs.get_registry()
    was = registry.enabled
    registry.reset()
    registry.enabled = True
    try:
        params, cfg = _decisive_model()
        engine = _engine(params, cfg)
        pol = SLOPolicy(parse_classes("rt:200:50,batch:0:0"),
                        default="batch")
        b = ContinuousBatcher(engine, policy=pol)
        ok = Request(prompt=np.arange(1, 5), max_new_tokens=2)
        # deadline_ms overrides the class target; by the time the
        # clock has advanced at all this is unmeetable -> shed
        doomed = Request(prompt=np.arange(2, 6), max_new_tokens=2,
                         priority="rt", deadline_ms=1e-6)
        m = b.run([ok, doomed])
        prom = prometheus_text(registry)
    finally:
        registry.enabled = was
        registry.reset()
    assert doomed.shed and doomed.finish_reason == "shed"
    assert not ok.shed and len(ok.tokens) == 2
    assert m["n_shed"] == 1
    assert m["classes"]["rt"]["n_shed"] == 1
    assert m["classes"]["batch"]["n_completed"] == 1
    assert 'serving_slo_shed_total{cls="rt"} 1' in prom
    assert 'serving_slo_ttft_seconds_count{cls="batch"} 1' in prom
    assert 'serving_slo_ttft_hit_rate{cls="batch"}' in prom
    engine.tables.check()


def test_fcfs_metrics_stable_keys_include_slo_fields():
    """The stable-key contract extends to the new scheduler keys:
    n_shed / n_cancelled / deadline_hit_rate / classes exist on EVERY
    return path (empty trace included), and FCFS reports them inert."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    b = ContinuousBatcher(engine)
    empty = b.run([])
    full = b.run([Request(prompt=np.arange(1, 5), max_new_tokens=3)])
    assert set(empty) == set(full)
    for m in (empty, full):
        assert m["n_shed"] == 0
        assert m["n_cancelled"] == 0
        assert m["deadline_hit_rate"] == 1.0
        assert m["classes"] == {}


# ---- HTTP backpressure ----------------------------------------------

def test_http_shed_gets_429_with_retry_after():
    """An HTTP client whose deadline the scheduler cannot meet gets
    429 + Retry-After (the shed path), while a deadline-free request
    on the same server is served."""
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import (
        ServingFrontend, SLOPolicy, parse_classes)

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    pol = SLOPolicy(parse_classes("rt:200:0,batch:0:0"),
                    default="batch")
    fe = ServingFrontend(ContinuousBatcher(engine, policy=pol))

    async def scenario():
        await fe.start()
        ok_status, _, ok_body = await _unary(
            fe.port, "/v1/completions",
            {"prompt": [1, 2, 3], "max_tokens": 2})
        status, headers, events = await _stream_completion(
            fe.port, {"prompt": [4, 5, 6], "max_tokens": 2,
                      "priority": "rt", "deadline_ms": 1e-6})
        m = await fe.stop()
        return ok_status, ok_body, status, headers, events, m

    ok_status, ok_body, status, headers, events, m = \
        asyncio.run(scenario())
    assert ok_status == 200
    assert len(ok_body["choices"][0]["token_ids"]) == 2
    assert status == 429
    assert "retry-after" in headers
    assert "shed" in events[0]["error"]["message"]
    assert m["n_shed"] == 1
    engine.tables.check()


@pytest.mark.slow
def test_http_soak_mixed_priority_cancel_shed_zero_recompiles():
    """The full-server soak: concurrent mixed-priority streaming
    clients, a mid-stream client disconnect, and deadline shedding,
    all against one live server — token streams stay exact per
    client, pages reclaim, and the decode executable compiles exactly
    once across everything."""
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import (
        ServingFrontend, SLOPolicy, parse_classes)

    params, cfg = _decisive_model(seq_len=64)
    engine = _engine(params, cfg, n_pages=32, max_slots=4)
    pol = SLOPolicy(parse_classes("rt:60000:0,batch:0:0"),
                    default="batch")
    fe = ServingFrontend(ContinuousBatcher(engine, policy=pol))
    rs = np.random.RandomState(7)

    async def one(i):
        cls = "rt" if i % 3 == 0 else "batch"
        prompt = [int(t) for t in rs.randint(0, 97, 4 + (i % 5))]
        status, _, events = await _stream_completion(
            fe.port, {"prompt": prompt, "max_tokens": 4 + (i % 4),
                      "priority": cls})
        toks = [t for e in events
                for t in e["choices"][0].get("token_ids", [])]
        return status, len(toks)

    async def cancelled_client():
        reader, writer = await _post(
            fe.port, "/v1/completions",
            {"prompt": [9, 9, 9, 9], "max_tokens": 40,
             "stream": True})
        await _read_head(reader)
        await reader.readline()        # one event, then vanish
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError):
            pass

    async def doomed_client():
        status, headers, _ = await _stream_completion(
            fe.port, {"prompt": [8, 8, 8], "max_tokens": 2,
                      "priority": "rt", "deadline_ms": 1e-6})
        return status

    async def scenario():
        await fe.start()
        results = await asyncio.gather(
            *(one(i) for i in range(10)), cancelled_client(),
            doomed_client())
        # let the cancel drain before shutdown
        await asyncio.sleep(0.2)
        m = await fe.stop()
        return results, m

    results, m = asyncio.run(scenario())
    statuses = [r[0] for r in results[:10]]
    assert all(s == 200 for s in statuses)
    assert results[-1] == 429                  # the doomed deadline
    assert m["n_shed"] >= 1
    assert m["n_cancelled"] >= 1
    assert engine.decode_compiles == 1         # THE contract
    # the chunk alone and the chunk with the lanes riding: which of
    # the two a soak's timing meets is not the contract (at most both)
    assert 1 <= engine.prefill_compiles <= 2
    engine.tables.check()
    assert engine.tables.n_free_pages == engine.n_pages - 1


# ---- YAML / config surface ------------------------------------------

def test_frontend_yaml_block_builds_policy_and_server(tmp_path):
    from torchbooster_tpu.config import FrontendConfig, ServingConfig
    from torchbooster_tpu.serving.frontend import (
        FCFSPolicy, ServingFrontend, SLOPolicy)

    yml = tmp_path / "serve.yml"
    yml.write_text(
        "page_size: 4\nn_pages: 16\nmax_slots: 2\n"
        "frontend:\n"
        "  policy: slo\n"
        "  classes: \"interactive:250:60,batch:5000:0\"\n"
        "  default_class: batch\n"
        "  port: 0\n")
    sc = ServingConfig.load(yml)
    assert isinstance(sc.frontend, FrontendConfig)
    pol = sc.frontend.make_policy()
    assert isinstance(pol, SLOPolicy)
    assert pol.default == "batch"
    assert pol.classes["interactive"].tpot_ms == 60
    params, cfg = _decisive_model()
    batcher = sc.make(params, cfg, compute_dtype=jnp.float32)
    assert isinstance(batcher.policy, SLOPolicy)
    fe = sc.frontend.make(batcher)
    assert isinstance(fe, ServingFrontend)
    # default block: FCFS, bit-for-bit the pre-frontend batcher
    assert isinstance(FrontendConfig().make_policy(), FCFSPolicy)
    with pytest.raises(ValueError, match="fcfs.*or.*slo"):
        FrontendConfig(policy="lifo").make_policy()


# ---- multi-LoRA model field (PR 19) ---------------------------------

def test_model_field_selects_adapter_and_rejects_unknown():
    """The OpenAI ``model`` field doubles as the adapter selector:
    absent / the served name -> base (response echoes the base
    name), a registered adapter name -> its lane (response echoes
    the adapter, stream visibly steered), an unknown name -> 400 at
    submit, any adapter on a lora-less engine -> 400, and a
    non-string model -> 400 — all before a page moves. The adapter
    billing counters land in /metrics."""
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.adapters import random_adapter
    from torchbooster_tpu.serving.frontend import ServingFrontend

    params, cfg = _decisive_model()
    engine = _engine(params, cfg, max_slots=4, n_pages=32,
                     lora_rank=4, lora_max_live=2)
    engine.adapters.register("a0", random_adapter(1, cfg, 4, std=1.0))
    fe = ServingFrontend(ContinuousBatcher(engine))
    prompt = [int(t) for t in np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (5,), 0, cfg.vocab))]

    async def scenario():
        await fe.start()
        p = {"prompt": prompt, "max_tokens": 6}
        s_base, _, base = await _unary(fe.port, "/v1/completions", p)
        s_named, _, named = await _unary(
            fe.port, "/v1/completions",
            {**p, "model": "torchbooster-tpu"})
        s_lora, _, lora = await _unary(
            fe.port, "/v1/completions", {**p, "model": "a0"})
        s_unk, _, unk = await _unary(
            fe.port, "/v1/completions", {**p, "model": "ghost"})
        s_bad, _, bad = await _unary(
            fe.port, "/v1/completions", {**p, "model": 7})
        _, prom = await _get(fe.port, "/metrics")
        metrics = await fe.stop()
        return (s_base, base, s_named, named, s_lora, lora,
                s_unk, unk, s_bad, bad, prom.decode(), metrics)

    (s_base, base, s_named, named, s_lora, lora, s_unk, unk,
     s_bad, bad, prom, metrics) = asyncio.run(scenario())
    assert s_base == s_named == s_lora == 200
    assert base["model"] == named["model"] == "torchbooster-tpu"
    assert lora["model"] == "a0"
    toks = lambda b: b["choices"][0]["token_ids"]
    assert toks(base) == toks(named)        # served-name == base
    assert toks(lora) != toks(base)         # the adapter steers
    assert s_unk == 400 and "unknown adapter" in \
        unk["error"]["message"]
    assert s_bad == 400 and "must be a string" in \
        bad["error"]["message"]
    assert "serving_adapter_tokens_total" in prom
    assert metrics["adapters"]["a0"] == {"n_requests": 1,
                                         "new_tokens": 6}
    assert metrics["n_adapter_loads"] == 1
    assert engine.adapters.pinned_count == 0
    engine.tables.check()

    # a lora-less engine rejects ANY adapter name with a 400
    plain = _engine(params, cfg)
    fe2 = ServingFrontend(ContinuousBatcher(plain))

    async def scenario2():
        await fe2.start()
        s, _, body = await _unary(
            fe2.port, "/v1/completions",
            {"prompt": prompt, "max_tokens": 2, "model": "a0"})
        await fe2.stop()
        return s, body

    s, body = asyncio.run(scenario2())
    assert s == 400 and "no LoRA lanes" in body["error"]["message"]
