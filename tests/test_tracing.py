"""Request-scoped tracing + engine flight recorder (observability/
tracing.py, flight.py) and their serving/front-door wiring:

- the flight ring's memory bound holds under a 10k-step synthetic
  churn (constant nbytes, bounded tail, bounded anomaly log) and the
  watchdog flags stalls + attributes recompiles to in-flight ids;
- span events are themselves valid Chrome trace events (the shared
  exporter satellite), golden-tested against the full schema;
- a cancelled, a preempted, and a speculative request each leave the
  exact expected lifecycle event sequence in the trace;
- the ``/debug/requests`` / ``/debug/engine`` / ``/debug/trace?id=``
  endpoints round-trip through a real asyncio client, and the front
  door honors/echoes ``X-Request-Id``;
- with tracing OFF the batcher's metrics dict is key-for-key AND
  value-for-value identical to the tracing-on run under a
  deterministic clock (tracing never touches the batcher clock), and
  the key set is exactly the pre-tracing stable contract;
- the pump's terminal-error path dumps the flight ring (+ the Chrome
  trace) before the exception resurfaces at ``stop()``.
"""
import asyncio
import json
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.models.gpt import GPT, GPTConfig
from torchbooster_tpu.observability.flight import FlightRecorder
from torchbooster_tpu.observability.tracing import (
    RequestTracer,
    write_chrome_trace,
)


def _decisive_model(seq_len=32):
    cfg = GPTConfig(vocab=97, n_layers=2, d_model=32, n_heads=4,
                    seq_len=seq_len, n_kv_heads=2)
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


def _kinds(tracer, request_id):
    return [e["kind"] for e in tracer.events(request_id)]


class _Tick:
    """Deterministic self-advancing clock (the batcher requires one
    that moves): every read advances by a fixed quantum, so two runs
    taking identical code paths read identical timestamps."""

    def __init__(self, dt=0.0005):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


# =====================================================================
# flight recorder: byte bound + watchdog
# =====================================================================

def test_flight_ring_byte_bound_under_10k_step_churn():
    rec = FlightRecorder(capacity=256, stall_mult=4.0)
    bound = rec.nbytes
    assert bound == 256 * rec._ring.dtype.itemsize
    for i in range(10_000):
        spike = i > 2000 and i % 400 == 0
        rec.record(
            kind=2, slots_live=i % 3, slots_filling=i % 2,
            pages_live=i % 7, pages_free=15 - i % 7, pages_cached=1,
            queue_depth=i % 5, tokens=i % 4,
            accept_rate=(i % 10) / 10.0,
            wall_s=5.0 if spike else 0.001 + (i % 3) * 1e-5,
            recompiled=(i == 5000),
            inflight=("req-a", "req-b") if i == 5000 else ())
    assert rec.nbytes == bound          # provably constant
    assert rec.n_recorded == 10_000
    tail = rec.tail()
    assert len(tail) == 256             # never more than capacity
    assert tail[-1]["seq"] == 9_999 and tail[0]["seq"] == 9_999 - 255
    anomalies = rec.anomaly_log()
    assert len(anomalies) <= 64         # the deque bound
    recompiles = [a for a in anomalies if a["what"] == "recompile"]
    stalls = [a for a in anomalies if a["what"] == "stall"]
    # the recompile may have rolled out of the bounded log under this
    # many later stalls; the ones retained must carry attributions
    assert stalls, "5000x-p99 spikes never flagged as stalls"
    assert all(a["wall_s"] > a["p99_s"] for a in stalls)
    for a in recompiles:
        assert a["requests"] == ["req-a", "req-b"]


def test_flight_recompile_attribution_and_dump(tmp_path):
    rec = FlightRecorder(capacity=8)
    for i in range(4):
        rec.record(kind=3, slots_live=1, slots_filling=1, pages_live=2,
                   pages_free=5, pages_cached=0, queue_depth=0,
                   tokens=1, accept_rate=0.0, wall_s=0.01,
                   recompiled=(i == 2), inflight=("req-z",))
    log = rec.anomaly_log()
    assert [a["what"] for a in log] == ["recompile"]
    assert log[0]["requests"] == ["req-z"]
    assert log[0]["kind"] == "prefill+decode"
    dump = rec.dump()
    assert dump["n_recorded"] == 4 and len(dump["records"]) == 4
    path = rec.write_jsonl(tmp_path / "flight.jsonl")
    lines = [json.loads(ln) for ln in
             path.read_text().strip().splitlines()]
    assert lines[0]["event"] == "flight_header"
    assert sum(ln["event"] == "flight_step" for ln in lines) == 4
    assert lines[-1]["event"] == "flight_anomaly"


def test_flight_stall_watchdog_arms_on_small_rings():
    """A ring smaller than the default warm-up sample count must still
    arm its stall watchdog once full — not stay silently dead."""
    rec = FlightRecorder(capacity=8, stall_mult=2.0)
    base = dict(kind=2, slots_live=1, slots_filling=0, pages_live=1,
                pages_free=1, pages_cached=0, queue_depth=0, tokens=1,
                accept_rate=0.0)
    for _ in range(16):
        rec.record(wall_s=0.001, **base)
    rec.record(wall_s=1.0, **base)
    assert any(a["what"] == "stall" for a in rec.anomaly_log())


def test_flight_validation():
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)
    with pytest.raises(ValueError):
        FlightRecorder(stall_mult=1.0)


# =====================================================================
# tracer ring + the shared Chrome exporter
# =====================================================================

def test_tracer_ring_bounded_disabled_noop_and_filtering():
    off = RequestTracer()                  # disabled by default
    off.emit("r", "enqueued")
    assert len(off) == 0
    tr = RequestTracer(enabled=True, ring_size=16)
    for i in range(40):
        tr.emit(f"r{i % 4}", "tokens", n=1)
    assert len(tr) == 16                   # oldest dropped
    assert set(tr.request_ids()) == {"r0", "r1", "r2", "r3"}
    only = tr.events("r3")
    assert only and all(e["request_id"] == "r3" for e in only)
    tses = [e["ts_us"] for e in tr.events()]
    assert tses == sorted(tses)            # monotonic stamps
    with pytest.raises(ValueError):
        RequestTracer(ring_size=0)


def test_span_events_are_chrome_trace_events_golden(tmp_path):
    """The satellite contract: span JSONL events carry ph/pid/tid and
    microsecond ts/dur, making them valid Chrome trace events the ONE
    shared exporter writes alongside tracer events. Schema pinned
    golden-style (volatile fields normalized)."""
    import torchbooster_tpu.observability as obs
    from torchbooster_tpu.observability.registry import Registry

    reg = Registry(enabled=True)
    events = []
    unsub = obs.span_events_subscribe(events.append)
    try:
        with obs.span("decode_step", reg):
            pass
    finally:
        unsub()
    (e,) = events
    assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
    assert e["dur"] >= 0
    assert e["pid"] == os.getpid()
    assert e["tid"] == threading.get_ident()
    golden = json.dumps(
        dict(e, ts=0, dur=0, dur_s=0.0, pid=1, tid=2), sort_keys=True)
    assert golden == (
        '{"cat": "span", "depth": 0, "dur": 0, "dur_s": 0.0, '
        '"event": "span", "name": "decode_step", "ok": true, '
        '"path": "decode_step", "ph": "X", "pid": 1, "tid": 2, '
        '"ts": 0}')
    # one exporter, both sinks: span events and tracer events land in
    # one valid Chrome trace file
    tr = RequestTracer(enabled=True)
    tr.emit("req-1", "enqueued", prompt_len=3)
    tr.emit(None, "decode_step", dur_s=0.002, slots=1)
    path = write_chrome_trace(tmp_path / "t.json",
                              [*events, *tr.chrome_events()])
    payload = json.loads(path.read_text())
    assert isinstance(payload["traceEvents"], list)
    assert all("ph" in ev and "name" in ev
               for ev in payload["traceEvents"])
    names = {ev["args"]["name"] for ev in payload["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert {"req-1", "decode_step"} <= names


# =====================================================================
# lifecycle event sequences: cancelled / preempted / speculative
# =====================================================================

def test_trace_cancelled_request_exact_sequence():
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    tracer = RequestTracer(enabled=True)
    b = ContinuousBatcher(engine, tracer=tracer)
    b.start_session()
    try:
        req = Request(prompt=np.arange(1, 6), max_new_tokens=8)
        b.submit(req)
        b.step()       # seat + the single prefill chunk + one launch
        b.step()       # ... which lands here, behind the next launch
        b.cancel(req)
        b.step()       # the cancel drains before anything else
    finally:
        b.finish_session()
    assert req.cancelled
    assert _kinds(tracer, req.request_id) == [
        "enqueued", "seated", "prefill_chunk", "first_token",
        "tokens", "cancelled"]
    # the engine track saw the chunk and the decode step, cross-linked
    # by the span names
    engine_kinds = set(_kinds(tracer, None))
    assert {"serving_prefill_chunk", "decode_step"} <= engine_kinds
    engine.tables.check()


def test_trace_preempted_request_exact_sequence():
    """Tight pool (the test_serving preemption geometry): a preempted
    request's trace must show the preemption with its fold size and
    the re-seat marked as a re-admission, ending retired."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (5,), 0, cfg.vocab))
    engine = _engine(params, cfg, n_pages=5)    # ~1.5 sequences
    tracer = RequestTracer(enabled=True, ring_size=4096)
    b = ContinuousBatcher(engine, tracer=tracer)
    reqs = [Request(prompt=ids, max_new_tokens=8) for _ in range(3)]
    b.run(reqs)
    preempted = [r for r in reqs
                 if any(e["kind"] == "preempted"
                        for e in tracer.events(r.request_id))]
    assert preempted, "tight pool never preempted — geometry drifted"
    for r in preempted:
        evs = tracer.events(r.request_id)
        kinds = ",".join(e["kind"] for e in evs)
        assert re.fullmatch(
            r"enqueued,seated(,prefill_chunk)*(,first_token)?"
            r"(,tokens)*"
            r"(,preempted,seated(,prefill_chunk)*(,first_token)?"
            r"(,tokens)*)+"
            r",retired", kinds), kinds
        assert kinds.count("first_token") == 1
        for e in evs:
            if e["kind"] == "preempted":
                assert e["fold_tokens"] >= 0
            if e["kind"] == "seated" and e["readmission"]:
                break
        else:
            pytest.fail("re-seat after preemption not marked "
                        "readmission=True")
        assert evs[-1]["reason"] == "length"


def test_trace_speculative_request_exact_sequence():
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    rs = np.random.RandomState(5)
    prompt = np.tile(rs.randint(0, 97, 2).astype(np.int32), 8)  # 16
    engine = _engine(params, cfg, n_pages=24, speculative=True,
                     draft_len=3)
    tracer = RequestTracer(enabled=True)
    b = ContinuousBatcher(engine, tracer=tracer)
    req = Request(prompt=prompt, max_new_tokens=10)
    b.run([req])
    kinds = ",".join(_kinds(tracer, req.request_id))
    assert re.fullmatch(
        r"enqueued,seated(,prefill_chunk)+,first_token(,tokens)+"
        r",retired", kinds), kinds
    tok_events = [e for e in tracer.events(req.request_id)
                  if e["kind"] == "tokens"]
    assert all(e["spec"] for e in tok_events)
    # the repetitive prompt must accept drafts: some burst carries
    # more than one token, and the engine track prices each verify
    assert any(e["n"] > 1 for e in tok_events)
    verify = [e for e in tracer.events(None)
              if e["kind"] == "spec_verify_step"]
    assert verify and all(e["proposed"] >= e["accepted"] >= 0
                          for e in verify)
    assert sum(e["accepted"] for e in verify) > 0
    engine.tables.check()


# =====================================================================
# tracing off == tracing on, bit for bit (metric values + key set)
# =====================================================================

# the pre-tracing stable key contract of ContinuousBatcher metrics
_STABLE_KEYS = {
    "n_requests", "new_tokens", "elapsed_s", "decode_tok_s",
    "total_tok_s", "latency_mean_s", "latency_p95_s", "ttft_mean_s",
    "n_admissions", "n_preemptions", "n_prefill_chunks",
    "prefix_hit_pages", "prefix_hit_rate", "n_spec_steps",
    "n_spec_proposed", "n_spec_accepted", "spec_accept_rate",
    "spec_mean_accepted", "n_forks", "fork_pages", "n_cow_copies",
    "n_spills", "n_promotions", "host_hit_pages",
    "n_structured", "structured_masked_frac",
    "n_shed", "n_cancelled",
    "deadline_hit_rate", "classes",
    "n_adapter_loads", "n_adapter_evictions", "n_adapter_hits",
    "adapters",
}


@pytest.mark.parametrize("telemetry", ["tracer", "registry", "both"])
def test_tracing_off_metrics_key_and_value_identical(telemetry):
    """Two identical traces under a deterministic clock — one with
    tracing off (the default), one with tracing ON — must return the
    SAME metrics dict, key for key and value for value: the tracer
    stamps its own clock and adds no batcher-clock reads, so enabling
    it cannot perturb a single metric. The key set is exactly the
    pre-tracing stable contract. The same holds with the REGISTRY on:
    the iteration's span tree (``sched_step`` and its children) times
    itself on ``perf_counter``, the queue-wait / prefill histograms
    observe stamps the batcher had taken anyway, and the ``step``
    field counts iterations — none reads the batcher's clock."""
    import torchbooster_tpu.observability as obs
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (5,), 0, cfg.vocab))

    def run(tracer):
        engine = _engine(params, cfg, n_pages=5)   # preemption-rich
        b = ContinuousBatcher(engine, clock=_Tick(), tracer=tracer)
        reqs = [Request(prompt=ids, max_new_tokens=8)
                for _ in range(3)]
        return b.run(reqs), reqs

    off, off_reqs = run(None)
    on_tracer = RequestTracer(enabled=telemetry != "registry")
    registry = obs.get_registry()
    was = registry.enabled
    registry.reset()
    registry.enabled = telemetry != "tracer"
    try:
        on, on_reqs = run(on_tracer)
        snap = registry.snapshot()
    finally:
        registry.enabled = was
        registry.reset()
    assert set(off) == _STABLE_KEYS
    assert off == on
    assert off["n_preemptions"] > 0        # the rich path, not idle
    # the requests' own stamps and tokens, not only the summary
    for a, b in zip(off_reqs, on_reqs):
        assert (a.tokens, a.admitted_at, a.first_token_at,
                a.finished_at) == (b.tokens, b.admitted_at,
                                   b.first_token_at, b.finished_at)
    if telemetry != "registry":
        assert len(on_tracer) > 0          # tracing actually ran
        kinds = {e["kind"] for e in on_tracer.events()}
        assert {"seated", "tokens", "decode_step"} <= kinds
        for e in on_tracer.events():
            if e["kind"] in ("tokens", "decode_step"):
                assert e["step"] >= 1
            if e["kind"] == "seated":
                assert e["queue_wait_s"] >= 0.0
    if telemetry != "tracer":
        # the new spans and histograms actually ran
        for name in ("sched_step", "sched_admit", "sched_grow",
                     "prefill_args", "decode_args", "decode_advance",
                     "sched_deliver"):
            assert snap[f"span_seconds{{name={name}}}_count"] > 0
        assert snap["serving_queue_wait_seconds_count"] == 3
        assert snap["serving_prefill_seconds_count"] == 3
    else:
        assert not snap                    # off: nothing was written


# =====================================================================
# /debug endpoints + X-Request-Id over a real asyncio client
# =====================================================================

# the hand-rolled asyncio HTTP/1.1 client dialect lives ONCE, in
# test_frontend (headers kwarg added there for the X-Request-Id
# round-trips below) — a second copy here could silently drift
from tests.test_frontend import (  # noqa: E402
    _post,
    _read_head,
    _unary,
)


async def _get(port, path):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
    await writer.drain()
    status, headers = await _read_head(reader)
    body = await reader.read()
    writer.close()
    return status, headers, json.loads(body) if body else None


def test_debug_endpoints_and_request_id_round_trip():
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import ServingFrontend

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    tracer = RequestTracer(enabled=True)
    b = ContinuousBatcher(engine, tracer=tracer)
    fe = ServingFrontend(b, port=0)

    async def run():
        await fe.start()
        port = fe.port
        # X-Request-Id honored: echoed header + OpenAI id + trace key
        status, hdrs, body = await _unary(
            port, "/v1/completions",
            {"prompt": [1, 2, 3, 4], "max_tokens": 4},
            {"X-Request-Id": "my-debug-1"})
        assert status == 200
        assert hdrs["x-request-id"] == "my-debug-1"
        assert body["id"] == "cmpl-my-debug-1"
        # and auto-generated when absent (returned both ways)
        status, hdrs2, body2 = await _unary(
            port, "/v1/completions",
            {"prompt": [5, 6, 7], "max_tokens": 2})
        assert status == 200
        auto = hdrs2["x-request-id"]
        assert auto.startswith("req-") and body2["id"] == f"cmpl-{auto}"
        # a malformed header is rejected before touching the scheduler
        status, _, err = await _unary(
            port, "/v1/completions",
            {"prompt": [1], "max_tokens": 1},
            {"X-Request-Id": "bad id with spaces!"})
        assert status == 400 and "X-Request-Id" in err["error"]["message"]

        status, _, reqs = await _get(port, "/debug/requests")
        assert status == 200
        assert reqs["active_session"] and reqs["tracing_enabled"]
        assert reqs["requests"] == []      # both already retired

        status, _, eng = await _get(port, "/debug/engine")
        assert status == 200
        assert eng["engine"]["backend"] == "xla"
        assert eng["engine"]["compiles"]["decode"] == 1
        assert eng["flight"]["n_recorded"] >= 1
        assert eng["flight"]["capacity"] > 0
        assert isinstance(eng["flight"]["records"], list)

        status, _, trace = await _get(port,
                                      "/debug/trace?id=my-debug-1")
        assert status == 200
        kinds = [e["kind"] for e in trace["events"]]
        assert kinds[0] == "enqueued" and kinds[-1] == "retired"
        assert "first_token" in kinds

        status, _, _ = await _get(port, "/debug/trace?id=absent")
        assert status == 404
        status, _, _ = await _get(port, "/debug/trace")
        assert status == 400

        # a SECOND request on an id still in flight is rejected (409)
        # — concurrent duplicates would merge two lifecycles into one
        # trace timeline; sequential reuse stays legal
        r1, w1 = await _post(port, "/v1/completions",
                             {"prompt": [9, 9, 9], "max_tokens": 29,
                              "stream": True},
                             {"X-Request-Id": "dup-1"})
        head = await r1.readuntil(b"\r\n\r\n")
        assert b" 200 " in head          # first token streaming
        status, _, err = await _unary(
            port, "/v1/completions", {"prompt": [1], "max_tokens": 1},
            {"X-Request-Id": "dup-1"})
        assert status == 409
        assert "in flight" in err["error"]["message"]
        w1.close()                       # disconnect -> cancel path
        await fe.stop()

    asyncio.run(run())
    engine.tables.check()


def test_pump_death_dumps_flight_and_trace(tmp_path):
    """PR 7's terminal-error path now leaves a post-mortem: when the
    pump dies mid-step the flight ring (and the Chrome trace, tracing
    being on) land at crash_dump_path BEFORE the exception resurfaces
    at stop()."""
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import ServingFrontend

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    b = ContinuousBatcher(engine, tracer=RequestTracer(enabled=True))
    fe = ServingFrontend(b, port=0,
                         crash_dump_path=str(tmp_path / "crash"))

    async def run():
        await fe.start()

        def boom(*launch):
            raise RuntimeError("synthetic engine death")

        # engine-level death: the batcher's step() wrapper still runs,
        # so the FATAL step itself must land a (partial) flight row —
        # the crash dump's last record is the step that died, not the
        # one before it (the look-ahead loop's entry is step_ahead)
        fe.batcher.engine.step = fe.batcher.engine.step_ahead = boom
        status, _, body = await _unary(
            fe.port, "/v1/completions",
            {"prompt": [1, 2, 3], "max_tokens": 4})
        assert status == 500
        with pytest.raises(RuntimeError, match="synthetic"):
            await fe.stop()

    asyncio.run(run())
    assert fe.last_flight is not None
    records = fe.last_flight["records"]
    assert records, "fatal step left no flight record"
    assert "prefill" in records[-1]["kind"]   # died between chunk+decode
    flight_lines = (tmp_path / "crash.flight.jsonl").read_text()
    assert json.loads(
        flight_lines.splitlines()[0])["event"] == "flight_header"
    trace = json.loads((tmp_path / "crash.trace.json").read_text())
    assert isinstance(trace["traceEvents"], list)


# =====================================================================
# live SLO quantile gauges (the reservoir-export satellite)
# =====================================================================

def test_slo_quantile_gauges_land_in_registry():
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
        pol = SLOPolicy(parse_classes("rt:5000:0,batch:0:0"),
                        default="batch")
        b = ContinuousBatcher(engine, policy=pol)
        b.run([Request(prompt=np.arange(1, 5), max_new_tokens=4,
                       priority="rt"),
               Request(prompt=np.arange(2, 6), max_new_tokens=4)])
        prom = prometheus_text(registry)
    finally:
        registry.enabled = was
        registry.reset()
    # live client-facing percentiles, per class and quantile — the
    # Prometheus SLO dashboard's plot series
    assert 'serving_slo_ttft_quantile{cls="rt",q="p50"}' in prom
    assert 'serving_slo_ttft_quantile{cls="rt",q="p99"}' in prom
    assert 'serving_slo_ttft_quantile{cls="batch",q="p50"}' in prom
    assert 'serving_slo_tpot_quantile{cls="rt",q="p50"}' in prom
    for line in prom.splitlines():
        if line.startswith("serving_slo_ttft_quantile"):
            assert float(line.rsplit(" ", 1)[1]) > 0.0


def test_config_tracing_block_builds_and_exports(tmp_path):
    from torchbooster_tpu.config import ObservabilityConfig

    yml = tmp_path / "obs.yml"
    yml.write_text(
        "enabled: false\n"
        "tracing:\n"
        "  enabled: true\n"
        "  ring_size: 64\n"
        f"  trace_path: {tmp_path}/t.jsonl\n"
        f"  chrome_path: {tmp_path}/t.chrome.json\n")
    conf = ObservabilityConfig.load(yml)
    tracer = conf.tracing.make()
    assert tracer.enabled and tracer.ring_size == 64
    tracer.emit("r1", "enqueued", prompt_len=1)
    written = conf.tracing.export(tracer)
    assert sorted(p.name for p in written) == ["t.chrome.json",
                                               "t.jsonl"]
    line = json.loads(
        (tmp_path / "t.jsonl").read_text().splitlines()[0])
    assert line["event"] == "trace" and line["kind"] == "enqueued"
    chrome = json.loads((tmp_path / "t.chrome.json").read_text())
    assert chrome["traceEvents"]
