"""Workload capture & deterministic replay harness
(torchbooster_tpu/serving/loadgen) on CPU:

- the versioned JSONL workload format round-trips byte-honestly
  (fingerprint recomputed and verified at load, tampering loud),
  scrubbed captures regenerate same-shape prompts without ever
  persisting content, and every synthetic generator emits the same
  format deterministically from its seed;
- REPLAY DETERMINISM (the ISSUE satellite): replaying one capture
  twice at x1 through the batcher ``step()`` core under the
  deterministic clock yields identical token streams AND an
  identical scheduler decision sequence (seat/shed/preempt order),
  for both FCFS and SLO policies — with real preemptions and a real
  shed in the trace;
- FlightRecorder ``tail()`` wrap-around (the other satellite): rows
  come back oldest-first with consecutive seqs and the ring's byte
  size stays constant after wrapping several times during a replay;
- the END-TO-END ROUND TRIP (the acceptance): a mixed-priority
  workload served with capture enabled on the real HTTP server, the
  capture replayed in-process at x1 and at a compressed factor, and
  the report's per-class request counts, token counts, and
  cancellation offsets matching the original trace exactly — with
  zero new compiles across all of it;
- the SLO conformance report's goodput/percentile math, the
  max-sustainable-x binary search, the ``replay_diff`` regression
  gate (fingerprint mismatches REFUSED, regressions flagged), and
  the one fingerprint-comparability predicate behind it;
- the ``loadgen:`` YAML block and the ``frontend.capture_path`` knob.
"""
import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.models.gpt import GPT, GPTConfig


def _decisive_model(seq_len=64):
    """Tiny GPT with a DECISIVE head (the test_serving trick): greedy
    picks must not sit in float near-ties, or replay 'determinism'
    would measure tie-breaking instead of the harness."""
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


def _workload(n=6, seed=0, cancel_idx=2, cancel_after=2, **kw):
    from torchbooster_tpu.serving.loadgen import synthesize

    kw.setdefault("rate", 50.0)
    kw.setdefault("vocab", 97)
    kw.setdefault("prompt_len", (4, 8))
    kw.setdefault("max_new_tokens", (3, 6))
    wl = synthesize("poisson", n_requests=n, seed=seed, **kw)
    if cancel_idx is not None:
        wl.requests[cancel_idx].cancel_after_tokens = cancel_after
    return wl


# ---- the format ------------------------------------------------------

def test_workload_format_roundtrip_fingerprint_and_tamper(tmp_path):
    from torchbooster_tpu.serving.loadgen import Workload

    wl = _workload(classes="rt:1,batch:2")
    path = wl.save(tmp_path / "wl.jsonl")
    back = Workload.load(path)
    assert len(back) == len(wl)
    assert back.fingerprint() == wl.fingerprint()
    assert back.vocab == wl.vocab
    for a, b in zip(wl.requests, back.requests):
        assert a.request_id == b.request_id
        assert np.array_equal(a.prompt, b.prompt)
        assert a.cancel_after_tokens == b.cancel_after_tokens
        assert a.priority == b.priority
    # request ids are identity, not content: renaming them must not
    # change the fingerprint the A/B gates compare
    for r in back.requests:
        r.request_id = "x-" + r.request_id
    assert back.fingerprint() == wl.fingerprint()
    # tampering with CONTENT after capture fails loudly at load
    lines = path.read_text().splitlines()
    d = json.loads(lines[1])
    d["max_new_tokens"] += 1
    lines[1] = json.dumps(d)
    path.write_text("\n".join(lines) + "\n")
    with pytest.raises(ValueError, match="fingerprint"):
        Workload.load(path)


def test_workload_validates_loudly():
    from torchbooster_tpu.serving.loadgen import (Workload,
                                                  WorkloadRequest,
                                                  synthesize)

    with pytest.raises(ValueError, match="unknown synthetic"):
        synthesize("uniform")
    with pytest.raises(ValueError, match="prompt_seed"):
        WorkloadRequest(arrival_s=0.0, max_new_tokens=2, prompt=None,
                        prompt_len=4)
    with pytest.raises(ValueError, match="cancel_after_tokens"):
        WorkloadRequest(arrival_s=0.0, max_new_tokens=2,
                        prompt=np.arange(1, 4),
                        cancel_after_tokens=0)
    with pytest.raises(ValueError, match="duplicate request_id"):
        Workload(requests=[
            WorkloadRequest(arrival_s=0.0, max_new_tokens=2,
                            prompt=np.arange(1, 4), request_id="a"),
            WorkloadRequest(arrival_s=0.1, max_new_tokens=2,
                            prompt=np.arange(1, 4), request_id="a")])


def test_synthetic_generators_deterministic_same_format():
    """Every kind emits the same format; same seed → same fingerprint
    (the synthetic A/B guarantee), different seed → different."""
    from torchbooster_tpu.serving.loadgen import (SYNTHETIC_KINDS,
                                                  synthesize)

    for kind in SYNTHETIC_KINDS:
        a = synthesize(kind, n_requests=8, seed=3, vocab=97,
                       classes="rt:1,batch:3", cancel_frac=0.3)
        b = synthesize(kind, n_requests=8, seed=3, vocab=97,
                       classes="rt:1,batch:3", cancel_frac=0.3)
        c = synthesize(kind, n_requests=8, seed=4, vocab=97,
                       classes="rt:1,batch:3", cancel_frac=0.3)
        assert a.fingerprint() == b.fingerprint(), kind
        assert a.fingerprint() != c.fingerprint(), kind
        assert a.kind == f"synthetic:{kind}"
        arrivals = [r.arrival_s for r in a.requests]
        assert arrivals == sorted(arrivals)
        assert all(r.arrival_s >= 0 for r in a.requests)
        assert {r.priority for r in a.requests} <= {"rt", "batch"}


def test_workload_v4_adapter_roundtrip(tmp_path):
    """v4 (multi-LoRA): ``synthesize(adapter_mix=)`` assigns adapters
    by weighted draw from its OWN stream — the base request content
    stays byte-identical to the mix-less workload — the field rides
    save/load and the fingerprint only when set, and a v3-headered
    file (no adapter keys) still loads, with every adapter ''."""
    import json as _json

    from torchbooster_tpu.serving.loadgen import Workload, synthesize

    kw = dict(n_requests=16, seed=3, vocab=97, prompt_len=(4, 8),
              max_new_tokens=(3, 6), rate=50.0)
    plain = synthesize("poisson", **kw)
    mixed = synthesize("poisson", adapter_mix="base:2,fr:1,de:1", **kw)
    names = {r.adapter for r in mixed.requests}
    assert names & {"fr", "de"} and "" in names    # the draw mixes
    assert "base" not in names                      # 'base' -> ''
    # the adapter draw must not perturb base content
    for a, b in zip(plain.requests, mixed.requests):
        assert np.array_equal(a.prompt, b.prompt)
        assert (a.arrival_s, a.max_new_tokens) == \
            (b.arrival_s, b.max_new_tokens)
    assert plain.fingerprint() != mixed.fingerprint()
    # round trip: adapters + fingerprint survive save/load
    back = Workload.load(mixed.save(tmp_path / "v4.jsonl"))
    assert [r.adapter for r in back.requests] == \
        [r.adapter for r in mixed.requests]
    assert back.fingerprint() == mixed.fingerprint()
    # adapter-less workloads keep the pre-v4 fingerprint (the field
    # enters the content key ONLY when set), so a v3-headered file
    # loads clean with the same recorded fingerprint
    path = plain.save(tmp_path / "v3.jsonl")
    lines = path.read_text().splitlines()
    hdr = _json.loads(lines[0])
    assert hdr["version"] == 4
    hdr["version"] = 3
    lines[0] = _json.dumps(hdr)
    path.write_text("\n".join(lines) + "\n")
    old = Workload.load(path)
    assert all(r.adapter == "" for r in old.requests)
    assert old.fingerprint() == plain.fingerprint()
    # determinism + validation
    again = synthesize("poisson", adapter_mix="base:2,fr:1,de:1", **kw)
    assert again.fingerprint() == mixed.fingerprint()
    with pytest.raises(ValueError, match="adapter"):
        from torchbooster_tpu.serving.loadgen import WorkloadRequest
        WorkloadRequest(arrival_s=0.0, max_new_tokens=2,
                        prompt=np.arange(1, 4), adapter=7)


# ---- replay determinism (ISSUE satellite) ----------------------------

def _decisions(tracer):
    """The scheduler decision sequence a replay produced, in event
    order — the seat/shed/preempt/cancel/retire trail per request."""
    return [(e["kind"], e["request_id"]) for e in tracer.events()
            if e["kind"] in ("seated", "shed", "preempted",
                             "cancelled", "retired")]


def test_replay_determinism_fcfs_and_slo_with_preempt_and_shed():
    """Replaying the same capture twice at x1 through the batcher
    ``step()`` core under the deterministic clock yields identical
    token streams AND an identical scheduler decision sequence, for
    both FCFS and SLO — on a trace that really preempts (pool sized
    below worst-case demand) and, under SLO, really sheds (a tight
    deadline arriving into full slots)."""
    from torchbooster_tpu.observability.tracing import RequestTracer
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import (SLOPolicy,
                                                   parse_classes)
    from torchbooster_tpu.serving.loadgen import replay_inprocess

    params, cfg = _decisive_model()
    # usable pool 7 pages vs 2 slots x 4-5 pages of worst-case live
    # context: preemption pressure by construction
    engine = _engine(params, cfg, n_pages=8, max_slots=2)
    wl = _workload(n=6, cancel_idx=3, cancel_after=2,
                   prompt_len=(6, 8), max_new_tokens=(8, 10),
                   classes="rt:1,batch:1")
    # a tight-deadline straggler: by the time it arrives the slots
    # are busy; a few virtual steps of queueing blow its 1 ms budget
    # and the SLO policy must shed it — deterministically
    wl.requests[-1].deadline_ms = 1.0

    def spawn_policy(name):
        if name == "fcfs":
            return None
        return SLOPolicy(parse_classes("rt:60000:0,batch:0:0"),
                         default="batch")

    for policy_name in ("fcfs", "slo"):
        runs = []
        for _ in range(2):
            tracer = RequestTracer(enabled=True, ring_size=1 << 14)
            b = ContinuousBatcher(engine, policy=spawn_policy(policy_name),
                                  tracer=tracer)
            res = replay_inprocess(b, wl, speed=1.0)
            runs.append((
                {r.request_id: list(r.tokens) for r in res.requests},
                _decisions(tracer), res.metrics))
        (tok_a, dec_a, m_a), (tok_b, dec_b, m_b) = runs
        assert tok_a == tok_b, f"{policy_name}: token streams differ"
        assert dec_a == dec_b, f"{policy_name}: decision order differs"
        assert m_a["n_preemptions"] == m_b["n_preemptions"] > 0, \
            f"{policy_name}: the trace must actually preempt"
        assert m_a["n_cancelled"] == m_b["n_cancelled"] == 1
        if policy_name == "slo":
            assert m_a["n_shed"] == m_b["n_shed"] == 1, \
                "the tight-deadline straggler must shed, both runs"
            assert ("shed", wl.requests[-1].request_id) in dec_a
    assert engine.decode_compiles == 1
    assert 1 <= engine.prefill_compiles <= 2


def test_flight_recorder_tail_wraparound_during_replay():
    """ISSUE satellite: after the always-on flight ring wraps several
    times during a replay run, ``tail()`` still returns rows
    oldest-first with consecutive seqs, and the ring's byte size is
    the same construction-time constant it started as."""
    from torchbooster_tpu.observability.flight import FlightRecorder
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.loadgen import replay_inprocess

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    flight = FlightRecorder(capacity=8)
    nbytes0 = flight.nbytes
    b = ContinuousBatcher(engine, flight=flight)
    replay_inprocess(b, _workload(n=6, max_new_tokens=(4, 8)),
                     speed=1.0)
    assert flight.n_recorded > 3 * flight.capacity, \
        "workload too small to wrap the ring several times"
    assert flight.nbytes == nbytes0
    rows = flight.tail()
    assert len(rows) == flight.capacity
    seqs = [r["seq"] for r in rows]
    assert seqs == list(range(flight.n_recorded - flight.capacity,
                              flight.n_recorded)), \
        "tail() must be oldest-first and contiguous after wrap"
    # a partial tail is the same rows, truncated from the OLD end
    assert [r["seq"] for r in flight.tail(3)] == seqs[-3:]


# ---- the end-to-end round trip (acceptance) --------------------------

def test_http_capture_replay_round_trip_exact(tmp_path):
    """Serve a mixed-priority workload with capture enabled on the
    real HTTP server (one client disconnecting mid-stream), replay
    the capture file in-process at x1 AND at a compressed factor,
    and prove the report's per-class request counts, served token
    counts, and cancellation offsets match the original trace
    exactly — with zero new compiles across all of it."""
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import (ServingFrontend,
                                                   SLOPolicy,
                                                   parse_classes)
    from torchbooster_tpu.serving.loadgen import (Workload,
                                                  replay_http,
                                                  replay_inprocess)

    params, cfg = _decisive_model()
    engine = _engine(params, cfg, n_pages=24)
    classes = parse_classes("rt:60000:0,batch:0:0")
    wl = _workload(n=6, classes="rt:1,batch:2")
    cap_path = tmp_path / "capture.jsonl"

    batcher = ContinuousBatcher(
        engine, policy=SLOPolicy(classes, default="batch"))

    async def scenario():
        fe = ServingFrontend(batcher, port=0,
                             capture_path=str(cap_path))
        await fe.start()
        res = await replay_http(fe.port, wl, speed=1.0,
                                classes=classes)
        await fe.stop()
        return res

    original = asyncio.run(scenario())
    assert cap_path.exists()
    cap = Workload.load(cap_path)
    assert len(cap) == len(wl)
    # the capture is keyed by the ORIGINAL request ids
    assert {r.request_id for r in cap.requests} \
        == {r.request_id for r in wl.requests}
    cancelled_rec = next(r for r in cap.requests
                         if r.cancel_after_tokens is not None)
    # the recorded cancel offset is what the server DELIVERED before
    # the disconnect landed (>= the client's 2-token read point)
    assert cancelled_rec.cancel_after_tokens >= 2

    by_id = {r.request_id: r for r in cap.requests}
    for speed in (1.0, 4.0):
        b = ContinuousBatcher(
            engine, policy=SLOPolicy(classes, default="batch"))
        res = replay_inprocess(b, cap, speed=speed)
        # per-class request counts match the original trace
        for cls in ("rt", "batch"):
            offered = sum(1 for r in cap.requests if r.priority == cls)
            assert res.report["classes"][cls]["n"] == offered
        # served token counts and the cancellation offset match
        for req in res.requests:
            rec = by_id[req.request_id]
            want = rec.cancel_after_tokens or rec.max_new_tokens
            assert len(req.tokens) == want, (speed, req.request_id)
            if rec.cancel_after_tokens is not None:
                assert req.cancelled
                assert len(req.tokens) == rec.cancel_after_tokens
        assert res.report["n_cancelled"] == 1
        assert res.report["n_shed"] == 0
        assert res.report["workload_fingerprint"] == cap.fingerprint()
        assert res.report["speed"] == speed
    # original HTTP run and both replays: token counts agree with the
    # offered budgets there too, and nothing ever recompiled
    assert original.report["n_cancelled"] == 1
    assert engine.decode_compiles == 1
    assert 1 <= engine.prefill_compiles <= 2
    engine.tables.check()


def test_capture_scrub_and_from_tracer_never_persist_content(tmp_path):
    """Privacy-scrubbed captures (frontend knob) and tracer-ring
    reconstructions carry seed+length recipes, never prompt ids —
    and the recipes replay deterministically."""
    from torchbooster_tpu.observability.tracing import RequestTracer
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import ServingFrontend
    from torchbooster_tpu.serving.loadgen import (Workload,
                                                  replay_http,
                                                  replay_inprocess)

    params, cfg = _decisive_model()
    engine = _engine(params, cfg, n_pages=24)
    tracer = RequestTracer(enabled=True, ring_size=1 << 14)
    batcher = ContinuousBatcher(engine, tracer=tracer)
    wl = _workload(n=4, cancel_idx=1)
    cap_path = tmp_path / "scrubbed.jsonl"

    async def scenario():
        fe = ServingFrontend(batcher, port=0,
                             capture_path=str(cap_path),
                             capture_scrub=True)
        await fe.start()
        await replay_http(fe.port, wl, speed=1.0)
        await fe.stop()

    asyncio.run(scenario())
    text = cap_path.read_text()
    cap = Workload.load(cap_path)
    assert cap.meta.get("scrubbed") is True
    for rec, orig in zip(
            sorted(cap.requests, key=lambda r: r.request_id),
            sorted(wl.requests, key=lambda r: r.request_id)):
        assert rec.prompt is None and rec.prompt_seed is not None
        assert rec.prompt_len == orig.prompt_len
        # the original token ids never appear in the file
        ids = " ".join(str(int(t)) for t in orig.prompt)
        assert f"[{ids.replace(' ', ', ')}]" not in text
        # the recipe is deterministic and replay-shaped
        a = rec.prompt_ids(cap.vocab)
        assert np.array_equal(a, rec.prompt_ids(cap.vocab))
        assert a.size == orig.prompt_len
    # same trace reconstructed from the tracing ring alone: same ids,
    # same arrivals (to the tracer's rounding), cancel offset kept
    twl = Workload.from_tracer(tracer, vocab=cfg.vocab)
    assert {r.request_id for r in twl.requests} \
        == {r.request_id for r in wl.requests}
    t_cancel = next(r for r in twl.requests
                    if r.cancel_after_tokens is not None)
    assert t_cancel.cancel_after_tokens >= 2
    # and it replays through the same driver
    res = replay_inprocess(ContinuousBatcher(engine), twl, speed=2.0)
    assert res.report["n_requests"] == len(wl)


def test_empty_capture_and_error_outcomes_survive(tmp_path):
    """Regressions from review: a capture-enabled server that served
    NO traffic must stop cleanly (empty workload written, not a
    crash), and an HTTP replay whose requests error (mismatched
    class table -> 400) must report them as errors — never as
    served-but-empty completions."""
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import (ServingFrontend,
                                                   SLOPolicy,
                                                   parse_classes)
    from torchbooster_tpu.serving.loadgen import (Workload,
                                                  WorkloadCapture,
                                                  replay_http)

    assert len(WorkloadCapture().finalize()) == 0
    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    cap_path = tmp_path / "empty.jsonl"
    batcher = ContinuousBatcher(engine)

    async def idle():
        fe = ServingFrontend(batcher, port=0,
                             capture_path=str(cap_path))
        await fe.start()
        await fe.stop()                # no traffic at all

    asyncio.run(idle())
    assert len(Workload.load(cap_path)) == 0
    # a replayed class the server's table doesn't know -> 400 per
    # request -> error outcomes, zero completions, nonzero error_rate
    b2 = ContinuousBatcher(
        engine, policy=SLOPolicy(parse_classes("only:0:0")))
    wl = _workload(n=3, cancel_idx=1, classes="ghost:1")

    async def errored():
        fe = ServingFrontend(b2, port=0)
        await fe.start()
        res = await replay_http(fe.port, wl, speed=4.0)
        await fe.stop()
        return res

    rep = asyncio.run(errored()).report
    assert rep["n_errors"] == 3 and rep["error_rate"] == 1.0
    assert rep["n_completed"] == 0 and rep["n_cancelled"] == 0
    assert rep["goodput_tok_s"] == 0.0


# ---- report / diff / gates -------------------------------------------

def _fake_report(fp="abc", goodput=100.0, hit=1.0, shed=0.0,
                 ttft99=0.1):
    return {"workload_fingerprint": fp, "speed": 1.0,
            "goodput_tok_s": goodput, "total_tok_s": goodput + 10,
            "deadline_hit_rate": hit, "shed_rate": shed,
            "classes": {"rt": {"ttft_p99_s": ttft99,
                               "tpot_p99_s": 0.01,
                               "deadline_hit_rate": hit,
                               "goodput_tok_s": goodput}}}


def test_conformance_report_goodput_counts_only_deadline_hit_tokens():
    from torchbooster_tpu.serving.loadgen import (Workload,
                                                  WorkloadRequest,
                                                  conformance_report)

    wl = Workload(requests=[WorkloadRequest(
        arrival_s=0.0, max_new_tokens=4, prompt=np.arange(1, 4))])
    mk = lambda **kw: {  # noqa: E731 — local outcome factory
        "request_id": kw.get("rid", "r"), "cls": kw.get("cls", "rt"),
        "arrival_s": 0.0, "ttft_s": kw.get("ttft", 0.05),
        "tpot_s": 0.01, "n_tokens": kw.get("n", 10),
        "shed": kw.get("shed", False),
        "cancelled": kw.get("cancelled", False),
        "deadline_s": kw.get("deadline", 0.1),
        "deadline_hit": kw.get("hit")}
    outcomes = [
        mk(rid="hit", hit=True, n=10),
        mk(rid="miss", hit=False, n=10, ttft=0.5),
        mk(rid="free", hit=None, deadline=None, n=10),   # no deadline
        mk(rid="shed", shed=True, hit=None, n=0, ttft=None),
        mk(rid="cxl", cancelled=True, hit=True, n=4),
        {**mk(rid="err", hit=None, n=0, ttft=None),
         "errored": True, "tpot_s": None},
    ]
    rep = conformance_report(wl, outcomes, speed=1.0, mode="test",
                             elapsed_s=2.0, wall_s=2.0,
                             n_preemptions=3)
    # goodput: hit (10) + deadline-free (10) — the miss, the shed,
    # the cancelled and the errored never count — over wall seconds
    assert rep["goodput_tok_s"] == 10.0
    assert rep["total_tok_s"] == 17.0
    assert rep["n_shed"] == 1 and rep["n_cancelled"] == 1
    # an HTTP error is neither a completion nor a shed: counted on
    # its own so a fully-errored run can never read as a valid arm
    assert rep["n_errors"] == 1
    assert rep["error_rate"] == round(1 / 6, 4)
    assert rep["n_completed"] == 3
    assert rep["shed_rate"] == round(1 / 6, 4)
    # 3 judged (hit, miss, and the cancelled request's pre-cancel
    # TTFT hit): 2/3
    assert rep["deadline_hit_rate"] == 0.6667
    assert rep["n_preemptions"] == 3
    assert rep["classes"]["rt"]["n"] == 6
    # an all-shed class reports null percentiles, never fake-perfect
    # 0.0 latencies (which would flag every later REAL measurement
    # as a regression against it)
    shed_only = conformance_report(
        wl, [{**mk(rid="s", shed=True, hit=None, n=0, ttft=None),
              "tpot_s": None}],
        speed=1.0, mode="test", elapsed_s=1.0, wall_s=1.0)
    assert shed_only["classes"]["rt"]["ttft_p50_s"] is None
    assert shed_only["classes"]["rt"]["tpot_p99_s"] is None


def test_max_sustainable_speed_binary_search():
    from torchbooster_tpu.serving.loadgen import max_sustainable_speed

    calls = []

    def run_at(speed):                 # SLOs hold up to x6.5
        calls.append(speed)
        return {"n_shed": 0 if speed <= 6.5 else 3,
                "deadline_hit_rate": 1.0 if speed <= 6.5 else 0.2}

    got = max_sustainable_speed(run_at, lo=1.0, hi=16.0, iters=6)
    assert 5.5 <= got <= 6.5
    assert len(calls) == 8             # lo + hi + 6 bisections
    # degenerate ends answer honestly
    assert max_sustainable_speed(
        lambda s: {"n_shed": 1, "deadline_hit_rate": 0.0},
        lo=1.0, hi=4.0) == 0.0
    assert max_sustainable_speed(
        lambda s: {"n_shed": 0, "deadline_hit_rate": 1.0},
        lo=1.0, hi=4.0) == 4.0
    with pytest.raises(ValueError, match="lo < hi"):
        max_sustainable_speed(run_at, lo=4.0, hi=4.0)


def test_diff_reports_flags_regressions_and_refuses_mismatch():
    from torchbooster_tpu.serving.loadgen import diff_reports

    base = _fake_report()
    # clean: small drift inside tolerance
    assert diff_reports(base, _fake_report(goodput=95.0)) == []
    # regressions: goodput drop, shed rise, per-class p99 rise
    regs = diff_reports(base, _fake_report(goodput=50.0, shed=0.5,
                                           ttft99=0.5, hit=0.4))
    text = "\n".join(regs)
    assert "goodput_tok_s" in text
    assert "shed_rate" in text
    assert "classes.rt.ttft_p99_s" in text
    assert "deadline_hit_rate" in text
    # an IMPROVEMENT is never a regression
    assert diff_reports(base, _fake_report(goodput=500.0,
                                           ttft99=0.001)) == []
    with pytest.raises(ValueError, match="fingerprints differ"):
        diff_reports(base, _fake_report(fp="zzz"))


def test_replay_diff_cli_exit_codes(tmp_path, capsys):
    import scripts.replay_diff as rd

    base, good, bad, other = (tmp_path / n for n in (
        "base.json", "good.json", "bad.json", "other.json"))
    base.write_text(json.dumps(_fake_report()))
    good.write_text(json.dumps(_fake_report(goodput=98.0)))
    bad.write_text(json.dumps(_fake_report(goodput=10.0)))
    other.write_text(json.dumps(_fake_report(fp="zzz")))
    assert rd.main([str(base), str(good)]) == 0
    assert rd.main([str(base), str(bad)]) == 1
    assert rd.main([str(base), str(other)]) == 2   # refused
    assert rd.main([str(base)]) == 2               # usage
    out = capsys.readouterr()
    assert "REGRESSION" in out.out
    assert "NOT COMPARABLE" in out.err


def test_replay_diff_per_class_names_the_regressed_class(tmp_path,
                                                         capsys):
    """--per-class (the ISSUE satellite): the gate names WHICH SLO
    class regressed — per-class comparison blocks, a [REGRESSED]
    marker, and a 'regressed classes:' verdict line — with exit codes
    unchanged vs the aggregate mode."""
    import scripts.replay_diff as rd

    base, bad, good = (tmp_path / n
                       for n in ("base.json", "bad.json", "good.json"))
    base.write_text(json.dumps(_fake_report()))
    # only the rt class regresses (its p99 TTFT blows up); aggregates
    # stay inside tolerance
    bad.write_text(json.dumps(_fake_report(ttft99=0.9)))
    good.write_text(json.dumps(_fake_report(goodput=98.0)))
    assert rd.main([str(base), str(bad), "--per-class"]) == 1
    out = capsys.readouterr().out
    assert "class rt [REGRESSED]" in out
    assert "regressed classes: rt" in out
    assert rd.main([str(base), str(good), "--per-class"]) == 0
    out = capsys.readouterr().out
    assert "regressed classes: none" in out
    # same inputs, aggregate mode: identical exit codes
    assert rd.main([str(base), str(bad)]) == 1
    assert rd.main([str(base), str(good)]) == 0


def test_fingerprints_comparable_and_replay_diff_refuses_a_mismatch(
        tmp_path, capsys):
    """The one comparability predicate (loadgen.report) over its five
    cases: two results may be compared unless BOTH name a workload and
    the names differ. And the gate built on it prints no comparison
    for a pair it refuses, in either mode: exit 2, nothing on stdout
    that a reader could take for an all-clear."""
    import scripts.replay_diff as rd
    from torchbooster_tpu.serving.loadgen.report import (
        fingerprints_comparable)

    cases = [({}, {}, True),
             ({"workload_fingerprint": "a"}, {}, True),
             ({"workload_fingerprint": "a"},
              {"workload_fingerprint": "a"}, True),
             ({"workload_fingerprint": "a"},
              {"workload_fingerprint": "b"}, False),
             (None, {"workload_fingerprint": "a"}, True)]
    for a, b, comparable in cases:
        assert fingerprints_comparable(a, b) is comparable
        assert fingerprints_comparable(b, a) is comparable
    base, other = tmp_path / "base.json", tmp_path / "other.json"
    base.write_text(json.dumps(_fake_report()))
    # the candidate is BETTER on every number, over another trace
    other.write_text(json.dumps(_fake_report(fp="zzz", goodput=500.0)))
    for mode in ([], ["--per-class"]):
        assert rd.main([str(base), str(other), *mode]) == 2
        out = capsys.readouterr()
        assert "NOT COMPARABLE" in out.err
        assert not out.out.strip()


# ---- YAML surface ----------------------------------------------------

def test_loadgen_yaml_block_and_capture_path_knob(tmp_path):
    from torchbooster_tpu.config import FrontendConfig, LoadgenConfig
    from torchbooster_tpu.serving.loadgen import Workload

    yml = tmp_path / "loadgen.yml"
    yml.write_text(
        "source: sharegpt\nn_requests: 5\nrate: 20.0\nseed: 7\n"
        "vocab: 97\nprompt_len: 4, 8\nmax_new_tokens: 3, 6\n"
        "classes: \"rt:1,batch:2\"\ncancel_frac: 0.2\nspeed: 3.0\n")
    lg = LoadgenConfig.load(yml)
    wl = lg.make()
    assert isinstance(wl, Workload)
    assert len(wl) == 5 and lg.speed == 3.0
    # the YAML speed knob actually governs replays: make() records it
    # on the workload and drivers called without speed= read it back
    assert wl.meta["speed"] == 3.0
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.loadgen import replay_inprocess
    params, cfg = _decisive_model()
    res = replay_inprocess(ContinuousBatcher(_engine(params, cfg)), wl)
    assert res.report["speed"] == 3.0
    assert wl.fingerprint() == LoadgenConfig.load(yml).make().fingerprint()
    # a capture file as the source round-trips through the same make()
    path = wl.save(tmp_path / "cap.jsonl")
    wl2 = LoadgenConfig(source=str(path)).make()
    assert wl2.fingerprint() == wl.fingerprint()
    with pytest.raises(ValueError, match="loadgen.source"):
        LoadgenConfig(source="uniform").make()
    # the frontend block grew the capture knobs
    fe = FrontendConfig(capture_path="logs/x.jsonl",
                        capture_scrub=True)
    assert fe.capture_path == "logs/x.jsonl" and fe.capture_scrub
