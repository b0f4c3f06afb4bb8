"""Disaggregated multi-host serving (PR 20) on CPU:

- the framed RPC codec round-trips: ``frame_blob``/``unframe_blob``
  byte-identical to the socket path, ``pack_pages``/``unpack_pages``
  in the host-spill demotion format, ``encode_request``/
  ``decode_request`` preserving the fold contract (``base_len``,
  delivered tokens, terminal flags);
- SOCKET PARITY (the ISSUE acceptance): a fleet of one in-process +
  one loopback-socket replica produces token streams AND a routing
  ``assignment_log`` identical to an all-in-process fleet;
- REPLICA DEATH OVER THE WIRE (the ISSUE satellite): killing the
  server mid-decode re-admits the remote's requests elsewhere with
  no lost or duplicated completions (token streams equal a no-death
  control, request-id-keyed), ``router_readmissions_total`` and the
  fleet ``/metrics`` survive;
- sender-relative readiness staleness: ``FleetHealth`` strikes on
  the wire's ``age_s`` (same-host clock deltas summed across the
  boundary) instead of differencing two hosts' clocks;
- :class:`~torchbooster_tpu.serving.disagg.DisaggPair`: token parity
  vs one unified batcher over the same mixed workload, streamed
  payload bytes EQUAL to ``comms.accounting.disagg_traffic``'s
  closed form, the decode side's one-decode/one-promote compile
  contract (prefill side compiles NO decode executable), loud
  validation, and a dead prefill worker re-raising on the driver;
- the ``longprompt_burst`` loadgen kind: deterministic from its
  seed, fingerprint-identical to ``poisson`` at ``long_frac: 0``,
  burst arrivals and id/priority shape pinned;
- the ``serving.disagg:`` and ``router.replicas:`` YAML blocks
  (build from config, validation loud) and the replica server's
  config builder.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.models.gpt import GPT, GPTConfig

VOCAB = 128
PAGE = 16


def _model(seq_len=128):
    cfg = GPTConfig(vocab=VOCAB, n_layers=2, d_model=32, n_heads=2,
                    seq_len=seq_len)
    params = GPT.init(jax.random.PRNGKey(0), cfg)
    # decisive head: parity assertions must not ride float near-ties
    params = {**params, "wte": {"table": params["wte"]["table"] * 4.0}}
    return params, cfg


_SHARED = {"params": None, "cfg": None}


def _shared_model():
    if _SHARED["params"] is None:
        _SHARED["params"], _SHARED["cfg"] = _model()
    return _SHARED["params"], _SHARED["cfg"]


def _serving_conf(disagg=False, min_prefill_pages=2, **kw):
    from torchbooster_tpu.config import (DisaggConfig, HostSpillConfig,
                                         ServingConfig)

    sc = ServingConfig(page_size=PAGE, n_pages=64, max_slots=4,
                       cache_dtype="int8", prefix_cache=True, **kw)
    sc.host_spill = HostSpillConfig(enabled=True, budget_mb=64.0)
    if disagg:
        sc.disagg = DisaggConfig(enabled=True,
                                 min_prefill_pages=min_prefill_pages)
    return sc


def _make(disagg=False, **kw):
    params, cfg = _shared_model()
    return _serving_conf(disagg=disagg, **kw).make(
        params, cfg, compute_dtype=jnp.float32)


def _pump(srv, reqs, cap=5000):
    srv.start_session()
    for r in reqs:
        srv.submit(r, arrival=0.0)
    n = 0
    while srv.has_work and n < cap:
        srv.step()
        n += 1
    assert n < cap, "drive loop did not drain"
    return srv.finish_session()


# ---- the framed codec ------------------------------------------------

def test_frame_blob_round_trip_and_socket_byte_identity():
    """unframe(frame(x)) == x, truncation is loud, and the in-memory
    blob is byte-identical to what the socket transport carries (the
    disagg accounting rides that equivalence)."""
    import socket

    from torchbooster_tpu.serving.router.rpc import (
        frame_blob, recv_msg, send_msg, unframe_blob)

    header = {"op": "page_stream", "request_id": "r7", "n": 3}
    frames = [b"abc", b"", b"\x00" * 17]
    blob = frame_blob(header, frames)
    h2, f2 = unframe_blob(blob)
    assert {k: h2[k] for k in header} == header
    assert f2 == frames

    a, b = socket.socketpair()
    try:
        sent = send_msg(a, header, frames)
        data = b.recv(1 << 20)
        assert sent == len(data)
        assert data == blob, "socket bytes must equal the blob form"
    finally:
        a.close()
        b.close()

    with pytest.raises(ValueError):
        unframe_blob(blob[:-1])


def test_pack_unpack_pages_demotion_format():
    from torchbooster_tpu.serving.router.rpc import (pack_pages,
                                                     unpack_pages)

    rs = np.random.RandomState(1)
    pages = []
    for p in range(3):
        payload = {
            "k": rs.randint(-120, 120, (2, 4, 2, 8)).astype(np.int8),
            "k_scale": rs.rand(2, 4, 2, 1).astype(np.float32),
            "v": rs.randint(-120, 120, (2, 4, 2, 8)).astype(np.int8),
            "v_scale": rs.rand(2, 4, 2, 1).astype(np.float32)}
        pages.append((f"chain{p}".encode(), payload))
    header, frames = pack_pages(pages)
    assert header["page_bytes"] == sum(
        arr.nbytes for _, pl in pages for arr in pl.values())
    out = unpack_pages(header, frames)
    assert [k for k, _ in out] == [k for k, _ in pages]
    for (_, got), (_, want) in zip(out, pages):
        for name in ("k", "k_scale", "v", "v_scale"):
            np.testing.assert_array_equal(got[name], want[name])


def test_request_codec_preserves_fold_contract():
    """A drained request's folded prompt crosses the wire with its
    ORIGINAL base_len and delivered tokens intact — the exactly-once
    readmission invariant."""
    from torchbooster_tpu.serving.batcher import Request
    from torchbooster_tpu.serving.router.rpc import (decode_request,
                                                     encode_request)

    req = Request(prompt=np.arange(8, dtype=np.int32),
                  max_new_tokens=6, request_id="fold-1",
                  priority="batch", deadline_ms=500)
    # simulate a post-fold mirror: two delivered tokens appended to
    # the prompt, base_len still the original
    req.tokens = [3, 5]
    req.prompt = np.concatenate(
        [req.prompt, np.asarray([3, 5], np.int32)])
    req.first_token_at = 0.25
    head, frames = encode_request(req)
    back = decode_request(head, frames)
    assert back.request_id == "fold-1"
    assert back.base_len == 8
    assert back.tokens == [3, 5]
    assert back.prompt.tolist() == req.prompt.tolist()
    assert back.max_new_tokens == 6
    assert back.priority == "batch" and back.deadline_ms == 500
    assert back.first_token_at == 0.25 and back.finished_at is None


# ---- the closed-form transfer model ----------------------------------

def test_disagg_traffic_formula():
    from torchbooster_tpu.comms.accounting import (disagg_traffic,
                                                   promotion_traffic)

    m = disagg_traffic(41, page_size=4, kv_heads=2, head_dim=8,
                       n_layers=2)
    # (41 - 1) // 4 = 10 full pages; per page K+V int8 over
    # L*ps*kvh*hd elems + fp32 scale per (layer, token, head)
    elems = 2 * 4 * 2
    per_page = 2 * elems * 8 + 2 * elems * 4
    assert m["n_pages"] == 10
    assert m["per_page_bytes"] == per_page
    assert m["total_bytes"] == 10 * per_page
    assert m["prompt_len"] == 41
    # delegation: byte-identical to the promotion model's pages
    p = promotion_traffic(10, page_size=4, kv_heads=2, head_dim=8,
                          n_layers=2)
    assert m["total_bytes"] == p["total_bytes"]
    # sub-page prompts ship nothing (decode re-runs the tail chunk)
    assert disagg_traffic(4, page_size=4, kv_heads=2, head_dim=8,
                          n_layers=2)["total_bytes"] == 0
    with pytest.raises(ValueError):
        disagg_traffic(0, page_size=4, kv_heads=2, head_dim=8,
                       n_layers=2)


# ---- the longprompt_burst workload -----------------------------------

def test_longprompt_burst_base_is_poisson_and_deterministic():
    from torchbooster_tpu.serving.loadgen.workload import synthesize

    kw = dict(n_requests=12, rate=50.0, seed=3, vocab=97,
              prompt_len=(4, 8), max_new_tokens=(2, 4))
    base = synthesize("poisson", **kw)
    off = synthesize("longprompt_burst", long_frac=0.0, **kw)
    assert off.fingerprint() == base.fingerprint(), \
        "long_frac=0 must be byte-identical to poisson"

    a = synthesize("longprompt_burst", long_frac=0.5, period_s=0.1,
                   long_prompt_len=(20, 30), **kw)
    b = synthesize("longprompt_burst", long_frac=0.5, period_s=0.1,
                   long_prompt_len=(20, 30), **kw)
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != base.fingerprint()

    longs = [r for r in a if r.request_id.startswith("w3-L")]
    assert len(longs) == 6  # round(12 * 0.5) extra requests
    assert len(list(a)) == 12 + 6
    for r in longs:
        assert 20 <= len(r.prompt_ids(97)) <= 30
        # mid-window burst arrivals, jitter < 0.05
        frac = (r.arrival_s % 0.1) / 0.1
        assert 0.5 <= frac <= 0.5 + 0.05 / 0.1 + 1e-9


def test_longprompt_burst_validation_loud():
    from torchbooster_tpu.serving.loadgen.workload import synthesize

    with pytest.raises(ValueError, match="long_frac"):
        synthesize("longprompt_burst", long_frac=1.5)
    with pytest.raises(ValueError, match="long_prompt_len"):
        synthesize("longprompt_burst", long_prompt_len=(0, 5))
    with pytest.raises(ValueError, match="period_s"):
        synthesize("longprompt_burst", period_s=0.0)
    # the knobs are inert for other kinds: no validation, no effect
    synthesize("poisson", n_requests=4, long_prompt_len=(0, 5))


# ---- DisaggPair ------------------------------------------------------

def _mixed_requests(seed=5, n_new=6):
    from torchbooster_tpu.serving.batcher import Request

    rs = np.random.RandomState(seed)
    lens = (40, 12, 50, 34, 8, 20)
    prompts = [rs.randint(0, VOCAB, n).astype(np.int32) for n in lens]
    return [Request(prompt=p, max_new_tokens=n_new,
                    request_id=f"r{i}")
            for i, p in enumerate(prompts)]


def test_disagg_pair_parity_bytes_and_compile_contract():
    """The tentpole's conservation laws: identical token streams vs
    one unified batcher, measured payload bytes EQUAL to the closed
    form, and zero new decode-side compiles (pages enter through the
    donated promotion lane; the prefill pool never decodes)."""
    from torchbooster_tpu.comms.accounting import disagg_traffic
    from torchbooster_tpu.serving.disagg import DisaggPair

    uni = _make(disagg=False)
    ra = _mixed_requests()
    _pump(uni, ra)

    pair = _make(disagg=True, min_prefill_pages=2)
    assert isinstance(pair, DisaggPair)
    rb = _mixed_requests()
    metrics = _pump(pair, rb)

    for x, y in zip(ra, rb):
        assert x.tokens == y.tokens, \
            f"{x.request_id}: disaggregation changed its stream"
        assert y.finished_at is not None

    d = metrics["disagg"]
    longs = [r for r in rb
             if (r.base_len - 1) // PAGE >= 2]
    assert d["prefill_requests"] == len(longs) == 3
    assert d["stranded"] == 0
    _, cfg = _shared_model()
    head_dim = cfg.d_model // cfg.n_heads
    model_bytes = sum(
        disagg_traffic(r.base_len, page_size=PAGE,
                       kv_heads=cfg.kv_heads, head_dim=head_dim,
                       n_layers=cfg.n_layers)["total_bytes"]
        for r in longs)
    assert d["page_bytes_streamed"] == model_bytes, \
        "measured payload bytes must EQUAL the closed form"
    assert d["framed_bytes_streamed"] > d["page_bytes_streamed"], \
        "framed blobs carry headers + key frames on top"
    assert d["pages_streamed"] == sum(
        (r.base_len - 1) // PAGE for r in longs)

    de = pair.decode.engine
    assert de.decode_compiles == 1
    assert 1 <= de.prefill_compiles <= 2
    assert de.promote_compiles == 1
    assert pair.prefill.prefill_compiles == 1
    assert pair.prefill.decode_compiles == 0, \
        "the prefill pool must never build a decode executable"


def test_disagg_pair_worker_death_is_loud():
    pair = _make(disagg=True, min_prefill_pages=2)
    pair.start_session()
    pair.prefill.admit_begin = lambda *a, **kw: (_ for _ in ()).throw(
        RuntimeError("prefill chip fell over"))
    [long_req] = [r for r in _mixed_requests() if r.request_id == "r2"]
    pair.submit(long_req, arrival=0.0)
    with pytest.raises(RuntimeError, match="prefill worker died"):
        deadline = time.time() + 30
        while time.time() < deadline:
            pair.step()
            time.sleep(0.005)
    metrics = pair.finish_session()
    assert metrics["disagg"]["stranded"] == 1


def test_disagg_validation_loud():
    from torchbooster_tpu.config import DisaggConfig
    from torchbooster_tpu.serving.disagg import DisaggPair

    params, cfg = _shared_model()
    with pytest.raises(TypeError, match="PagedEngine"):
        DisaggPair(object(), object())

    sc = _serving_conf(disagg=True)
    sc.host_spill.enabled = False
    with pytest.raises(ValueError, match="host_spill"):
        sc.make(params, cfg, compute_dtype=jnp.float32)

    sc = _serving_conf(disagg=True)
    sc.prefix_cache = False
    with pytest.raises(ValueError, match="prefix_cache"):
        sc.make(params, cfg, compute_dtype=jnp.float32)

    sc = _serving_conf(disagg=True)
    sc.disagg = DisaggConfig(enabled=True, min_prefill_pages=0)
    with pytest.raises(ValueError, match="min_prefill_pages"):
        sc.make(params, cfg, compute_dtype=jnp.float32)

    sc = _serving_conf(disagg=True)
    sc.router.n_replicas = 2
    with pytest.raises(ValueError, match="router"):
        sc.make(params, cfg, compute_dtype=jnp.float32)

    # submit-time rejection: a prompt the prefill pool can never hold
    pair = _make(disagg=True, min_prefill_pages=2)
    from torchbooster_tpu.serving.batcher import Request
    pair.start_session()
    with pytest.raises(ValueError):
        pair.submit(Request(
            prompt=np.zeros(4096, np.int32), max_new_tokens=2,
            request_id="too-long"), arrival=0.0)
    pair.finish_session()


# ---- socket-backed replicas ------------------------------------------

def _fleet(members, **kw):
    from torchbooster_tpu.serving.router import EngineFleet

    kw.setdefault("routing", "affinity")
    kw.setdefault("audit", 256)
    return EngineFleet(members, **kw)


def test_socket_replica_parity_tokens_and_assignments():
    """One in-process + one loopback-socket replica vs two in-process
    replicas: identical token streams AND identical assignment_log —
    the router cannot tell a remote from a local."""
    from torchbooster_tpu.serving.replica_server import serve_in_thread
    from torchbooster_tpu.serving.router.audit import (diff_routing,
                                                       routing_artifact)
    from torchbooster_tpu.serving.router.rpc import RemoteReplica

    def run(members):
        fleet = _fleet(members)
        reqs = _mixed_requests(seed=7)
        _pump(fleet, reqs)
        return reqs, list(fleet.assignment_log), \
            routing_artifact(fleet, "parity-trace")

    ra, la, aa = run([_make(), _make()])
    handle = serve_in_thread(_make())
    try:
        rb, lb, ab = run([_make(), RemoteReplica(handle.endpoint,
                                                 replica_id=1)])
    finally:
        handle.stop()

    for x, y in zip(ra, rb):
        assert x.tokens == y.tokens, \
            f"{x.request_id}: the socket changed its stream"
        assert y.finished_at is not None
    assert la == lb, "routing decisions must be wire-invariant"
    assert diff_routing(aa, ab) == [], \
        "replay_diff --routing must see identical decision sequences"


def test_socket_replica_death_readmits_and_metrics_survive():
    """Kill the server mid-decode: the dropped connection is replica
    death — the client folds delivered tokens into each mirror's
    prompt, the router re-admits on the survivor, every request
    completes exactly once with streams equal to a no-death control,
    and /metrics (router_readmissions_total) survives."""
    from torchbooster_tpu.observability.export import prometheus_text
    from torchbooster_tpu.serving.replica_server import serve_in_thread
    from torchbooster_tpu.serving.router.rpc import RemoteReplica

    def run(kill_at_step):
        handle = serve_in_thread(_make())
        fleet = _fleet(
            [_make(), RemoteReplica(handle.endpoint, replica_id=1)],
            routing="round_robin")
        fleet.start_session()
        reqs = _mixed_requests(seed=11, n_new=8)
        for r in reqs:
            fleet.submit(r, arrival=0.0)
        steps = 0
        while fleet.has_work and steps < 5000:
            fleet.step()
            steps += 1
            if steps == kill_at_step:
                handle.kill()
        metrics = fleet.finish_session()
        handle.stop()
        return fleet, reqs, metrics

    _, control, _ = run(kill_at_step=-1)
    fleet, reqs, metrics = run(kill_at_step=3)
    assert fleet.n_live == 1
    by_id = {r.request_id: r for r in reqs}
    for c in control:
        r = by_id[c.request_id]
        assert r.finished_at is not None and not r.cancelled
        assert r.tokens == c.tokens, \
            f"{r.request_id}: server death changed its stream"
    assert metrics["router"]["n_readmitted"] > 0
    assert metrics["n_requests"] == len(reqs)
    txt = prometheus_text()
    assert "router_readmissions_total" in txt
    assert "router_replicas_live" in txt


def test_remote_readiness_age_is_sender_relative():
    """The wire readiness payload ages by SAME-HOST clock deltas on
    each side; no term differences two hosts' clocks. Between probes
    the client-side age grows monotonically without an RPC."""
    from torchbooster_tpu.serving.replica_server import serve_in_thread
    from torchbooster_tpu.serving.router.rpc import RemoteReplica

    handle = serve_in_thread(_make())
    try:
        rep = RemoteReplica(handle.endpoint, replica_id=0)
        rep.start_session()
        rep.step()  # refreshes the cached probe
        r1 = rep.readiness()
        assert "age_s" in r1 and r1["age_s"] >= 0.0
        assert "stamped_s" in r1  # legacy field still present
        time.sleep(0.05)
        r2 = rep.readiness()
        assert r2["age_s"] >= r1["age_s"] + 0.04, \
            "cached payload must age on the client's own clock"
        rep.finish_session()
        rep.close()
    finally:
        handle.stop()


def test_fleet_health_strikes_on_wire_age():
    """FleetHealth's staleness strike reads age_s directly when the
    payload carries it (remote replicas): a frozen step_seq with work
    and an old payload strikes; a fresh payload never does, whatever
    stamped_s says."""
    from torchbooster_tpu.serving.router.health import (DEGRADED,
                                                        FleetHealth,
                                                        HEALTHY)

    class _Stub:
        def __init__(self):
            self.replica_id = 0
            self.alive = True
            self.has_work = True
            self.age = 0.0

        def readiness(self):
            return {"step_seq": 7, "stamped_s": 123.0,
                    "age_s": self.age, "queue_depth": 0,
                    "pages_free": 64, "pages_cached": 0}

    class _Fleet:
        def __init__(self, rep):
            self.replicas = [rep]

    rep = _Stub()
    fleet = _Fleet(rep)
    health = FleetHealth(every=1, degrade_after=1, stale_s=2.0)
    health.observe(fleet)  # records the (seq, stamp) baseline
    rep.age = 0.5
    health.observe(fleet)  # frozen seq, fresh payload: no strike
    assert health.state(0) == HEALTHY
    rep.age = 5.0
    health.observe(fleet)  # frozen seq, old payload: stale strike
    assert health.state(0) == DEGRADED
    assert "stale" in health.snapshot()["last_strikes"][0]


# ---- YAML construction -----------------------------------------------

def test_router_replicas_yaml_builds_and_validates():
    from torchbooster_tpu.serving.router import EngineFleet

    params, cfg = _shared_model()
    sc = _serving_conf()
    sc.router.replicas = ["inproc", "inproc"]
    fleet = sc.make(params, cfg, compute_dtype=jnp.float32)
    assert isinstance(fleet, EngineFleet)
    assert len(fleet.replicas) == 2

    sc = _serving_conf()
    sc.router.replicas = ["carrier-pigeon"]
    with pytest.raises(ValueError, match="replicas"):
        sc.make(params, cfg, compute_dtype=jnp.float32)


def test_replica_server_build_from_config(tmp_path):
    from torchbooster_tpu.serving.batcher import ContinuousBatcher
    from torchbooster_tpu.serving.replica_server import \
        build_from_config

    path = tmp_path / "replica.yml"
    path.write_text(
        "seed: 0\nvocab: 97\nn_layers: 1\nd_model: 16\nn_heads: 2\n"
        "seq_len: 64\n"
        "serving:\n  page_size: 4\n  n_pages: 16\n  max_slots: 2\n")
    batcher = build_from_config(str(path))
    assert isinstance(batcher, ContinuousBatcher)
    assert batcher.engine.page_size == 4

    path.write_text(
        "seq_len: 64\nserving:\n  router:\n    n_replicas: 2\n")
    with pytest.raises(SystemExit, match="ONE batcher"):
        build_from_config(str(path))
