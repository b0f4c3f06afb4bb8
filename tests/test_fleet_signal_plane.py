"""The fleet health & SLO signal plane (this PR's tentpole), on CPU:

- :class:`SLOBurnEngine` unit behavior on a private registry —
  multi-window burn arithmetic over synthetic deadline counters with
  an explicit test clock, the fire/resolve FSM (fast AND slow to
  fire, fast alone to resolve), structured alert events through the
  sink (a broken sink never raises), the goodput-floor alert, and the
  exporter integration (burn gauges land in the SAME JSONL metrics
  snapshot, alert transitions ride alongside);
- :class:`FleetHealth` unit behavior on duck-typed replicas — every
  strike kind (anomaly-by-seq, queue, pages, staleness, dead), the
  one-level-at-a-time hysteresis walk in both directions, the
  ``every`` observation sub-cadence, weights, reset, and the exported
  gauge/counter;
- :class:`RoutingAudit` + the routing artifact — ring bounds, the
  Perfetto router track (pid 3), artifact/diff semantics including
  both rc-2 refusals, and the ``replay_diff --routing`` CLI exit
  codes (0 identical / 1 diverged / 2 refused);
- the PLANE-OFF INVARIANT (the ISSUE acceptance): with
  ``health_aware`` off, running the scorer + audit ring leaves the
  assignment sequence byte-identical to a bare fleet on the same
  workload;
- the fleet behind the front door: ``GET /debug/router`` (200 on a
  fleet, 404 on a single batcher), and the fleet crash dump — ONE
  ``.flight.jsonl`` holding every replica's ring replica-tagged plus
  the router decisions that led up to the death;
- the autoscaler contract (satellite): ``EngineFleet.readiness()``
  and ``finish_session()``'s merged metrics keep stable key sets —
  including the dead-replica row — and the class-histogram merge is
  correct against the per-replica blocks it pooled;
- the ``router.health:`` / ``observability.slo:`` YAML blocks (build
  from config, validation loud).
"""
import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.observability.registry import Registry

from tests.test_router import (
    _batcher,
    _decisive_model,
    _fleet,
    _tenant_workload,
)


# =====================================================================
# SLO burn-rate engine (observability/slo.py)
# =====================================================================

def _burn_engine(reg=None, **kw):
    from torchbooster_tpu.observability.slo import SLOBurnEngine

    kw.setdefault("target", 0.9)          # budget 0.1: burn = 10x rate
    kw.setdefault("fast_window_s", 60.0)
    kw.setdefault("slow_window_s", 600.0)
    return SLOBurnEngine(reg if reg is not None
                         else Registry(enabled=True), **kw)


def _outcomes(reg, cls="rt", hits=0, misses=0):
    """Land synthetic deadline outcomes in the registry — the exact
    series SLOPolicy writes, split across kinds like production."""
    hit = reg.counter("serving_slo_deadline_hit_total", "test")
    miss = reg.counter("serving_slo_deadline_miss_total", "test")
    for n, fam, kind in ((hits, hit, "ttft"), (misses, miss, "tpot")):
        if n:
            fam.inc(n, cls=cls, kind=kind)


def test_slo_burn_engine_validation_is_loud():
    with pytest.raises(ValueError, match="target"):
        _burn_engine(target=1.0)
    with pytest.raises(ValueError, match="fast_window_s"):
        _burn_engine(fast_window_s=600.0, slow_window_s=60.0)
    with pytest.raises(ValueError, match="hysteresis"):
        _burn_engine(fire_burn=1.0, resolve_burn=2.0)


def test_slo_burn_fire_and_resolve_fsm_with_events():
    """The multi-window FSM end to end under an explicit clock: a
    pure-miss window fires (both windows over fire_burn), recovery
    drops the fast window under resolve_burn and resolves — one
    structured event per transition, counters and the active gauge
    tracking each edge."""
    reg = Registry(enabled=True)
    events = []
    eng = _burn_engine(reg, fire_burn=2.0, resolve_burn=1.0,
                       sink=events.append)

    _outcomes(reg, hits=8)
    assert eng.tick(now=0.0) == {("rt", "fast"): 0.0,
                                 ("rt", "slow"): 0.0}, \
        "one sample spans no window: unknown must read as burn 0"
    assert eng.active == {}

    _outcomes(reg, misses=10)              # a pure-miss 30 s window
    burns = eng.tick(now=30.0)
    assert burns[("rt", "fast")] == burns[("rt", "slow")] == 10.0
    assert eng.active == {"rt": True}
    assert eng.n_fired == 1 and eng.n_resolved == 0

    _outcomes(reg, hits=90)                # recovery traffic
    burns = eng.tick(now=90.0)             # miss burst left the fast
    assert burns[("rt", "fast")] < 1.0     # window; slow still burns
    assert eng.active == {"rt": False}
    assert eng.n_fired == 1 and eng.n_resolved == 1

    assert [e["state"] for e in events] == ["firing", "resolved"]
    assert all(e["event"] == "slo_alert" and e["cls"] == "rt"
               for e in events)
    assert events[0]["burn_fast"] == 10.0
    assert events[0]["now_s"] == 30.0      # engine-relative clock

    # the exported surface matches the FSM
    assert reg.gauge("slo_burn_rate", "t").value(
        cls="rt", window="fast") == burns[("rt", "fast")]
    assert reg.gauge("slo_alert_active", "t").value(cls="rt") == 0
    assert reg.counter("slo_alerts_fired_total", "t").value(
        cls="rt") == 1
    assert reg.counter("slo_alerts_resolved_total", "t").value(
        cls="rt") == 1

    snap = eng.snapshot()
    assert set(snap) == {"target", "fast_window_s", "slow_window_s",
                         "fire_burn", "resolve_burn", "n_ticks",
                         "n_fired", "n_resolved", "burns",
                         "goodput_tok_s", "active"}
    assert snap["burns"]["rt/fast"] == burns[("rt", "fast")]


def test_slo_burn_needs_both_windows_over_fire():
    """The slow window vetoes blips: a miss burst that saturates the
    fast window but not the slow one must NOT fire."""
    reg = Registry(enabled=True)
    eng = _burn_engine(reg, fire_burn=2.0)
    _outcomes(reg, hits=1)
    eng.tick(now=0.0)
    _outcomes(reg, hits=999)               # a healthy half-window
    eng.tick(now=500.0)
    _outcomes(reg, misses=60)              # burst in the last 60 s
    burns = eng.tick(now=560.0)
    assert burns[("rt", "fast")] == 10.0   # fast window: all misses
    assert burns[("rt", "slow")] < 2.0     # slow window: 60/1059
    assert eng.active == {}, \
        "a fast-window blip alone must not page anyone"


def test_slo_goodput_floor_alert_inverts_the_comparison():
    """Starved decode throughput fires the fleet-level goodput alert
    under the same FSM (scored as floor/goodput), and recovery
    resolves it."""
    reg = Registry(enabled=True)
    events = []
    eng = _burn_engine(reg, goodput_floor_tok_s=100.0, fire_burn=2.0,
                       resolve_burn=1.0, sink=events.append)
    tok = reg.counter("serving_decode_tokens_total", "t")

    eng.tick(now=0.0)
    tok.inc(300)                           # 10 tok/s: 10x under floor
    eng.tick(now=30.0)
    assert eng.active == {"goodput": True}
    assert eng.goodput == {"fast": 10.0, "slow": 10.0}
    tok.inc(30_000)                        # 1000 tok/s: healthy again
    eng.tick(now=60.0)
    assert eng.active == {"goodput": False}
    assert [e["cls"] for e in events] == ["goodput", "goodput"]
    assert reg.gauge("slo_goodput_tok_s", "t").value(window="fast") \
        > 100.0


def test_slo_burn_sink_failure_never_raises():
    reg = Registry(enabled=True)

    def broken(event):
        raise OSError("disk full")

    eng = _burn_engine(reg, sink=broken)
    _outcomes(reg, hits=1)
    eng.tick(now=0.0)
    _outcomes(reg, misses=10)
    eng.tick(now=30.0)                     # fires -> emits -> raises
    assert eng.n_fired == 1, \
        "the FSM transition must land even when the sink is broken"


def test_slo_burn_disabled_registry_stays_inert():
    reg = Registry(enabled=False)
    eng = _burn_engine(reg)
    assert eng.tick(now=0.0) == {}         # no series, no burns
    assert eng.tick(now=30.0) == {}
    assert eng.snapshot()["n_ticks"] == 2


def test_exporter_ticks_slo_into_the_same_snapshot(tmp_path):
    """MetricsExporter wiring: constructing with an engine auto-wires
    the JSONL sink, and each tick() runs the burn FSM BEFORE writing
    the metrics line — the firing edge and the burn gauges land in
    one snapshot of one file."""
    from torchbooster_tpu.observability.export import MetricsExporter

    reg = Registry(enabled=True)
    eng = _burn_engine(reg, fire_burn=2.0)
    path = tmp_path / "telemetry.jsonl"
    exp = MetricsExporter(reg, jsonl_path=path, slo=eng)
    assert eng.sink is not None, "the exporter must wire the sink"
    try:
        _outcomes(reg, hits=1)
        exp.tick()
        _outcomes(reg, misses=50)
        exp.tick()
    finally:
        exp.stop()
    lines = [json.loads(l) for l in
             path.read_text().splitlines()]
    alerts = [l for l in lines if l.get("event") == "slo_alert"]
    metrics = [l for l in lines if l.get("event") == "metrics"]
    assert len(alerts) == 1 and alerts[0]["state"] == "firing"
    assert any("slo_burn_rate" in json.dumps(m) for m in metrics), \
        "burn gauges must ride the exported registry snapshot"


def test_slo_yaml_block_builds_engine_or_none():
    from torchbooster_tpu.config import SLOBurnConfig

    assert SLOBurnConfig().make() is None, "off by default"
    eng = SLOBurnConfig(enabled=True, target=0.95, fire_burn=3.0,
                        goodput_floor_tok_s=50.0).make()
    assert eng.target == 0.95 and eng.fire_burn == 3.0
    assert eng.goodput_floor_tok_s == 50.0
    with pytest.raises(ValueError, match="target"):
        SLOBurnConfig(enabled=True, target=2.0).make()


# =====================================================================
# per-replica health scoring (serving/router/health.py)
# =====================================================================

class _FakeFlight:
    def __init__(self):
        self.anomalies = []

    def anomaly_log(self):
        return list(self.anomalies)


class _FakeRep:
    """Duck-typed replica: exactly the surface _strikes_for reads."""

    def __init__(self, rid=0):
        self.replica_id = rid
        self.alive = True
        self.has_work = False
        self.batcher = type("B", (), {})()
        self.batcher.flight = _FakeFlight()
        self.ready = {"queue_depth": 0, "pages_free": 8,
                      "pages_cached": 0, "step_seq": 0,
                      "stamped_s": 0.0}

    def readiness(self):
        return dict(self.ready)


class _FakeFleet:
    def __init__(self, *reps):
        self.replicas = list(reps)


def _health(**kw):
    from torchbooster_tpu.serving.router import FleetHealth

    kw.setdefault("registry", Registry(enabled=True))
    kw.setdefault("every", 1)
    kw.setdefault("degrade_after", 2)
    kw.setdefault("recover_after", 2)
    kw.setdefault("queue_limit", 4)
    return FleetHealth(**kw)


def test_health_validation_is_loud():
    with pytest.raises(ValueError, match="every"):
        _health(every=0)
    with pytest.raises(ValueError, match="degrade_after"):
        _health(degrade_after=0)
    with pytest.raises(ValueError, match="queue_limit"):
        _health(queue_limit=0)
    with pytest.raises(ValueError, match="degraded_weight"):
        _health(degraded_weight=8.0, unhealthy_weight=2.0)


def test_health_hysteresis_walks_one_level_per_threshold():
    """2 bad observations per level down, 2 clean per level up — and
    a single bad observation (or a single clean one mid-recovery)
    never moves the state: the anti-flap contract."""
    h = _health()
    rep = _FakeRep()
    fleet = _FakeFleet(rep)

    rep.ready["queue_depth"] = 10          # over queue_limit
    h.observe(fleet)
    assert h.state_name(0) == "healthy"    # 1 strike < degrade_after
    h.observe(fleet)
    assert h.state_name(0) == "degraded"
    assert h.weight(0) == 4.0
    h.observe(fleet)
    h.observe(fleet)
    assert h.state_name(0) == "unhealthy"  # one level at a time
    assert h.weight(0) == 16.0

    rep.ready["queue_depth"] = 0           # recovery
    h.observe(fleet)
    assert h.state_name(0) == "unhealthy"
    h.observe(fleet)
    assert h.state_name(0) == "degraded"
    h.observe(fleet)
    h.observe(fleet)
    assert h.state_name(0) == "healthy"
    assert h.weight(0) == 1.0
    assert h.n_flaps == 4
    snap = h.snapshot()
    assert set(snap) == {"states", "last_strikes", "n_observations",
                         "n_flaps", "every", "degrade_after",
                         "recover_after"}
    assert snap["states"] == {0: "healthy"}

    h.reset()
    assert h.n_flaps == 0 and h.snapshot()["states"] == {}


def test_health_dead_replica_is_immediately_unhealthy():
    h = _health()
    rep = _FakeRep()
    rep.alive = False
    h.observe(_FakeFleet(rep))
    assert h.state_name(0) == "unhealthy"
    assert h.snapshot()["last_strikes"] == {0: ["dead"]}
    assert h.n_flaps == 1


def test_health_strike_kinds_anomaly_pages_stale():
    """Each remaining signal strikes for its own reason — and the
    anomaly cursor advances by seq, so a retained deque entry never
    double-strikes."""
    h = _health(min_free_pages=2, stale_s=1.0)
    rep = _FakeRep()
    fleet = _FakeFleet(rep)

    rep.batcher.flight.anomalies = [{"what": "stall", "seq": 0}]
    h.observe(fleet)
    assert h.snapshot()["last_strikes"] == {0: ["stall"]}
    h.observe(fleet)                       # same deque entry
    assert h.snapshot()["last_strikes"] == {}, \
        "an already-seen anomaly seq must not strike twice"
    rep.batcher.flight.anomalies.append(
        {"what": "recompile", "seq": 1})
    h.observe(fleet)
    assert h.snapshot()["last_strikes"] == {0: ["recompile"]}

    rep.batcher.flight.anomalies = []
    rep.ready.update(pages_free=1, pages_cached=1)   # <= min_free
    h.observe(fleet)
    assert h.snapshot()["last_strikes"] == {0: ["pages"]}
    rep.ready.update(pages_free=8, pages_cached=0)

    # staleness: frozen step_seq + work on the plate + stamp delta
    rep.has_work = True
    rep.ready.update(step_seq=7, stamped_s=10.0)
    h.observe(fleet)                       # baseline stamp, no strike
    rep.ready["stamped_s"] = 11.5
    h.observe(fleet)
    assert h.snapshot()["last_strikes"] == {0: ["stale"]}
    rep.ready.update(step_seq=8, stamped_s=12.0)     # progress again
    h.observe(fleet)
    assert h.snapshot()["last_strikes"] == {}


def test_health_every_subcadence_and_metrics():
    reg = Registry(enabled=True)
    h = _health(registry=reg, every=3, degrade_after=1)
    rep = _FakeRep()
    rep.ready["queue_depth"] = 10
    fleet = _FakeFleet(rep)
    h.observe(fleet)
    h.observe(fleet)
    assert h.n_observations == 0, "ticks 1-2 of every=3 must skip"
    h.observe(fleet)
    assert h.n_observations == 1
    assert h.state_name(0) == "degraded"
    assert reg.gauge("router_replica_health", "t").value(
        replica="0") == 1
    assert reg.counter("router_health_transitions_total", "t").value(
        replica="0", to="degraded") == 1


def test_health_yaml_block_builds_scorer_and_validates():
    from torchbooster_tpu.config import RouterConfig, RouterHealthConfig

    assert RouterHealthConfig().make() is None, "off by default"
    h = RouterHealthConfig(enabled=True, every=3, queue_limit=9).make()
    assert h.every == 3 and h.queue_limit == 9
    rc = RouterConfig(n_replicas=2, health_aware=True)
    with pytest.raises(ValueError, match="health_aware"):
        rc.make([])                        # no scorer to consult
    with pytest.raises(ValueError, match="degrade_after"):
        RouterHealthConfig(enabled=True, degrade_after=0).make()


# =====================================================================
# routing audit trail (serving/router/audit.py) + replay_diff gate
# =====================================================================

def _decision(i, replica=0, reason="round_robin"):
    return {"seq": i, "request_id": f"r{i}", "arrival": i * 0.25,
            "replica": replica, "reason": reason, "key": None,
            "candidates": []}


def test_audit_ring_bounds_and_tail():
    from torchbooster_tpu.serving.router import RoutingAudit

    with pytest.raises(ValueError, match="capacity"):
        RoutingAudit(0)
    ring = RoutingAudit(capacity=4)
    for i in range(10):
        ring.record(_decision(i))
    assert len(ring) == 4 and ring.n_records == 10
    assert [r["seq"] for r in ring.tail()] == [6, 7, 8, 9]
    assert [r["seq"] for r in ring.tail(2)] == [8, 9]
    ring.reset()
    assert len(ring) == 0 and ring.n_records == 0


def test_chrome_router_events_pid3_track():
    from torchbooster_tpu.serving.router import chrome_router_events

    assert chrome_router_events([]) == []
    events = chrome_router_events(
        [_decision(0, replica=1), _decision(1, replica=0)])
    meta = [e for e in events if e["ph"] == "M"]
    assert {(e["name"], e["tid"]) for e in meta} == {
        ("process_name", 0), ("thread_name", 0), ("thread_name", 1)}
    assert all(e["pid"] == 3 for e in events)
    instants = [e for e in events if e["ph"] == "i"]
    assert [e["tid"] for e in instants] == [1, 0]
    assert instants[1]["ts"] == 0.25 * 1e6
    assert instants[0]["args"]["request_id"] == "r0"


def _artifact(assignments, fingerprint="fp", policy="round_robin",
              n_replicas=2):
    return {"version": 1, "kind": "routing",
            "workload_fingerprint": fingerprint, "policy": policy,
            "n_replicas": n_replicas, "n_routed": len(assignments),
            "assignments": [list(a) for a in assignments],
            "reasons": []}


def test_diff_routing_semantics_and_refusals():
    from torchbooster_tpu.serving.router import diff_routing

    base = _artifact([("a", 0), ("b", 1), ("c", 0)])
    assert diff_routing(base, _artifact([("a", 0), ("b", 1),
                                         ("c", 0)])) == []
    lines = diff_routing(base, _artifact([("a", 0), ("b", 0),
                                          ("c", 0)]))
    assert lines == ["decision 1: b -> replica 1 became "
                     "b -> replica 0"]
    lines = diff_routing(base, _artifact([("a", 0)], policy="affinity",
                                         n_replicas=3))
    assert any(l.startswith("policy:") for l in lines)
    assert any(l.startswith("n_replicas:") for l in lines)
    assert any(l.startswith("decision count:") for l in lines)
    # the divergence list is bounded, with an explicit elision line
    many = [(f"r{i}", 0) for i in range(30)]
    flipped = [(f"r{i}", 1) for i in range(30)]
    lines = diff_routing(_artifact(many), _artifact(flipped),
                         max_lines=5)
    assert len(lines) == 6 and lines[-1] == \
        "... and 25 more divergences"
    with pytest.raises(ValueError, match="not a routing artifact"):
        diff_routing({"kind": "tokens"}, base)
    with pytest.raises(ValueError, match="fingerprints differ"):
        diff_routing(base, _artifact([("a", 0)], fingerprint="other"))


def test_replay_diff_routing_cli_exit_codes(tmp_path, capsys):
    """The shipped gate: rc 0 identical, rc 1 diverged, rc 2 refused
    (fingerprint mismatch AND unreadable file)."""
    from scripts.replay_diff import main

    base = _artifact([("a", 0), ("b", 1)])
    paths = {}
    for name, art in (
            ("base", base),
            ("same", _artifact([("a", 0), ("b", 1)])),
            ("flip", _artifact([("a", 1), ("b", 1)])),
            ("foreign", _artifact([("a", 0), ("b", 1)],
                                  fingerprint="other"))):
        p = tmp_path / f"{name}.json"
        p.write_text(json.dumps(art))
        paths[name] = str(p)
    assert main([paths["base"], paths["same"], "--routing"]) == 0
    assert "routing identical" in capsys.readouterr().out
    assert main([paths["base"], paths["flip"], "--routing"]) == 1
    assert "ROUTING DIVERGED" in capsys.readouterr().out
    assert main([paths["base"], paths["foreign"], "--routing"]) == 2
    assert "NOT COMPARABLE" in capsys.readouterr().err
    assert main([paths["base"], str(tmp_path / "absent.json"),
                 "--routing"]) == 2
    assert main([paths["base"], "--routing"]) == 2   # usage error


# =====================================================================
# the plane on a real fleet: byte-identity, audit content, debug
# =====================================================================

def _plane_fleet(n=2, **kw):
    """A fleet with the full signal plane attached (audit ring +
    health scorer, health_aware OFF unless asked)."""
    from torchbooster_tpu.serving import EngineFleet
    from torchbooster_tpu.serving.router import FleetHealth

    kw.setdefault("audit", 64)
    kw.setdefault("health", FleetHealth(
        every=2, registry=Registry(enabled=False)))
    return EngineFleet([_batcher() for _ in range(n)],
                       routing="affinity", **kw)


def test_signal_plane_off_routing_is_byte_identical():
    """THE acceptance invariant: scorer observing + audit recording
    with health_aware off must not move a single routing decision
    relative to a bare fleet on the same workload."""
    from torchbooster_tpu.serving.loadgen import replay_inprocess

    from torchbooster_tpu.serving import EngineFleet

    wl = _tenant_workload(n=12, tenants=2)
    bare = EngineFleet([_batcher() for _ in range(2)],
                       routing="affinity", audit=0)
    replay_inprocess(bare, wl, speed=1.0)

    plane = _plane_fleet(n=2)
    replay_inprocess(plane, wl, speed=1.0)
    assert plane.assignment_log == bare.assignment_log, \
        "the observing plane changed a routing decision"
    assert plane.health.n_observations > 0, \
        "the scorer must actually have been observing"
    assert len(plane.audit) > 0


def test_audit_records_carry_the_load_picture():
    from torchbooster_tpu.serving.loadgen import replay_inprocess

    fleet = _plane_fleet(n=2)
    replay_inprocess(fleet, _tenant_workload(n=8, tenants=2),
                     speed=1.0)
    recs = fleet.audit.tail()
    assert [r["seq"] for r in recs] == list(range(len(recs)))
    for rec in recs:
        assert set(rec) == {"seq", "request_id", "arrival", "replica",
                            "reason", "key", "candidates", "health",
                            "adapter"}
        assert rec["adapter"] == ""      # base traffic records ""
        assert rec["reason"] in {"affinity", "bind", "spill",
                                 "least_loaded", "directory"}
        for cand in rec["candidates"]:
            assert set(cand) == {"replica", "queue_depth", "inflight",
                                 "slack_s", "affinity_pages"}
        assert set(rec["health"].values()) <= {"healthy", "degraded",
                                               "unhealthy"}
    # the audit tail IS the artifact's reason block
    by_id = {r["request_id"]: r["replica"] for r in recs}
    for rid, rep in fleet.assignment_log:
        assert by_id[rid] == rep

    stats = fleet.router_stats()
    assert stats["audit"] == {"capacity": 64, "depth": len(recs),
                              "n_records": len(recs)}
    assert stats["health_aware"] is False
    assert stats["health"]["n_observations"] > 0
    payload = fleet.debug_router(tail=3)
    assert set(payload) == {"router", "decisions"}
    assert len(payload["decisions"]) == 3

    # a new session clears the plane with the rest of router state
    fleet.start_session()
    assert len(fleet.audit) == 0 and fleet.audit.n_records == 0
    assert fleet.health.n_observations == 0
    fleet.finish_session()


def test_fleet_signal_plane_constructor_validation():
    from torchbooster_tpu.serving import EngineFleet

    with pytest.raises(ValueError, match="audit"):
        EngineFleet([_batcher()], audit=-1)
    with pytest.raises(ValueError, match="health_aware"):
        EngineFleet([_batcher()], health_aware=True)
    fleet = EngineFleet([_batcher()], audit=0)
    assert fleet.audit is None
    assert fleet.debug_router()["decisions"] == []
    assert fleet.router_stats()["audit"] is None


def test_routing_artifact_round_trip_on_a_real_fleet():
    from torchbooster_tpu.serving.loadgen import replay_inprocess
    from torchbooster_tpu.serving.router import (diff_routing,
                                                 routing_artifact)

    wl = _tenant_workload(n=8, tenants=2)
    arts = []
    for _ in range(2):
        fleet = _plane_fleet(n=2)
        replay_inprocess(fleet, wl, speed=1.0)
        arts.append(routing_artifact(fleet, wl.fingerprint()))
    assert diff_routing(*arts) == [], \
        "two replays of one workload must produce one artifact"
    assert arts[0]["n_routed"] == len(arts[0]["assignments"]) > 0
    assert {r["request_id"] for r in arts[0]["reasons"]} == \
        {rid for rid, _ in arts[0]["assignments"]}


# =====================================================================
# the front door: GET /debug/router + the fleet crash dump
# =====================================================================

def test_debug_router_endpoint_fleet_200_batcher_404():
    from tests.test_frontend import _get, _unary
    from torchbooster_tpu.serving.frontend import ServingFrontend

    async def scenario():
        fleet = _plane_fleet(n=2)
        fe = ServingFrontend(fleet, port=0)
        await fe.start()
        status, _, _ = await _unary(
            fe.port, "/v1/completions",
            {"prompt": [1, 2, 3, 4, 5], "max_tokens": 3})
        assert status == 200
        status, raw = await _get(fe.port, "/debug/router")
        body = json.loads(raw.split(b"\r\n\r\n")[-1] or raw)
        status_t, raw = await _get(fe.port, "/debug/router?tail=1")
        tail1 = json.loads(raw.split(b"\r\n\r\n")[-1] or raw)
        await fe.stop()

        b = _batcher()
        fe = ServingFrontend(b, port=0)
        await fe.start()
        status_single, raw = await _get(fe.port, "/debug/router")
        err = json.loads(raw.split(b"\r\n\r\n")[-1] or raw)
        await fe.stop()
        return status, body, status_t, tail1, status_single, err

    status, body, status_t, tail1, status_single, err = \
        asyncio.run(scenario())
    assert status == 200
    assert set(body) == {"router", "decisions"}
    assert body["router"]["policy"] == "affinity"
    assert len(body["decisions"]) >= 1
    assert status_t == 200 and len(tail1["decisions"]) == 1
    assert status_single == 404
    assert "single batcher" in err["error"]["message"]


def test_fleet_crash_dump_tags_replicas_and_audit(tmp_path):
    """Pump death on a fleet leaves ONE post-mortem file: the fleet
    header, every replica's flight ring replica-tagged, and the
    router decisions that placed the dying work."""
    from tests.test_frontend import _unary
    from torchbooster_tpu.serving.frontend import ServingFrontend

    fleet = _plane_fleet(n=1)
    fe = ServingFrontend(fleet, port=0,
                         crash_dump_path=str(tmp_path / "crash"))

    async def run():
        await fe.start()

        def boom(*launch):
            raise RuntimeError("synthetic replica death")

        engine = fleet.replicas[0].batcher.engine
        engine.step = engine.step_ahead = boom
        status, _, _ = await _unary(
            fe.port, "/v1/completions",
            {"prompt": [1, 2, 3], "max_tokens": 4})
        assert status == 500
        with pytest.raises(RuntimeError, match="synthetic"):
            await fe.stop()

    asyncio.run(run())
    assert set(fe.last_flight) == {"replicas", "router_audit"}
    assert fe.last_flight["router_audit"], \
        "the routed-then-died request must be in the audit tail"
    lines = [json.loads(l) for l in
             (tmp_path / "crash.flight.jsonl").read_text()
             .splitlines()]
    assert lines[0]["event"] == "fleet_flight_header"
    assert lines[0]["n_replicas"] == 1
    assert lines[0]["n_audit"] == len(fe.last_flight["router_audit"])
    events = {l["event"] for l in lines}
    assert {"flight_header", "flight_step",
            "router_decision"} <= events
    assert all("replica" in l for l in lines
               if l["event"].startswith("flight_"))
    decisions = [l for l in lines if l["event"] == "router_decision"]
    assert decisions[-1]["replica"] == 0


def test_fleet_write_chrome_merges_router_track(tmp_path):
    from torchbooster_tpu.observability.tracing import RequestTracer
    from torchbooster_tpu.serving import EngineFleet
    from torchbooster_tpu.serving.loadgen import replay_inprocess

    tracer = RequestTracer(enabled=True)
    from torchbooster_tpu.serving import ContinuousBatcher, PagedEngine
    from tests.test_router import _SHARED

    if _SHARED["params"] is None:
        _SHARED["params"], _SHARED["cfg"] = _decisive_model()
    batchers = [ContinuousBatcher(
        PagedEngine(_SHARED["params"], _SHARED["cfg"], page_size=4,
                    n_pages=24, max_slots=2,
                    compute_dtype=jnp.float32), tracer=tracer)
        for _ in range(2)]
    fleet = EngineFleet(batchers, routing="round_robin", audit=64)
    replay_inprocess(fleet, _tenant_workload(n=6, tenants=2),
                     speed=1.0)
    fleet.write_chrome(tmp_path / "fleet.trace.json")
    trace = json.loads((tmp_path / "fleet.trace.json").read_text())
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert 3 in pids, "the router track must ride the merged trace"
    assert pids - {3}, "the request/engine tracks must survive"
    router_events = [e for e in trace["traceEvents"]
                     if e["pid"] == 3 and e["ph"] == "i"]
    assert len(router_events) == len(fleet.assignment_log)


# =====================================================================
# the autoscaler contract (satellite): stable schemas + merge math
# =====================================================================

_READINESS_ROW_KEYS = {"status", "queue_depth", "pages_free",
                       "pages_cached", "pages_host", "inflight",
                       "occupancy", "est_step_s", "step_seq",
                       "stamped_s", "replica", "alive"}
_MERGED_KEYS = {"n_requests", "new_tokens", "elapsed_s",
                "decode_tok_s", "total_tok_s", "latency_mean_s",
                "latency_p95_s", "ttft_mean_s", "n_admissions",
                "n_preemptions", "n_prefill_chunks",
                "prefix_hit_pages", "n_shed", "n_cancelled",
                "deadline_hit_rate", "router", "replicas", "classes"}


def test_fleet_readiness_schema_is_stable_with_a_dead_replica():
    """The autoscaler reads readiness() on a poll loop: its key set —
    top level AND per-replica rows, dead replicas included — is a
    wire contract, not an implementation detail."""
    fleet = _fleet(n=2)
    fleet.start_session()
    fleet.kill(0)
    ready = fleet.readiness()
    fleet.finish_session()
    assert set(ready) == {"status", "replicas_live", "replicas_total",
                          "queue_depth", "pages_free", "pages_cached",
                          "inflight", "occupancy", "est_step_s",
                          "replicas"}
    assert ready["status"] == "ok" and ready["replicas_live"] == 1
    assert len(ready["replicas"]) == 2, \
        "the dead replica's row must stay in the payload"
    for row in ready["replicas"]:
        assert set(row) == _READINESS_ROW_KEYS
    dead = [r for r in ready["replicas"] if not r["alive"]]
    assert [r["replica"] for r in dead] == [0]
    # the aggregates only pool LIVE replicas
    live_row = next(r for r in ready["replicas"] if r["alive"])
    assert ready["pages_free"] == live_row["pages_free"]


def test_merged_metrics_schema_and_histogram_merge_correctness():
    """finish_session()'s fleet merge: stable top-level keys, counters
    sum, percentiles conservative (max over replicas), means
    completion-weighted — all re-derivable from the per-replica
    blocks the payload itself carries."""
    from torchbooster_tpu.serving.frontend import (SLOPolicy,
                                                   parse_classes)
    from torchbooster_tpu.serving.loadgen import replay_inprocess

    fleet = _fleet(
        n=2, routing="round_robin",
        policy_factory=lambda: SLOPolicy(
            parse_classes("rt:60000:0,batch:0:0"), default="batch"))
    res = replay_inprocess(
        fleet, _tenant_workload(n=10, tenants=2), speed=1.0)
    m = res.metrics
    assert set(m) == _MERGED_KEYS
    reps = [r for r in m["replicas"] if r]
    assert len(reps) == 2
    assert m["new_tokens"] == sum(r["new_tokens"] for r in reps)
    assert m["n_admissions"] == sum(r["n_admissions"] for r in reps)
    assert m["elapsed_s"] == round(
        max(r["elapsed_s"] for r in reps), 4)
    assert m["latency_p95_s"] == round(
        max(r["latency_p95_s"] for r in reps), 4)
    assert m["n_requests"] == len({rid for rid, _
                                   in fleet.assignment_log})
    # completion-weighted mean, rebuilt from the replica blocks
    wsum = sum(r["n_requests"] for r in reps)
    expect = sum(r["latency_mean_s"] * r["n_requests"]
                 for r in reps) / wsum
    assert m["latency_mean_s"] == pytest.approx(expect, abs=1e-3)
    # per-class histogram merge: counts POOL, percentiles take the
    # conservative max over the replicas that saw the class
    for cls, blk in m["classes"].items():
        per = [r["classes"][cls] for r in reps
               if cls in (r.get("classes") or {})]
        assert blk["n_requests"] == sum(p["n_requests"] for p in per)
        assert blk["n_completed"] == sum(p["n_completed"]
                                         for p in per)
        for q in ("ttft_p50_s", "ttft_p99_s",
                  "tpot_p50_s", "tpot_p99_s"):
            assert blk[q] == max((p[q] or 0.0) for p in per)
    assert "batch" in m["classes"], \
        "the default class's block must appear"
    assert set(m["classes"]) <= {"rt", "batch"}


def test_merged_metrics_schema_survives_a_dead_replica():
    """A replica lost mid-session still leaves the merged payload
    schema-stable: the survivors' numbers land, the dead replica's
    block degrades to {} in `replicas` rather than vanishing."""
    from torchbooster_tpu.serving.batcher import Request
    from torchbooster_tpu.serving.loadgen import ReplayClock

    fleet = _fleet(n=2, routing="round_robin")
    clock = ReplayClock()
    fleet.clock = clock
    fleet.start_session()
    rs = np.random.RandomState(5)
    for i in range(4):
        fleet.submit(Request(
            prompt=rs.randint(0, 97, 6).astype(np.int32),
            max_new_tokens=4, request_id=f"r{i}"), arrival=0.0)
    steps = 0
    while fleet.has_work and steps < 2000:
        fleet.step()
        clock.advance(0.005)
        steps += 1
        if steps == 3:
            fleet.kill(0)
    m = fleet.finish_session()
    assert set(m) == _MERGED_KEYS
    assert len(m["replicas"]) == 2
    assert m["n_requests"] == 4
    assert set(m["router"]) == {
        "policy", "n_replicas", "replicas_live", "n_routed",
        "n_affinity_hits", "n_spills", "n_directory_hits",
        "n_directory_evictions", "n_readmitted", "n_rebalanced",
        "n_pending", "directory", "audit", "health_aware", "health"}
    assert m["router"]["replicas_live"] == 1
    assert m["router"]["n_readmitted"] > 0


def test_router_yaml_health_and_audit_blocks_build(tmp_path):
    from torchbooster_tpu.config import ServingConfig
    from torchbooster_tpu.serving import EngineFleet
    from tests.test_router import _SHARED

    if _SHARED["params"] is None:
        _SHARED["params"], _SHARED["cfg"] = _decisive_model()
    path = tmp_path / "serve.yml"
    path.write_text(
        "page_size: 4\nn_pages: 24\nmax_slots: 2\n"
        "router:\n  n_replicas: 2\n  policy: affinity\n"
        "  audit: 32\n  health_aware: true\n"
        "  health:\n    enabled: true\n    every: 2\n"
        "    queue_limit: 8\n")
    sc = ServingConfig.load(path)
    fleet = sc.make(_SHARED["params"], _SHARED["cfg"],
                    compute_dtype=jnp.float32)
    assert isinstance(fleet, EngineFleet)
    assert fleet.audit.capacity == 32
    assert fleet.health_aware is True
    assert fleet.health.every == 2 and fleet.health.queue_limit == 8
    assert fleet.routing.health is fleet.health, \
        "health_aware must hand the scorer to the routing policy"

    # loud refusal: health_aware with no scorer configured
    path.write_text(
        "page_size: 4\nn_pages: 24\nmax_slots: 2\n"
        "router:\n  n_replicas: 2\n  health_aware: true\n")
    with pytest.raises(ValueError, match="health_aware"):
        ServingConfig.load(path).make(
            _SHARED["params"], _SHARED["cfg"],
            compute_dtype=jnp.float32)
