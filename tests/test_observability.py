"""Telemetry subsystem (torchbooster_tpu/observability) tests:

- registry semantics: counters/gauges/histograms, labels, disabled
  no-op, deferred device scalars (no per-step sync), thread safety;
- spans: nesting, event emission, exception transparency;
- recompile sentinel: budgeted first compile, the three policies, and
  a DELIBERATE recompile inside a watched region (the acceptance
  scenario);
- instrumenting a compiled step adds ZERO new compiles;
- exporters: JSONL events, Prometheus text format, cadence thread;
- ObservabilityConfig YAML block + LogCallback drain;
- the instrumented serving batcher: registry counters agree with the
  (newly stable) ``run()`` metric keys through admission AND
  preemption paths;
- the import-time logging satellite: importing the package must not
  clobber a pre-configured root logger (subprocess tests).
"""
from __future__ import annotations

import json
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchbooster_tpu import observability as obs
from torchbooster_tpu.observability.registry import Registry


@pytest.fixture
def reg():
    """A private enabled registry (global default stays untouched)."""
    return Registry(enabled=True)


@pytest.fixture
def global_obs():
    """Enable the process default registry for the test, restore after."""
    registry = obs.get_registry()
    was = registry.enabled
    registry.reset()
    registry.enabled = True
    yield registry
    registry.enabled = was
    registry.reset()


# =====================================================================
# registry
# =====================================================================

def test_counter_gauge_histogram_and_labels(reg):
    c = reg.counter("reqs_total")
    c.inc()
    c.inc(2, kv="4")
    g = reg.gauge("slots")
    g.set(3)
    g.set(5)
    h = reg.histogram("lat_s")
    for v in (0.01, 0.03, 0.5):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["reqs_total"] == 1.0
    assert snap["reqs_total{kv=4}"] == 2.0          # separate series
    assert snap["slots"] == 5.0                      # last value wins
    assert snap["lat_s_count"] == 3.0
    assert snap["lat_s_sum"] == pytest.approx(0.54)
    assert snap["lat_s_mean"] == pytest.approx(0.18)
    assert snap["lat_s_p95"] == pytest.approx(h.percentile(95))
    assert h.mean() == pytest.approx(0.18)
    assert h.percentile(100) == pytest.approx(0.5)


def test_disabled_registry_is_noop():
    reg = Registry(enabled=False)
    reg.counter("c").inc(100)
    reg.gauge("g").set(5)
    reg.histogram("h").observe(1.0)
    assert reg.snapshot() == {}


def test_device_scalars_stay_deferred_until_read(reg):
    """The core no-per-step-sync contract: observations queue the raw
    jax array; nothing is host-read until the registry is read."""
    h = reg.histogram("loss")
    series = h.labels()
    for i in range(4):
        h.observe(jnp.asarray(float(i)))
    assert len(series.pending) == 4       # still un-materialized
    assert series.count == 0
    assert reg.snapshot()["loss_count"] == 4.0
    assert series.pending == []           # drained exactly at the read


def test_unread_backlog_is_bounded(reg):
    """An enabled registry nobody reads must not leak: past
    _MAX_PENDING queued observations a series self-drains in place."""
    from torchbooster_tpu.observability.registry import _MAX_PENDING

    h = reg.histogram("hot")
    series = h.labels()
    for i in range(_MAX_PENDING * 2 + 7):
        h.observe(0.01)
    assert len(series.pending) < _MAX_PENDING     # auto-drained
    assert series.count >= _MAX_PENDING * 2       # nothing lost
    assert reg.snapshot()["hot_count"] == _MAX_PENDING * 2 + 7


def test_metric_kind_collision_raises(reg):
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")


def test_registry_thread_safety(reg):
    c = reg.counter("n")
    h = reg.histogram("v")

    def worker():
        for _ in range(500):
            c.inc()
            h.observe(0.01)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["n"] == 8 * 500
    assert snap["v_count"] == 8 * 500


# =====================================================================
# spans
# =====================================================================

def test_span_nesting_events_histogram(reg):
    events = []
    unsub = obs.span_events_subscribe(events.append)
    try:
        with obs.span("outer", reg):
            with obs.span("inner", reg):
                pass
    finally:
        unsub()
    assert [(e["name"], e["path"], e["depth"]) for e in events] == [
        ("inner", "outer/inner", 1), ("outer", "outer", 0)]
    assert all(e["ok"] for e in events)
    snap = reg.snapshot()
    assert snap["span_seconds{name=outer}_count"] == 1.0
    assert snap["span_seconds{name=inner}_count"] == 1.0


def test_span_disabled_is_shared_noop():
    disabled = Registry(enabled=False)
    s1, s2 = obs.span("a", disabled), obs.span("b", disabled)
    assert s1 is s2                       # the no-op singleton
    with s1:
        pass
    assert disabled.snapshot() == {}


def test_span_exception_transparent(reg):
    events = []
    unsub = obs.span_events_subscribe(events.append)
    try:
        with pytest.raises(ValueError):
            with obs.span("bad", reg):
                raise ValueError("boom")
    finally:
        unsub()
    assert events[0]["name"] == "bad" and events[0]["ok"] is False
    # the span stack unwound: a following span sits at depth 0
    with obs.span("after", reg):
        assert obs.spans.current_span_path() == "after"


# =====================================================================
# recompile sentinel
# =====================================================================

def test_sentinel_budgeted_first_compile_then_steady(reg):
    f = jax.jit(lambda x: x * 2)
    with obs.RecompileSentinel(f, expected=1, name="warm",
                               registry=reg) as s:
        f(jnp.ones(3))
    assert s.extra == 0
    with obs.RecompileSentinel(f, on_recompile="raise", name="steady",
                               registry=reg) as s:
        f(jnp.ones(3))                    # cache hit: no compile
    assert s.extra == 0
    assert "recompiles_total" not in str(reg.snapshot())


def test_sentinel_counts_warns_raises_on_deliberate_recompile(reg, caplog):
    """The acceptance scenario: deliberately trigger a recompile inside
    a watched region and check each on_recompile policy."""
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(3))

    # ignore: counted, no log, no raise
    with obs.RecompileSentinel(f, on_recompile="ignore", name="r1",
                               registry=reg) as s:
        f(jnp.ones((2, 2)))               # new shape -> recompile
    assert s.extra == 1
    assert reg.snapshot()["recompiles_total{region=r1}"] == 1.0

    # warn: logged
    import logging

    with caplog.at_level(logging.WARNING):
        with obs.RecompileSentinel(f, on_recompile="warn", name="r2",
                                   registry=reg):
            f(jnp.ones((3, 3)))
    assert any("recompile sentinel [r2]" in r.message
               for r in caplog.records)

    # raise: RecompileError
    with pytest.raises(obs.RecompileError, match="r3"):
        with obs.RecompileSentinel(f, on_recompile="raise", name="r3",
                                   registry=reg):
            f(jnp.ones((4, 4)))


def test_sentinel_policy_validation():
    with pytest.raises(ValueError, match="on_recompile"):
        obs.RecompileSentinel([], on_recompile="explode")


def test_sentinel_accepts_count_callables(reg):
    calls = [0]
    with obs.RecompileSentinel(lambda: calls[0], on_recompile="ignore",
                               name="cb", registry=reg) as s:
        calls[0] = 3
    assert s.extra == 3


def test_instrument_step_adds_zero_compiles(global_obs):
    """Wrapping a warm compiled step with telemetry must not perturb
    its jit cache — the <2%-overhead claim's compile half, checked the
    same way the bench obs A/B checks it."""
    from torchbooster_tpu.utils import TrainState, instrument_step, make_step

    def loss(p, b, rng):
        return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2), {}

    tx = optax.sgd(1e-2)
    step = make_step(loss, tx)
    batch = {"x": jnp.ones((8, 4)), "y": jnp.ones((8, 1))}

    def fresh():
        return TrainState.create({"w": jnp.zeros((4, 1))}, tx)

    state = fresh()
    state, _ = step(state, batch)         # warm (the one real compile)
    instrumented = instrument_step(step)
    with obs.RecompileSentinel(step, on_recompile="raise",
                               name="train") as s:
        state2 = fresh()
        for _ in range(3):
            state2, _ = instrumented(state2, batch)
    assert s.extra == 0
    snap = global_obs.snapshot()
    assert snap["steps_total{step=train_step}"] == 3.0
    assert snap["step_seconds{step=train_step}_count"] == 3.0


# =====================================================================
# compile accounting (recompile.install_compile_listener)
# =====================================================================

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
SETUP_READERS = ("setup_lower_s", "setup_load_s", "setup_compile_s",
                 "setup_programs", "setup_build_s")


@pytest.fixture
def compiles():
    """The default registry switched on as a program does
    (``set_enabled``, which installs the listener), reset around."""
    registry = obs.get_registry()
    was = registry.enabled
    registry.reset()
    obs.set_enabled(True)
    yield registry
    registry.enabled = was
    registry.reset()


@pytest.fixture
def own_cache(tmp_path):
    """A persistent compilation cache of the test's own that keeps
    every executable; the process's cache settings restored after."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {name: getattr(jax.config, name) for name in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jc"))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


def _booked(registry, programs) -> dict:
    """``{(stage, program, span): count}`` of ``compile_seconds`` for
    the named programs."""
    out = {}
    for metric in registry.metrics():
        if metric.name != "compile_seconds":
            continue
        for key, series in metric.series_items():
            labels = dict(key)
            if labels["program"] in programs:
                out[labels["stage"], labels["program"],
                    labels["span"]] = series.read()[0]
    return out


def _nested_program():
    """A program that calls a jitted function and jnp ops: each is
    traced inside the program's own trace, none lowered alone."""

    @jax.jit
    def acc_inner(x):
        return jnp.sin(x) * 2

    def acc_outer(x):
        return acc_inner(x) @ x.T + jnp.cos(x).sum()

    return jax.jit(acc_outer)


NESTED = ("acc_outer", "acc_inner", "sin", "cos", "matmul")


def test_a_program_books_one_trace_lower_and_compile(compiles, own_cache):
    x = np.ones((4, 4), np.float32)
    events = []
    unsub = obs.span_events_subscribe(events.append)
    try:
        _nested_program()(x).block_until_ready()
    finally:
        unsub()
    assert _booked(compiles, NESTED) == {
        ("trace", "acc_outer", "none"): 1,
        ("lower", "acc_outer", "none"): 1,
        ("compile", "acc_outer", "none"): 1}
    # the same three on the span sinks: Chrome X slices in order
    mine = [e for e in events
            if e["cat"] == "compile" and e["args"]["program"] == "acc_outer"]
    assert [e["name"] for e in mine] == [
        "compile/trace", "compile/lower", "compile/compile"]
    assert all(e["ph"] == "X" and e["cat"] == "compile" for e in mine)
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1
               for a, b in zip(mine, mine[1:]))


def test_a_compile_carries_the_span_that_caused_it(compiles, own_cache):
    x = np.ones((4, 4), np.float32)
    events = []
    unsub = obs.span_events_subscribe(events.append)
    try:
        with obs.span("decode_step"):
            _nested_program()(x).block_until_ready()
    finally:
        unsub()
    assert set(_booked(compiles, NESTED)) == {
        (stage, "acc_outer", "decode_step")
        for stage in ("trace", "lower", "compile")}
    # JAX's time.time() stamps moved onto the spans' perf_counter
    # timebase: every compile slice lies inside the span round it
    (outer,) = [e for e in events if e["name"] == "decode_step"]
    for e in events:
        if e.get("cat") == "compile":
            assert outer["ts"] - 1000 <= e["ts"]
            assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1000


def test_a_persistent_cache_hit_books_load_not_compile(compiles,
                                                       own_cache):
    x = np.ones((4, 4), np.float32)
    _nested_program()(x).block_until_ready()
    compiles.reset()
    jax.clear_caches()
    _nested_program()(x).block_until_ready()
    # traced and lowered again (the key is the lowered module), then
    # the executable read back
    assert _booked(compiles, NESTED) == {
        ("trace", "acc_outer", "none"): 1,
        ("lower", "acc_outer", "none"): 1,
        ("load", "acc_outer", "none"): 1}


def test_registry_off_books_nothing_and_emits_nothing():
    from jax._src import monitoring

    from torchbooster_tpu.observability import recompile

    registry = obs.get_registry()
    was = registry.enabled
    registry.reset()
    try:
        obs.set_enabled(True)
        obs.set_enabled(True)             # installed once, however often
        obs.set_enabled(False)
        events = []
        unsub = obs.span_events_subscribe(events.append)
        try:
            jax.jit(lambda x: x * 3 + 1)(np.ones(5, np.float32))
            with obs.span("serving_build") as s:
                assert s is obs.span("decode_step")   # the shared no-op
        finally:
            unsub()
    finally:
        registry.enabled = was
    assert events == []
    assert registry.snapshot() == {}
    assert monitoring.get_event_time_span_listeners().count(
        recompile._on_time_span) == 1


@pytest.mark.parametrize("snapshot,want", [
    # a warm start: every executable a load, one compile under the build
    ({"compile_seconds{program=_decode_fn}{span=sched_step}"
      "{stage=trace}_sum": 0.5,
      "compile_seconds{program=_decode_fn}{span=sched_step}"
      "{stage=trace}_count": 1.0,
      "compile_seconds{program=_decode_fn}{span=sched_step}"
      "{stage=lower}_sum": 1.25,
      "compile_seconds{program=_decode_fn}{span=sched_step}"
      "{stage=lower}_count": 1.0,
      "compile_seconds{program=_decode_fn}{span=sched_step}"
      "{stage=load}_sum": 0.75,
      "compile_seconds{program=_decode_fn}{span=sched_step}"
      "{stage=load}_count": 1.0,
      "compile_seconds{program=zeros}{span=serving_build}"
      "{stage=lower}_sum": 0.125,
      "compile_seconds{program=zeros}{span=serving_build}"
      "{stage=lower}_count": 2.0,
      "compile_seconds{program=zeros}{span=serving_build}"
      "{stage=load}_sum": 0.25,
      "compile_seconds{program=zeros}{span=serving_build}"
      "{stage=load}_count": 2.0,
      "span_seconds{name=serving_build}_sum": 3.0,
      "span_seconds{name=serving_build}_count": 1.0},
     {"setup_lower_s": 1.875, "setup_load_s": 1.0,
      "setup_compile_s": 0.0, "setup_programs": 3.0,
      "setup_build_s": 2.625}),
    # a cold start: compiles, no load
    ({"compile_seconds{program=build}{span=none}{stage=compile}_sum": 9.5,
      "compile_seconds{program=build}{span=none}{stage=compile}_count":
          1.0,
      "compile_seconds{program=build}{span=none}{stage=trace}_sum": 0.5,
      "compile_seconds{program=build}{span=none}{stage=trace}_count": 1.0,
      "span_seconds{name=serving_build}_sum": 2.0},
     {"setup_lower_s": 0.5, "setup_load_s": 0.0,
      "setup_compile_s": 9.5, "setup_programs": 1.0,
      "setup_build_s": 2.0}),
    # the parent: spans, no compile_seconds at all
    ({"span_seconds{name=decode_step}_sum": 4.0,
      "span_seconds{name=serving_build}_sum": 2.0},
     dict.fromkeys(SETUP_READERS)),
], ids=["warm", "cold", "parent"])
def test_the_start_up_readers(snapshot, want):
    layers = {"registry_open": snapshot,
              "registry_close": {**snapshot, "extra": 1.0}}
    readers = {name: _reader(name) for name in SETUP_READERS}
    got = {name: r.read(f"{name}.setup", layers)
           for name, r in readers.items()}
    assert got == pytest.approx(want)
    # none reads anything but the registry when the window opened
    assert all(r.read(f"{name}.setup", {"registry_close": snapshot})
               is None for name, r in readers.items())


def _reader(name: str):
    """``benchmark/layer_metrics/<name>.py`` loaded as the harness
    loads it (its directory on the path, for the shared helper)."""
    import importlib.util

    folder = BENCH / "layer_metrics"
    if str(folder) not in sys.path:
        sys.path.append(str(folder))
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", folder / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_start_up_metrics_are_the_serve_cells():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    serve = [c["name"] for c in manifest["workloads"]
             if c["traffic"].startswith("serve")]
    entries = {m["name"]: m for m in manifest["per_layer"]
               if m["name"].startswith("setup_")}
    assert sorted(entries) == sorted(f"{n}.setup" for n in SETUP_READERS)
    for entry in entries.values():
        assert entry["workloads"] == serve
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert (BENCH / "layer_metrics" /
                (entry["name"].split(".")[0] + ".py")).exists()


# =====================================================================
# device stats
# =====================================================================

def test_record_memory_gauges_cpu_is_clean_noop(reg):
    # CPU devices report no memory_stats: no gauges, no crash
    out = obs.record_memory_gauges(reg)
    assert out == {}


# =====================================================================
# exporters
# =====================================================================

def test_prometheus_text_format(reg):
    reg.counter("a_total").inc(2, kv="4")
    reg.gauge("b").set(1.5)
    h = reg.histogram("c_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    reg.histogram("span_seconds").observe(0.1, name='load "ckpt"\n')
    text = obs.prometheus_text(reg)
    assert "# TYPE a_total counter" in text
    assert 'a_total{kv="4"} 2.0' in text
    # label values escape quotes/newlines per the exposition format
    assert 'name="load \\"ckpt\\"\\n"' in text
    assert '\nckpt' not in text
    assert "# TYPE b gauge" in text and "b 1.5" in text
    assert 'c_s_bucket{le="0.1"} 1' in text
    assert 'c_s_bucket{le="1.0"} 2' in text       # cumulative
    assert 'c_s_bucket{le="+Inf"} 3' in text
    assert "c_s_count 3" in text


def test_jsonl_exporter_and_cadence_thread(reg, tmp_path):
    reg.counter("ticks_total").inc(7)
    exporter = obs.MetricsExporter(
        reg, jsonl_path=tmp_path / "events.jsonl",
        prom_path=tmp_path / "metrics.prom", cadence_s=0.02)
    exporter.start()
    exporter.start()                      # idempotent
    with obs.span("traced", reg):
        pass
    import time

    time.sleep(0.08)
    exporter.stop()                       # joins + final flush
    lines = [json.loads(ln) for ln in
             (tmp_path / "events.jsonl").read_text().splitlines()]
    kinds = {ln["event"] for ln in lines}
    assert kinds == {"span", "metrics"}
    metric_lines = [ln for ln in lines if ln["event"] == "metrics"]
    assert metric_lines[-1]["ticks_total"] == 7.0
    prom = (tmp_path / "metrics.prom").read_text()
    assert "ticks_total 7.0" in prom
    # stopped: no .tmp leftover from the atomic rewrite
    assert not list(tmp_path.glob("*.tmp"))


def test_enable_is_idempotent_on_default_session(tmp_path):
    """Two entry points calling enable() in one process must not stack
    cadence threads or double-subscribe span sinks (duplicate JSONL
    span events)."""
    try:
        s1 = obs.enable(jsonl_path=tmp_path / "a.jsonl", cadence_s=60)
        s2 = obs.enable(jsonl_path=tmp_path / "b.jsonl", cadence_s=60)
        with obs.span("once"):
            pass
        s2.close()
    finally:
        obs.set_enabled(False)
        obs.get_registry().reset()
    # the first session was replaced: its file got no span event, the
    # second got exactly one
    a_spans = [ln for ln in (tmp_path / "a.jsonl").read_text()
               .splitlines() if '"event": "span"' in ln]
    b_spans = [ln for ln in (tmp_path / "b.jsonl").read_text()
               .splitlines() if '"event": "span"' in ln]
    assert len(a_spans) == 0
    assert len(b_spans) == 1


def test_drain_batches_device_reads(reg):
    """The backlog materializes in ONE device_get over the pending
    list, and mixed python/device values both land correctly."""
    h = reg.histogram("mixed")
    h.observe(1.0)
    h.observe(jnp.asarray(2.0))
    h.observe(3)
    snap = reg.snapshot()
    assert snap["mixed_count"] == 3.0
    assert snap["mixed_sum"] == pytest.approx(6.0)


# =====================================================================
# config + callback
# =====================================================================

def test_observability_config_block(tmp_path):
    from torchbooster_tpu.config import ObservabilityConfig

    path = tmp_path / "obs.yml"
    path.write_text(
        "enabled: true\n"
        f"jsonl_path: {tmp_path}/t.jsonl\n"
        f"prom_path: {tmp_path}/m.prom\n"
        "cadence_s: 0.02\n"
        "on_recompile: raise\n")
    conf = ObservabilityConfig.load(path)
    assert conf.enabled and conf.on_recompile == "raise"
    session = conf.make()
    try:
        assert session.registry.enabled
        sentinel = session.sentinel([], name="x")
        assert sentinel.on_recompile == "raise"
    finally:
        session.close()
        obs.set_enabled(False)
        obs.get_registry().reset()
    assert (tmp_path / "t.jsonl").exists()
    assert (tmp_path / "m.prom").exists()


def test_observability_block_nests_in_user_config(tmp_path):
    """The documented shape: an ``observability:`` block inside a user
    experiment config, resolved by the pseudo-annotation machinery."""
    from dataclasses import dataclass

    from torchbooster_tpu.config import BaseConfig, ObservabilityConfig

    @dataclass
    class _ObsExpConfig(BaseConfig):
        name: str = "exp"
        observability: ObservabilityConfig = None

    path = tmp_path / "exp.yml"
    path.write_text(
        "name: run1\n"
        "observability:\n"
        "  enabled: false\n"
        "  on_recompile: ignore\n"
        "  cadence_s: 5\n")
    conf = _ObsExpConfig.load(path)
    assert isinstance(conf.observability, ObservabilityConfig)
    assert conf.observability.on_recompile == "ignore"
    assert conf.observability.cadence_s == 5.0
    assert not conf.observability.enabled


def test_observability_config_disabled_and_invalid():
    from torchbooster_tpu.config import ObservabilityConfig

    session = ObservabilityConfig().make()
    assert session.exporter is None
    assert not session.registry.enabled
    with pytest.raises(ValueError, match="on_recompile"):
        ObservabilityConfig(on_recompile="nope").make()


def test_observability_config_disabled_is_authoritative():
    """`enabled: false` must turn a previously-enabled process default
    OFF — otherwise instrumentation keeps queueing with no exporter
    left to drain it."""
    from torchbooster_tpu.config import ObservabilityConfig

    try:
        obs.set_enabled(True)
        session = ObservabilityConfig(enabled=False).make()
        assert not session.registry.enabled
        assert not obs.get_registry().enabled
    finally:
        obs.set_enabled(False)
        obs.get_registry().reset()


def test_log_callback_drains_at_cadence(reg):
    from torchbooster_tpu.callbacks import LogCallback

    cb = LogCallback(every=2, registry=reg)
    # steps dispatched AFTER construction: the delta steps/s measures
    reg.counter("steps_total").inc(10, step="train_step")
    assert cb(loss=1.0) is None           # step 1: off-cadence
    out = cb(loss=0.25)                   # step 2: drain
    assert out["step"] == 2
    assert out["loss"] == 0.25
    assert out["steps_total{step=train_step}"] == 10.0
    assert out["steps_per_s"] > 0
    # stable key set: a tick with no step progress still has the key
    cb.every = 1
    assert cb().get("steps_per_s") == 0.0


# =====================================================================
# instrumented serving batcher
# =====================================================================

def _decisive_model():
    from torchbooster_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig(vocab=97, n_layers=2, d_model=32, n_heads=4,
                    seq_len=32, n_kv_heads=2)
    params = GPT.init(jax.random.PRNGKey(0), cfg)
    params = {**params, "wte": {"table": params["wte"]["table"] * 4.0}}
    return params, cfg


def test_batcher_metrics_view_and_stable_keys(global_obs):
    """run() reports admissions/preemptions on EVERY path with the
    same key set, and the registry's serving_* counters carry the same
    events for the exporters."""
    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (5,), 0, cfg.vocab))

    # ample pool: no preemption
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, compute_dtype=jnp.float32)
    batcher = ContinuousBatcher(engine)
    empty = batcher.run([])
    reqs = [Request(prompt=prompt, max_new_tokens=6) for _ in range(3)]
    metrics = batcher.run(reqs)
    assert set(empty) == set(metrics)     # stable key set (satellite)
    assert metrics["n_admissions"] == 3
    assert metrics["n_preemptions"] == 0
    snap = global_obs.snapshot()
    assert snap["serving_admissions_total"] == 3.0
    assert snap["serving_retired_total"] == 3.0
    assert snap["serving_ttft_seconds_count"] == 3.0
    assert snap["serving_latency_seconds_count"] == 3.0
    assert snap["serving_decode_tokens_total"] > 0
    assert snap["serving_slots_live"] == 0.0        # drained at end

    # tight pool (the test_serving preemption geometry): the youngest
    # preempts, so n_preemptions must surface — previously invisible
    engine = PagedEngine(params, cfg, page_size=4, n_pages=5,
                         max_slots=2, compute_dtype=jnp.float32)
    metrics = ContinuousBatcher(engine).run(
        [Request(prompt=prompt, max_new_tokens=8) for _ in range(3)])
    assert metrics["n_preemptions"] >= 1
    # re-admissions after preemption are counted as admissions
    assert metrics["n_admissions"] == 3 + metrics["n_preemptions"]
    delta = global_obs.snapshot()
    assert delta["serving_preemptions_total"] == metrics["n_preemptions"]


def test_batcher_rejects_invalid_policy_at_build_time():
    """A YAML typo must fail when the batcher is BUILT, not deep
    inside the first run() after requests were accepted."""
    from torchbooster_tpu.serving import ContinuousBatcher

    with pytest.raises(ValueError, match="on_recompile"):
        ContinuousBatcher(object(), on_recompile="rais")


def test_batcher_sentinel_guards_decode_recompiles(global_obs, caplog):
    """The zero-recompile contract as a runtime guard: a healthy run
    never trips it (decode's single warmup compile is budgeted), and
    the on_recompile='raise' batcher wires the policy through."""
    import logging

    from torchbooster_tpu.serving import (ContinuousBatcher,
                                          PagedEngine, Request)

    params, cfg = _decisive_model()
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(4), (5,), 0, cfg.vocab))
    engine = PagedEngine(params, cfg, page_size=4, n_pages=16,
                         max_slots=2, compute_dtype=jnp.float32)
    batcher = ContinuousBatcher(engine, on_recompile="raise")
    with caplog.at_level(logging.WARNING):
        batcher.run([Request(prompt=prompt, max_new_tokens=4)])
        batcher.run([Request(prompt=prompt, max_new_tokens=4)])
    assert engine.decode_compiles == 1
    assert not any("recompile sentinel" in r.message
                   for r in caplog.records)
    assert "recompiles_total" not in str(global_obs.snapshot())

    # exception safety: an engine failure mid-run must still land the
    # gauges on engine truth (the seated slot IS still live) instead
    # of freezing a stale mid-loop value in the export forever
    from unittest import mock

    with mock.patch.object(engine, "step_ahead",
                           side_effect=RuntimeError("boom")):
        with pytest.raises(RuntimeError, match="boom"):
            batcher.run([Request(prompt=prompt, max_new_tokens=4)])
    snap = global_obs.snapshot()
    assert snap["serving_slots_live"] == 1.0      # truth at abort
    assert snap["serving_pages_free"] == float(
        engine.tables.n_free_pages)


# =====================================================================
# logging bootstrap satellite (subprocess: import-time behavior)
# =====================================================================

_REPO = Path(__file__).resolve().parents[1]


def _run_py(code: str, **env) -> subprocess.CompletedProcess:
    import os

    full_env = {**os.environ, **env}
    return subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                          capture_output=True, text=True, env=full_env,
                          timeout=120)


def test_import_does_not_clobber_configured_root_logger():
    proc = _run_py(
        "import logging\n"
        "logging.basicConfig(level=logging.ERROR, format='MINE:%(message)s')\n"
        "before = list(logging.getLogger().handlers)\n"
        "import torchbooster_tpu\n"
        "root = logging.getLogger()\n"
        "assert root.handlers == before, root.handlers\n"
        "assert root.level == logging.ERROR, root.level\n"
        "print('OK')\n")
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


def test_import_no_log_setup_escape_hatch():
    proc = _run_py(
        "import logging\n"
        "import torchbooster_tpu\n"
        "assert logging.getLogger().handlers == [], "
        "logging.getLogger().handlers\n"
        "print('OK')\n",
        TORCHBOOSTER_NO_LOG_SETUP="1")
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


@pytest.mark.slow
def test_import_configures_virgin_root_logger():
    # slow tier: same subprocess machinery as the two tier-1 tests
    # above; this one only re-confirms the pre-existing default
    proc = _run_py(
        "import logging\n"
        "import torchbooster_tpu\n"
        "assert logging.getLogger().handlers, 'no bootstrap happened'\n"
        "print('OK')\n")
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout
