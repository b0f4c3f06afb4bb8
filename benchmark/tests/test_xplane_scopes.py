"""The raw-trace decoder and the scope / clock readers over it: against
the small trace recorded on the chip (the same events ``ProfileData``
shows, plus what it cannot: ``tf_op`` and the host's enqueue), and
against a hand-made trace with known arithmetic. Then each new reader,
and the thirteen manifest entries resolving from a copy of the toy
manifest."""
import json
import shutil
import sys
from pathlib import Path

import pytest

import run as harness
import xplane_scopes as xs

TESTS = Path(__file__).resolve().parent
DATA = TESTS / "data"
BENCH = TESTS.parent
US = 1e-6


def synthetic_bytes() -> bytes:
    from jax.profiler import ProfileData

    text = "\n".join(
        line for line in (DATA / "synthetic_scopes.txt").read_text()
        .splitlines() if not line.startswith("#"))
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture(scope="module")
def synthetic():
    return xs.decode(synthetic_bytes())


@pytest.fixture()
def kept_trace(tmp_path, monkeypatch):
    """The hand-made trace where ``run.py`` keeps a run's trace while
    the readers run: ``.bench_out/<cell>/trace/plugins/profile/...``."""
    out = tmp_path / ".bench_out"
    kept = out / "some-cell" / "trace" / "plugins" / "profile" / "t0"
    kept.mkdir(parents=True)
    (kept / "host.xplane.pb").write_bytes(synthetic_bytes())
    monkeypatch.setattr(xs, "BENCH_OUT", out)
    return out


# ---------------------------------------------------------------------
# the decoder against ProfileData, on the trace recorded on the chip
# ---------------------------------------------------------------------

def test_decoder_sees_the_events_profile_data_sees():
    from jax.profiler import ProfileData

    path = DATA / "tiny_tpu.xplane.pb"
    mine = xs.load(path)
    plane = next(p for p in ProfileData.from_file(str(path)).planes
                 if p.name == "/device:TPU:0")
    for line_name, events in (("XLA Ops", mine.ops["/device:TPU:0"]),
                              ("XLA Modules",
                               mine.modules["/device:TPU:0"])):
        line = next(ln for ln in plane.lines if ln.name == line_name)
        theirs = sorted((ev.start_ns, ev.duration_ns, ev.name)
                        for ev in line.events if ev.duration_ns > 0)
        assert len(events) == len(theirs)
        for e, (start_ns, dur_ns, name) in zip(events, theirs):
            assert e.name == name
            # ProfileData rounds to whole nanoseconds
            assert e.start * 1e9 == pytest.approx(start_ns, abs=1.0)
            assert (e.end - e.start) * 1e9 == pytest.approx(dur_ns, abs=1.0)


def test_decoder_reads_what_profile_data_cannot():
    trace = xs.load(DATA / "tiny_tpu.xplane.pb")
    ops = trace.ops["/device:TPU:0"]
    fusions = [op for op in ops if op.name.startswith("%fusion")]
    assert len(fusions) == 16           # four matmul fusions a run
    assert {op.tf_op for op in fusions} == {"jit(toy)/dot_general:"}
    assert [m.run_id for m in trace.modules["/device:TPU:0"]] == \
        [4, 5, 6, 7]
    assert [e.run_id for e in trace.enqueues] == [4, 5, 6, 7]
    # run 4: enqueued at host 45,552,622 ns, stamped on the device at
    # 44,337,461 ns: the device's clock leads by at least 1.215 ms
    pairs = {e.run_id: e.start - m.start for e, m in xs.joined_runs(trace)}
    assert pairs[4] == pytest.approx(1.215161e-3, abs=2e-9)
    lead = xs.clock_lead_seconds(trace)
    assert lead == max(pairs.values())
    assert lead == pytest.approx(1.2164e-3, abs=1e-7)
    # the toy program has no named scope: nothing is claimed
    assert xs.scoped_share(trace) is None
    assert set(xs.scope_seconds(trace, "toy")) == {xs.UNSCOPED}


def test_recorded_scoped_trace():
    """``data/scoped_tpu.xplane.pb``: recorded on a v5e by
    ``record_scoped_fixture.py`` — three runs each of a toy train step
    (a scan of checkpointed blocks, differentiated) and a toy decode
    program, as the chip's compiler names and fuses them."""
    trace = xs.load(DATA / "scoped_tpu.xplane.pb")
    device = trace.devices[0]
    step = xs.scope_seconds(trace, "toy_step_fn", by_phase=True)
    # forward, backward (transpose(jvp()) round the scan) and the
    # recomputed forward all keep the scope
    assert {"mlp", "mlp.bwd", "mlp.remat", "attn_core.bwd",
            "attn_core.remat", "optimizer", xs.UNSCOPED} <= set(step)
    assert all(len(runs) == 3 for runs in step.values())
    decode = xs.scope_seconds(trace, "toy_decode_fn")
    assert set(decode) == {"attn_core", "kv_write", xs.UNSCOPED}
    assert all(1e-6 < s < 2e-6 for s in decode["kv_write"])   # nested
    # a run's scopes sum, with unscoped, to that run's op time
    for pattern, by_scope in (("toy_step_fn", step),
                              ("toy_decode_fn", decode)):
        runs = [m for m in trace.modules[device] if pattern in m.name]
        for i, run in enumerate(runs):
            ops = sum(op.end - op.start for op in trace.ops[device]
                      if run.start <= op.start < run.end
                      and not xs.CONTAINER_OP.match(op.name))
            assert sum(v[i] for v in by_scope.values()) == \
                pytest.approx(ops, rel=1e-9)
            assert ops <= run.end - run.start
    assert 75.0 < xs.scoped_share(trace) < 85.0
    # every run of every program joins its enqueue; the lead is the
    # millisecond PERF.md spoke of
    assert len(xs.joined_runs(trace)) == len(trace.modules[device]) == 12
    assert 1.1e-3 < xs.clock_lead_seconds(trace) < 1.4e-3
    idle = xs.idle_by_span(trace)
    assert idle["decode_step"] > 0 and idle["unattributed"] > 0


# ---------------------------------------------------------------------
# known arithmetic
# ---------------------------------------------------------------------

def test_scope_of_strips_wrappers_and_takes_the_innermost():
    assert xs.scope_of("jit(f)/while/body/attn_core/dot_general:") == \
        ("attn_core", "fwd")
    assert xs.scope_of("jit(f)/attn_core/kv_write/scatter") == \
        ("kv_write", "fwd")
    assert xs.scope_of("jit(f)/transpose(jvp(attn_core))/dot_general") == \
        ("attn_core", "bwd")
    # under a scan the wrapper sits on an outer component
    assert xs.scope_of("jit(f)/transpose(jvp())/while/body/closed_call/"
                       "checkpoint/mlp/add") == ("mlp", "bwd")
    assert xs.scope_of("jit(f)/transpose(jvp())/while/body/closed_call/"
                       "checkpoint/rematted_computation/mlp/add") == \
        ("mlp", "remat")
    assert xs.scope_of("jit(f)/jvp(head)/dot_general:") == ("head", "fwd")
    # a name that only contains a scope's name is no scope
    assert xs.scope_of("jit(f)/my_mlp_thing/headless/add") == \
        (xs.UNSCOPED, "fwd")
    assert xs.scope_of("") == (xs.UNSCOPED, "fwd")


def test_ops_go_to_the_run_that_contains_them(synthetic):
    assert synthetic.devices == ["/device:TPU:0"]    # the first only
    decode = xs.scope_seconds(synthetic, "decode_fn")
    # the while over the scoped ops is left out; fusion.1 is attn_core
    # HERE and kv_write in the chunk program
    assert decode["attn_core"] == pytest.approx([4 * US, 6 * US])
    assert decode["kv_write"] == pytest.approx([1 * US, 0.0])
    assert decode["mlp"] == pytest.approx([3 * US, 2 * US])
    assert decode[xs.UNSCOPED] == pytest.approx([1 * US, 0.0])
    # a run's scopes sum (with unscoped) to its op time: 9 and 8 us
    assert [sum(v[i] for v in decode.values()) for i in (0, 1)] == \
        pytest.approx([9 * US, 8 * US])
    chunk = xs.scope_seconds(synthetic, "chunk_fn", by_phase=True)
    assert chunk == {"kv_write": [pytest.approx(3 * US)],
                     "attn_core.bwd": [pytest.approx(4 * US)],
                     "embed": [pytest.approx(3 * US)]}
    step = xs.scope_seconds(synthetic, "step_fn", by_phase=True)
    assert step["attn_core.bwd"] == [pytest.approx(3 * US)]
    assert step["attn_core.remat"] == [pytest.approx(2 * US)]
    assert xs.scope_seconds(synthetic, "no_such_program") == {}


def test_shares_clock_and_idle_time(synthetic):
    # 37 us of ops, two unscoped copies of 1 us
    assert xs.scoped_share(synthetic) == pytest.approx(100 * 35 / 37)
    assert [(e.run_id, m.name) for e, m in xs.joined_runs(synthetic)] == [
        (7, "jit__decode_fn(1)"), (8, "jit__chunk_fn(2)"),
        (9, "jit__decode_fn(1)"), (10, "jit_step_fn(3)")]
    assert xs.clock_lead_seconds(synthetic) == pytest.approx(3 * US)
    # gaps [9,20) [30,40) [48,60) on the device's clock; host spans
    # moved 3 us earlier: decode_args [13,19), sched_step [27,57) with
    # sched_admit [33,37) inside; PjitFunction is the runtime's
    idle = xs.idle_by_span(synthetic, min_gap=0.5 * US)
    assert idle == {"decode_args": pytest.approx(11 * US),
                    "sched_admit": pytest.approx(10 * US),
                    "sched_step": pytest.approx(12 * US)}
    assert "attn_core" in xs.report(synthetic)


def test_top_ops_name_program_scope_and_the_stacks_end(synthetic):
    top = xs.top_ops(synthetic)
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    by_name = dict(top)
    # fusion.1 is two ops: attn_core in the decode program (4 + 6 us),
    # kv_write in the chunk program (3 us); the while is left out
    assert by_name[
        "decode_fn/attn_core/dot_general fusion.1 bf16[8,128]"] == \
        pytest.approx(10 * US)
    assert by_name[
        "chunk_fn/kv_write/scatter fusion.1 bf16[8,128]"] == \
        pytest.approx(3 * US)
    assert by_name[
        "chunk_fn/attn_core.bwd/dot_general fusion.4 bf16[8,128]"] == \
        pytest.approx(4 * US)
    assert by_name["chunk_fn/embed/gather fusion.5 bf16[8,128]"] == \
        pytest.approx(3 * US)
    assert not any("while" in name for name in by_name)
    # every op of the device is ranked once: 37 us
    assert sum(v for _, v in xs.top_ops(synthetic, top=99)) == \
        pytest.approx(37 * US)
    assert xs.top_ops(None) == []


def test_top_ops_on_the_recorded_traces():
    top = xs.top_ops(xs.load(DATA / "scoped_tpu.xplane.pb"))
    assert top[0][0] == "toy_step_fn/mlp/dot_general fusion.101 bf16[512,512]"
    assert {name.split("/")[1] for name, _ in top} >= {
        "mlp", "mlp.bwd", "attn_core.bwd", "attn_core.remat", "optimizer"}
    # a program without a scope: the compiler's name still says what
    top = xs.top_ops(xs.load(DATA / "tiny_tpu.xplane.pb"))
    assert top[0][0] == "toy/unscoped/dot_general fusion bf16[1024,1024]"
    assert top[0][1] == pytest.approx(50.5e-6, rel=1e-2)


def test_no_trace_kept_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(xs, "BENCH_OUT", tmp_path / ".bench_out")
    assert xs.newest_trace() is None and xs.load() is None
    assert xs.median_scope_ms(None, "decode_fn", ("attn_core",)) is None
    assert xs.scoped_share(None) is None
    assert xs.clock_lead_seconds(None) is None


# ---------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------

TRACE_READINGS = {
    "decode_attn_ms.lat": 5e-3,         # median of 4 and 6 us
    "decode_kv_write_ms.lat": 0.5e-3,   # median of 1 and 0 us
    "decode_weights_ms.lat": 2.5e-3,    # mlp alone here: 3 and 2 us
    "chunk_attn_ms.lat": 4e-3,
    "chunk_kv_write_ms.lat": 3e-3,
    "scoped_share.lat": 100 * 35 / 37,
    "clock_lead_ms.lat": 3e-3,
    "attn_dev_ms.train": 5e-3,          # backward 3 + recomputed 2
    "head_dev_ms.train": 2e-3,          # head 1 + loss 1
    "opt_dev_ms.train": 2e-3,
    "scoped_share.train": 100 * 35 / 37,
}
REGISTRY = {
    "registry_open": {
        "serving_queue_wait_seconds_sum": 1.0,
        "serving_queue_wait_seconds_count": 10.0,
        "span_seconds{name=sched_step}_sum": 10.0,
        "span_seconds{name=sched_step}_count": 100.0,
        "span_seconds{name=decode_step}_sum": 8.0,
        "span_seconds{name=prefill_finish}_sum": 0.25,
        "span_seconds{name=serving_prefill_chunk}_sum": 0.5},
    "registry_close": {
        "serving_queue_wait_seconds_sum": 1.6,
        "serving_queue_wait_seconds_count": 14.0,
        "span_seconds{name=sched_step}_sum": 16.0,
        "span_seconds{name=sched_step}_count": 150.0,
        "span_seconds{name=decode_step}_sum": 13.0,
        "span_seconds{name=prefill_finish}_sum": 0.55,
        "span_seconds{name=serving_prefill_chunk}_sum": 0.7}}
REGISTRY_READINGS = {
    "queue_wait_ms.lat": 150.0,         # 0.6 s over 4 requests
    "sched_host_ms.lat": 10.0,      # (6 - 5 - 0.2 - 0.3) s over 50 steps
}


def read_one(name: str, layers: dict):
    # as run.read_layer_metrics does before it loads a reader
    if str(BENCH / "layer_metrics") not in sys.path:
        sys.path.append(str(BENCH / "layer_metrics"))
    reader = harness.load_module(
        BENCH / "layer_metrics" / (name.split(".")[0] + ".py"))
    return reader.read(name, layers)


@pytest.mark.parametrize("name", sorted(TRACE_READINGS))
def test_trace_reader(name, kept_trace):
    assert read_one(name, {}) == pytest.approx(TRACE_READINGS[name])


PATH_READERS = ["chunk_attn_ms.lat", "chunk_kv_write_ms.lat",
                "clock_lead_ms.lat", "decode_attn_ms.lat",
                "decode_kv_write_ms.lat", "decode_weights_ms.lat"]


@pytest.mark.parametrize("name", PATH_READERS)
def test_reader_takes_the_trace_from_the_path_in_layers(
        name, tmp_path, monkeypatch):
    """``run.py`` puts ``trace_path`` into ``layers``: the serve readers
    read THAT file and hunt for no other (the train cells' readers are
    as they were and still take the newest kept trace)."""
    monkeypatch.setattr(xs, "BENCH_OUT", tmp_path / "none")
    path = tmp_path / "elsewhere" / "host.xplane.pb"
    path.parent.mkdir()
    path.write_bytes(synthetic_bytes())
    assert read_one(name, {}) is None
    assert read_one(name, {"trace_path": str(path)}) == \
        pytest.approx(TRACE_READINGS[name])
    assert read_one(name, {"trace_path": str(tmp_path / "gone.pb")}) is None


@pytest.mark.parametrize("name", sorted(REGISTRY_READINGS))
def test_registry_reader(name):
    assert read_one(name, REGISTRY) == pytest.approx(REGISTRY_READINGS[name])


@pytest.mark.parametrize(
    "name", sorted({**TRACE_READINGS, **REGISTRY_READINGS}))
def test_reader_finds_nothing_on_a_program_without_the_spans_and_scopes(
        name, tmp_path, monkeypatch):
    """What the parent commit gives: no trace kept, or a trace whose
    ops carry no scope, and a registry without the new series. A
    reader then returns None (never 0) and does not raise; only the
    clock's lead, which needs no scope, still reads."""
    monkeypatch.setattr(xs, "BENCH_OUT", tmp_path / "none")
    assert read_one(name, {}) is None
    old = {"registry_open": {"serving_ttft_seconds_sum": 1.0},
           "registry_close": {"serving_ttft_seconds_sum": 2.0}}
    kept = tmp_path / "out" / "cell" / "trace" / "plugins" / "profile" / "t"
    kept.mkdir(parents=True)
    shutil.copy(DATA / "tiny_tpu.xplane.pb", kept / "h.xplane.pb")
    monkeypatch.setattr(xs, "BENCH_OUT", tmp_path / "out")
    got = read_one(name, old)
    if name == "clock_lead_ms.lat":
        assert got == pytest.approx(1.2164, abs=1e-4)
    else:
        assert got is None


def test_the_thirteen_entries_resolve_from_a_copy_of_the_toy_manifest(
        tmp_path, kept_trace):
    """As ``rehearse.py`` runs a cell: the toy manifest (copied, not
    edited) plus this PR's entries of the real one, each cell's list
    resolved and read through ``run.py``'s own functions."""
    root = tmp_path / "root"
    shutil.copytree(TESTS / "tiny", root,
                    ignore=shutil.ignore_patterns(".bench_out"))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    toy = json.loads((root / "BENCHMARK.json").read_text())
    had = {m["name"] for m in toy["per_layer"]}
    cells = {"gpt2-xl.serve-chat-r80": "gpt2-tiny.serve-tiny",
             "gpt2-small.train-s1024": "gpt2-tiny.train-tiny",
             "gpt2-large.train-s1024-fsdp4": "gpt2-tiny.train-tiny-fsdp4"}
    new = [dict(m, workloads=[cells[w] for w in m["workloads"]])
           for m in real["per_layer"] if m["name"] not in had]
    assert sorted(m["name"] for m in new) == \
        sorted({**TRACE_READINGS, **REGISTRY_READINGS})
    toy["per_layer"] += new
    (root / "BENCHMARK.json").write_text(json.dumps(toy))
    dirs = harness.bench_dirs(toy, root)
    names = {m["name"] for m in new}
    for cell, suffix in (("gpt2-tiny.serve-tiny", ".lat"),
                         ("gpt2-tiny.train-tiny", ".train"),
                         ("gpt2-tiny.train-tiny-fsdp4", ".train")):
        wanted = [m for m in harness.metrics_of(toy, cell, "per_layer")
                  if m["name"] in names]
        assert {m["name"] for m in wanted} == \
            {n for n in names if n.endswith(suffix)}
        read = harness.read_layer_metrics(wanted, dict(REGISTRY), dirs)
        assert set(read) == {m["name"] for m in wanted}
        for m in wanted:
            want = {**TRACE_READINGS, **REGISTRY_READINGS}[m["name"]]
            assert read[m["name"]] == {
                "value": pytest.approx(want), "unit": m["unit"]}
