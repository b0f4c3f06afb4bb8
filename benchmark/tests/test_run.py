"""The harness end to end at a toy size on the CPU: the last line and
its keys, ``correct`` coming out false under each planted fault and
under the control, and a cell, a configuration, a traffic mix, a kind
of job and a per-layer metric added by files alone. Nothing here is a measurement."""
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import control
import flops
import program
import run as harness

TINY = Path(__file__).resolve().parent / "tiny"
PEAKS = flops.peaks_of("TPU v5 lite")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def execute(workload, seed=2**31 + 7, seconds=1.5, root=TINY, chips=1):
    # the command line's look for a chip is the one step skipped here
    return harness.execute(workload, seed, seconds, False, root=root,
                           devices=jax.devices()[:chips], peaks=PEAKS)


def check_line(line: dict, metrics: set):
    line = json.loads(json.dumps(line))          # it parses
    assert set(line) == LINE_KEYS and list(line)[-1] == "compared"
    assert set(line["device"]) == DEVICE_KEYS
    assert set(line["metrics"]) == metrics
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    for c in line["compared"].values():
        assert {"value", "limit"} <= set(c)


@pytest.fixture(scope="module")
def serve_out():
    return execute("gpt2-tiny.serve-tiny")


@pytest.fixture(scope="module")
def train_out():
    return execute("gpt2-tiny.train-tiny")


def test_serve_line_holds_the_contracts_keys_and_is_correct(serve_out):
    check_line(serve_out["line"], {"itl_p95_ms", "serve_tok_s", "setup_s"})
    assert serve_out["line"]["correct"] is True
    assert serve_out["line"]["failed"] == 0
    assert serve_out["line"]["attempted"] > 0
    log = serve_out["log"]
    assert log["gap_histogram_10ms"] and log["per_request"]
    assert log["compiles_in_window"] == 0


def test_train_line_holds_the_contracts_keys_and_is_correct(train_out):
    check_line(train_out["line"], {"train_tok_s", "setup_s"})
    assert train_out["line"]["correct"] is True
    assert set(train_out["line"]["compared"]) == {
        "loss_gap", "grad_gap", "delta_gap"}
    # a key's bias has no gradient under softmax: left out by the rule
    assert train_out["checks"]["leaves_left_out_of_delta"] == ["k_b"]


def test_fsdp4_cell_runs_sharded_and_is_correct():
    out = execute("gpt2-tiny.train-tiny-fsdp4", chips=4)
    assert out["line"]["correct"] is True
    assert out["line"]["device"]["count"] == 4


def test_without_an_accelerator_the_command_line_path_refuses():
    with pytest.raises(SystemExit, match="no accelerator"):
        harness.find_devices(1)


# ---- the timed path broken underneath: correct must read false ------

def broken_train(monkeypatch, wrap):
    real = program.build_train

    def build(cfg, recipe, seed):
        state, step, shard, mesh = real(cfg, recipe, seed)
        return wrap(state, step, shard, mesh)

    monkeypatch.setattr(program, "build_train", build)


def test_a_step_that_returns_its_state_unchanged_fails(monkeypatch):
    def wrap(state, step, shard, mesh):
        def stuck(state, batch):
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics

        return state, stuck, shard, mesh

    broken_train(monkeypatch, wrap)
    # the harness reads the step's compiled size through .lower
    monkeypatch.setattr(harness.Context, "program_bytes",
                        lambda self, *a: 0)
    out = execute("gpt2-tiny.train-tiny")
    assert out["line"]["correct"] is False
    assert out["line"]["compared"]["delta_gap"]["value"] == pytest.approx(1)


def test_half_of_the_batch_left_out_fails(monkeypatch):
    def wrap(state, step, shard, mesh):
        return state, step, lambda tokens: shard(
            tokens[:len(tokens) // 2]), mesh

    broken_train(monkeypatch, wrap)
    out = execute("gpt2-tiny.train-tiny")
    assert out["line"]["correct"] is False
    grad = out["line"]["compared"]["grad_gap"]
    assert grad["value"] > 10 * 0.0025       # ten times a sound reading


def test_a_token_altered_where_it_is_produced_fails(monkeypatch):
    real = program.build_serve

    def build(cfg, block, seed):
        batcher, frontend, conf = real(cfg, block, seed)
        step = batcher.step

        def altered():
            return [(req, [(t + 1) % cfg["vocab_size"] for t in toks])
                    for req, toks in step()]

        batcher.step = altered
        return batcher, frontend, conf

    monkeypatch.setattr(program, "build_serve", build)
    out = execute("gpt2-tiny.serve-tiny")
    assert out["line"]["correct"] is False
    gap = out["line"]["compared"]["served_gap_max"]
    assert gap["value"] > gap["limit"]


def test_control_and_missing_exchange_fail_at_a_test_runs_size():
    _, _, cfg, traffic = harness.resolve("gpt2-tiny.train-tiny-fsdp4", TINY)
    rows = control.train_readings(cfg, traffic, 11, jax.devices()[:4])
    assert control.verdicts(rows) == {
        "control_fp8": False, "half_batch": False, "no_exchange": False}
    # one chip's share of the rows: a noisier gradient, a larger norm
    assert rows["no_exchange"]["grad_gap"]["value"] > \
        10 * traffic["limits"]["grad_gap"]


def test_serve_control_reads_above_the_program(serve_out):
    _, _, cfg, traffic = harness.resolve("gpt2-tiny.serve-tiny", TINY)
    import weights
    from loadgen import plan

    seed = serve_out["log"]["seed"]
    requests = plan.make_requests(
        traffic, seed, traffic["preroll_s"] + serve_out["log"]["seconds"],
        cfg["vocab_size"])
    w = weights.generate(cfg, seed, jnp.bfloat16)
    row = control.serve_control(serve_out["records"], requests, cfg,
                                traffic, seed, w)
    # through the harness's own comparison, as a run's numbers go
    assert control.verdicts(row) == {"program": True, "control_fp8": False,
                                     "altered_token": False}
    assert row["control_fp8"]["served_gap_max"]["value"] > \
        3 * row["program"]["served_gap_max"]["value"]


# ---- where a tail sits between the gap's two modes -------------------

def test_gap_modes_find_the_split_and_say_where_the_tail_lies():
    import numpy as np

    from jobs import serve

    rng = np.random.default_rng(0)
    plain = rng.normal(21e-3, 0.8e-3, 8000)      # a decode step
    behind = rng.normal(35e-3, 1.5e-3, 2000)     # ... behind a chunk
    modes = serve.gap_modes(np.concatenate([plain, behind]).tolist())
    assert 20 <= modes["peak_ms"] <= 22 and 34 <= modes["upper_peak_ms"] <= 36
    assert 24 <= modes["split_ms"] <= 32
    assert modes["upper_share"] == pytest.approx(0.2, abs=0.005)
    assert modes["tail_in_one_mode"] is True     # p92, p95, p98 all above
    # 6 % of gaps behind a chunk: p92 lies in the lower mode, p95 and
    # p98 in the upper: the p95 sits within three percentiles of the jump
    few = serve.gap_modes(np.concatenate([plain, behind[:510]]).tolist())
    assert few["tail_in_one_mode"] is False
    assert few["tail_ms"]["92"] < few["split_ms"] < few["tail_ms"]["95"]
    # one mode: nothing to split, and a tail cannot straddle it
    one = serve.gap_modes(plain.tolist())
    assert one["split_ms"] is None and one["tail_in_one_mode"] is True
    assert serve.gap_modes([0.02] * 5) == {}


# ---- a later PR adds files and entries, and edits nothing -----------

def test_a_cell_config_mix_job_and_metric_are_added_by_files_alone(tmp_path):
    root = tmp_path / "root"
    shutil.copytree(TINY, root, ignore=shutil.ignore_patterns(".bench_out"))
    bench = root / "benchmark"
    cfg = json.loads((bench / "configs" / "gpt2-tiny.json").read_text())
    cfg.update(name="gpt2-wider", n_embd=96, n_head=6)
    (bench / "configs" / "gpt2-wider.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "train-tiny.json").read_text())
    mix["recipe"]["loader"]["batch_size"] = 2
    mix["job"] = "train-again"          # a kind of job is a file too
    (bench / "traffic" / "train-b2.json").write_text(json.dumps(mix))
    (bench / "jobs").mkdir()
    (bench / "jobs" / "train-again.py").write_text(
        "import run as harness\n"
        "train = harness.load_module(harness.HERE / 'jobs' / 'train.py')\n"
        "def run(ctx):\n"
        "    out = train.run(ctx)\n"
        "    out['log']['job_file'] = __file__\n"
        "    return out\n")
    (bench / "layer_metrics").mkdir()
    (bench / "layer_metrics" / "steps_taken.py").write_text(
        "def read(name, layers):\n    return layers.get('steps')\n")
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "gpt2-wider", "source": "none", "reduced": [],
        "file": "benchmark/configs/gpt2-wider.json", "why": "test"})
    manifest["workloads"].append({
        "name": "gpt2-wider.train-b2", "config": "gpt2-wider",
        "traffic": "train-b2", "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tok_s":
            m["workloads"].append("gpt2-wider.train-b2")
    manifest["per_layer"].append({
        "name": "steps_taken.new", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tok_s", "workloads": ["gpt2-wider.train-b2"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    out = execute("gpt2-wider.train-b2", root=root)
    check_line(out["line"], {"train_tok_s", "setup_s"})
    assert out["line"]["correct"] is True
    assert out["log"]["job_file"] == str(bench / "jobs" / "train-again.py")
    wanted = harness.metrics_of(manifest, "gpt2-wider.train-b2", "per_layer")
    assert [m["name"] for m in wanted] == ["steps_taken.new"]
    read = harness.read_layer_metrics(
        wanted, {"steps": 9}, harness.bench_dirs(manifest, root))
    assert read == {"steps_taken.new": {"value": 9.0, "unit": "count"}}
