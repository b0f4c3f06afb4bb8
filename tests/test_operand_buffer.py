"""A serve step's host-known operands cross to the device as ONE packed
int32 buffer (``kv_pages.OperandBuffer``), and the rng key stays on the
device: every program of every engine mode takes ``(params, pool, the
buffer, the key, what a mode rides beside)``, splits the key itself and
hands the new key back.

What is held here: the number of transfers a step issues
(``PagedEngine.operand_puts`` / ``serving_operand_puts_total``: 1 a
plain, a mixed and a lone-chunk step; one more for each LARGE operand a
mode rides beside the buffer) under churn, with no program compiled
anew; that the key the programs carry is the key the host used to
split, pick for pick; and that the host may write the buffer again as
soon as the transfer has returned.
"""
import json
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
import program_sarvam_mla  # noqa: E402
import weights_lfm2  # noqa: E402
import weights_sarvam_mla  # noqa: E402

import torchbooster_tpu.observability as obs  # noqa: E402
from tests.test_lfm2 import TOY as LFM2_TOY  # noqa: E402
from tests.test_sarvam_mla import TOY as MLA_TOY  # noqa: E402
from torchbooster_tpu.models.gpt import GPT, GPTConfig  # noqa: E402
from torchbooster_tpu.serving import (ContinuousBatcher,  # noqa: E402
                                      PagedEngine, Request)
from torchbooster_tpu.serving.kv_pages import (BlockTables,  # noqa: E402
                                               OperandBuffer)

STEPS = ("step", "mixed_step", "prefill_step", "spec_step")


def gpt():
    cfg = GPTConfig(vocab=97, n_layers=2, d_model=32, n_heads=4,
                    seq_len=64, n_kv_heads=2)
    return GPT.init(jax.random.PRNGKey(0), cfg), cfg, 4


def family(name):
    """(params, config, page size) of a served family at a toy size."""
    if name == "gpt":
        return gpt()
    if name == "lfm2":
        return (weights_lfm2.generate(
            LFM2_TOY, 11, jnp.float32,
            arrange=program_lfm2.arranger(LFM2_TOY)),
            program_lfm2.model_config(LFM2_TOY), 8)
    return (weights_sarvam_mla.generate(
        MLA_TOY, 11, jnp.float32,
        arrange=program_sarvam_mla.arranger(MLA_TOY)),
        program_sarvam_mla.model_config(MLA_TOY), 8)


def prompt(seed, n, vocab=97):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def count_puts(engine) -> dict[str, list[int]]:
    """Wrap the engine's step entries: per entry, the transfers each
    call that launched a program issued, by the engine's count and by
    the registry's (they must agree)."""
    seen = {name: [] for name in STEPS}
    total = obs.get_registry().counter("serving_operand_puts_total")
    for name in STEPS:
        def wrapped(real=getattr(engine, name), name=name):
            before = engine.operand_puts, total.value()
            launched = not (name == "prefill_step"
                            and not engine.has_pending)
            out = real()
            puts = engine.operand_puts - before[0]
            assert puts == total.value() - before[1]
            if launched:
                seen[name].append(puts)
            return out
        setattr(engine, name, wrapped)

    # the look-ahead loop's entry: a launch of the plain or the mixed
    # step behind the one in flight (``mixed`` None: it only lands)
    def ahead(flight, mixed, real=engine.step_ahead):
        before = engine.operand_puts, total.value()
        out = real(flight, mixed)
        puts = engine.operand_puts - before[0]
        assert puts == total.value() - before[1]
        if mixed is not None:
            seen["mixed_step" if mixed else "step"].append(puts)
        return out
    engine.step_ahead = ahead
    return seen


@pytest.fixture
def registry():
    reg = obs.get_registry()
    was = reg.enabled
    reg.reset()
    reg.enabled = True
    yield reg
    reg.enabled = was
    reg.reset()


def churn(batcher, vocab, lens):
    return batcher.run(
        [Request(prompt=prompt(10 + i, p, vocab), max_new_tokens=n)
         for i, (p, n) in enumerate(lens)])


@pytest.mark.parametrize("name", ["gpt", "lfm2", "latent"])
def test_one_transfer_a_step_under_churn(name, registry):
    """Five requests over two slots and a pool too small for them
    (seats, retirements, reuse, a preemption, prompts of several
    chunks, chunks alone and riding): every step, plain or mixed, and
    every lone chunk issues exactly ONE transfer, and the second pass
    of the same traffic compiles nothing."""
    params, cfg, page = family(name)
    vocab = 97 if name == "gpt" else 128
    engine = PagedEngine(params, cfg, page_size=page, n_pages=11,
                         max_slots=2, prefill_chunk_pages=2,
                         compute_dtype=jnp.float32)
    seen = count_puts(engine)
    batcher = ContinuousBatcher(engine)
    lens = [(p * page // 8, n * page // 8) for p, n in
            [(21, 40), (37, 30), (9, 40), (50, 9), (17, 25)]]
    assert churn(batcher, vocab, lens)["n_preemptions"] > 0
    first = engine.decode_compiles, engine.prefill_compiles
    assert first == (1, 2)
    churn(batcher, vocab, lens[::-1])
    assert (engine.decode_compiles, engine.prefill_compiles) == first
    for entry in ("step", "mixed_step", "prefill_step"):
        assert len(seen[entry]) > 3, (entry, seen[entry])
        assert set(seen[entry]) == {1}, (entry, seen[entry])
    assert registry.snapshot()["serving_operand_puts_total"] \
        == engine.operand_puts == sum(map(len, seen.values()))
    engine.tables.check()


# mode -> (engine options, transfers a call of each entry issues: the
# buffer, and one more for each large operand that rides beside it)
MODES = {
    "speculative": ({"speculative": True, "draft_len": 3},
                    {"spec_step": 1, "prefill_step": 1}),
    # + the tree's visibility matrix, + the compaction's offsets
    "spec_tree": ({"speculative": True, "draft_len": 3,
                   "spec_tree": True},
                  {"spec_step": 3, "prefill_step": 1}),
    "parallel": ({"parallel_sampling": True},
                 {"step": 1, "prefill_step": 1}),
    # + the (slots, vocab) legality mask; a lone chunk its slot's row
    "structured": ({"structured": True},
                   {"step": 2, "mixed_step": 2, "prefill_step": 2}),
    "lora": ({"lora_rank": 2, "lora_max_live": 2},
             {"step": 1, "prefill_step": 1}),
    "pallas": ({"decode_backend": "pallas"},
               {"step": 1, "mixed_step": 1, "prefill_step": 1}),
    "prefix_cache": ({"prefix_cache": True},
                     {"step": 1, "mixed_step": 1, "prefill_step": 1}),
}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_every_mode_packs(mode, registry):
    """Each engine mode's small operands (drafts, parents and depths,
    branch keys, adapter lanes, the pallas walk) ride the ONE buffer:
    a step issues one transfer, and one more only where a large
    operand rides beside it; no mode keeps an unpacked path."""
    params, cfg, page = gpt()
    options, expected = MODES[mode]
    engine = PagedEngine(params, cfg, page_size=page, n_pages=24,
                         max_slots=3, prefill_chunk_pages=2,
                         compute_dtype=jnp.float32, **options)
    seen = count_puts(engine)
    batcher = ContinuousBatcher(engine)
    n = 2 if mode == "parallel" else 1
    reqs = [Request(prompt=prompt(20 + i, p), max_new_tokens=m, n=n,
                    seed=i)
            for i, (p, m) in enumerate([(21, 9), (13, 12), (30, 6),
                                        (5, 8)])]
    batcher.run(reqs)
    assert all(len(req.tokens) or req.branches for req in reqs)
    ran = {entry: set(puts) for entry, puts in seen.items() if puts}
    assert ran == {entry: {n} for entry, n in expected.items()}, seen
    engine.tables.check()


def test_the_device_key_is_the_key_the_host_split():
    """A sampling engine's programs carry the key: each splits what
    the last one left (``key, sub = split(key)``) and picks with
    ``sub``, the mixed program with the halves of ``sub``. Recorded
    where they are used, the pick keys are those of a replica that
    splits the same seed on the host, once a program, and the tokens
    the engine returns are that replica's picks from the same logits.
    """
    params, cfg, page = gpt()
    seed = jax.random.PRNGKey(7)
    engine = PagedEngine(params, cfg, page_size=page, n_pages=24,
                         max_slots=3, prefill_chunk_pages=2,
                         compute_dtype=jnp.float32, temperature=0.9,
                         rng=seed)
    picks, real = [], engine._pick

    def recording(key, logits):
        jax.debug.callback(
            lambda k, l: picks.append((np.asarray(k), np.asarray(l))),
            key, logits, ordered=True)
        return real(key, logits)

    engine._pick = recording
    # (program, tokens it returned): three lone chunks, plain steps, a
    # mixed step, plain steps
    programs = []
    slot_a, first = engine.admit(prompt(1, 11))         # 2 chunks
    programs += [("chunk", None), ("chunk", [first])]
    slot_b, first = engine.admit(prompt(2, 5))
    programs += [("chunk", [first])]
    live = [slot_a, slot_b]
    for _ in range(3):
        assert not engine.grow_slots()
        programs.append(("decode", engine.step()[live]))
    assert engine.admit_begin(prompt(3, 7)) is not None
    assert not engine.grow_slots()
    tokens, done = engine.mixed_step()
    programs.append(("mixed", ([done[1]], tokens[live])))
    for _ in range(2):
        assert not engine.grow_slots()
        programs.append(("decode", engine.step()[live]))
    jax.effects_barrier()

    key, used = seed, iter(picks)
    for program, tokens in programs:
        key, sub = jax.random.split(key)
        subs = jax.random.split(sub) if program == "mixed" else [sub]
        if program != "mixed":
            tokens = (tokens,)
        for sub, want in zip(subs, tokens):
            got_key, logits = next(used)
            np.testing.assert_array_equal(got_key, np.asarray(sub))
            if want is not None:
                replica = np.asarray(real(sub, jnp.asarray(logits)))
                rows = live if len(replica) > 1 else [0]
                np.testing.assert_array_equal(replica[rows], want)
    assert next(used, None) is None
    # ... and the key the engine holds is the chain's last
    np.testing.assert_array_equal(np.asarray(engine._rng),
                                  np.asarray(key))


@pytest.mark.parametrize("name", ["gpt", "lfm2"])
def test_the_buffer_may_be_written_as_soon_as_it_is_put(name):
    """``device_put`` snapshots the host buffer during the call: with
    every word of it overwritten between the transfer and the launch,
    and left so until the program's results are there, the tokens are
    those of an untouched engine (sampled: the same seed), through
    lone chunks, plain and mixed steps."""
    params, cfg, page = family(name)
    vocab = 97 if name == "gpt" else 128

    def run(scribble):
        engine = PagedEngine(params, cfg, page_size=page, n_pages=24,
                             max_slots=3, prefill_chunk_pages=2,
                             compute_dtype=jnp.float32, temperature=0.7,
                             rng=jax.random.PRNGKey(3))
        host = engine.operands.host

        def scribbled(real):
            def launch(*args, **kw):
                kept = host.copy()
                host[:] = 0x5A5A5A5A
                outs = jax.block_until_ready(real(*args, **kw))
                host[:] = kept
                return outs
            launch._cache_size = real._cache_size   # the compile counters
            return launch

        if scribble:
            engine._decode_jit = scribbled(engine._decode_jit)
            engine._chunk_jit = scribbled(engine._chunk_jit)
        batcher = ContinuousBatcher(engine)
        reqs = [Request(prompt=prompt(30 + i, p, vocab), max_new_tokens=m)
                for i, (p, m) in enumerate([(2 * page + 3, 9), (5, 14),
                                            (3 * page, 6), (page, 8)])]
        batcher.run(reqs)
        assert engine.mixed_steps > 0
        engine.tables.check()
        return [list(req.tokens) for req in reqs]

    assert run(scribble=True) == run(scribble=False)


def test_the_tables_are_views_of_the_buffer():
    """``BlockTables.bind`` moves the int32 arrays into the buffer:
    what seat / advance / retire write is in ``host`` with no copy,
    ``active`` after ``pack()`` as 0/1, and ``unpack`` hands every
    field back by name in its shape."""
    cfg = GPTConfig(vocab=97, n_layers=1, d_model=16, n_heads=2,
                    seq_len=32)
    tables = BlockTables(cfg, page_size=4, n_pages=10, max_slots=3)
    tables.seat(1, prompt(0, 9))
    fields = {**tables.operand_fields(), "chunk": (3,), "ids": (2, 4)}
    operands = OperandBuffer(fields)
    assert operands.host.dtype == np.int32
    assert operands.host.size == sum(
        int(np.prod(shape)) for shape in fields.values())
    tables.bind(operands)
    tables.activate(1, 42)
    tables.advance(1, 43)
    tables.pack()
    got = operands.unpack(operands.host)
    assert list(got) == list(fields)
    for field in ("tables", "lengths", "refs", "page_pos", "last_ids"):
        assert np.shares_memory(getattr(tables, field), operands.host)
        np.testing.assert_array_equal(got[field], getattr(tables, field))
    assert got["lengths"][1] == 10 and got["last_ids"][1] == 43
    assert tables.active.dtype == bool
    np.testing.assert_array_equal(got["active"], [0, 1, 0])
    assert got["ids"].shape == (2, 4) and got["chunk"].shape == (3,)
    operands.view("ids")[1, 2] = 7
    assert operands.unpack(jnp.asarray(operands.host))["ids"][1, 2] == 7
    tables.retire(1)
    tables.check()
    assert not operands.view("lengths").any()


def test_the_reader_divides_the_transfers_by_the_decode_steps():
    """``operand_puts_per_step.lat``: the window's growth of
    ``serving_operand_puts_total`` over that of the ``decode_step``
    span's count; None (never 0, never an exception) on a program
    without the counter, which is what the parent commit gives."""
    import run as harness

    if str(BENCH / "layer_metrics") not in sys.path:
        sys.path.append(str(BENCH / "layer_metrics"))
    reader = harness.load_module(
        BENCH / "layer_metrics" / "operand_puts_per_step.py")
    steps = "span_seconds{name=decode_step}_count"
    name = "operand_puts_per_step.lat"
    window = {"registry_open": {"serving_operand_puts_total": 40.0,
                                steps: 30.0},
              "registry_close": {"serving_operand_puts_total": 243.0,
                                 steps: 230.0}}
    assert reader.read(name, window) == pytest.approx(203 / 200)
    parent = {"registry_open": {steps: 30.0},
              "registry_close": {steps: 230.0}}
    assert reader.read(name, parent) is None
    assert reader.read(name, {}) is None
    idle = {"registry_open": {"serving_operand_puts_total": 4.0, steps: 3.0},
            "registry_close": {"serving_operand_puts_total": 4.0,
                               steps: 3.0}}
    assert reader.read(name, idle) is None
    # ... and the manifest's entry finds this file, in the serve cells
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for cell in manifest["workloads"]:
        wanted = {m["name"] for m in harness.metrics_of(
            manifest, cell["name"], "per_layer")}
        assert (name in wanted) == ("serve" in cell["traffic"])
