"""Structured generation (PR 18) on CPU:

- the regex -> char DFA -> token DFA compiler: escape/class/number/
  unicode-escape edges, the JSON-schema lowering subset, loud
  rejection of unknown ``response_format`` types (naming the value),
  fingerprint caching, the token-level trim (the only dead end is an
  accepting state) and the unsatisfiable-vocabulary failure;
- SlotCursors: prefix replay == stepwise advance (the preemption
  restore path), fork rebasing, reset, and the illegal-token /
  EOS-at-non-accepting desync guards;
- the batcher end to end: mixed constrained/unconstrained traffic
  conforms 100% with ``finish_reason: stop``, stable metric keys,
  the flight recorder's ``structured`` column, and the submit-time
  validation (non-structured engine, missing eos_id, unknown type);
- the zero-recompile contract: every library schema churned through
  ONE engine leaves ``decode_compiles`` at exactly 1;
- composition: constrained x speculative (token parity vs the
  non-speculative structured engine, one verify compile) and
  constrained x n-way parallel sampling (reproducible branch
  streams, every branch conforms) plus preemption token-exactness;
- the YAML knobs (``serving.structured``, ``loadgen.structured_frac``)
  and workload format v3 (response_format round-trip, fingerprint
  coverage only-when-set, v2 compatibility);
- the HTTP surface: 400 naming the offending type / the missing
  engine flag, and a constrained completion served over the wire.
"""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.models.gpt import GPT, GPTConfig
from torchbooster_tpu.serving.structured import (
    SCHEMA_LIBRARY,
    SlotCursors,
    bytes_vocab,
    compile_regex,
    compile_response_format,
    conforms,
    library_response_format,
    response_format_fingerprint,
    response_format_regex,
    schema_budget,
    schema_to_regex,
    token_dfa,
    validate_response_format,
)

from tests.test_frontend import _get, _unary  # noqa: E402

EOS = 299


def _decisive_model(seq_len=128):
    """Tiny GPT whose vocabulary COVERS the byte alphabet (ids < 256
    render chr(id); the library schemas emit printable ASCII) with a
    decisive argmax head — same trick as test_serving."""
    cfg = GPTConfig(vocab=300, n_layers=2, d_model=32, n_heads=4,
                    seq_len=seq_len)
    params = GPT.init(jax.random.PRNGKey(0), cfg)
    params = {**params, "wte": {"table": params["wte"]["table"] * 4.0}}
    return params, cfg


def _engine(params, cfg, **kw):
    from torchbooster_tpu.serving import PagedEngine

    kw.setdefault("page_size", 8)
    kw.setdefault("n_pages", 64)
    kw.setdefault("max_slots", 4)
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("structured", True)
    return PagedEngine(params, cfg, **kw)


def _text(tokens, eos=EOS):
    toks = tokens[:-1] if tokens and tokens[-1] == eos else tokens
    return "".join(chr(int(t)) for t in toks if int(t) < 256)


# ---- the compiler: regex / schema / response_format ----------------

def test_char_dfa_matches_edges():
    d = compile_regex("(ab|ac)*d")
    assert d.matches("d") and d.matches("abacd")
    assert not d.matches("abc") and not d.matches("")
    # escapes reach the literal characters
    assert compile_regex(r"\{\}").matches("{}")
    assert compile_regex(r'"\\"').matches('"\\"')
    # classes, negation, ranges
    cls = compile_regex(r"[a-c][^x]")
    assert cls.matches("by") and not cls.matches("bx")
    assert not cls.matches("dy")
    # bounded repetition
    rep = compile_regex("a{2,3}")
    assert rep.matches("aa") and rep.matches("aaa")
    assert not rep.matches("a") and not rep.matches("aaaa")
    # syntax / empty-language failures are loud
    with pytest.raises(ValueError):
        compile_regex("(a")


def test_schema_to_regex_number_string_unicode_edges():
    num = compile_regex(schema_to_regex({"type": "number"}))
    for ok in ("0", "-7", "3.25", "1e9", "-1.5e-3", "10E+2"):
        assert num.matches(ok), ok
    for bad in ("01", "1.", "+1", "--2", ".5", "1e"):
        assert not num.matches(bad), bad
    integer = compile_regex(schema_to_regex({"type": "integer"}))
    assert integer.matches("42") and not integer.matches("007")
    assert not integer.matches("1.0")
    # strings: the canonical JSON alphabet includes \uXXXX escapes
    # and excludes raw control characters / bare quotes
    s = compile_regex(schema_to_regex({"type": "string"}))
    assert s.matches('"hi"') and s.matches('"\\u0041\\n"'
                                           .replace("\\n", "\\n"))
    assert s.matches('"a\\\\b"') and not s.matches('"a"b"')
    assert not s.matches('"\t"')
    bounded = compile_regex(schema_to_regex(
        {"type": "string", "minLength": 1, "maxLength": 2}))
    assert bounded.matches('"a"') and bounded.matches('"ab"')
    assert not bounded.matches('""') and not bounded.matches('"abc"')
    # arrays/objects lower to the canonical no-whitespace rendering
    arr = compile_regex(schema_to_regex(
        {"type": "array", "items": {"enum": ["x"]},
         "minItems": 1, "maxItems": 2}))
    assert arr.matches('["x"]') and arr.matches('["x","x"]')
    assert not arr.matches("[]") and not arr.matches('["x","x","x"]')
    with pytest.raises(ValueError, match="unsupported"):
        schema_to_regex({"type": "tuple"})
    with pytest.raises(ValueError, match="enum"):
        schema_to_regex({"enum": []})


def test_response_format_parsing_names_the_offending_type():
    assert response_format_regex({"type": "text"}) is None
    # both schema nestings are accepted and agree
    flat = {"type": "json_schema", "schema": {"type": "boolean"}}
    nested = {"type": "json_schema",
              "json_schema": {"schema": {"type": "boolean"}}}
    assert response_format_regex(flat) == response_format_regex(nested)
    with pytest.raises(ValueError, match="json_schemaa"):
        validate_response_format({"type": "json_schemaa"})
    with pytest.raises(ValueError, match="pattern"):
        validate_response_format({"type": "regex"})
    with pytest.raises(ValueError, match="schema"):
        validate_response_format({"type": "json_schema"})
    # json_object accepts any canonical object
    assert conforms({"type": "json_object"}, '{"a":1}')
    assert not conforms({"type": "json_object"}, "[1]")


def test_token_dfa_trim_eos_discipline_and_cache():
    vocab = bytes_vocab(300)
    spec = library_response_format("enum_color")
    cache: dict = {}
    dfa = compile_response_format(spec, vocab, cache)
    assert compile_response_format(spec, vocab, cache) is dfa
    assert cache[response_format_fingerprint(spec)] is dfa
    # EOS ids are never grammar tokens; every non-accepting state
    # keeps >= 1 legal token (the trim guarantee), so forced
    # termination only happens at an accepting dead end
    assert not dfa.mask[:, EOS].any()
    for s in range(dfa.n_states):
        if not dfa.accepting[s]:
            assert dfa.mask[s].any()
    # walking '"red"' ends accepting with no continuation (bounded)
    s = dfa.start
    for ch in '"red"':
        assert dfa.mask[s, ord(ch)]
        s = int(dfa.nxt[s, ord(ch)])
    assert dfa.accepting[s] and not dfa.mask[s].any()
    # a constraint no token can render fails loudly
    with pytest.raises(ValueError, match="unsatisfiable"):
        token_dfa(compile_regex(chr(233)), bytes_vocab(128))


def test_schema_library_budgets_are_bounded():
    for sid in SCHEMA_LIBRARY:
        assert schema_budget(sid) >= 2
        validate_response_format(library_response_format(sid))


# ---- SlotCursors ---------------------------------------------------

def test_cursor_prefix_replay_matches_stepwise_advance():
    vocab = bytes_vocab(300)
    dfa = compile_response_format(
        library_response_format("label_score"), vocab)
    text = '{"label":"b","score":3}'
    toks = [ord(c) for c in text]

    step = SlotCursors(4, 300)
    step.begin(0, dfa, EOS)
    for t in toks:
        step.observe(0, [t])
    replay = SlotCursors(4, 300)
    replay.begin(1, dfa, EOS, prefix_tokens=toks)   # the preempt path
    assert step.state_of(0) == replay.state_of(1)
    np.testing.assert_array_equal(step.mask[0], replay.mask[1])
    # the finished automaton is EOS-only; observing EOS parks it
    assert step.mask[0, EOS] and step.mask[0].sum() == 1
    step.observe(0, [EOS])
    assert step.state_of(0) < 0


def test_cursor_fork_reset_and_desync_guards():
    vocab = bytes_vocab(300)
    dfa = compile_response_format(
        library_response_format("enum_color"), vocab)
    c = SlotCursors(4, 300)
    c.begin(0, dfa, EOS)
    c.observe(0, [ord('"'), ord("r")])
    c.fork_child(0, 2)                  # rebased to the START state
    np.testing.assert_array_equal(c.mask[2], c.start_row(0))
    assert c.live_count == 2
    c.reset(2)
    assert bool(c.mask[2].all()) and c.live_count == 1
    # desyncs raise instead of silently corrupting the mask
    with pytest.raises(ValueError, match="not a legal"):
        c.observe(0, [ord("z")])
    with pytest.raises(ValueError, match="non-accepting"):
        c.observe(0, [EOS])
    # an EOS inside the schema alphabet is rejected at begin
    with pytest.raises(ValueError, match="shadow"):
        SlotCursors(4, 300).begin(1, dfa, ord('"'))
    with pytest.raises(ValueError, match="outside the vocabulary"):
        SlotCursors(4, 300).begin(1, dfa, 300)


def test_cursor_draft_rows_truncate_illegal_suffix():
    vocab = bytes_vocab(300)
    dfa = compile_response_format(
        library_response_format("enum_color"), vocab)
    c = SlotCursors(2, 300)
    c.begin(0, dfa, EOS)
    draft = [ord('"'), ord("r"), ord("z"), ord("d")]
    d, rows = c.draft_rows(0, draft)
    assert list(d) == [ord('"'), ord("r"), -1, -1]
    assert rows.shape == (5, 300)
    assert rows[1, ord("r")] and not rows[2, ord("z")]


# ---- batcher end to end --------------------------------------------

def test_batcher_structured_conformance_metrics_and_flight():
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    batcher = ContinuousBatcher(engine)
    reqs = [
        Request(prompt=np.arange(1, 9), max_new_tokens=40, eos_id=EOS,
                response_format=library_response_format("label_score"),
                request_id="r0"),
        Request(prompt=np.arange(3, 11), max_new_tokens=8,
                request_id="r1"),
        Request(prompt=np.arange(5, 13), max_new_tokens=40, eos_id=EOS,
                response_format=library_response_format("enum_color"),
                request_id="r2"),
    ]
    m = batcher.run(reqs)
    for r in reqs:
        if r.response_format is None:
            assert r.finish_reason == "length"
            continue
        assert r.finish_reason == "stop"
        assert conforms(r.response_format, _text(r.tokens))
    assert m["n_structured"] == 2
    assert 0.0 < m["structured_masked_frac"] <= 1.0
    assert engine.decode_compiles == 1 \
        and 1 <= engine.prefill_compiles <= 2
    assert any(rec["structured"] > 0 for rec in batcher.flight.tail(8))
    stats = engine.debug_stats()
    assert stats["structured"] and stats["structured_requests"] == 2
    assert stats["structured_schemas"] == 2
    engine.tables.check()


def test_structured_submit_validation():
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    rf = library_response_format("bool_flag")
    # a constraining format without an eos_id fails at construction
    with pytest.raises(ValueError, match="eos_id"):
        Request(prompt=np.arange(4), max_new_tokens=4,
                response_format=rf)
    with pytest.raises(TypeError, match="response_format"):
        Request(prompt=np.arange(4), max_new_tokens=4,
                response_format="json_object")
    # unknown type -> submit-time ValueError NAMING the value, even
    # on a structured engine
    b = ContinuousBatcher(_engine(params, cfg))
    with pytest.raises(ValueError, match="json_schemaa"):
        b.run([Request(prompt=np.arange(4), max_new_tokens=4,
                       eos_id=EOS,
                       response_format={"type": "json_schemaa"})])
    # a non-structured engine names the flag to turn on
    b2 = ContinuousBatcher(_engine(params, cfg, structured=False))
    with pytest.raises(ValueError, match="structured"):
        b2.run([Request(prompt=np.arange(4), max_new_tokens=4,
                        eos_id=EOS, response_format=rf)])
    # {"type": "text"} is a no-op everywhere
    req = Request(prompt=np.arange(1, 7), max_new_tokens=4,
                  response_format={"type": "text"})
    m = b2.run([req])
    assert len(req.tokens) == 4 and m["n_structured"] == 0
    assert m["structured_masked_frac"] == 0.0


def test_structured_schema_churn_zero_recompiles():
    """Every library schema through ONE engine: the mask is a traced
    VALUE operand, so the schema mix can never re-specialize the
    compiled decode step."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    engine = _engine(params, cfg)
    batcher = ContinuousBatcher(engine)
    batcher.run([Request(prompt=np.arange(1, 7), max_new_tokens=4)])
    for i, sid in enumerate(sorted(SCHEMA_LIBRARY)):
        req = Request(prompt=np.arange(1 + i, 9 + i),
                      max_new_tokens=schema_budget(sid), eos_id=EOS,
                      response_format=library_response_format(sid))
        batcher.run([req])
        assert req.finish_reason == "stop"
        assert conforms(req.response_format, _text(req.tokens))
    assert engine.decode_compiles == 1
    assert engine.prefill_compiles == 1
    assert engine.debug_stats()["structured_schemas"] == \
        len(SCHEMA_LIBRARY)


def test_structured_preemption_resumes_token_exact():
    """A constrained request evicted mid-decode re-prefills from its
    folded context; begin()'s prefix replay restores the automaton
    token-exactly, so the stream matches the unpreempted run."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model(seq_len=64)
    rf = library_response_format("label_score")
    budget = schema_budget("label_score")
    prompt = np.arange(1, 7)

    ref = Request(prompt=prompt, max_new_tokens=budget, eos_id=EOS,
                  response_format=rf)
    ContinuousBatcher(_engine(params, cfg, page_size=4,
                              n_pages=32)).run([ref])
    assert ref.finish_reason == "stop"

    engine = _engine(params, cfg, page_size=4, n_pages=10,
                     max_slots=2)
    filler = Request(prompt=np.arange(11, 17), max_new_tokens=16,
                     arrival=0.0)
    req = Request(prompt=prompt, max_new_tokens=budget, eos_id=EOS,
                  response_format=rf, arrival=0.01)
    m = ContinuousBatcher(engine).run([filler, req])
    assert m["n_preemptions"] > 0
    assert req.tokens == ref.tokens
    assert conforms(rf, _text(req.tokens))
    engine.tables.check()


def test_permissive_schema_leaves_greedy_stream_unchanged():
    """When the grammar PERMITS the unconstrained greedy stream, the
    mask must not perturb it: over a byte-complete vocabulary a
    constraint allowing every character reduces to the all-ones row,
    and the constrained picks match the unconstrained ones exactly."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    cfg = GPTConfig(vocab=128, n_layers=2, d_model=32, n_heads=4,
                    seq_len=64)
    params = GPT.init(jax.random.PRNGKey(0), cfg)
    params = {**params, "wte": {"table": params["wte"]["table"] * 4.0}}
    eos = 127
    prompt = np.arange(1, 7)

    plain = Request(prompt=prompt, max_new_tokens=8)
    ContinuousBatcher(_engine(params, cfg, structured=False)).run(
        [plain])
    assert eos not in plain.tokens      # eos stays out of the stream

    # [^\x7f]* permits every token except the EOS byte, every state
    # accepting — the allowed set equals the full vocabulary
    req = Request(prompt=prompt, max_new_tokens=8, eos_id=eos,
                  response_format={"type": "regex",
                                   "pattern": "[^\\x7f]*"})
    engine = _engine(params, cfg)
    m = ContinuousBatcher(engine).run([req])
    assert req.tokens == plain.tokens
    assert m["n_structured"] == 1
    assert engine.decode_compiles == 1


def test_replay_inprocess_passes_response_format_through():
    """Structured traffic is capturable/replayable: a synthesized
    structured workload replayed through the batcher core serves its
    constrained requests to conformance."""
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.loadgen import replay_inprocess
    from torchbooster_tpu.serving.loadgen.workload import synthesize

    params, cfg = _decisive_model()
    wl = synthesize("poisson", n_requests=6, seed=3, vocab=300,
                    prompt_len=(4, 8), max_new_tokens=(4, 8),
                    structured_frac=0.5)
    constrained_ids = {r.request_id for r in wl.requests
                      if r.response_format is not None}
    assert constrained_ids
    engine = _engine(params, cfg)
    res = replay_inprocess(ContinuousBatcher(engine), wl, speed=100.0)
    assert res.metrics["n_structured"] == len(constrained_ids)
    for r in res.requests:
        if r.request_id in constrained_ids:
            assert r.finish_reason == "stop"
            assert conforms(r.response_format, _text(r.tokens))
    assert engine.decode_compiles == 1


# ---- composition: speculative / parallel sampling ------------------

def test_structured_spec_parity_and_one_verify_compile():
    """Constrained x speculative: drafts are pre-validated and verify
    logits masked, so the greedy constrained stream is TOKEN-EXACT vs
    the non-speculative structured engine — and the accept-length
    churn leaves exactly one verify compile."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()

    def serve(**kw):
        reqs = [
            Request(prompt=np.arange(1, 9), max_new_tokens=40,
                    eos_id=EOS,
                    response_format=library_response_format(
                        "label_score")),
            Request(prompt=np.arange(2, 10), max_new_tokens=40,
                    eos_id=EOS,
                    response_format=library_response_format("tags")),
            Request(prompt=np.arange(3, 11), max_new_tokens=12),
        ]
        engine = _engine(params, cfg, **kw)
        ContinuousBatcher(engine).run(reqs)
        return engine, [list(r.tokens) for r in reqs], reqs

    _, want, _ = serve()
    engine, got, reqs = serve(speculative=True, draft_len=4)
    assert got == want
    for r in reqs[:2]:
        assert r.finish_reason == "stop"
        assert conforms(r.response_format, _text(r.tokens))
    assert engine.verify_compiles == 1
    assert engine.decode_compiles == 0   # spec engines never chain


def test_structured_nway_branches_conform_and_reproduce():
    """Constrained x parallel sampling: the cursor forks with the
    slot, so every sampled branch stays inside the grammar — and the
    seeded family reproduces across fresh engines."""
    from torchbooster_tpu.serving import ContinuousBatcher, Request

    params, cfg = _decisive_model()
    rf = library_response_format("verdict")

    def family():
        req = Request(prompt=np.arange(1, 9),
                      max_new_tokens=schema_budget("verdict"),
                      eos_id=EOS, response_format=rf, n=2, seed=7)
        engine = _engine(params, cfg, parallel_sampling=True,
                         temperature=1.0)
        ContinuousBatcher(engine).run([req])
        engine.tables.check()
        return engine, req

    engine, fam = family()
    assert len(fam.branches) == 2
    for br in fam.branches:
        assert br.finish_reason == "stop"
        assert conforms(rf, _text(br.tokens))
    assert engine.decode_compiles == 1
    _, again = family()
    assert [b.tokens for b in again.branches] == \
        [b.tokens for b in fam.branches]


# ---- config / loadgen ----------------------------------------------

def test_serving_yaml_structured_knob(tmp_path):
    from torchbooster_tpu.config import ServingConfig

    params, cfg = _decisive_model()
    yml = tmp_path / "s.yml"
    yml.write_text("page_size: 8\nn_pages: 32\nmax_slots: 2\n"
                   "structured:\n  enabled: true\n")
    sc = ServingConfig.load(yml)
    assert sc.structured.enabled is True
    batcher = sc.make(params, cfg, compute_dtype=jnp.float32)
    assert batcher.engine.structured is True
    # default stays off — the cold engine carries no cursor table
    off = ServingConfig(page_size=8, n_pages=32, max_slots=2)
    assert off.structured.enabled is False
    assert off.make(params, cfg).engine.structured is False


def test_workload_v3_response_format_roundtrip_and_v2(tmp_path):
    import json

    from torchbooster_tpu.serving.loadgen.workload import (
        Workload, WorkloadRequest)

    rf = library_response_format("enum_color")

    def wl(spec=None, eos=None):
        return Workload(requests=[WorkloadRequest(
            arrival_s=0.0, max_new_tokens=8,
            prompt=np.arange(1, 5, dtype=np.int32),
            request_id="r0", eos_id=eos, response_format=spec)])

    plain, constrained = wl(), wl(rf, EOS)
    # the fingerprint covers response_format ONLY when set
    assert plain.fingerprint() != constrained.fingerprint()
    assert wl(rf, EOS).fingerprint() == constrained.fingerprint()
    path = constrained.save(tmp_path / "w.jsonl")
    header = json.loads(path.read_text().splitlines()[0])
    assert header["version"] == 4    # the PR 19 adapter field's bump
    loaded = Workload.load(path)
    assert loaded.requests[0].response_format == rf
    assert loaded.fingerprint() == constrained.fingerprint()
    # a v2 file (no response_format field) still loads, unconstrained
    v2 = tmp_path / "v2.jsonl"
    lines = [json.loads(ln) for ln in
             plain.save(tmp_path / "p.jsonl").read_text().splitlines()]
    lines[0]["version"] = 2
    for rec in lines[1:]:
        rec.pop("response_format", None)
    v2.write_text("\n".join(json.dumps(d) for d in lines) + "\n")
    assert Workload.load(v2).requests[0].response_format is None
    # malformed values are rejected loudly
    with pytest.raises(ValueError, match="response_format"):
        WorkloadRequest(arrival_s=0.0, max_new_tokens=1,
                        prompt=np.asarray([1], np.int32),
                        response_format="json_object")
    with pytest.raises(ValueError, match="eos_id"):
        WorkloadRequest(arrival_s=0.0, max_new_tokens=1,
                        prompt=np.asarray([1], np.int32),
                        response_format=rf)


def test_synthesize_structured_frac_deterministic_and_validated():
    from torchbooster_tpu.serving.loadgen.workload import synthesize

    a = synthesize("poisson", n_requests=40, seed=7,
                   structured_frac=0.5)
    b = synthesize("poisson", n_requests=40, seed=7,
                   structured_frac=0.5)
    assert a.fingerprint() == b.fingerprint()
    specs = [r.response_format for r in a.requests]
    assert any(s is not None for s in specs)
    assert any(s is None for s in specs)
    for r in a.requests:
        if r.response_format is not None:
            assert r.eos_id is not None
            validate_response_format(r.response_format)
    # the knob draws off its OWN stream: plain requests' prompts are
    # unchanged between structured_frac 0 and > 0
    base = synthesize("poisson", n_requests=40, seed=7)
    for r0, r1 in zip(base.requests, a.requests):
        np.testing.assert_array_equal(r0.prompt, r1.prompt)
    assert base.fingerprint() == synthesize(
        "poisson", n_requests=40, seed=7,
        structured_frac=0.0).fingerprint()
    with pytest.raises(ValueError, match="structured_frac"):
        synthesize("poisson", structured_frac=1.5)
    with pytest.raises(ValueError, match="vocab"):
        synthesize("poisson", structured_frac=0.5, vocab=100)


def test_loadgen_yaml_structured_frac(tmp_path):
    from torchbooster_tpu.config import LoadgenConfig

    yml = tmp_path / "l.yml"
    yml.write_text("source: poisson\nn_requests: 12\nseed: 3\n"
                   "structured_frac: 0.75\n")
    wl = LoadgenConfig.load(yml).make()
    assert any(r.response_format is not None for r in wl.requests)


# ---- the HTTP surface ----------------------------------------------

def test_http_response_format_400_paths_and_constrained_serve():
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import ServingFrontend

    params, cfg = _decisive_model()
    fe = ServingFrontend(ContinuousBatcher(_engine(params, cfg)))
    rf = library_response_format("label_score")

    async def scenario():
        await fe.start()
        base = {"prompt": list(range(1, 9)), "max_tokens": 40,
                "eos_id": EOS}
        # unknown type -> 400 naming the offending value
        s1, _, e1 = await _unary(fe.port, "/v1/completions",
                                 {**base, "response_format":
                                  {"type": "json_schemaa"}})
        # constraining format without an eos_id -> 400 naming eos_id
        s2, _, e2 = await _unary(fe.port, "/v1/completions",
                                 {"prompt": [1, 2, 3], "max_tokens": 4,
                                  "response_format": rf})
        # the happy path: a conforming completion over the wire
        s3, _, body = await _unary(fe.port, "/v1/completions",
                                   {**base, "response_format": rf})
        mstatus, prom = await _get(fe.port, "/metrics")
        await fe.stop()
        return s1, e1, s2, e2, s3, body, mstatus, prom.decode()

    s1, e1, s2, e2, s3, body, mstatus, prom = asyncio.run(scenario())
    assert s1 == 400 and "json_schemaa" in e1["error"]["message"]
    assert s2 == 400 and "eos_id" in e2["error"]["message"]
    assert s3 == 200
    choice = body["choices"][0]
    assert choice["finish_reason"] == "stop"
    assert conforms(rf, _text(choice["token_ids"]))
    assert mstatus == 200
    assert "serving_structured_requests_total" in prom


def test_http_constrained_against_plain_engine_400():
    from torchbooster_tpu.serving import ContinuousBatcher
    from torchbooster_tpu.serving.frontend import ServingFrontend

    params, cfg = _decisive_model()
    fe = ServingFrontend(ContinuousBatcher(
        _engine(params, cfg, structured=False)))

    async def scenario():
        await fe.start()
        status, _, err = await _unary(
            fe.port, "/v1/completions",
            {"prompt": [1, 2, 3], "max_tokens": 4, "eos_id": EOS,
             "response_format": library_response_format("bool_flag")})
        await fe.stop()
        return status, err

    status, err = asyncio.run(scenario())
    assert status == 400
    assert "structured" in err["error"]["message"]
