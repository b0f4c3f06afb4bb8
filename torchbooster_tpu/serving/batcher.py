"""Host-side continuous batching: policy-driven admission over the
paged engine.

The reference framework has no serving story at all (DDP training
only); this is the scheduling core of the serving subsystem. Requests
queue; whenever a slot AND enough pages are free, a request picked by
the SCHEDULER POLICY is SEATED (its prompt pages allocated, cached
prefix pages mapped in) and its prefill streams in as fixed-size
chunks — each scheduling iteration issues ONE prefill chunk, then one
compiled decode step over all live slots, so a long arriving prompt
adds at most one chunk of latency between decode steps instead of
stalling them for its whole prefill. Sequences retire on EOS, on
their ``max_new_tokens``, or at the ``seq_len`` cache horizon — all
without touching the compiled steps (kv_pages.py fixed-shape tables).

The per-iteration body lives in :meth:`ContinuousBatcher.step` — a
PUMPABLE core. :meth:`run` drives it synchronously over a whole
request trace (the bench/test surface, unchanged); the asyncio front
door (serving/frontend/server.py) drives the same ``step`` from an
event loop, feeding it via the thread-safe :meth:`submit` /
:meth:`cancel` inboxes and streaming the per-step token events back
to HTTP clients. Cancellation routes through the engine's existing
abort paths: a queued request just leaves the queue, a mid-prefill
request hits the pending-slot abort (PR 4), a decoding request
retires — all page-reclaiming, none recompiling.

WHICH request seats next, which queued requests are SHED (rejected
with backpressure instead of a guaranteed deadline miss), and which
seated request is PREEMPTED under pool pressure are delegated to a
:class:`~torchbooster_tpu.serving.frontend.scheduler.SchedulerPolicy`.
The default :class:`FCFSPolicy` reproduces the pre-frontend batcher
exactly (strict arrival order, head-of-line blocking, never shed,
youngest victim); :class:`SLOPolicy` makes admission deadline-driven
(earliest slack first over priority classes) and picks victims by
re-admission cost (a prefix-cached victim is nearly free to re-seat).

Pool pressure is handled by PREEMPTION, not failure: when a growing
sequence cannot get its next page (even after evicting cached
prefixes), the policy's victim — mid-prefill or decoding — is pushed
back to the FRONT of the queue with its generated tokens folded into
its prompt (it re-prefills later and keeps going); requests too big
for the whole pool fail loudly at submit.

Metrics mirror the training A/B machinery's spirit — every number a
JSON-serializable scalar so serving rows land in the same logs:
per-request latency (arrival → completion) and time-to-first-token,
plus aggregate decode tokens/s over the busy window, plus the
admission/preemption/shed/cancel counts, prefill-chunk count, and
prefix-cache hit stats; SLO policies add per-class TTFT/TPOT
percentiles and deadline hit rates (``classes`` sub-dicts). Every run
also feeds the telemetry registry (``serving_*`` — and, under an SLO
policy, ``serving_slo_*`` — counters/histograms/gauges, the
exporters' view of the same events) and is watched by a
:class:`~torchbooster_tpu.observability.RecompileSentinel`, which
turns the engine's zero-recompile contract into a runtime guard
(``on_recompile`` selects ignore/warn/raise).
"""
from __future__ import annotations

import time
import uuid
import zlib
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from torchbooster_tpu.observability import (
    RecompileSentinel,
    get_registry,
    span,
)
from torchbooster_tpu.observability.flight import (
    FlightRecorder,
    step_kind_code,
)
from torchbooster_tpu.observability.recompile import POLICIES
from torchbooster_tpu.observability.tracing import RequestTracer
from torchbooster_tpu.serving.engine import PagedEngine
from torchbooster_tpu.serving.kv_pages import PoolExhausted
from torchbooster_tpu.serving.structured import (
    validate_response_format,
)
from torchbooster_tpu.serving.frontend.scheduler import (
    FCFSPolicy,
    SchedulerPolicy,
)


@dataclass(eq=False)
class Request:
    """One generation request — identity-compared (``eq=False``): the
    scheduler queues/cancels BY OBJECT, and field equality over numpy
    prompts is ambiguous anyway. ``arrival`` is an offset (seconds) from
    the batcher's clock start — 0 means "already waiting"; the bench's
    Poisson trace sets real offsets and the HTTP front door stamps
    submit time. ``eos_id=None`` never stops early.

    SLO fields (all optional — the FCFS path ignores them, so a
    pre-frontend ``Request(prompt, max_new_tokens, ...)`` construction
    is untouched): ``priority`` names a configured
    :class:`~torchbooster_tpu.serving.frontend.scheduler.PriorityClass`
    ("" = the policy's default class; membership is validated at
    submit time, where the class table is known), ``deadline_ms``
    overrides the class TTFT deadline, and ``arrival_time`` is the
    submitter's wall-clock timestamp (informational — scheduling runs
    on the batcher clock via ``arrival``).

    Parallel sampling (OpenAI ``n``/``best_of``; needs a
    ``parallel_sampling=True`` engine): ``n`` completions are
    returned, ``best_of`` (default ``n``) branches are decoded and
    ranked by cumulative logprob — ONE prefill forks into
    ``best_of`` copy-on-write branches at the first token. ``seed``
    pins the request's sampling key family (branch b samples with
    ``fold_in(PRNGKey(seed), b)``); ``None`` derives one from the
    request id, so replays with stable ids reproduce exactly. The
    batcher materializes sibling branches as internal child Requests
    (``parent``/``branch``/``branches`` fields) that ride every
    scheduling path — preemption folds and re-admits a branch alone,
    its key keeps its stream token-exact.

    Structured generation (OpenAI ``response_format``; constraining
    types need a ``structured=True`` engine): ``None`` or ``{"type":
    "text"}`` is unconstrained; ``json_object``/``json_schema``/
    ``regex`` bind a token-DFA cursor at seat time that masks every
    sampling step to legal continuations. Constraining types REQUIRE
    ``eos_id`` — the automaton signals "the output is complete" by
    forcing EOS, and without a stop id the request could only ever
    finish by length, mid-schema. Schema validation (the 400 surface)
    happens at submit via the engine's compiler, not here."""
    prompt: np.ndarray
    max_new_tokens: int = 32
    eos_id: int | None = None
    arrival: float = 0.0
    priority: str = ""
    deadline_ms: float | None = None
    arrival_time: float | None = None
    n: int = 1
    best_of: int | None = None
    seed: int | None = None
    # structured generation: an OpenAI response_format object (None =
    # unconstrained, same as {"type": "text"})
    response_format: dict | None = None
    # multi-LoRA serving: the adapter NAME this request decodes
    # through (the HTTP surface's ``model`` field; "" = the base
    # model). Validated at submit against the engine's registry — an
    # unknown name is a 400 before any pages move. The batcher
    # acquires a registry pin at seat time and releases it on every
    # retire path; a preempted request re-acquires on re-seat
    # (possibly a different device lane — lanes are traced values,
    # so nothing recompiles).
    adapter: str = ""
    # stable identity for tracing and the HTTP surface: auto-generated
    # when empty; the front door honors a client X-Request-Id header
    # by passing it through here
    request_id: str = ""
    # filled by the batcher
    tokens: list = field(default_factory=list)
    admitted_at: float | None = None
    first_token_at: float | None = None
    finished_at: float | None = None
    finish_reason: str | None = None
    shed: bool = False
    cancelled: bool = False
    # fork bookkeeping (filled by the batcher at fork time): branch 0
    # is the submitted request itself; siblings are internal child
    # Requests pointing back via ``parent``; ``branches`` (on branch
    # 0 only) lists the whole family in branch order once forked —
    # also the "already forked" latch a preempted-and-reseated branch
    # 0 relies on. ``cum_logprob`` accumulates the picked tokens'
    # logprobs for best_of ranking.
    parent: "Request | None" = None
    branch: int = 0
    branches: "list | None" = None
    cum_logprob: float = 0.0

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if not isinstance(self.priority, str):
            raise TypeError(
                f"priority must be a class NAME (str, '' = policy "
                f"default), got {type(self.priority).__name__} "
                f"{self.priority!r}")
        if self.deadline_ms is not None and self.deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be > 0 (None = class default), got "
                f"{self.deadline_ms}")
        if self.arrival_time is not None and self.arrival_time < 0:
            raise ValueError(
                f"arrival_time must be a non-negative timestamp, got "
                f"{self.arrival_time}")
        if not isinstance(self.request_id, str):
            raise TypeError(
                f"request_id must be a str ('' = auto-generate), got "
                f"{type(self.request_id).__name__}")
        if not isinstance(self.adapter, str):
            raise TypeError(
                f"adapter must be a registered adapter NAME (str, "
                f"'' = base model), got "
                f"{type(self.adapter).__name__} {self.adapter!r}")
        if not isinstance(self.n, int) or self.n < 1:
            raise ValueError(f"n must be an int >= 1, got {self.n!r}")
        if self.best_of is not None and (
                not isinstance(self.best_of, int)
                or self.best_of < self.n):
            raise ValueError(
                f"best_of must be an int >= n ({self.n}), got "
                f"{self.best_of!r}")
        if self.seed is not None and not isinstance(self.seed, int):
            raise TypeError(
                f"seed must be an int or None, got "
                f"{type(self.seed).__name__}")
        if self.response_format is not None:
            if not isinstance(self.response_format, dict):
                raise TypeError(
                    f"response_format must be a dict or None, got "
                    f"{type(self.response_format).__name__}")
            if self.response_format.get("type") != "text" \
                    and self.eos_id is None:
                raise ValueError(
                    "a constraining response_format requires eos_id: "
                    "the automaton terminates the output by forcing "
                    "EOS at an accepting state")
        if not self.request_id:
            self.request_id = "req-" + uuid.uuid4().hex[:16]
        if self.seed is None:
            # id-derived: deterministic whenever ids are (captured/
            # synthetic replays), effectively random under the uuid
            # auto-id — the branch-key family every sampling decision
            # of this request folds from
            self.seed = zlib.crc32(self.request_id.encode()) \
                & 0x7fffffff
        # the ORIGINAL prompt length: preemption folds generated tokens
        # into ``prompt`` for the re-prefill, so the true context length
        # is base_len + len(tokens) — counting from the grown prompt
        # would double-count and truncate the request at the horizon
        self.base_len = int(self.prompt.size)

    @property
    def n_branches(self) -> int:
        """Branches decoded for this request: ``best_of`` when set,
        else ``n`` (1 = the ordinary single-stream request)."""
        return self.best_of if self.best_of is not None else self.n


class _Session:
    """One pumping session's mutable state (a ``run()`` trace, or the
    whole lifetime of the HTTP front door). Plain attribute bag —
    every field the old run() closure held, promoted so ``step()``
    can be driven externally."""

    # bounded percentile reservoirs: the front door keeps ONE session
    # open for the server's whole lifetime, so per-request lists must
    # not grow with traffic (the registry's _MAX_SAMPLES discipline);
    # oldest samples drop first, run()-sized traces are unaffected
    MAX_SAMPLES = 8192

    def __init__(self, batcher: "ContinuousBatcher"):
        eng = batcher.engine
        self.queue: list[Request] = []
        self.live: dict[int, Request] = {}       # decoding
        self.filling: dict[int, Request] = {}    # seated, prefill streaming
        self.admit_order: list[int] = []         # oldest-first seated slots
        self.t0 = batcher.clock()
        # scheduler iterations so far: the ``step`` number that the
        # engine-track step event and the per-request ``tokens``
        # events of one iteration share (observability/tracing.py)
        self.n_steps = 0
        self.decoded = 0
        self.decode_time = 0.0
        self.n_admissions = 0
        self.n_preemptions = 0
        self.n_shed = 0
        self.n_cancelled = 0
        # RUNNING aggregates, not retained Request objects: a
        # long-lived front-door session must not hold every prompt
        # array it ever served
        self.n_seen = 0
        self.new_tokens = 0
        self.lat: list[float] = []
        self.ttft: list[float] = []
        # per-class SLO accounting (SLO policies only): name ->
        # {"ttft": [...], "tpot": [...], hit/evaluated counts, n, shed}
        self.per_class: dict[str, dict] = {}
        self.hits0 = eng.prefix_hit_pages
        self.lookups0 = eng.prefix_lookup_pages
        self.chunks0 = eng.prefill_chunks
        self.spills0 = eng.spills
        self.promotions0 = eng.promotions
        self.host_hits0 = eng.host_hit_pages
        self.spec_steps0 = eng.spec_steps
        self.spec_prop0 = eng.spec_proposed
        self.spec_acc0 = eng.spec_accepted
        self.forks0 = eng.forks
        self.fork_pages0 = eng.fork_pages
        self.cow0 = eng.cow_copies
        self.structured0 = eng.structured_requests
        self.smasked0 = eng.structured_masked_sum
        self.srows0 = eng.structured_masked_rows
        # per-tenant (adapter) attribution: terminal-event token/
        # request tallies keyed by adapter name ("" = base), plus the
        # registry's load/evict/hit counter baselines — all zero/empty
        # on a lora-less engine
        self.per_adapter: dict[str, dict] = {}
        ad = eng.adapters
        self.aloads0 = ad.loads if ad is not None else 0
        self.aevict0 = ad.evictions if ad is not None else 0
        self.ahits0 = ad.hits if ad is not None else 0
        # the look-ahead loop (``PagedEngine.looks_ahead``): the step
        # launched by the last iteration and not landed yet (the
        # engine's handle), per slot the tokens it may still be
        # LAUNCHED for before a ``length`` stop, and the slots that
        # stopped (EOS) with a token still in flight: retired when
        # that step lands
        self.flight = None
        self.left = np.zeros(eng.max_slots, np.int64)
        self.stopped: list[int] = []
        self.closed = False

    def sample(self, series: list[float], value: float) -> None:
        series.append(value)
        if len(series) > self.MAX_SAMPLES:
            del series[:len(series) - self.MAX_SAMPLES]

    @property
    def has_seated(self) -> bool:
        return bool(self.live or self.filling)


class ContinuousBatcher:
    """Policy-driven admission queue driving a :class:`PagedEngine`.

    ``run(requests)`` processes a whole trace synchronously and
    returns a metrics dict; finished requests carry their generated
    ``tokens`` and timing fields. For an external driver (the asyncio
    HTTP front door), ``start_session()`` / ``step()`` /
    ``finish_session()`` expose the same loop one iteration at a
    time, with ``submit``/``cancel`` as thread-safe inboxes the next
    ``step()`` drains. ``policy`` is the scheduler
    (:class:`FCFSPolicy` default — behavior and metric values
    identical to the pre-frontend batcher). ``clock`` is injectable
    for deterministic tests — it MUST advance on its own (the batcher
    real-sleeps up to 50 ms while idle before an arrival; a frozen
    clock with a future arrival would wait forever)."""

    def __init__(self, engine: PagedEngine, clock=time.perf_counter,
                 on_recompile: str = "warn",
                 policy: SchedulerPolicy | None = None,
                 tracer: RequestTracer | None = None,
                 flight: FlightRecorder | None = None):
        # the zero-recompile contract as a RUNTIME guard, not just a
        # test assert: every run() watches the decode jit cache
        # (observability/recompile.py); policy ignore | warn | raise —
        # validated HERE so a YAML typo fails at build time, not deep
        # inside the first run() after requests were accepted
        if on_recompile not in POLICIES:
            raise ValueError(
                f"on_recompile={on_recompile!r}: expected one of "
                f"{POLICIES}")
        if policy is not None and not isinstance(policy, SchedulerPolicy):
            raise TypeError(
                f"policy must be a SchedulerPolicy (frontend."
                f"scheduler), got {type(policy).__name__}")
        if tracer is not None and not isinstance(tracer, RequestTracer):
            raise TypeError(
                f"tracer must be an observability.tracing."
                f"RequestTracer, got {type(tracer).__name__}")
        if flight is not None and not isinstance(flight, FlightRecorder):
            raise TypeError(
                f"flight must be an observability.flight."
                f"FlightRecorder, got {type(flight).__name__}")
        self.on_recompile = on_recompile
        self.policy = policy if policy is not None else FCFSPolicy()
        # request-scoped tracing: disabled-by-default sink — emits are
        # one branch when off, and the tracer stamps its OWN monotonic
        # clock, never this batcher's injectable one, so tracing
        # on/off leaves every metric value bit-for-bit identical.
        # The flight recorder is ALWAYS on (fixed-size ring, provably
        # bounded bytes): one row write per step() from values this
        # loop already holds.
        self.tracer = tracer if tracer is not None else RequestTracer()
        self.flight = flight if flight is not None else FlightRecorder()
        self.engine = engine
        self.clock = clock
        # usable pool capacity in tokens (page 0 is the reserved null)
        self._capacity = (engine.n_pages - 1) * engine.page_size
        # EWMA service-time estimates (host perf_counter deltas) the
        # SLO policy's slack math consumes; zero until measured, so a
        # cold batcher never sheds on a guess
        self.est_chunk_s = 0.0
        self.est_step_s = 0.0
        self._s: _Session | None = None
        self._sentinel: RecompileSentinel | None = None
        self._inst: dict | None = None
        # thread-safe inboxes (deque appends are atomic): the event
        # loop submits/cancels while step() runs on the pump thread
        self._inbox_submit: deque[Request] = deque()
        self._inbox_cancel: deque[Request] = deque()

    # ---- capacity & estimates ------------------------------------
    def _check_fits(self, req: Request) -> None:
        worst = req.base_len + req.max_new_tokens
        if worst > self.engine.cfg.seq_len:
            raise ValueError(
                f"prompt ({req.base_len}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds cfg.seq_len "
                f"({self.engine.cfg.seq_len})")
        nb = req.n_branches
        if nb > 1:
            if not self.engine.parallel:
                raise ValueError(
                    f"n/best_of > 1 ({req.n}/{req.best_of}) needs a "
                    "parallel-sampling engine: set "
                    "serving.parallel_sampling: true")
            if nb > self.engine.max_slots:
                raise ValueError(
                    f"best_of ({nb}) exceeds serving.max_slots "
                    f"({self.engine.max_slots}): every branch "
                    "decodes in its own slot")
            # worst-case page footprint of the whole family ALONE:
            # the full prompt pages once (shared) + every branch's
            # private tail/output pages
            shared = req.base_len // self.engine.page_size
            per_branch = self.engine.tables.pages_for(worst) - shared
            if shared + nb * per_branch > self.engine.n_pages - 1:
                raise ValueError(
                    f"request needs {shared} shared prompt pages + "
                    f"{nb} x {per_branch} per-branch pages but the "
                    f"pool holds {self.engine.n_pages - 1}; grow "
                    "serving.n_pages or lower best_of")
        reserve = worst
        if self.engine.speculative:
            # grow_slots demands 1 + draft_len write positions ahead
            # of the cursor on EVERY step, so a speculative request's
            # page footprint peaks draft_len positions past its final
            # token (clamped to the horizon) — admit against that
            # peak, or a request sized exactly to the pool starves on
            # its last page and preempt-thrashes itself (one full
            # re-prefill per emitted token)
            reserve = min(worst + self.engine.draft_len,
                          self.engine.cfg.seq_len)
        if self.engine.tables.pages_for(reserve) > \
                (self.engine.n_pages - 1):
            raise ValueError(
                f"request needs {reserve} tokens of pages "
                + (f"({worst} prompt+output + the speculative "
                   "write-ahead) " if reserve > worst else "")
                + f"but the pool holds {self._capacity}; grow "
                f"serving.n_pages")
        if req.response_format is not None:
            # syntactic/schema validation FIRST: an unknown type or a
            # malformed schema is a 400 naming the problem regardless
            # of engine configuration
            validate_response_format(req.response_format)
            if req.response_format.get("type") != "text":
                if not self.engine.structured:
                    raise ValueError(
                        "response_format type "
                        f"{req.response_format['type']!r} needs a "
                        "structured-generation engine: set "
                        "serving.structured.enabled: true")
                # token-level compile NOW (fingerprint-cached on the
                # engine): vocabulary-level unsatisfiability and EOS/
                # alphabet collisions fail at submit, before any
                # pages move — and the seat path hits a warm cache
                dfa = self.engine.structured_compile(
                    req.response_format)
                if not 0 <= req.eos_id < self.engine.cfg.vocab:
                    raise ValueError(
                        f"eos_id {req.eos_id} outside the vocabulary "
                        f"(size {self.engine.cfg.vocab})")
                if bool(dfa.mask[:, req.eos_id].any()):
                    raise ValueError(
                        f"eos_id {req.eos_id} renders a character "
                        "the schema can emit — the EOS bit would "
                        "shadow a legal content token; pick an EOS "
                        "id outside the schema alphabet")
        if req.adapter:
            # the multi-LoRA 400 surface: an unknown adapter name (or
            # any adapter at all on a lora-less engine) fails at
            # submit, before any pages move — the seat-time acquire
            # can then only ever fail on PIN pressure (backpressure,
            # not an error)
            if not self.engine.lora:
                raise ValueError(
                    f"request names adapter {req.adapter!r} but the "
                    "engine has no LoRA lanes: set serving.adapters."
                    "rank > 0")
            if not self.engine.adapters.known(req.adapter):
                raise ValueError(
                    f"unknown adapter {req.adapter!r} — registered: "
                    f"{self.engine.adapters.names}")

    def est_ttft_s(self, req: Request) -> float:
        """Estimated seconds from now to ``req``'s first token were it
        seated next: its own prefill chunks plus the chunks already
        queued ahead of it, at the measured EWMA chunk time, plus one
        decode step. Prefix-cache hits only ever shorten it (the
        estimate skips the index walk — too hot for per-step use)."""
        # len(prompt), not base_len: preemption folds generated tokens
        # into the prompt, and the re-prefill pays for all of them
        chunks = -(-len(req.prompt) // self.engine.chunk_tokens)
        ahead = self.engine.pending_chunk_count
        return (chunks + ahead) * self.est_chunk_s + self.est_step_s

    def readmission_cost(self, req: Request) -> int:
        """Tokens a preemption victim would re-prefill on re-seat:
        its full folded context net of the prompt pages the prefix
        cache would map straight back. A mid-decode slot whose prompt
        pages are all registered is nearly free to evict; a cold
        long-prompt slot is the expensive victim."""
        folded = len(req.prompt) - req.base_len
        ctx = np.concatenate(
            [req.prompt, np.asarray(req.tokens[folded:], np.int32)])
        matched = self.engine.tables.match_pages(ctx)
        return len(ctx) - len(matched) * self.engine.page_size

    def _free_slot_count(self) -> int:
        # the tables' own idle definition — never a re-implementation
        # (kv_pages.n_free_slots), so the admission gate and the
        # seating code cannot drift apart
        return self.engine.tables.n_free_slots()

    def _reserved_slots(self) -> int:
        """Slots spoken for by mid-prefill n-way requests: their
        ``best_of - 1`` siblings fork the moment prefill completes,
        so plain admissions must not seat into them (a fork with no
        free slot would have to preempt what was just admitted)."""
        s = self._s
        if s is None:
            return 0
        return sum(r.n_branches - 1 for r in s.filling.values()
                   if r.branches is None and r.n_branches > 1)

    @property
    def occupancy(self) -> float:
        """Fraction of usable pool pages not immediately allocatable
        (free AND evictable-cached both count as available)."""
        avail = self.engine.tables.n_available_pages
        return 1.0 - avail / max(self.engine.n_pages - 1, 1)

    @property
    def queue_depth(self) -> int:
        s = self._s
        return (len(self._inbox_submit)
                + (len(s.queue) if s is not None else 0))

    @property
    def has_work(self) -> bool:
        s = self._s
        return s is not None and bool(
            s.queue or s.live or s.filling or s.flight
            or self._inbox_submit or self._inbox_cancel)

    @property
    def session_active(self) -> bool:
        """Whether a pumpable session is open (the fleet router and
        the replay driver's cleanup path share this — neither should
        reach into ``_s``)."""
        return self._s is not None

    @property
    def inflight(self) -> int:
        """Seated requests (prefilling + decoding) — ONE definition
        of in-flight for the readiness payload and the fleet router's
        load scorer alike."""
        s = self._s
        return 0 if s is None else len(s.live) + len(s.filling)

    def readiness(self) -> dict:
        """The readiness payload: queue depth, free/cached pages,
        in-flight count, occupancy, and the EWMA step estimate — ONE
        dict serving both the front door's ``GET /healthz?full=1``
        probe and the fleet router's load scorer (the contract that
        keeps an external health check and the routing decision
        reading the same numbers). Host counters only.

        ``step_seq`` / ``stamped_s`` are the STALENESS stamp the
        fleet health scorer reads: the flight recorder's step count
        (advances once per step, always on) paired with the moment
        of stamping on the batcher's injectable session clock. A
        payload whose ``step_seq`` froze while ``stamped_s`` kept
        advancing is a replica that stopped making progress —
        detectable from the payload alone, which is what an
        out-of-process replica ships over the wire."""
        eng = self.engine
        return {
            "status": "ok",
            "queue_depth": self.queue_depth,
            "pages_free": int(eng.tables.n_free_pages),
            "pages_cached": int(eng.tables.n_cached_pages),
            "pages_host": int(eng.tables.n_host_pages),
            "inflight": self.inflight,
            "occupancy": round(self.occupancy, 4),
            "est_step_s": round(self.est_step_s, 6),
            "step_seq": int(self.flight.n_recorded),
            "stamped_s": (round(self.clock() - self._s.t0, 6)
                          if self._s is not None else 0.0),
        }

    def drain_unfinished(self, retire_seated: bool = True) -> list:
        """Remove and return EVERY unfinished request of the active
        session — the fleet router's cross-replica readmission path.
        Seated requests leave with their generated tokens folded into
        their prompts (exactly the preemption fold), so a drained
        request re-prefills its full context on whatever replica
        re-admits it and keeps its delivered tokens: nothing lost,
        nothing duplicated. ``retire_seated=False`` skips the engine
        retire calls — a DEAD replica's engine is not to be trusted,
        and in-process its pages die with the object."""
        if self._s is None:
            return []
        s = self._s
        if retire_seated:
            # a step in flight lands first, and its tokens are dropped
            # (never delivered, never folded): whoever re-admits the
            # request decodes them again
            self._land_flight(s, None, None, "drain")
        s.flight = None
        s.stopped.clear()
        out: list[Request] = []
        while self._inbox_submit:
            out.append(self._inbox_submit.popleft())
        out.extend(s.queue)
        s.queue.clear()
        seated = sorted([*s.filling.items(), *s.live.items()])
        s.filling.clear()
        s.live.clear()
        s.admit_order.clear()
        for slot, req in seated:
            if retire_seated:
                self.engine.retire(slot)
            # the registry is HOST bookkeeping on this batcher's
            # engine: drop the pin even when the (dead) engine isn't
            # retired, so refcounts stay balanced either way
            self._release_adapter(req)
            folded = len(req.prompt) - req.base_len
            if self.tracer.enabled:
                self.tracer.emit(req.request_id, "drained", slot=slot,
                                 fold_tokens=len(req.tokens) - folded)
            req.prompt = np.concatenate(
                [req.prompt,
                 np.asarray(req.tokens[folded:], np.int32)])
            out.append(req)
        return out

    def drain_queued(self, n: int) -> list:
        """Remove and return up to ``n`` QUEUED (never seated this
        visit) requests from the BACK of the queue — the cheap end of
        the readmission-cost scale (no engine state, no fold), which
        is why the fleet's hot-spot rebalance migrates exactly these.
        Arrival order among the returned requests is preserved."""
        if self._s is None or n < 1:
            return []
        s = self._s
        while self._inbox_submit:
            s.queue.append(self._inbox_submit.popleft())
        out: list[Request] = []
        while s.queue and len(out) < n:
            out.append(s.queue.pop())
        out.reverse()
        return out

    # ---- external driver surface ---------------------------------
    def submit(self, req: Request, arrival: float | None = None) -> None:
        """Thread-safe enqueue for an externally-driven session: the
        request joins the scheduling queue at the next :meth:`step`.
        Raises (in the caller) when the request can never fit the pool
        or its priority class is unknown to the policy — the front
        door maps that to HTTP 400 before any pages move."""
        if self._s is None:
            raise RuntimeError(
                "no active session: start_session() first (run() "
                "manages its own)")
        self._check_fits(req)
        self.policy.validate(req)
        req.arrival = self.session_now() if arrival is None else arrival
        self._inbox_submit.append(req)

    def cancel(self, req: Request) -> None:
        """Thread-safe cancellation: at the next :meth:`step` the
        request leaves the queue, or — if seated — its slot retires
        through the engine's abort paths (mid-prefill pending-slot
        abort, mid-decode/mid-spec retire), reclaiming every page
        without touching the compiled steps. Unknown/finished
        requests are ignored (cancel races completion benignly)."""
        self._inbox_cancel.append(req)

    def session_now(self) -> float:
        """Seconds since the active session started (the ``arrival``
        clock)."""
        if self._s is None:
            raise RuntimeError("no active session")
        return self.clock() - self._s.t0

    def start_session(self) -> None:
        """Open a pumpable session (the front door's whole lifetime):
        sets up instruments and the recompile sentinel, and cancels
        stale mid-prefill slots a crashed driver may have left."""
        s = self._begin()
        self._sentinel.__enter__()
        self._s = s

    def finish_session(self) -> dict:
        """Close the pumpable session: closes the sentinel watch
        (firing its policy), lands gauges/counters, and returns the
        same metrics dict :meth:`run` does."""
        if self._s is None:
            raise RuntimeError("no active session")
        s = self._s
        try:
            self._land_flight(s, None, None, "drain")
            self._sentinel.__exit__(None, None, None)
        finally:
            self._land(s)
        return self._metrics(s)

    # ---- session internals ---------------------------------------
    def _begin(self) -> _Session:
        if self._s is not None:
            raise RuntimeError(
                "a session is already active on this batcher")
        # stale inbox entries belong to a DEAD session (a crashed pump
        # left them undrained): replaying them into a fresh trace
        # would seat unrelated dead-client requests and pollute its
        # metrics
        self._inbox_submit.clear()
        self._inbox_cancel.clear()
        # a previous run that aborted mid-loop (engine error,
        # KeyboardInterrupt) can leave the engine holding
        # half-prefilled slots — cross-run state chunked prefill
        # introduced (the old synchronous admit could not). Their
        # requests belong to the dead trace: cancel them up front so
        # this run's prefill_step never completes a slot it never
        # seated.
        for slot in self.engine.pending_slots:
            self.engine.retire(slot)
        reg = get_registry()
        inst = {
            "lat": reg.histogram("serving_latency_seconds",
                                 "request arrival -> completion"),
            "ttft": reg.histogram("serving_ttft_seconds",
                                  "request arrival -> first token"),
            # the two halves of TTFT, observed beside it at retirement
            # so the three series cover the same requests
            "queue_wait": reg.histogram(
                "serving_queue_wait_seconds",
                "request arrival -> first seat"),
            "prefill": reg.histogram(
                "serving_prefill_seconds",
                "first seat -> first token"),
            "slots": reg.gauge("serving_slots_live",
                               "occupied decode slots"),
            "pages": reg.gauge("serving_pages_free",
                               "free KV pages in the pool"),
            "admissions": reg.counter(
                "serving_admissions_total",
                "requests seated (re-admissions count)"),
            "preemptions": reg.counter(
                "serving_preemptions_total",
                "scheduler-victim preemptions"),
            "retired": reg.counter(
                "serving_retired_total",
                "sequences retired (EOS/max/horizon)"),
            "tokens": reg.counter("serving_decode_tokens_total",
                                  "tokens produced by decode steps"),
            "hit_pages": reg.counter(
                "serving_prefix_hit_pages_total",
                "prompt pages served from the prefix cache"),
            # both land per chunk (not at the session's end): their
            # ratio over any window is the share of chunks that rode
            # a decode step
            "chunks": reg.counter("serving_prefill_chunks_total",
                                  "prefill chunks issued"),
            "mixed": reg.counter(
                "serving_mixed_steps_total",
                "prefill chunks issued in ONE program with the decode "
                "step (PagedEngine.mixed_step)"),
            "hit_rate": reg.gauge(
                "serving_prefix_hit_rate",
                "prefix-cache page hit rate over this run"),
            "spec_prop": reg.counter(
                "serving_spec_proposed_total",
                "draft tokens proposed to the speculative verify step"),
            "spec_acc": reg.counter(
                "serving_spec_accepted_total",
                "draft tokens the verify step accepted"),
            "spec_rate": reg.gauge(
                "serving_spec_accept_rate",
                "accepted/proposed draft tokens over this run"),
            "fork_pages": reg.counter(
                "serving_fork_pages_total",
                "pages shared into sibling branches at fork "
                "(copy-on-write parallel sampling)"),
            "cow_copies": reg.counter(
                "serving_cow_copies_total",
                "private tail pages copied at fork (the only bytes "
                "n-way sampling duplicates)"),
            # the look-ahead loop: how often the next step was
            # launched behind one in flight, how often (and why) a
            # step was waited for with nothing launched behind it,
            # and what a late EOS stop cost
            "lookahead": reg.counter(
                "serving_lookahead_steps_total",
                "decode steps launched while the step before them "
                "was still in flight"),
            "sync_lands": reg.counter(
                "serving_sync_lands_total",
                "decode steps landed with no step launched behind "
                "them, by reason: mode (the engine's mode needs every "
                "token on the host), preempt, drain (cancel, drain, "
                "session end), idle (nothing left to launch)"),
            "wasted": reg.counter(
                "serving_wasted_lanes_total",
                "tokens dropped at a land because their slot had "
                "stopped (an EOS stop applies one step late)"),
        }
        if self.engine.structured:
            # structured generation only (absent with
            # structured=False so the unconstrained registry view is
            # untouched): constrained admissions and how much of the
            # vocabulary the automaton masked — host integer adds
            # per landing, never a device read
            inst["structured"] = reg.counter(
                "serving_structured_requests_total",
                "constrained (response_format) requests admitted")
            inst["structured_frac"] = reg.gauge(
                "serving_structured_masked_frac",
                "mean masked-vocabulary fraction over committed "
                "constrained cursor rows this run")
        if self.engine.host_spill:
            # the host spill tier only (absent with host_spill=False
            # so the spill-less registry view is untouched): tier
            # traffic counters, host integer adds per landing
            inst["spills"] = reg.counter(
                "serving_page_spills_total",
                "KV pages demoted HBM -> host at eviction")
            inst["promotions"] = reg.counter(
                "serving_page_promotions_total",
                "KV pages promoted host -> HBM at seat time")
            inst["host_hits"] = reg.counter(
                "serving_host_hit_pages_total",
                "prompt pages matched in the host spill tier")
        if self.engine.lora:
            # multi-LoRA serving only (absent with lora off so the
            # single-tenant registry view is untouched): billing-grade
            # per-tenant attribution (labels adapter; "base" is
            # un-adaptered traffic) plus the registry's lane churn —
            # host integer adds at terminal events, never a device
            # read
            inst["adapter_tokens"] = reg.counter(
                "serving_adapter_tokens_total",
                "tokens delivered per adapter name (per-tenant "
                "billing attribution)")
            inst["adapter_reqs"] = reg.counter(
                "serving_adapter_requests_total",
                "requests reaching a terminal state per adapter name")
            inst["adapter_loads"] = reg.counter(
                "serving_adapter_loads_total",
                "adapter lane hot-loads (cold load or refresh)")
            inst["adapter_evictions"] = reg.counter(
                "serving_adapter_evictions_total",
                "cached adapter lanes displaced (LRU)")
        if self.engine.tp > 1:
            # tensor-parallel serving only (absent at tp=1 so the
            # single-chip registry view is untouched): the modeled
            # per-chip wire bytes of each decode/verify step's
            # decode-output psum (serving/tp.py step_traffic, a
            # closed-form model). One host-side float add per step,
            # never a device read.
            inst["tp_bytes"] = reg.counter(
                "serving_tp_bytes_total",
                "modeled per-chip decode-output psum wire bytes "
                "(tensor-parallel serving)")
            self._tp_decode_bytes = \
                self.engine.tp_step_traffic(1)["wire_bytes"]
            self._tp_verify_bytes = self.engine.tp_step_traffic(
                1 + self.engine.draft_len)["wire_bytes"]
        if self.policy.slo:
            # per-class SLO families (absent entirely under FCFS so
            # the cold path's registry view is untouched); every
            # observation is a host perf_counter delta — deferred
            # registry reads, never a device sync
            inst.update({
                "slo_ttft": reg.histogram(
                    "serving_slo_ttft_seconds",
                    "per-class arrival -> first token"),
                "slo_tpot": reg.histogram(
                    "serving_slo_tpot_seconds",
                    "per-class mean inter-token time"),
                "slo_shed": reg.counter(
                    "serving_slo_shed_total",
                    "requests shed by the SLO policy (per class)"),
                "slo_cancel": reg.counter(
                    "serving_slo_cancelled_total",
                    "requests cancelled by the client (per class)"),
                "slo_hit": reg.counter(
                    "serving_slo_deadline_hit_total",
                    "deadline hits (per class, kind=ttft|tpot)"),
                "slo_miss": reg.counter(
                    "serving_slo_deadline_miss_total",
                    "deadline misses (per class, kind=ttft|tpot)"),
                "slo_ttft_rate": reg.gauge(
                    "serving_slo_ttft_hit_rate",
                    "TTFT deadline hit rate over this run (per class)"),
                "slo_tpot_rate": reg.gauge(
                    "serving_slo_tpot_hit_rate",
                    "TPOT deadline hit rate over this run (per class)"),
                # LIVE client-facing quantiles from the session
                # reservoirs (labels cls + q=p50|p99), refreshed on
                # every completion so the Prometheus scrape can plot
                # the SLO dashboard mid-run instead of waiting for the
                # final session summary
                "slo_ttft_q": reg.gauge(
                    "serving_slo_ttft_quantile",
                    "per-class TTFT quantile over the session "
                    "reservoir (labels cls, q)"),
                "slo_tpot_q": reg.gauge(
                    "serving_slo_tpot_quantile",
                    "per-class TPOT quantile over the session "
                    "reservoir (labels cls, q)"),
            })
        self._inst = inst
        s = _Session(self)
        if self.policy.slo:
            for name in self.policy.classes:
                s.per_class[name] = {
                    "n": 0, "completed": 0, "shed": 0,
                    "ttft": [], "tpot": [],
                    "ttft_hit": 0, "ttft_n": 0,
                    "tpot_hit": 0, "tpot_n": 0}
        # expected compiles in the watched region: the decode (or, in
        # speculative mode, verify) step's very first compile is
        # legitimate; anything after is a broken geometry contract
        # (engine.py's zero-recompile design). One watch covers both
        # executables — a spec engine must not quietly recompile its
        # never-used decode step either.
        step_compiles = lambda: (self.engine.decode_compiles
                                 + self.engine.verify_compiles)
        self._sentinel = RecompileSentinel(
            step_compiles,
            on_recompile=self.on_recompile,
            expected=0 if step_compiles() else 1,
            name="serving_decode", registry=reg)
        return s

    def _class_stats(self, req: Request) -> dict | None:
        if not self.policy.slo:
            return None
        name = self.policy.cls_of(req).name
        return self._s.per_class[name]

    def _release_adapter(self, req: Request) -> None:
        """Drop the request's registry pin — exactly one per SEATED
        slot (fork branches each pin at fork time), so every path
        that retires a seated slot funnels through here exactly once;
        queued-only exits (shed, queued cancel) never acquired."""
        if req.adapter:
            self.engine.adapters.release(req.adapter)

    def _account_adapter(self, req: Request) -> None:
        """Per-tenant attribution at a request's TERMINAL event
        (finish/cancel/shed): tokens delivered and requests closed
        under each adapter name ('' = base). Feeds the
        ``serving_adapter_*`` families and ``_metrics()['adapters']``
        — absent entirely on a lora-less engine so the single-tenant
        view is untouched."""
        if not self.engine.lora:
            return
        ad = self._s.per_adapter.setdefault(
            req.adapter, {"n_requests": 0, "new_tokens": 0})
        ad["n_requests"] += 1
        ad["new_tokens"] += len(req.tokens)
        label = req.adapter or "base"
        self._inst["adapter_reqs"].inc(adapter=label)
        if req.tokens:
            self._inst["adapter_tokens"].inc(len(req.tokens),
                                             adapter=label)

    def _finish_request(self, slot: int) -> None:
        s, inst = self._s, self._inst
        req = s.live.pop(slot)
        s.admit_order.remove(slot)
        req.finished_at = self.clock() - s.t0
        inst["retired"].inc()
        s.new_tokens += len(req.tokens)
        self._account_adapter(req)
        s.sample(s.lat, req.finished_at - req.arrival)
        inst["lat"].observe(req.finished_at - req.arrival)
        if req.first_token_at is not None:
            s.sample(s.ttft, req.first_token_at - req.arrival)
            inst["ttft"].observe(req.first_token_at - req.arrival)
            inst["queue_wait"].observe(req.admitted_at - req.arrival)
            inst["prefill"].observe(
                req.first_token_at - req.admitted_at)
        flight = s.flight
        if flight is not None and flight.carries(slot):
            # stopped by a token the host could not know when the
            # step in flight was launched (EOS): the slot rides it to
            # its end — the extra token is dropped and the pages go
            # back when that step lands
            self.engine.hold(slot)
            s.stopped.append(slot)
        else:
            self.engine.retire(slot)
        self._release_adapter(req)
        if self.tracer.enabled:
            self.tracer.emit(req.request_id, "retired",
                             reason=req.finish_reason or "",
                             n_tokens=len(req.tokens))
        cs = self._class_stats(req)
        if cs is None:
            return
        cls = self.policy.cls_of(req)
        cs["completed"] += 1
        ttft = req.first_token_at - req.arrival
        s.sample(cs["ttft"], ttft)
        inst["slo_ttft"].observe(ttft, cls=cls.name)
        if len(req.tokens) > 1:
            tpot = (req.finished_at - req.first_token_at) \
                / (len(req.tokens) - 1)
            s.sample(cs["tpot"], tpot)
            inst["slo_tpot"].observe(tpot, cls=cls.name)
        else:
            tpot = None
        # refresh the live per-class quantile gauges from the bounded
        # reservoirs — one np.percentile over <= MAX_SAMPLES host
        # floats per COMPLETION (never per step), so the exporters
        # can plot p50/p99 TTFT/TPOT mid-session
        q50, q99 = np.percentile(
            np.asarray(cs["ttft"], np.float64), [50, 99]).tolist()
        inst["slo_ttft_q"].set(round(q50, 6), cls=cls.name, q="p50")
        inst["slo_ttft_q"].set(round(q99, 6), cls=cls.name, q="p99")
        if cs["tpot"]:
            q50, q99 = np.percentile(
                np.asarray(cs["tpot"], np.float64), [50, 99]).tolist()
            inst["slo_tpot_q"].set(round(q50, 6), cls=cls.name,
                                   q="p50")
            inst["slo_tpot_q"].set(round(q99, 6), cls=cls.name,
                                   q="p99")
        deadline = self.policy.ttft_deadline_s(req)
        if deadline is not None:
            hit = ttft <= deadline
            cs["ttft_n"] += 1
            cs["ttft_hit"] += int(hit)
            inst["slo_hit" if hit else "slo_miss"].inc(
                cls=cls.name, kind="ttft")
        tpot_target = self.policy.tpot_deadline_s(req)
        if tpot_target is not None and tpot is not None:
            hit = tpot <= tpot_target
            cs["tpot_n"] += 1
            cs["tpot_hit"] += int(hit)
            inst["slo_hit" if hit else "slo_miss"].inc(
                cls=cls.name, kind="tpot")

    def _maybe_stop(self, slot: int, token: int,
                    finish: bool = True) -> bool:
        """Append ``token`` and evaluate the stop conditions. Returns
        True when the request is done; ``finish=False`` defers the
        actual :meth:`_finish_request` to the caller — the spec arm
        emits its whole-burst trace event first so ``retired`` stays
        the LAST event on a request's timeline."""
        s = self._s
        req = s.live[slot]
        req.tokens.append(int(token))
        if req.first_token_at is None:
            req.first_token_at = self.clock() - s.t0
            if self.tracer.enabled:
                self.tracer.emit(
                    req.request_id, "first_token",
                    ttft_s=round(req.first_token_at - req.arrival, 6))
        hit_eos = req.eos_id is not None and token == req.eos_id
        full = (req.base_len + len(req.tokens)
                >= self.engine.cfg.seq_len)
        if hit_eos or len(req.tokens) >= req.max_new_tokens or full:
            req.finish_reason = "stop" if hit_eos else "length"
            if finish:
                self._finish_request(slot)
            return True
        return False

    def _cancel_request(self, req: Request, events: list) -> None:
        s = self._s
        req.cancelled = True
        req.finished_at = self.clock() - s.t0
        req.finish_reason = "cancelled"
        if self.tracer.enabled:
            self.tracer.emit(req.request_id, "cancelled",
                             n_tokens=len(req.tokens))
        s.n_cancelled += 1
        s.new_tokens += len(req.tokens)  # delivered before the cancel
        self._account_adapter(req)       # delivered tokens are billed
        events.append((req, []))
        cs = self._class_stats(req)
        if cs is not None:
            self._inst["slo_cancel"].inc(
                cls=self.policy.cls_of(req).name)

    def _cancel_ids(self) -> set[int]:
        """The requests the cancel inbox names, by ``id`` (a snapshot:
        other threads append while the loop thread reads)."""
        return {id(r) for root in tuple(self._inbox_cancel)
                for r in (root.branches or [root])}

    def _cancels_seated(self, s: _Session) -> bool:
        """Whether a cancel in the inbox names a seated request."""
        if not self._inbox_cancel:
            return False
        named = self._cancel_ids()
        return any(id(r) in named
                   for r in (*s.filling.values(), *s.live.values()))

    def _drain_cancels(self, events: list) -> None:
        s = self._s
        while self._inbox_cancel:
            root = self._inbox_cancel.popleft()
            # cancelling an n-way request cancels its WHOLE family:
            # the client asked for one completion set, the branches
            # have no independent existence on the wire
            for req in (root.branches or [root]):
                if req.finished_at is not None:
                    continue                  # raced completion: done
                if any(req is q for q in s.queue):
                    s.queue.remove(req)
                    self._cancel_request(req, events)
                    continue
                for table in (s.filling, s.live):
                    slot = next((sl for sl, r in table.items()
                                 if r is req), None)
                    if slot is not None:
                        # the engine abort paths: retire() cancels an
                        # in-flight chunked prefill (PR 4 pending-slot
                        # abort) and reclaims the slot's pages either
                        # way
                        table.pop(slot)
                        s.admit_order.remove(slot)
                        self.engine.retire(slot)
                        self._release_adapter(req)
                        self._cancel_request(req, events)
                        break

    def _shed_request(self, req: Request, events: list) -> None:
        s = self._s
        req.shed = True
        req.finished_at = self.clock() - s.t0
        req.finish_reason = "shed"
        if self.tracer.enabled:
            self.tracer.emit(req.request_id, "shed",
                             waited_s=round(req.finished_at
                                            - req.arrival, 6))
        s.n_shed += 1
        self._account_adapter(req)       # terminal: 0 tokens billed
        events.append((req, []))
        cs = self._class_stats(req)
        if cs is not None:
            cs["shed"] += 1
            self._inst["slo_shed"].inc(
                cls=self.policy.cls_of(req).name)

    def _preempt_one(self, s: _Session,
                     exclude: frozenset | set = frozenset()) -> bool:
        """Evict ONE policy-chosen seated victim back to the front of
        the queue with its generated tokens folded into its prompt
        (mid-prefill victims fold nothing). ``exclude`` shields slots
        the caller is mid-operation on (a forking parent must not
        evict itself). Returns False when no eligible victim exists.
        """
        order = [sl for sl in s.admit_order if sl not in exclude]
        if not order:
            return False
        seated = {sl: r for sl, r in {**s.filling, **s.live}.items()
                  if sl not in exclude}
        victim = self.policy.select_victim(order, seated, self)
        req = (s.live.pop(victim) if victim in s.live
               else s.filling.pop(victim))
        s.admit_order.remove(victim)
        self.engine.retire(victim)
        # the victim's adapter pin drops with its seat (NOT a
        # terminal event — no billing): its lane may be evicted while
        # it queues, and the re-seat re-acquires whatever lane the
        # registry then lands it on
        self._release_adapter(req)
        # fold generated tokens into the prompt so it resumes
        # from its full context on re-admission — only the
        # NOT-yet-folded suffix: a second preemption would
        # otherwise re-append tokens already in the prompt,
        # duplicating context (prompt always holds base_len +
        # folded tokens, so the folded count is its excess; a
        # mid-prefill victim has no tokens and folds nothing)
        folded = len(req.prompt) - req.base_len
        if self.tracer.enabled:
            self.tracer.emit(req.request_id, "preempted",
                             slot=victim,
                             fold_tokens=len(req.tokens) - folded)
        req.prompt = np.concatenate(
            [req.prompt,
             np.asarray(req.tokens[folded:], np.int32)])
        s.queue.insert(0, req)
        s.n_preemptions += 1
        self._inst["preemptions"].inc()
        return True

    def _fork_request(self, slot: int, req: Request,
                      events: list) -> None:
        """Split a just-prefilled n-way request into its ``best_of``
        copy-on-write branches: the engine forks the pages and
        samples every branch's own first token; sibling branches
        materialize as internal child Requests riding every ordinary
        scheduling path from here on (stop checks, preemption,
        cancellation, metrics). Under pool pressure the fork preempts
        policy victims — never its own family — and retries."""
        s = self._s
        while True:
            try:
                branches = self.engine.fork(slot, req.n_branches)
                break
            except PoolExhausted:
                # no slots/pages for the siblings: evict a victim and
                # retry (submit-time _check_fits guarantees the
                # family fits an EMPTY pool, so this terminates).
                # ONLY genuine capacity pressure retries — a fork
                # contract violation (plain RuntimeError) must
                # surface immediately, not mass-preempt the pool on
                # its way out.
                if not self._preempt_one(s, exclude={slot}):
                    raise
        req.branch = 0
        family = [req]
        for b, (sb, tok, lp) in enumerate(branches[1:], start=1):
            child = Request(
                prompt=req.prompt, max_new_tokens=req.max_new_tokens,
                eos_id=req.eos_id, arrival=req.arrival,
                priority=req.priority, deadline_ms=req.deadline_ms,
                arrival_time=req.arrival_time,
                request_id=f"{req.request_id}#{b}", seed=req.seed,
                response_format=req.response_format,
                adapter=req.adapter)
            child.parent = req
            child.branch = b
            child.admitted_at = req.admitted_at
            if child.adapter:
                # one pin per SEATED slot: the sibling pins the
                # (necessarily resident — the parent holds a pin)
                # lane the engine's fork just copied into its slot,
                # so every retire path releases uniformly and a
                # preempted sibling re-acquires alone
                self.engine.adapters.acquire(child.adapter)
            s.live[sb] = child
            s.admit_order.append(sb)
            family.append(child)
        req.branches = family
        if self.tracer.enabled:
            self.tracer.emit(req.request_id, "forked",
                             n_branches=req.n_branches,
                             shared_pages=int(
                                 req.base_len // self.engine.page_size))
        for (sb, tok, lp), branch_req in zip(branches, family):
            branch_req.cum_logprob += lp
            self._maybe_stop(sb, int(tok))
            events.append((branch_req, [int(tok)]))

    def step(self) -> list[tuple[Request, list[int]]]:
        """ONE scheduling iteration — the old run() loop body, now
        drivable from outside: drain the submit/cancel inboxes, shed
        (policy), seat admissible requests (policy order), issue one
        prefill chunk, grow/preempt (policy victim), then one
        compiled decode (or speculative verify) step.

        Returns this iteration's token events — ordered ``(request,
        tokens)`` pairs: one per delivered token (a whole accepted
        spec burst is one event; shed/cancelled requests appear once
        with no tokens) — which the async front door streams out as
        SSE. ``run()`` ignores them (requests accumulate their own
        ``tokens``).

        **One step in flight** (an engine that looks ahead,
        ``PagedEngine.looks_ahead``): the decode step this call
        LAUNCHES is not the one whose tokens it returns. It launches
        step N+1 behind step N — launched by the last call and running
        all through this one's host work — and only then waits for N:
        the events of iteration N are returned with N+1 in flight (the
        first call after an idle point launches and returns no decode
        tokens; ``has_work`` stays true until what is in flight has
        landed). A stop by ``max_new_tokens`` or the ``seq_len``
        horizon counts the token in flight, so a slot is never
        launched past its last; **a stop by EOS applies one step
        late** — the request finishes when the EOS lands, its slot
        rides the step already in flight, whose token for it is
        dropped, and is retired when that step lands. Where the next
        iteration cannot be predicted the step in flight is landed
        first and the loop falls back to depth 0 for that iteration: a
        starved ``grow`` (preemption), a cancel of a seated request,
        ``drain_unfinished`` / ``finish_session``, nothing left to
        launch; and an engine whose mode needs the token on the host
        (``structured``, ``speculative``, ``parallel_sampling``,
        adapters, ``tp > 1``) stays at depth 0 throughout.

        Every iteration also lands ONE row in the (always-on, fixed
        size) flight recorder — step kind, slots/pages/queue, tokens,
        accept rate, wall time from the dts this loop already
        measured, and a recompile flag from the engine's jit-cache
        sizes (the sentinel's observable) — and, when tracing is
        enabled, the per-request lifecycle events tracing.py
        documents. Neither reads the device or this batcher's
        injectable clock, so metric values are unchanged either
        way."""
        if self._s is None:
            raise RuntimeError(
                "no active session: start_session() first (run() "
                "manages its own)")
        s = self._s
        eng = self.engine
        c0 = (eng.decode_compiles + eng.verify_compiles
              + eng.prefill_compiles)
        # host-tier baselines: the flight row carries THIS step's tier
        # traffic (deltas of the engine's cumulative counters). The
        # promote executable is excluded from the recompile diff for
        # the same reason the cow one is: its single lazy first-use
        # compile is the contract, not an anomaly.
        sp0, pr0, hh0 = eng.spills, eng.promotions, eng.host_hit_pages
        st = {"wall": 0.0, "prefill": False, "decode": False,
              "spec": False, "prop": 0, "acc": 0}
        events: list = []
        s.n_steps += 1
        with span("sched_step"):
            try:
                self._step_body(s, st, events)
            finally:
                # record in a finally so the step that KILLS the pump
                # still lands its (partial) row — the crash dump's last
                # record must be the fatal step, not the one before it
                recompiled = (eng.decode_compiles + eng.verify_compiles
                              + eng.prefill_compiles) > c0
                self.flight.record(
                    kind=step_kind_code(st["prefill"], st["decode"],
                                        st["spec"]),
                    slots_live=len(s.live),
                    slots_filling=len(s.filling),
                    pages_live=int(eng.tables.n_live_pages),
                    pages_free=int(eng.tables.n_free_pages),
                    pages_cached=int(eng.tables.n_cached_pages),
                    pages_host=int(eng.tables.n_host_pages),
                    spills=eng.spills - sp0,
                    promotions=eng.promotions - pr0,
                    host_hit_pages=eng.host_hit_pages - hh0,
                    queue_depth=len(s.queue),
                    tokens=sum(len(toks) for _, toks in events),
                    accept_rate=(st["acc"] / st["prop"]) if st["prop"]
                    else 0.0,
                    wall_s=st["wall"], recompiled=recompiled,
                    inflight=([r.request_id
                               for r in (*s.filling.values(),
                                         *s.live.values())]
                              if recompiled else ()),
                    tp=eng.tp,
                    branches=eng.branch_slot_count,
                    structured=eng.structured_slot_count,
                    adapters=eng.adapter_slot_count)
        return events

    def _step_body(self, s: _Session, st: dict, events: list) -> None:
        """The iteration's phases, each under its span (a shared no-op
        while the registry is off): the tree docs/observability.md
        draws, read by the benchmark's ``sched_host_ms``.

        Where the engine looks ahead, the decode step this iteration
        LAUNCHES is not the one it lands: the step launched by the
        last iteration is in flight all through the host's work here
        (``s.flight``), the next is launched behind it from what the
        host can predict, and only then are its tokens waited for and
        delivered (:meth:`_decode_one`). Whatever cannot be predicted
        lands the step in flight first and goes on as the synchronous
        loop does (:meth:`_land_flight`)."""
        eng = self.engine
        with span("sched_admit"):
            self._admit(s, st, events)
        # --- ONE prefill chunk per iteration, interleaved with
        # decode: long prompts stream in while the live slots keep
        # producing tokens. A pending chunk and decoding slots are ONE
        # program (the chunk rides the decode step: each weight read
        # once) where the engine's mode allows; the lanes run in it,
        # so every live slot's write page must exist first and grow
        # moves in front — and may preempt the seat that was filling,
        # or the last live slot ---
        grown = mixed = eng.mixes and eng.has_pending and eng.has_lanes
        if mixed:
            with span("sched_grow"):
                self._grow(s, st, events)
            mixed = eng.has_pending and eng.has_lanes
        if mixed:
            self._decode_one(s, st, events, mixed=True)
        elif eng.has_pending:
            self._prefill_one(s, st, events)
        self._inst["slots"].set(len(s.live))
        self._inst["pages"].set(eng.tables.n_free_pages)
        if mixed:
            return
        if eng.has_lanes and not grown:
            with span("sched_grow"):
                self._grow(s, st, events)
        if eng.has_lanes:
            self._decode_one(s, st, events, mixed=False)
        else:
            # nothing rides a further launch: what is in flight lands
            self._land_flight(s, st, events, "idle")

    def _admit(self, s: _Session, st: dict, events: list) -> None:
        now = lambda: self.clock() - s.t0
        # submits drain BEFORE cancels: a request submitted and then
        # cancelled between two steps must be found in the queue
        while self._inbox_submit:
            req = self._inbox_submit.popleft()
            s.n_seen += 1
            s.queue.append(req)
            if self.tracer.enabled:
                self.tracer.emit(req.request_id, "enqueued",
                                 prompt_len=int(req.base_len),
                                 priority=req.priority,
                                 arrival=round(req.arrival, 6))
            cs = self._class_stats(req)
            if cs is not None:
                cs["n"] += 1
        if s.flight is not None and self._cancels_seated(s):
            # a seat cannot be taken back under a step in flight
            self._land_flight(s, st, events, "drain")
        self._drain_cancels(events)
        # --- shed: the policy's "this deadline is already lost"
        # verdict turns into immediate backpressure (FCFS: never) ---
        for req in self.policy.shed(s.queue, now(), self):
            s.queue.remove(req)
            self._shed_request(req, events)
        # --- seat every admissible request the policy picks; cached
        # prefix pages map in here, so a hit's remaining prefill is
        # only its private tail. FCFS stops at the first failed seat
        # (head-of-line, strict arrival order); SLO keeps trying
        # other candidates ---
        tried: set[int] = set()
        while True:
            pool = [r for r in s.queue if id(r) not in tried]
            req = self.policy.next_admission(pool, now(), self)
            if req is None:
                break
            hits0 = self.engine.prefix_hit_pages
            # slot budget: an n-way request needs its whole family's
            # slots effectively free (it seats one now and RESERVES
            # the rest for the fork at its prefill boundary); plain
            # requests must not eat into standing reservations
            need = req.n_branches if req.branches is None else 1
            if self._free_slot_count() - self._reserved_slots() < need:
                slot = None
            else:
                # adapter pin BEFORE the engine seat: acquire returns
                # None when every lane is pinned by seated slots —
                # the same keep-it-queued backpressure as pool
                # exhaustion (never an error; unknown names already
                # 400'd at submit). A seat that fails AFTER the
                # acquire must drop the pin, or the lane leaks pinned
                # forever.
                lane = (self.engine.adapters.acquire(req.adapter)
                        if req.adapter else 0)
                if lane is None:
                    slot = None
                else:
                    slot = self.engine.admit_begin(
                        req.prompt, seed=req.seed, branch=req.branch,
                        adapter_lane=lane)
                    if slot is None and req.adapter:
                        self.engine.adapters.release(req.adapter)
            if slot is None:
                if self.policy.stop_on_admit_failure:
                    break         # no slot/pages: keep FCFS order
                tried.add(id(req))
                continue
            s.queue.remove(req)
            s.filling[slot] = req
            s.admit_order.append(slot)
            s.n_admissions += 1
            self._inst["admissions"].inc()
            if self.engine.structured \
                    and req.response_format is not None:
                # bind the automaton cursor at seat time; a
                # preemption victim's folded generated tokens
                # (prompt past base_len) replay so the cursor
                # resumes at the exact state it was evicted in
                if self.engine.structured_begin(
                        slot, req.response_format, req.eos_id,
                        prefix_tokens=req.prompt[req.base_len:]):
                    self._inst["structured"].inc()
            # the first seat stamps the end of the queue wait; a
            # re-admission after preemption keeps it
            readmission = req.admitted_at is not None
            if not readmission:
                req.admitted_at = now()
            if self.tracer.enabled:
                self.tracer.emit(
                    req.request_id, "seated", slot=slot,
                    prefix_hit_pages=int(
                        self.engine.prefix_hit_pages - hits0),
                    readmission=readmission,
                    queue_wait_s=round(
                        req.admitted_at - req.arrival, 6),
                    # adapter attribution only when one is in play:
                    # base-traffic event payloads stay byte-identical
                    # with the feature off
                    **({"adapter": req.adapter} if req.adapter
                       else {}))

    def _prefill_one(self, s: _Session, st: dict, events: list) -> None:
        # host->HBM promotions dispatch BEFORE the chunk issues:
        # a host-tier hit's TTFT pays the async H2D stream
        # (overlapped with this iteration's chunk/decode work),
        # never the recompute FLOPs the hit skipped — and a chunk
        # that attends promoted pages is ordered after the write
        # by the donated-pool data dependency
        if self.engine.host_spill:
            self.engine.issue_promotions()
        # the chunk's slot, read only when tracing will use it
        # (pending_slots builds a list — not free on the hot loop)
        fill_slot = (self.engine.pending_slots[0]
                     if self.tracer.enabled else -1)
        t_chunk = self.clock()
        done = self.engine.prefill_step()
        dt = self.clock() - t_chunk
        self._inst["chunks"].inc()
        self._chunk_issued(s, st, fill_slot, dt)
        st["wall"] += dt
        if done is not None:
            if self.engine.looks_ahead:
                self._first_token_issued(s, done[0])
            self._prefill_done(s, *done, events)

    def _chunk_issued(self, s: _Session, st: dict, fill_slot: int,
                      dt: float) -> None:
        """Book one chunk (alone or in a mixed step) that took ``dt``
        on the host's clock (the two counters of chunks move where a
        chunk is ISSUED)."""
        self.est_chunk_s = dt if not self.est_chunk_s \
            else 0.8 * self.est_chunk_s + 0.2 * dt
        st["prefill"] = True
        if self.tracer.enabled:
            # the engine-track slice shares its name with the
            # serving_prefill_chunk profiler span (spans.py), so
            # a host trace and a device capture cross-link
            self.tracer.emit(None, "serving_prefill_chunk",
                             dur_s=round(dt, 6), slot=fill_slot)
            fr = s.filling.get(fill_slot)
            if fr is not None:
                self.tracer.emit(fr.request_id, "prefill_chunk",
                                 slot=fill_slot,
                                 dur_s=round(dt, 6))

    def _prefill_done(self, s: _Session, slot: int, first: int,
                      events: list) -> None:
        """A prompt's last chunk gave its first token: the request
        moves from filling to live and the token is delivered."""
        req = s.filling.pop(slot)
        s.live[slot] = req
        if req.n_branches > 1 and req.branches is None:
            # one prefill, best_of decode branches: fork at
            # the boundary so every branch diverges from its
            # own first token (branch 0's pick == `first`)
            self._fork_request(slot, req, events)
        else:
            if self.engine.parallel:
                # the first token's logprob belongs to the
                # sequence logprob too (n = 1 requests and
                # re-admitted fork branches alike — a
                # preempted branch skipping it would bias
                # best_of toward preempted siblings); this
                # also frees the stashed prompt logits a
                # never-forking request otherwise holds
                req.cum_logprob += \
                    self.engine.take_first_logprob(slot)
            self._maybe_stop(slot, first)  # prefill's token
            events.append((req, [int(first)]))

    def _grow(self, s: _Session, st: dict, events: list) -> None:
        # --- grow: every decoding slot's next write page must exist
        # (cached prefixes evict first); starved slots preempt the
        # POLICY's victim (FCFS: youngest seated) ---
        starved = self.engine.grow_slots()
        if starved and s.flight is not None:
            # a victim's token in flight belongs to its stream before
            # it is folded, and the step may end a sequence and free
            # the very pages that are short
            self._land_flight(s, st, events, "preempt")
            starved = self.engine.grow_slots()
        while starved:
            if not self._preempt_one(s):
                break
            starved = self.engine.grow_slots() if s.live else []

    def _decode_one(self, s: _Session, st: dict, events: list,
                    mixed: bool) -> None:
        """One compiled step over every decoding slot — with the
        pending chunk in the same program where ``mixed``
        (``PagedEngine.mixed_step``): the step's host time then feeds
        BOTH service-time estimates (the chunk and the step are one
        wait), a prompt's first token leaves with the step's decode
        tokens and its slot decodes from the next step on.

        An engine that looks ahead launches this step BEHIND the one
        the last iteration launched and lands THAT one: the events
        are one step older than the program just issued."""
        eng = self.engine
        if eng.tp > 1:
            # the step about to run pays its decode-output psum on
            # the wire: land the MODELED per-chip bytes (precomputed
            # constants — one float add, no device read)
            self._inst["tp_bytes"].inc(
                self._tp_verify_bytes if eng.speculative
                else self._tp_decode_bytes)
        t_step = self.clock()
        if eng.speculative:
            # draft → batched verify → accept: each slot emits
            # 1..draft_len+1 tokens per step; stop checks run per
            # token IN ORDER, so EOS or max_new_tokens mid-burst
            # truncates exactly where sequential decode would have
            # stopped
            prop0 = eng.spec_proposed
            acc0 = eng.spec_accepted
            emitted = eng.spec_step()
            dt = self.clock() - t_step
            s.decode_time += dt
            self.est_step_s = dt if not self.est_step_s \
                else 0.8 * self.est_step_s + 0.2 * dt
            st["spec"] = True
            st["wall"] += dt
            st["prop"] = int(eng.spec_proposed - prop0)
            st["acc"] = int(eng.spec_accepted - acc0)
            if self.tracer.enabled:
                self.tracer.emit(None, "spec_verify_step",
                                 dur_s=round(dt, 6),
                                 slots=len(emitted),
                                 proposed=st["prop"],
                                 accepted=st["acc"],
                                 step=s.n_steps)
            self._inst["sync_lands"].inc(reason="mode")
            with span("sched_deliver"):
                self._deliver_bursts(s, emitted, events)
            return
        if mixed:
            self._inst["mixed"].inc()
            self._inst["chunks"].inc()
        if not eng.looks_ahead:
            decoders = list(s.live)
            # the chunk's slot, read only when tracing will use it
            chunk_slot = None if not mixed else \
                eng.pending_slots[0] if self.tracer.enabled else -1
            tokens, done = eng.mixed_step() if mixed \
                else (eng.step(), None)
            self._inst["sync_lands"].inc(reason="mode")
            self._step_landed(s, st, events, chunk_slot, decoders,
                              tokens, done, self.clock() - t_step)
            return
        # (a launch that raises leaves nothing in flight to land)
        flight, s.flight = s.flight, None
        ahead, landed = eng.step_ahead(flight, mixed)
        s.flight = ahead
        # what the launch settles for the one after it: a slot whose
        # token in flight is its last by ``max_new_tokens`` or the
        # ``seq_len`` horizon rides no further launch (tokens
        # delivered plus tokens in flight are what is counted, so a
        # ``length`` stop costs no lane)
        s.left[ahead.active] -= 1
        if ahead.last:
            self._first_token_issued(s, ahead.pending["slot"])
        for slot in np.flatnonzero(ahead.active & (s.left <= 0)):
            eng.hold(int(slot))
        if flight is not None:
            self._inst["lookahead"].inc()
            self._flight_landed(s, st, events, flight, landed,
                                self.clock() - t_step)

    def _first_token_issued(self, s: _Session, slot: int) -> None:
        """The chunk that ends ``slot``'s prompt has been issued, so
        its first token is on its way (a look-ahead engine): how many
        further tokens the request may be launched for, and no launch
        at all where the first is its last."""
        req = s.filling[slot]
        room = min(req.max_new_tokens,
                   self.engine.cfg.seq_len - req.base_len)
        s.left[slot] = room - len(req.tokens) - 1
        if s.left[slot] <= 0:
            self.engine.hold(slot)

    def _land_flight(self, s: _Session, st: dict | None,
                     events: list | None, reason: str) -> None:
        """A synchronous point: wait for the step in flight (if any)
        with nothing launched behind it, so that what follows sees
        the engine as the synchronous loop would. ``events`` None: its
        tokens are dropped, not delivered (a drain, the session's
        end)."""
        if s.flight is None:
            return
        flight, s.flight = s.flight, None
        t_step = self.clock()
        _, landed = self.engine.step_ahead(flight, None)
        self._inst["sync_lands"].inc(reason=reason)
        if events is None:
            self._retire_stopped(s)
            return
        self._flight_landed(s, st, events, flight, landed,
                            self.clock() - t_step)

    def _flight_landed(self, s: _Session, st: dict, events: list,
                       flight, landed: tuple, dt: float) -> None:
        self._retire_stopped(s)
        # the lanes it decoded, in the order they came to life (a
        # slot whose prompt the step before it ended is live by now)
        decoders = [slot for slot in s.live if flight.active[slot]]
        chunk = flight.pending
        self._step_landed(s, st, events,
                          None if chunk is None else chunk["slot"],
                          decoders, *landed, dt)

    def _retire_stopped(self, s: _Session) -> None:
        """The step the stopped slots were riding has landed: their
        extra tokens are dropped, their pages go back."""
        stopped, s.stopped = s.stopped, []
        for slot in stopped:
            self._inst["wasted"].inc()
            self.engine.retire(slot)

    def _step_landed(self, s: _Session, st: dict, events: list,
                     chunk_slot: int | None, decoders: list[int],
                     tokens: np.ndarray, done: tuple | None,
                     dt: float) -> None:
        """Book and deliver one landed decode step that took ``dt`` on
        the host's clock — a mixed one where ``chunk_slot`` is the
        slot whose chunk rode it (-1: not looked up)."""
        if chunk_slot is not None:
            self._chunk_issued(s, st, chunk_slot, dt)
        self._step_done(s, st, dt, len(decoders))
        if done is not None:
            self._prefill_done(s, *done, events)
        with span("sched_deliver"):
            self._deliver_tokens(s, tokens, events, decoders)

    def _step_done(self, s: _Session, st: dict, dt: float,
                   n_slots: int) -> None:
        """Book one decode step (plain or mixed) over ``n_slots`` live
        slots that took ``dt`` on the host's clock."""
        s.decode_time += dt
        self.est_step_s = dt if not self.est_step_s \
            else 0.8 * self.est_step_s + 0.2 * dt
        st["decode"] = True
        st["wall"] += dt
        if self.tracer.enabled:
            self.tracer.emit(None, "decode_step", dur_s=round(dt, 6),
                             slots=n_slots, step=s.n_steps)
        s.decoded += n_slots
        self._inst["tokens"].inc(n_slots)

    def _deliver_bursts(self, s: _Session, emitted: dict,
                        events: list) -> None:
        # a cancel that landed while the step ran drops the whole
        # burst (the slot leaves ``live`` here, before emission)
        self._drain_cancels(events)
        # count DELIVERED tokens only: a burst tail past
        # EOS/max_new_tokens never reaches req.tokens, and
        # counting it would inflate decode_tok_s vs the
        # non-speculative arm (whose every counted token is
        # appended)
        delivered = 0
        for slot in sorted(emitted):
            burst: list[int] = []
            req = s.live.get(slot)
            finished = False
            for tok in emitted[slot]:
                if finished or slot not in s.live:
                    break
                delivered += 1
                burst.append(int(tok))
                # retirement DEFERRED past the burst event below:
                # the per-burst token delta must precede retired
                # on the request's trace timeline
                finished = self._maybe_stop(slot, int(tok),
                                            finish=False)
            if burst:
                # the whole accepted burst is ONE event — the SSE
                # contract is one message per pool read's yield
                if self.tracer.enabled:
                    self.tracer.emit(req.request_id, "tokens",
                                     n=len(burst), spec=True,
                                     step=s.n_steps)
                events.append((req, burst))
            if finished and slot in s.live:
                self._finish_request(slot)
        s.decoded += delivered
        self._inst["tokens"].inc(delivered)

    def _deliver_tokens(self, s: _Session, tokens: np.ndarray,
                        events: list, decoders: list[int]) -> None:
        """The step's token to every slot in ``decoders`` (the slots
        live when it ran) that a cancel has not taken since. Under a
        step in flight a cancelled seat cannot be retired yet: its
        token is dropped here and the cancel waits for the next
        iteration, which lands that step first."""
        cancelled: set[int] = set()
        if s.flight is None:
            self._drain_cancels(events)
        elif self._inbox_cancel:
            cancelled = self._cancel_ids()
        lps = self.engine.step_logprobs
        for slot in decoders:
            req = s.live.get(slot)
            if req is None or id(req) in cancelled:
                continue
            if lps is not None:
                # per-branch sequence logprob — what best_of
                # ranks by (parallel-sampling engines only)
                req.cum_logprob += float(lps[slot])
            # token delta BEFORE the stop-check: retired must be
            # the last event on the request's trace timeline
            if self.tracer.enabled:
                self.tracer.emit(req.request_id, "tokens", n=1,
                                 step=s.n_steps)
            self._maybe_stop(slot, int(tokens[slot]))
            events.append((req, [int(tokens[slot])]))

    def debug_snapshot(self, timeline_tail: int = 20) -> dict:
        """Live per-request view for the ``/debug/requests`` endpoint:
        every queued/filling/decoding request's state plus (when
        tracing is enabled) the tail of its event timeline.

        Must run on the thread that drives :meth:`step` — the front
        door submits it to the pump executor, so the walk over the
        session dicts is serialized with the scheduler loop and needs
        no locks."""
        s = self._s
        # ONE pass over the (bounded) ring, then index lookups per
        # request — a per-request ring scan would make one debug poll
        # O(ring_size x requests) on the pump thread, which IS the
        # decode loop's thread
        timelines: dict[str, list] = {}
        if self.tracer.enabled:
            for e in self.tracer.events():
                rid = e["request_id"]
                if rid is not None:
                    timelines.setdefault(rid, []).append(e)

        def view(req: Request, state: str,
                 slot: int | None = None) -> dict:
            d = {
                "request_id": req.request_id, "state": state,
                "priority": req.priority,
                "adapter": req.adapter,
                "prompt_len": int(req.base_len),
                "n_tokens": len(req.tokens),
                "arrival_s": round(req.arrival, 6),
                "admitted_at_s": None if req.admitted_at is None
                else round(req.admitted_at, 6),
                "first_token_at_s": None if req.first_token_at is None
                else round(req.first_token_at, 6),
            }
            if slot is not None:
                d["slot"] = slot
            if self.tracer.enabled:
                evs = timelines.get(req.request_id, [])
                d["timeline_tail"] = evs[-timeline_tail:]
            return d

        out: dict = {"active_session": s is not None,
                     "tracing_enabled": self.tracer.enabled,
                     "queue_depth": self.queue_depth if s is not None
                     else len(self._inbox_submit),
                     "requests": []}
        if s is None:
            return out
        out["session_now_s"] = round(self.clock() - s.t0, 6)
        for req in s.queue:
            out["requests"].append(view(req, "queued"))
        for slot, req in sorted(s.filling.items()):
            out["requests"].append(view(req, "prefill", slot))
        for slot, req in sorted(s.live.items()):
            out["requests"].append(view(req, "decode", slot))
        return out

    def _land(self, s: _Session) -> None:
        """Exception or not, the gauges land on engine truth at exit
        (an aborted run may leave seated slots — report them rather
        than freezing a stale mid-loop value in the Prometheus export
        forever); clean exits read 0 live."""
        if s.closed:
            return
        s.closed = True
        inst = self._inst
        inst["slots"].set(len(s.live))
        inst["pages"].set(self.engine.tables.n_free_pages)
        hit_pages = self.engine.prefix_hit_pages - s.hits0
        lookups = self.engine.prefix_lookup_pages - s.lookups0
        inst["hit_pages"].inc(hit_pages)
        inst["hit_rate"].set(hit_pages / max(lookups, 1))
        n_spec_prop = self.engine.spec_proposed - s.spec_prop0
        n_spec_acc = self.engine.spec_accepted - s.spec_acc0
        inst["spec_prop"].inc(n_spec_prop)
        inst["spec_acc"].inc(n_spec_acc)
        inst["spec_rate"].set(n_spec_acc / max(n_spec_prop, 1))
        inst["fork_pages"].inc(self.engine.fork_pages - s.fork_pages0)
        inst["cow_copies"].inc(self.engine.cow_copies - s.cow0)
        if "structured" in inst:
            rows = self.engine.structured_masked_rows - s.srows0
            inst["structured_frac"].set(
                (self.engine.structured_masked_sum - s.smasked0)
                / max(rows, 1))
        if "spills" in inst:
            inst["spills"].inc(self.engine.spills - s.spills0)
            inst["promotions"].inc(
                self.engine.promotions - s.promotions0)
            inst["host_hits"].inc(
                self.engine.host_hit_pages - s.host_hits0)
        if "adapter_loads" in inst:
            ad = self.engine.adapters
            inst["adapter_loads"].inc(ad.loads - s.aloads0)
            inst["adapter_evictions"].inc(ad.evictions - s.aevict0)
        if self.policy.slo:
            for name, cs in s.per_class.items():
                inst["slo_ttft_rate"].set(
                    cs["ttft_hit"] / max(cs["ttft_n"], 1), cls=name)
                inst["slo_tpot_rate"].set(
                    cs["tpot_hit"] / max(cs["tpot_n"], 1), cls=name)
        self._s = None
        self._sentinel = None

    @staticmethod
    def _pct(vals: list[float], q: float) -> float:
        arr = np.percentile(np.asarray(vals or [0.0], np.float64), q)
        return round(arr.tolist(), 4)

    def _metrics(self, s: _Session) -> dict:
        elapsed = self.clock() - s.t0
        lat = s.lat or [0.0]
        ttft = s.ttft or [0.0]
        new_tokens = s.new_tokens
        ttft_hit = sum(cs["ttft_hit"] for cs in s.per_class.values())
        ttft_n = sum(cs["ttft_n"] for cs in s.per_class.values())
        classes = {}
        for name, cs in s.per_class.items():
            classes[name] = {
                "n_requests": cs["n"],
                "n_completed": cs["completed"],
                "n_shed": cs["shed"],
                "ttft_p50_s": self._pct(cs["ttft"], 50),
                "ttft_p99_s": self._pct(cs["ttft"], 99),
                "tpot_p50_s": self._pct(cs["tpot"], 50),
                "tpot_p99_s": self._pct(cs["tpot"], 99),
                "ttft_hit_rate": round(
                    cs["ttft_hit"] / max(cs["ttft_n"], 1), 4),
                "tpot_hit_rate": round(
                    cs["tpot_hit"] / max(cs["tpot_n"], 1), 4),
            }
        return {
            "n_requests": s.n_seen,
            "new_tokens": new_tokens,
            "elapsed_s": round(elapsed, 4),
            "decode_tok_s": round(
                s.decoded / max(s.decode_time, 1e-9), 1),
            "total_tok_s": round(new_tokens / max(elapsed, 1e-9), 1),
            "latency_mean_s": round(float(np.mean(lat)), 4),
            "latency_p95_s": round(float(np.percentile(lat, 95)), 4),
            "ttft_mean_s": round(float(np.mean(ttft)), 4),
            # previously invisible to callers: how often the
            # preemption path actually fired, how many seatings
            # (INCLUDING re-admissions after preemption) the trace
            # cost, and what the prefix cache + chunked prefill
            # actually did — the registry's serving_* counters carry
            # the same events for the exporters
            "n_admissions": s.n_admissions,
            "n_preemptions": s.n_preemptions,
            "n_prefill_chunks": self.engine.prefill_chunks - s.chunks0,
            "prefix_hit_pages": self.engine.prefix_hit_pages - s.hits0,
            "prefix_hit_rate": round(
                (self.engine.prefix_hit_pages - s.hits0)
                / max(self.engine.prefix_lookup_pages - s.lookups0, 1),
                4),
            # speculation stats (all zero on a non-speculative
            # engine): mean accepted DRAFT tokens per verify step —
            # tokens/step is that + 1 (the fallback/bonus pick)
            "n_spec_steps": self.engine.spec_steps - s.spec_steps0,
            "n_spec_proposed":
                self.engine.spec_proposed - s.spec_prop0,
            "n_spec_accepted":
                self.engine.spec_accepted - s.spec_acc0,
            "spec_accept_rate": round(
                (self.engine.spec_accepted - s.spec_acc0)
                / max(self.engine.spec_proposed - s.spec_prop0, 1), 4),
            "spec_mean_accepted": round(
                (self.engine.spec_accepted - s.spec_acc0)
                / max(self.engine.spec_steps - s.spec_steps0, 1), 4),
            # host spill tier stats (all zero on a spill-less
            # engine): demotions, promotions, and the prompt pages
            # whose TTFT paid the H2D stream instead of recompute
            "n_spills": self.engine.spills - s.spills0,
            "n_promotions": self.engine.promotions - s.promotions0,
            "host_hit_pages":
                self.engine.host_hit_pages - s.host_hits0,
            # copy-on-write parallel sampling (all zero on a
            # non-parallel engine): forks performed, pages SHARED
            # into branches (HBM reads amortized), and the private
            # tail-page copies — the only bytes n-way duplicates
            "n_forks": self.engine.forks - s.forks0,
            "fork_pages": self.engine.fork_pages - s.fork_pages0,
            "n_cow_copies": self.engine.cow_copies - s.cow0,
            # structured generation (all zero on an unconstrained
            # engine): constrained cursor bindings and the mean
            # masked-vocabulary fraction over their committed rows
            "n_structured":
                self.engine.structured_requests - s.structured0,
            "structured_masked_frac": round(
                (self.engine.structured_masked_sum - s.smasked0)
                / max(self.engine.structured_masked_rows - s.srows0,
                      1), 4),
            # multi-LoRA serving (all zero/empty on a lora-less
            # engine): per-tenant billing attribution — terminal
            # requests and delivered tokens keyed by adapter name
            # ("" = base) — plus the registry's lane churn
            "n_adapter_loads": (
                self.engine.adapters.loads - s.aloads0
                if self.engine.adapters is not None else 0),
            "n_adapter_evictions": (
                self.engine.adapters.evictions - s.aevict0
                if self.engine.adapters is not None else 0),
            "n_adapter_hits": (
                self.engine.adapters.hits - s.ahits0
                if self.engine.adapters is not None else 0),
            "adapters": {name: dict(ad) for name, ad
                         in sorted(s.per_adapter.items())},
            # SLO scheduler stats — stable keys on EVERY return path
            # (the established contract): zero/empty under FCFS,
            # populated per configured class under an SLO policy
            "n_shed": s.n_shed,
            "n_cancelled": s.n_cancelled,
            "deadline_hit_rate": round(
                ttft_hit / ttft_n, 4) if ttft_n else 1.0,
            "classes": classes,
        }

    # ---- the synchronous trace driver ----------------------------
    def run(self, requests: list[Request]) -> dict:
        if not requests:
            return {"n_requests": 0, "new_tokens": 0, "elapsed_s": 0.0,
                    "decode_tok_s": 0.0, "total_tok_s": 0.0,
                    "latency_mean_s": 0.0, "latency_p95_s": 0.0,
                    "ttft_mean_s": 0.0,
                    # stable key set: the preemption/admission/prefill
                    # /speculation/SLO stats exist on EVERY return
                    # path, not just busy ones
                    "n_admissions": 0, "n_preemptions": 0,
                    "n_prefill_chunks": 0, "prefix_hit_pages": 0,
                    "prefix_hit_rate": 0.0,
                    "n_spills": 0, "n_promotions": 0,
                    "host_hit_pages": 0,
                    "n_spec_steps": 0, "n_spec_proposed": 0,
                    "n_spec_accepted": 0, "spec_accept_rate": 0.0,
                    "spec_mean_accepted": 0.0,
                    "n_forks": 0, "fork_pages": 0, "n_cow_copies": 0,
                    "n_structured": 0, "structured_masked_frac": 0.0,
                    "n_adapter_loads": 0, "n_adapter_evictions": 0,
                    "n_adapter_hits": 0, "adapters": {},
                    "n_shed": 0, "n_cancelled": 0,
                    "deadline_hit_rate": 1.0, "classes": {
                        name: {"n_requests": 0, "n_completed": 0,
                               "n_shed": 0, "ttft_p50_s": 0.0,
                               "ttft_p99_s": 0.0, "tpot_p50_s": 0.0,
                               "tpot_p99_s": 0.0, "ttft_hit_rate": 0.0,
                               "tpot_hit_rate": 0.0}
                        for name in (self.policy.classes
                                     if self.policy.slo else ())}}
        for r in requests:
            self._check_fits(r)
            self.policy.validate(r)
        s = self._begin()
        self._s = s
        s.n_seen = len(requests)
        s.queue = sorted(requests, key=lambda r: r.arrival)
        if self.tracer.enabled:
            for r in s.queue:
                self.tracer.emit(r.request_id, "enqueued",
                                 prompt_len=int(r.base_len),
                                 priority=r.priority,
                                 arrival=round(r.arrival, 6))
        if self.policy.slo:
            for r in requests:
                s.per_class[self.policy.cls_of(r).name]["n"] += 1
        try:
            # `with sentinel` (not manual enter/exit): an exception
            # escaping the loop still closes the watch — the policy
            # only fires on clean exits by design
            with self._sentinel:
                while s.queue or s.live or s.filling or s.flight:
                    self.step()
                    if not s.live and not s.filling and s.queue:
                        # idle until the next arrival
                        wait = min(r.arrival for r in s.queue) \
                            - (self.clock() - s.t0)
                        if wait > 0:
                            time.sleep(min(wait, 0.05))
        finally:
            self._land(s)
        return self._metrics(s)


__all__ = ["ContinuousBatcher", "Request"]
