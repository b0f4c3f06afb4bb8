"""Serving subsystem: continuous batching over a paged KV cache.

The decode roofline (docs/performance.md has the byte models, PERF.md
what the chip measured) says the step time IS the cache bytes it
streams. This package stops streaming dead bytes:

- :mod:`kv_pages` — the fixed page pool + host-side block tables with
  REFCOUNTED pages and a prompt-prefix index (seat/retire/evict
  without recompiles; retired prompts' prefixes stay resident and
  shareable, LRU-evicted under pressure);
- :mod:`engine` — chunked prefill/decode split; ONE compiled decode
  step whose signature depends only on pool geometry, with attention
  reading the pool once per step and routing shared pages to every
  referencing slot (length-masked pages, online-softmax combine), and
  ONE compiled prefill chunk serving every prompt length;
- :mod:`batcher` — the PUMPABLE scheduling core: policy-driven
  admission (FCFS default), one prefill chunk interleaved per decode
  step, preemption under pool pressure, thread-safe submit/cancel
  inboxes, latency/TTFT/tokens-per-second + prefix-hit + speculation
  (+ per-class SLO) metrics;
- :mod:`speculative` — draft → batched-verify → accept/rewind decode
  (``speculative: true``): model-free prompt-lookup drafting plus ONE
  compiled multi-token verify step, so each pool read yields
  ``accepted + 1`` tokens instead of one (greedy-parity-exact);
  ``spec_tree: true`` upgrades the chain to a TREE of candidate
  branches verified in the same pass through ancestor-only
  visibility masks, the best accepted root-to-leaf path winning;
  copy-on-write parallel sampling (``parallel_sampling: true``, the
  OpenAI ``n``/``best_of`` surface) forks a prefilled slot into n
  branches sharing every full page through the refs lanes with
  per-branch PRNG keys and logprob accounting;
- :mod:`loadgen` — the workload capture & deterministic replay
  harness: a versioned JSONL workload format with content
  fingerprints, front-door capture (``frontend.capture_path``),
  synthetic generators (Poisson/bursty/diurnal/sharegpt), open-loop
  replay drivers (in-process deterministic clock, or real HTTP
  clients, at ×N time compression), and SLO conformance reports with
  a baseline-diff gate (``scripts/replay_diff.py``);
- :mod:`tp` — tensor-parallel serving (``tp: N``): every compiled
  step's attention — Q/K/V/O projections, the KV page pool, the
  decode sweep, the pallas table walk, the fused verify — sharded
  over a committed mesh's ``tp`` (heads) axis via shard_map, so
  per-chip KV bytes/step divide by ``tp`` for ONE activation psum
  per layer; block tables and all scheduling stay host-side and
  replicated (docs/parallelism.md "Tensor-parallel serving");
- :mod:`frontend` — the request-facing surface: scheduler policies
  (:class:`FCFSPolicy`/:class:`SLOPolicy` — priority classes,
  deadline-driven admission, cost-aware preemption, load shedding)
  and the stdlib asyncio OpenAI-compatible HTTP/SSE server
  (:class:`ServingFrontend`) that pumps the batcher from an event
  loop (docs/serving.md);
- :mod:`router` — the engine FLEET: N data-parallel replicas behind
  one batcher-shaped front door (:class:`EngineFleet`), with
  prefix-affinity + SLO-aware routing, a load-spill threshold,
  cross-replica readmission on replica death or sustained hot-spot,
  and fleet-wide ``router_*`` telemetry — ``ServingFrontend(fleet)``
  and ``replay_inprocess(fleet, ...)`` both drive it unchanged
  (docs/serving.md "The engine fleet").

Entry points: build a :class:`~torchbooster_tpu.serving.engine.
PagedEngine` (or via ``ServingConfig.make`` from YAML), wrap it in a
:class:`~torchbooster_tpu.serving.batcher.ContinuousBatcher`, and feed
it :class:`~torchbooster_tpu.serving.batcher.Request`s — or serve it
over HTTP with ``ServingConfig.frontend.make(batcher)``.
"""
from torchbooster_tpu.serving.adapters import AdapterRegistry
from torchbooster_tpu.serving.batcher import ContinuousBatcher, Request
from torchbooster_tpu.serving.engine import PagedEngine
from torchbooster_tpu.serving.frontend import (
    FCFSPolicy,
    PriorityClass,
    SLOPolicy,
    SchedulerPolicy,
)
from torchbooster_tpu.serving.kv_pages import (
    BlockTables,
    HostPagePool,
    NULL_PAGE,
    make_pool,
)
from torchbooster_tpu.serving.speculative import (
    NO_DRAFT,
    PromptLookupDrafter,
    TreeLookupDrafter,
)


_ROUTER_NAMES = ("EngineFleet", "InProcessReplica", "AffinityRouting",
                 "RoundRobinRouting", "PrefixDirectory")


def __getattr__(name: str):
    if name == "ServingFrontend":     # lazy: pulls in the http layer
        from torchbooster_tpu.serving.frontend import ServingFrontend

        return ServingFrontend
    if name in _ROUTER_NAMES:         # lazy: the fleet layer
        from torchbooster_tpu.serving import router

        return getattr(router, name)
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


__all__ = ["AdapterRegistry", "AffinityRouting", "BlockTables",
           "ContinuousBatcher",
           "EngineFleet", "FCFSPolicy", "HostPagePool",
           "InProcessReplica", "NO_DRAFT", "NULL_PAGE", "PagedEngine",
           "PrefixDirectory", "PriorityClass", "PromptLookupDrafter",
           "Request", "RoundRobinRouting", "SLOPolicy",
           "SchedulerPolicy", "ServingFrontend", "TreeLookupDrafter",
           "make_pool"]
