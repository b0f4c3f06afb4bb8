"""The ``serve_sarvam_mla`` job: ``jobs/serve.py``'s open loop against
the HTTP front door, for a model of the latent-attention
(``sarvam_mla``) family.

``jobs/serve.py`` builds GPT-2 and ``jobs/serve_lfm2.py`` LFM2-MoE,
and neither may be edited, so this file states the same run with this
family's three pieces in their place —
``program_sarvam_mla.build_serve``, ``weights_sarvam_mla.generate``,
``reference/sarvam_mla.py`` — and takes everything else by import:
the warm-up, the load generator's pass (``offer``), the client-side
numbers, the gap's modes and the sample that is checked from
``serve``, the streams' variety from ``serve_lfm2``. The result has
``serve``'s keys, so every model-agnostic reader reads it unchanged.
The traffic's ids are drawn from the configuration's vocabulary SLICE
(``vocab_size`` is the slice: ``loadgen.plan.make_requests`` draws
below it), and the logits, the sampling and this comparison are over
the slice.

One thing is not ``serve``'s: the pre-roll and the window are planned
apart (:func:`plan_requests`). ``loadgen/plan.py`` fixes the multiset
of lengths and of arrival gaps over the horizon it is given; given the
pre-roll and the window as one horizon, which 51 of the 67 requests
fall inside the window is the seed's, and here a request costs what
its prompt costs (a chunk walks to its own position) — the window's
p95 followed how many arrivals and which prompts it happened to hold
(PERF.md section 6: 47-57 first tokens a window, 46-59 % of the gaps
behind a chunk). Given each as a horizon of its own, every run's
WINDOW is offered the same work, and the seed orders it.
"""
from __future__ import annotations

import asyncio
import gc
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import program  # noqa: E402
import program_sarvam_mla  # noqa: E402
import weights_sarvam_mla  # noqa: E402
from jobs import serve  # noqa: E402
from jobs.serve_lfm2 import stream_variety  # noqa: E402
from loadgen import plan as loadplan  # noqa: E402


def plan_requests(traffic: dict, seed: int, preroll: float, seconds: float,
                  vocab: int) -> list[dict]:
    """The run's requests: ``loadgen.plan.make_requests`` once for the
    pre-roll and once for the window, each with the file's rate, the
    fixed multisets of its own horizon and an order of its own (the
    run's seed orders the window; the pre-roll's is past any seed the
    harness is given)."""
    before = loadplan.make_requests(traffic, int(seed) + (1 << 32),
                                    preroll, vocab)
    inside = loadplan.make_requests(traffic, seed, seconds, vocab)
    for r in inside:
        r["id"] += len(before)
        r["due"] += preroll
    return before + inside


def compare(records: list[dict], requests: list[dict], cfg: dict,
            traffic: dict, seed: int, make_weights) -> dict:
    """``serve.compare`` against ``reference/sarvam_mla.py``:
    ``served_gap_max`` and ``served_gap_p99`` over every served token
    of the sampled requests (the longest always among them),
    ``bad_streams`` over all."""
    from reference import sarvam_mla

    by_id = {r["id"]: r for r in requests}
    bad = sum(
        rec["finished"] and (
            len(rec["tokens"]) != by_id[rec["id"]]["max_tokens"]
            or not all(0 <= t < cfg["vocab_size"] for t in rec["tokens"]))
        for rec in records)
    limits = traffic["limits"]
    sample = serve.pick_sample(records, seed, traffic["check_requests"])
    out = {"bad_streams": {"value": int(bad),
                           "limit": limits["bad_streams"]},
           "checked_requests": len(sample),
           "checked_tokens": sum(len(r["tokens"]) for r in sample)}
    worst = p99 = None
    if sample:
        w = make_weights()
        gaps = np.concatenate([np.asarray(sarvam_mla.served_gaps(
            w, by_id[rec["id"]]["prompt"], rec["tokens"], cfg,
            pad_to=traffic["max_positions"])) for rec in sample])
        worst, p99 = float(gaps.max()), float(np.percentile(gaps, 99))
        out["tokens_off_best"] = int((gaps > 0).sum())
    # the widest gap is an extreme of ~1,000 tokens and catches a
    # token that is plainly wrong; the 99th percentile is the steadier
    # reading of lost precision (PERF.md section 4 gives both limits)
    out["served_gap_p99"] = {"value": p99,
                             "limit": limits["served_gap_p99"]}
    out["served_gap_max"] = {"value": worst,
                             "limit": limits["served_gap_max"]}
    return out


class _StretchEdges:
    """The harness's context with the registry read at the traced
    stretch's two edges (``serve.offer`` reads it at the window's):
    the plain decode steps' own counts — experts hit, routed pairs —
    are then of the steps the trace holds, not of the window's."""

    def __init__(self, ctx):
        self._ctx = ctx
        self.edges: dict = {}

    def __getattr__(self, name):
        return getattr(self._ctx, name)

    def start_trace(self) -> None:
        self._ctx.start_trace()
        self.edges["registry_trace_open"] = program.registry_snapshot()

    def stop_trace(self) -> None:
        self.edges["registry_trace_close"] = program.registry_snapshot()
        self._ctx.stop_trace()


def run(ctx) -> dict:
    """``ctx``: the harness's :class:`run.Context`."""
    import jax

    cfg, traffic, seconds = ctx.cfg, ctx.traffic, ctx.seconds
    program.set_telemetry(ctx.trace)
    batcher, frontend, conf = program_sarvam_mla.build_serve(
        cfg, traffic["serving"], ctx.seed, traffic["max_positions"])
    preroll = float(traffic["preroll_s"])
    requests = plan_requests(traffic, ctx.seed, preroll, seconds,
                             cfg["vocab_size"])
    state: dict = {}
    traced = _StretchEdges(ctx)

    async def scenario() -> None:
        await frontend.start()
        try:
            await serve.warm_up(frontend.port, cfg,
                                conf.prefill_chunk_pages * conf.page_size,
                                ctx.seed)
            gc.collect()
            gc.freeze()
            state.update(await serve.offer(
                traced, frontend.port, requests, preroll, seconds,
                ctx.trace))
        finally:
            await frontend.stop(drain=False)

    asyncio.run(scenario())
    records = state["records"]
    by_id = {r["id"]: r for r in requests}

    # the program's state goes before the reference comes
    del batcher, frontend
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()

    lo, hi = preroll, preroll + seconds
    win = serve.client_numbers(records, lo, hi)
    attempted = sum(r["sent"] is not None for r in records)
    failed = sum((r["status"] not in (None, 200)) or
                 (r["error"] is not None and not r["aborted"])
                 for r in records)
    if not win["gaps"]:
        raise RuntimeError("no token gap fell inside the window")
    e2e = {
        "itl_p95_ms": loadplan.pooled_percentile(win["gaps"], 95) * 1e3,
        "serve_tok_s": win["tokens_in"] / seconds,
        "setup_s": state["opened_at"] - ctx.t_start,
    }
    checks = compare(
        records, requests, cfg, traffic, ctx.seed,
        lambda: weights_sarvam_mla.generate(cfg, ctx.seed,
                                            jax.numpy.bfloat16))
    log = {
        "cell": ctx.cell, "seed": ctx.seed, "seconds": seconds,
        "requests_planned": len(requests), "attempted": attempted,
        "failed": failed,
        "finished": sum(r["finished"] for r in records),
        "aborted_at_close": sum(r["aborted"] for r in records),
        "compiles_in_window": state["compiles_in_window"],
        "window": {k: v for k, v in win.items()
                   if not isinstance(v, list)},
        "n_gaps": len(win["gaps"]), "n_ttft": len(win["ttfts"]),
        "gap_ms_percentiles": {
            str(q): loadplan.pooled_percentile(win["gaps"], q) * 1e3
            for q in serve.GAP_PERCENTILES},
        "gap_modes": serve.gap_modes(win["gaps"]),
        "gap_p50_ms_by_5s": serve.gap_median_by_slice(records, lo, hi),
        "gap_histogram_10ms": serve.gap_histogram(win["gaps"]),
        "stream_variety": stream_variety(records),
        "per_request": [
            {"id": r["id"], "due": round(r["due"], 3),
             "prompt": r["prompt_len"], "asked": by_id[r["id"]]["max_tokens"],
             "got": len(r["tokens"]), "status": r["status"],
             "late": (round(r["sent"] - r["due"], 4)
                      if r["sent"] is not None else None),
             "ttft": (round(r["times"][0] - r["due"], 4)
                      if r["times"] else None),
             "finished": r["finished"], "aborted": r["aborted"],
             "error": r["error"]} for r in records],
    }
    stretch = serve.client_numbers(
        records, lo + float(traffic["trace_at_s"]),
        lo + float(traffic["trace_at_s"]) + float(traffic["trace_s"]))
    return {
        "e2e": e2e, "checks": checks,
        "attempted": attempted, "failed": failed,
        "memory_peak_bytes": state["memory"],
        "compiles_in_window": state["compiles_in_window"],
        "log": log,
        "layers": {
            "window": win, "seconds": seconds, "records": records,
            "registry_open": state["registry_open"],
            "registry_close": state["registry_close"],
            "serving": traffic["serving"],
            **traced.edges,
            # the tokens decoded INSIDE the traced stretch and the
            # context each read (the client's clock: the stretch opens
            # trace_at_s into the window), summed: the live rows the
            # lanes' kernel had to read in the runs the trace holds
            "traced_decode_tokens": stretch["decode_tokens"],
            "traced_context_read": stretch["context_read"],
            # visible (query, key) pairs of the prompts whose first
            # token fell in the window (the attention readers' count)
            "prefill_pairs": sum(
                r["prompt_len"] * (r["prompt_len"] + 1) / 2
                for r in records
                if r["times"] and lo <= r["times"][0] < hi),
        },
    }
