"""The ``serve_lfm2`` job: ``jobs/serve.py``'s open loop against the
HTTP front door, for a model of the LFM2-MoE family.

``jobs/serve.py`` builds GPT-2 (``program.build_serve``,
``weights.py``, ``reference/gpt2.py``) and may not be edited, so this
file states the same run with this family's three pieces in their
place — ``program_lfm2.build_serve``, ``weights_lfm2.generate``,
``reference/lfm2.py`` — and takes everything else from ``serve`` by
import: the warm-up, the load generator's pass (``offer``), the
client-side numbers, the gap's modes, the sample that is checked.
The result has ``serve``'s keys, so every model-agnostic reader reads
it unchanged.
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
import program_lfm2  # noqa: E402
import weights_lfm2  # noqa: E402
from jobs import serve  # noqa: E402
from loadgen import plan as loadplan  # noqa: E402


def compare(records: list[dict], requests: list[dict], cfg: dict,
            traffic: dict, seed: int, make_weights) -> dict:
    """``serve.compare`` against ``reference/lfm2.py``:
    ``served_gap_max`` and ``served_gap_p99`` over every served token
    of the sampled requests (the longest always among them),
    ``bad_streams`` over all."""
    from reference import lfm2

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
        gaps = np.concatenate([np.asarray(lfm2.served_gaps(
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


def stream_variety(records: list[dict]) -> dict:
    """Do served streams differ from context to context? (Weights whose
    spreads drag every context to one token would pass every other
    check.) Distinct tokens over all served, and the commonest's
    share."""
    tokens = [t for r in records for t in r["tokens"]]
    if not tokens:
        return {}
    values, counts = np.unique(tokens, return_counts=True)
    return {"served_tokens": len(tokens), "distinct": int(len(values)),
            "commonest_share": float(counts.max() / len(tokens))}


def run(ctx) -> dict:
    """``ctx``: the harness's :class:`run.Context`."""
    import jax

    cfg, traffic, seconds = ctx.cfg, ctx.traffic, ctx.seconds
    program.set_telemetry(ctx.trace)
    batcher, frontend, conf = program_lfm2.build_serve(
        cfg, traffic["serving"], ctx.seed, traffic["max_positions"])
    preroll = float(traffic["preroll_s"])
    requests = loadplan.make_requests(traffic, ctx.seed, preroll + seconds,
                                      cfg["vocab_size"])
    state: dict = {}

    async def scenario() -> None:
        await frontend.start()
        try:
            await serve.warm_up(frontend.port, cfg,
                                conf.prefill_chunk_pages * conf.page_size,
                                ctx.seed)
            gc.collect()
            gc.freeze()
            state.update(await serve.offer(
                ctx, frontend.port, requests, preroll, seconds, ctx.trace))
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
        lambda: weights_lfm2.generate(cfg, ctx.seed, jax.numpy.bfloat16))
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
        },
    }
