"""The ``serve_afmoe`` job: ``jobs/serve.py``'s open loop against the
HTTP front door, for a model of the AFMoE family (``model_type:
afmoe``: window and full attention layers, each kind with a cache of
its own).

``jobs/serve.py`` builds GPT-2, ``jobs/serve_lfm2.py`` LFM2-MoE and
``jobs/serve_sarvam_mla.py`` the latent-attention family, and none may
be edited. ``serve_sarvam_mla.run`` is already the run this cell wants
— the pre-roll and the window planned as two horizons, the registry
read at the traced stretch's edges, the streams' variety — but for the
three names it takes from its own family, so :func:`run` puts this
family's in their place for the call (``program_afmoe``,
``weights_afmoe``, this file's :func:`compare` against
``reference/afmoe.py``) and copies nothing. The result has ``serve``'s
keys, so every model-agnostic reader reads it unchanged.

Two things are this family's. The compared sample must hold what the
two caches can get wrong: a prompt longer than two rings' span (the
ring wrapped more than once) and a decode that crosses a
page boundary past the ring's span (a ring page recycled under a
decoding slot); :func:`compare` counts both and adds such a request
where the draw held none. And the attention readers need the (query,
key) pairs a query really SEES by kind of layer — at most the window
in a sliding layer — which :func:`attn_pairs` counts from the client's
records.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import program_afmoe  # noqa: E402
import weights_afmoe  # noqa: E402
from jobs import serve, serve_sarvam_mla  # noqa: E402
from jobs.serve_sarvam_mla import plan_requests  # noqa: E402,F401


def ring_span(cfg: dict, serving: dict) -> int:
    """Positions of one slot's ring (``kv_pages.ring_pages`` in the
    yardstick's own arithmetic): window + chunk + one page."""
    page = serving["page_size"]
    return cfg["sliding_window"] \
        + (serving["prefill_chunk_pages"] + 1) * page


def _wraps_twice(rec: dict, span: int) -> bool:
    return rec["prompt_len"] > 2 * span


def _recycles_in_decode(rec: dict, span: int, page: int) -> bool:
    first, last = rec["prompt_len"], rec["prompt_len"] + len(rec["tokens"])
    return first >= span and first // page != (last - 1) // page


def sample(records: list[dict], seed: int, n: int, span: int,
           page: int) -> list[dict]:
    """``serve.pick_sample`` (the longest request always among them),
    and where the draw holds no twice-wrapped ring or no decode over a
    recycled page, the longest finished request that has one."""
    picked = serve.pick_sample(records, seed, n)
    done = sorted((r for r in records if r["finished"] and r["tokens"]),
                  key=lambda r: -r["prompt_len"])
    for has in (lambda r: _wraps_twice(r, span),
                lambda r: _recycles_in_decode(r, span, page)):
        if not any(has(r) for r in picked):
            picked += [r for r in done if has(r)][:1]
    return picked


def compare(records: list[dict], requests: list[dict], cfg: dict,
            traffic: dict, seed: int, make_weights) -> dict:
    """``serve.compare`` against ``reference/afmoe.py``:
    ``served_gap_max`` and ``served_gap_p99`` over every served token
    of the sampled requests, ``bad_streams`` over all; and how many of
    the compared requests wrapped their ring more than once, how many
    decoded over a recycled ring page."""
    from reference import afmoe

    by_id = {r["id"]: r for r in requests}
    bad = sum(
        rec["finished"] and (
            len(rec["tokens"]) != by_id[rec["id"]]["max_tokens"]
            or not all(0 <= t < cfg["vocab_size"] for t in rec["tokens"]))
        for rec in records)
    limits = traffic["limits"]
    span = ring_span(cfg, traffic["serving"])
    page = traffic["serving"]["page_size"]
    picked = sample(records, seed, traffic["check_requests"], span, page)
    out = {"bad_streams": {"value": int(bad),
                           "limit": limits["bad_streams"]},
           "checked_requests": len(picked),
           "checked_tokens": sum(len(r["tokens"]) for r in picked),
           "checked_prompts": [r["prompt_len"] for r in picked],
           "wrapped_rings_checked": sum(_wraps_twice(r, span)
                                        for r in picked),
           "recycling_decodes_checked": sum(
               _recycles_in_decode(r, span, page) for r in picked)}
    worst = p99 = None
    if picked:
        w = make_weights()
        gaps = np.concatenate([np.asarray(afmoe.served_gaps(
            w, by_id[rec["id"]]["prompt"], rec["tokens"], cfg,
            pad_to=traffic["max_positions"])) for rec in picked])
        worst, p99 = float(gaps.max()), float(np.percentile(gaps, 99))
        out["tokens_off_best"] = int((gaps > 0).sum())
    out["served_gap_p99"] = {"value": p99,
                             "limit": limits["served_gap_p99"]}
    out["served_gap_max"] = {"value": worst,
                             "limit": limits["served_gap_max"]}
    return out


def attn_pairs(records: list[dict], lo: float, hi: float,
               window: int) -> dict:
    """Visible (query, key) pairs in ONE layer of each kind, of the
    tokens decoded in ``[lo, hi)`` (each its context; a sliding layer
    at most ``window`` of it) and of the prompts whose first token
    fell there (``n (n + 1) / 2`` in a full layer; in a sliding one
    the first ``window`` queries see what a full layer's do, the rest
    ``window`` each)."""
    decoded = {"window": 0.0, "full": 0.0}
    prompts = {"window": 0.0, "full": 0.0}
    for rec in records:
        times, n = rec["times"], rec["prompt_len"]
        for i in range(1, len(times)):
            if lo <= times[i] < hi:
                decoded["full"] += n + i
                decoded["window"] += min(n + i, window)
        if times and lo <= times[0] < hi:
            head = min(n, window)
            prompts["full"] += n * (n + 1) / 2
            prompts["window"] += head * (head + 1) / 2 + (n - head) * window
    return {"window": decoded["window"] + prompts["window"],
            "full": decoded["full"] + prompts["full"],
            "prefill_window": prompts["window"],
            "prefill_full": prompts["full"]}


def run(ctx) -> dict:
    """``ctx``: the harness's :class:`run.Context`. The run IS
    ``serve_sarvam_mla.run`` — the same stack built from a
    ``serving:`` block, warm-up, two-horizon plan, pass of the
    generator, traced stretch's edges and result keys — with this
    family's three pieces put in its module's place for the call (as
    ``sweep_afmoe.py`` puts ``build_serve`` in ``program``'s), and the
    attention pairs by kind added to what the readers get."""
    job = serve_sarvam_mla
    theirs = job.program_sarvam_mla, job.weights_sarvam_mla, job.compare
    job.program_sarvam_mla, job.weights_sarvam_mla, job.compare = \
        program_afmoe, weights_afmoe, compare
    try:
        result = job.run(ctx)
    finally:
        job.program_sarvam_mla, job.weights_sarvam_mla, job.compare = theirs
    lo = float(ctx.traffic["preroll_s"])
    # visible (query, key) pairs by kind of layer, of the window's
    # decoded tokens and finished prompts
    result["layers"]["attn_pairs"] = attn_pairs(
        result["layers"]["records"], lo, lo + ctx.seconds,
        ctx.cfg["sliding_window"])
    return result
