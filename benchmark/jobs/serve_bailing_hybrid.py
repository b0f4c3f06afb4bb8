"""The ``serve_bailing_hybrid`` job: ``jobs/serve.py``'s open loop
against the HTTP front door, for a model of the Bailing hybrid family
(``model_type: bailing_hybrid``: Kimi-delta-attention layers with a
float32 state a slot, beside head-gated latent-attention layers with a
paged latent pool).

As ``jobs/serve_qwen3_next.py``: ``serve_sarvam_mla.run`` is already
the run this cell wants — the stack built from a ``serving:`` block,
warm-up, the pre-roll and the window planned as two horizons, the
registry read at the traced stretch's edges, the streams' variety —
but for the three names it takes from its own family, so :func:`run`
puts this family's in their place for the call
(``program_bailing_hybrid``, ``weights_bailing_hybrid``, this file's
:func:`compare` against ``reference/bailing_hybrid.py``) and copies
nothing. The result has ``serve``'s keys, so every model-agnostic
reader reads it unchanged; the log adds how many requests were seated
at the window's two edges (:func:`occupancy`: a pre-roll long enough
leaves the first within a tenth of the second).

The compared sample is ``serve.pick_sample``'s (the longest request
always among it): every served token's logit, from the prefill's
first and the decode steps' after it, against the reference's full
forward — KDA token by token over prompt and output, up to 16,640
positions — so a state carried wrongly across a chunk or a decode step
shows in every token after it. :func:`compare` also counts the prompt
chunks the compared requests' states crossed.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import program_bailing_hybrid  # noqa: E402
import weights_bailing_hybrid  # noqa: E402
from jobs import serve, serve_sarvam_mla  # noqa: E402
from jobs.serve_sarvam_mla import plan_requests  # noqa: E402,F401


def compare(records: list[dict], requests: list[dict], cfg: dict,
            traffic: dict, seed: int, make_weights) -> dict:
    """``serve.compare`` against ``reference/bailing_hybrid.py``:
    ``served_gap_max`` and ``served_gap_p99`` over every served token
    of the sampled requests, ``bad_streams`` over all; and the chunk
    boundaries the compared prompts' states crossed."""
    from reference import bailing_hybrid

    by_id = {r["id"]: r for r in requests}
    bad = sum(
        rec["finished"] and (
            len(rec["tokens"]) != by_id[rec["id"]]["max_tokens"]
            or not all(0 <= t < cfg["vocab_size"] for t in rec["tokens"]))
        for rec in records)
    limits = traffic["limits"]
    serving = traffic["serving"]
    chunk = serving["page_size"] * serving["prefill_chunk_pages"]
    picked = serve.pick_sample(records, seed, traffic["check_requests"])
    out = {"bad_streams": {"value": int(bad),
                           "limit": limits["bad_streams"]},
           "checked_requests": len(picked),
           "checked_tokens": sum(len(r["tokens"]) for r in picked),
           "checked_prompts": [r["prompt_len"] for r in picked],
           "chunk_boundaries_checked": sum(
               -(-r["prompt_len"] // chunk) - 1 for r in picked)}
    worst = p99 = None
    if picked:
        w = make_weights()
        gaps = np.concatenate([np.asarray(bailing_hybrid.served_gaps(
            w, by_id[rec["id"]]["prompt"], rec["tokens"], cfg,
            pad_to=traffic["max_positions"])) for rec in picked])
        worst, p99 = float(gaps.max()), float(np.percentile(gaps, 99))
        out["tokens_off_best"] = int((gaps > 0).sum())
    out["served_gap_p99"] = {"value": p99,
                             "limit": limits["served_gap_p99"]}
    out["served_gap_max"] = {"value": worst,
                             "limit": limits["served_gap_max"]}
    return out


def occupancy(records: list[dict], at: float) -> int:
    """Requests seated at ``at`` seconds (the client's clock): past
    their first token, and before their last or still streaming when
    the run closed."""
    return sum(bool(r["times"]) and r["times"][0] <= at
               and (at <= r["times"][-1] or not r["finished"])
               for r in records)


def run(ctx) -> dict:
    """``ctx``: the harness's :class:`run.Context`. The run IS
    ``serve_sarvam_mla.run`` with this family's three pieces put in its
    module's place for the call (as ``jobs/serve_qwen3_next.py`` does),
    and the window's edges' occupancy in its log."""
    job = serve_sarvam_mla
    theirs = job.program_sarvam_mla, job.weights_sarvam_mla, job.compare
    job.program_sarvam_mla, job.weights_sarvam_mla, job.compare = \
        program_bailing_hybrid, weights_bailing_hybrid, compare
    try:
        result = job.run(ctx)
    finally:
        job.program_sarvam_mla, job.weights_sarvam_mla, job.compare = theirs
    lo = float(ctx.traffic["preroll_s"])
    records = result["layers"]["records"]
    result["log"]["occupancy_at_window"] = [
        occupancy(records, lo), occupancy(records, lo + ctx.seconds)]
    return result
