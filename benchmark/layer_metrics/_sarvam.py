"""Shared by the latent-attention (``sarvam_mla``) readers: the
window's means an iteration of this cell is charged with. The step
this cell's p95 sits on is the MIXED program (``jit__chunk_fn`` with
the decode lanes riding: a prompt is ~18 chunks), so most of these
readers read the mixed program's runs and take what the plain steps
alone report — the program's count of routed pairs here and elsewhere
— from the whole window's registry; ``sarvam_decode_roofline`` and the
five model-agnostic readers of ``decode_fn`` read the plain program
where the traced stretch holds it. Every helper returns None where the
run has nothing to read (a configuration of another family, a program
without the series, a window without a plain step)."""
from __future__ import annotations

import math
import statistics

from _lib import registry_delta
from _subscope import seconds_by_run

import flops_sarvam_mla as fl

PAIRS = "serving_moe_pairs_total{where=%s}"
STEP_PROGRAMS = "decode_fn|chunk_fn"


def is_family(layers: dict) -> bool:
    return "kv_lora_rank" in layers.get("cfg", {})


def pairs_here_share(layers: dict):
    """Share of the routed pairs whose expert is held here: the
    program's counters over the window's plain decode steps. None
    where the program has no such series or the window no plain
    step: nothing stands in for a count that was not made."""
    if not is_family(layers):
        return None
    here = registry_delta(layers, PAIRS % "here")
    away = registry_delta(layers, PAIRS % "elsewhere")
    if here is None or away is None or not here + away:
        return None
    return here / (here + away)


def step_means(layers: dict):
    """What a mean MIXED iteration of the window holds: decoding
    sequences, their live cached tokens, the chunk's real tokens, its
    visible (query, key) pairs and the prior positions it reads, the
    routed pairs computed here and the held experts they hit (summed
    over the expert layers; the hit count by arithmetic — Poisson at
    the mean pairs an expert — since a mixed step reports no counts).
    None where any source is missing."""
    tokens = registry_delta(layers, "serving_decode_tokens_total")
    steps = registry_delta(layers, "span_seconds{name=decode_step}_count")
    chunks = registry_delta(layers, "serving_prefill_chunks_total")
    win, pairs = layers.get("window"), layers.get("prefill_pairs")
    share = pairs_here_share(layers)
    if not (share is not None and tokens and steps and chunks and pairs
            and win and win["decode_tokens"] and win["prefill_tokens"]):
        return None
    cfg = layers["cfg"]
    seqs = tokens / steps
    chunk_tokens = win["prefill_tokens"] / chunks
    n_moe = fl.layer_counts(cfg)["moe"]
    routed = (seqs + chunk_tokens) * cfg["num_experts_per_tok"] \
        * n_moe * share
    held = n_moe * cfg["num_experts"]
    return {
        "seqs": seqs,
        "live": seqs * win["context_read"] / win["decode_tokens"],
        "chunk_tokens": chunk_tokens,
        "chunk_pairs": pairs / chunks,
        "chunk_context": pairs / win["prefill_tokens"],
        "routed": routed,
        "hit": held * (1.0 - math.exp(-routed / held)),
    }


def scope_ms(layers: dict, program: str, name: str):
    """Median milliseconds under scope ``name`` over the traced runs
    of ``program`` that have any; None where none has."""
    runs = [s for s in seconds_by_run(layers, program, name) if s > 0]
    return statistics.median(runs) * 1e3 if runs else None
