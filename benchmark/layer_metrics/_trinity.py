"""Shared by the AFMoE (``model_type: afmoe``; Trinity) readers: the
window's means a MIXED iteration of this cell is charged with (the
step its p95 sits on: a prompt is ~36 chunks), a kind of attention
layer at a time. The program's counters give the routed pairs here
and elsewhere (``serving_moe_pairs_total``, ``_sarvam.py``'s reading)
and the rows a layer of each kind had to read for the decoding slots
(``serving_kv_rows_read_total{kind}``); the job gives the (query, key)
pairs each kind really saw (``layers["attn_pairs"]``). Every helper
returns None where the run has nothing to read: a configuration of
another family, a program without the series."""
from __future__ import annotations

import math

from _lib import registry_delta
from _sarvam import PAIRS

import flops_afmoe as fl

ROWS = "serving_kv_rows_read_total{kind=%s}"


def is_family(layers: dict) -> bool:
    return layers.get("cfg", {}).get("model_type") == "afmoe"


def pairs_here_share(layers: dict):
    """Share of the routed pairs whose expert is held here, by the
    program's counters over the window's plain decode steps."""
    if not is_family(layers):
        return None
    here = registry_delta(layers, PAIRS % "here")
    away = registry_delta(layers, PAIRS % "elsewhere")
    if here is None or away is None or not here + away:
        return None
    return here / (here + away)


def step_means(layers: dict):
    """What a mean MIXED iteration of the window holds: decoding
    sequences, the rows their reads need in a layer of each kind, the
    chunk's real tokens, its visible (query, key) pairs and the prior
    rows it reads by kind, the routed pairs computed here and the held
    experts they hit (summed over the expert layers; by arithmetic —
    Poisson at the mean pairs an expert — since a mixed step reports
    no counts). None where any source is missing."""
    tokens = registry_delta(layers, "serving_decode_tokens_total")
    steps = registry_delta(layers, "span_seconds{name=decode_step}_count")
    chunks = registry_delta(layers, "serving_prefill_chunks_total")
    rows = {k: registry_delta(layers, ROWS % k) for k in ("window", "full")}
    win, pairs = layers.get("window"), layers.get("attn_pairs")
    share = pairs_here_share(layers)
    if not (share is not None and tokens and steps and chunks and pairs
            and win and win["prefill_tokens"] and all(rows.values())):
        return None
    cfg = layers["cfg"]
    seqs = tokens / steps
    chunk_tokens = win["prefill_tokens"] / chunks
    n_moe = fl.layer_counts(cfg)["moe"]
    routed = (seqs + chunk_tokens) * cfg["num_experts_per_tok"] \
        * n_moe * share
    held = n_moe * cfg["num_experts"]
    chunk = {k: pairs["prefill_" + k] / chunks for k in ("window", "full")}
    return {
        "seqs": seqs, "chunk_tokens": chunk_tokens,
        # the lanes': rows a step's reads need, a layer of the kind
        "live": {k: rows[k] / steps for k in rows},
        # the chunk's: visible pairs, and the prior rows it reads (a
        # row is read once for all of a chunk's queries that see it)
        "chunk_pairs": chunk,
        "chunk_rows": {k: chunk[k] / chunk_tokens for k in chunk},
        "routed": routed,
        "hit": held * (1.0 - math.exp(-routed / held)),
    }
