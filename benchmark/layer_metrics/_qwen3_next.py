"""Shared by the Qwen3-Next (``model_type: qwen3_next``) readers: the
window's means a MIXED iteration of this cell is charged with, and the
slot state's bytes the program counted. The program's counters give
the routed pairs here and elsewhere (``serving_moe_pairs_total``,
``_sarvam.py``'s reading) and the bytes of slot state its steps read
and wrote (``serving_slot_state_bytes_total{kind, part}``: host
arithmetic where a step is packed, the live lanes' rows and the
chunk's slot's); the job gives the full layer's visible pairs of the
window's prompts (``layers["prefill_pairs"]``) and the context the
decoded tokens read. Every helper returns None where the run has
nothing to read: a configuration of another family, a program without
the series."""
from __future__ import annotations

import math

from _lib import registry_delta
from _sarvam import PAIRS

import flops_qwen3_next as fl

STATE = "serving_slot_state_bytes_total"
STEPS = "span_seconds{name=decode_step}_count"


def is_family(layers: dict) -> bool:
    return layers.get("cfg", {}).get("model_type") == "qwen3_next"


def _edges(layers: dict, stretch: bool) -> dict:
    """``layers`` with the registry's edges of the traced stretch in
    place of the window's where ``stretch`` and the job read them."""
    if stretch and "registry_trace_close" in layers:
        return {**layers, "registry_open": layers["registry_trace_open"],
                "registry_close": layers["registry_trace_close"]}
    return layers


def state_bytes(layers: dict, part: str, kinds=("delta", "conv"),
                stretch: bool = False):
    """Growth of the slot state's bytes of ``part`` (``lanes`` or
    ``chunk``), summed over ``kinds``; None where the program has no
    such series."""
    edges = _edges(layers, stretch)
    keys = [key for key in edges.get("registry_close") or {}
            if key.startswith(STATE) and "{part=%s}" % part in key
            and any("{kind=%s}" % k in key for k in kinds)]
    if not is_family(layers) or not keys:
        return None
    return sum(registry_delta(edges, key) for key in keys)


def decode_steps(layers: dict, stretch: bool = False):
    return registry_delta(_edges(layers, stretch), STEPS)


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
    sequences and the cached rows their full-layer reads need, the
    chunk's real tokens and the prior rows it reads, the routed pairs
    computed here and the held experts they hit (summed over the
    layers; by arithmetic — Poisson at the mean pairs an expert —
    since a mixed step reports no counts), and the slot state's bytes
    (the lanes' a step, the chunk's a chunk). None where any source is
    missing."""
    tokens = registry_delta(layers, "serving_decode_tokens_total")
    steps = decode_steps(layers)
    chunks = registry_delta(layers, "serving_prefill_chunks_total")
    win, pairs = layers.get("window"), layers.get("prefill_pairs")
    share = pairs_here_share(layers)
    lanes, chunk = state_bytes(layers, "lanes"), state_bytes(layers, "chunk")
    if not (share is not None and tokens and steps and chunks and pairs
            and win and win["decode_tokens"] and win["prefill_tokens"]
            and lanes is not None and chunk is not None):
        return None
    cfg = layers["cfg"]
    seqs = tokens / steps
    chunk_tokens = win["prefill_tokens"] / chunks
    n_moe = fl.layer_counts(cfg)["moe"]
    routed = (seqs + chunk_tokens) * cfg["num_experts_per_tok"] \
        * n_moe * share
    held = n_moe * cfg["num_experts"]
    return {
        "seqs": seqs, "chunk_tokens": chunk_tokens,
        "live": seqs * win["context_read"] / win["decode_tokens"],
        "chunk_pairs": pairs / chunks,
        "chunk_rows": pairs / win["prefill_tokens"],
        "routed": routed,
        "hit": held * (1.0 - math.exp(-routed / held)),
        "state": lanes / steps + chunk / chunks,
    }
