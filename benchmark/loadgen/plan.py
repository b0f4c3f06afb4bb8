"""Turn a traffic file into the requests of one run. No JAX here.

A traffic file states distributions; a run needs numbers. Every run of
a cell offers the SAME work: the multiset of (prompt, output) lengths
and the multiset of arrival gaps are fixed by the file (quantiles of
its distributions), and ``--seed`` only orders them and draws the token
ids. So two seeds differ in which request meets which, never in how
much is asked of the server.

(The distributions are the ones ``serving/loadgen/workload.py`` draws
from — log-normal lengths, exponential gaps; that module samples afresh
per run and lives in the program, so the yardstick keeps its own.)
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(median: float, sigma: float, lo: int, hi: int,
                        n: int) -> list[int]:
    """``n`` lengths at the mid-quantiles of a log-normal, clipped."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(max(round(median * math.exp(sigma * z)), lo), hi)))
    return out


def exponential_quantile_gaps(n: int, total: float) -> list[float]:
    """``n`` gaps at the mid-quantiles of an exponential, scaled to sum
    to ``total`` seconds: Poisson-shaped arrivals with a fixed count."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total / sum(raw)
    return [g * scale for g in raw]


def length_multiset(spec: dict, n: int) -> list[tuple[int, int]]:
    """The fixed (prompt, output) pairs: each marginal by quantiles,
    paired by a permutation fixed in the traffic file (``pair_seed``),
    output trimmed so prompt + output fits the model's positions."""
    p, o = spec["prompt"], spec["output"]
    prompts = lognormal_quantiles(p["median"], p["sigma"], p["min"],
                                  p["max"], n)
    outputs = lognormal_quantiles(o["median"], o["sigma"], o["min"],
                                  o["max"], n)
    order = np.random.default_rng(spec.get("pair_seed", 0)).permutation(n)
    limit = spec["max_positions"]
    return [(pl, min(outputs[j], limit - pl))
            for pl, j in zip(prompts, order)]


def make_requests(spec: dict, seed: int, horizon_s: float,
                  vocab: int) -> list[dict]:
    """The run's requests, due times from 0 to ``horizon_s``: a fixed
    count ``round(rate * horizon)``, the fixed multisets in the order
    ``seed`` draws, token ids drawn from ``seed``."""
    n = max(int(round(spec["rate"] * horizon_s)), 1)
    pairs = length_multiset(spec, n)
    gaps = exponential_quantile_gaps(n, horizon_s)
    rng = np.random.default_rng(int(seed))
    pair_order = rng.permutation(n)
    gap_order = rng.permutation(n)
    # each arrival opens its gap: the first is due at once, and the
    # last gap runs out the horizon
    due, t = [], 0.0
    for j in gap_order:
        due.append(t)
        t += gaps[j]
    requests = []
    for i, j in enumerate(pair_order):
        plen, olen = pairs[j]
        requests.append({
            "id": i, "due": due[i],
            "prompt": rng.integers(0, vocab, plen).tolist(),
            "max_tokens": int(olen),
        })
    return requests


def pooled_percentile(values, q: float) -> float:
    """Percentile ``q`` (0-100) of all values pooled, by linear
    interpolation between order statistics (numpy's default)."""
    if len(values) == 0:
        raise ValueError("no values to take a percentile of")
    return float(np.percentile(np.asarray(values, np.float64), q))
