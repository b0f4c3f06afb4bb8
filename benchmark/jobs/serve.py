"""The ``serve`` job: one cell of open-loop traffic against the HTTP
front door, with the load generator in a process of its own.

Set-up (all of it ``setup_s``): weights from the seed, the serving
stack from the cell's ``serving:`` block, one warm-up exchange that
compiles the prefill-chunk and decode programs, then the generator
starts and the open loop runs for ``preroll_s`` so the window opens on
a batch that is already full. The window is ``--seconds`` long;
whatever streams at either edge contributes the tokens and gaps that
fall inside. At the close the generator drops what is still in flight,
the server stops, its memory is read and freed, and only then does the
reference run over a sample of the finished requests.
"""
from __future__ import annotations

import asyncio
import gc
import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import program  # noqa: E402
import weights  # noqa: E402
from loadgen import plan as loadplan  # noqa: E402

CLIENT = HERE / "loadgen" / "client.py"
# the pooled gaps' percentiles a run's log and a sweep's row both give
GAP_PERCENTILES = (50, 75, 90, 92, 95, 97.5, 98, 99)


# ---------------------------------------------------------------------
# what the client saw -> numbers
# ---------------------------------------------------------------------

def client_numbers(records: list[dict], lo: float, hi: float) -> dict:
    """Pool every token and every gap that falls in ``[lo, hi)`` (the
    client's clock, seconds since the schedule's origin) over all
    requests: nothing per request, nothing per chunk."""
    gaps, ttfts, late, live_ctx = [], [], [], 0.0
    tokens_in = decode_tokens = prefill_tokens = 0
    for rec in records:
        times = rec["times"]
        tokens_in += sum(lo <= t < hi for t in times)
        for i in range(1, len(times)):
            if lo <= times[i] < hi:
                gaps.append(times[i] - times[i - 1])
                decode_tokens += 1
                live_ctx += rec["prompt_len"] + i
        if times and lo <= times[0] < hi:
            ttfts.append(times[0] - rec["due"])
            prefill_tokens += rec["prompt_len"]
        if rec["sent"] is not None and lo <= rec["due"] < hi:
            late.append(rec["sent"] - rec["due"])
    return {"gaps": gaps, "ttfts": ttfts, "late": late,
            "tokens_in": tokens_in, "decode_tokens": decode_tokens,
            "prefill_tokens": prefill_tokens,
            # sum over decoded tokens of the context each one read
            "context_read": live_ctx}


def gap_histogram(gaps: list[float], width_ms: float = 10.0) -> list:
    """[[bin start in ms, count], ...] of the pooled gaps."""
    if not gaps:
        return []
    ms = np.asarray(gaps) * 1e3
    edges = np.arange(0.0, ms.max() + width_ms, width_ms)
    counts, _ = np.histogram(ms, edges)
    return [[float(e), int(c)] for e, c in zip(edges, counts) if c]


def gap_modes(gaps: list[float], bin_ms: float = 1.0) -> dict:
    """Where the pooled gaps' two modes part. A gap is a decode step, or
    a decode step behind one prefill chunk: two peaks of a 1 ms
    histogram (up to the 99.5th percentile, smoothed over three bins).
    The second peak is the tallest bin, four bins or more from the
    tallest, that a valley under half its own height parts from it;
    ``split_ms`` is the bottom of that valley. Gives the two peaks, the
    split, the share of gaps above it and on which side p92, p95 and p98
    fall (``tail_in_one_mode``: a p95 with both neighbours on its side
    lies inside a mode, not on the jump between them). One peak only:
    ``split_ms`` None, and the tail lies in one mode by definition."""
    ms = np.asarray(gaps, np.float64) * 1e3
    if len(ms) < 20:
        return {}
    edges = np.arange(0.0, np.percentile(ms, 99.5) + 2 * bin_ms, bin_ms)
    counts, _ = np.histogram(ms, edges)
    smooth = np.convolve(counts, np.ones(3) / 3.0, "same")
    first = int(np.argmax(smooth))
    second = valley = None
    for j in np.argsort(-smooth):
        j = int(j)
        if abs(j - first) < 4 or smooth[j] < 0.01 * smooth[first]:
            continue
        lo, hi = sorted((first, j))
        k = lo + int(np.argmin(smooth[lo:hi + 1]))
        if smooth[k] < 0.5 * smooth[j]:
            second, valley = j, k
            break
    tail = {str(q): float(np.percentile(ms, q)) for q in (92, 95, 98)}
    out = {"peak_ms": float(edges[first] + bin_ms / 2), "split_ms": None,
           "upper_share": None, "tail_ms": tail, "tail_in_one_mode": True}
    if second is None:
        return out
    split = float(edges[valley] + bin_ms / 2)
    sides = {v > split for v in tail.values()}
    out.update(
        peak_ms=float(edges[min(first, second)] + bin_ms / 2),
        upper_peak_ms=float(edges[max(first, second)] + bin_ms / 2),
        split_ms=split, upper_share=float((ms > split).mean()),
        tail_in_one_mode=len(sides) == 1)
    return out


def gap_median_by_slice(records: list[dict], lo: float, hi: float,
                        width_s: float = 5.0) -> list[float]:
    """Median gap, in ms, of each ``width_s`` slice of the window (by
    when the gap ended): tells a run that was slow throughout from one
    that was held up for a while."""
    slices: list[list[float]] = [[] for _ in range(
        max(int(np.ceil((hi - lo) / width_s)), 1))]
    for rec in records:
        times = rec["times"]
        for i in range(1, len(times)):
            if lo <= times[i] < hi:
                slices[int((times[i] - lo) // width_s)].append(
                    times[i] - times[i - 1])
    return [float(np.median(g)) * 1e3 if g else None for g in slices]


def lifetimes(records: list[dict], lo: float, hi: float) -> list[float]:
    """Seconds from due to last token of the finished requests that
    were due in ``[lo, hi)``: what a pre-roll has to cover."""
    return [r["times"][-1] - r["due"] for r in records
            if r["finished"] and r["times"] and lo <= r["due"] < hi]


# ---------------------------------------------------------------------
# correctness: served tokens against the float32 reference
# ---------------------------------------------------------------------

def pick_sample(records: list[dict], seed: int, n: int) -> list[dict]:
    """``n`` finished requests drawn from the seed, the longest always
    among them."""
    done = [r for r in records if r["finished"] and r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(int(seed) + 1).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:max(n - 1, 0)]]


def compare(records: list[dict], requests: list[dict], cfg: dict,
            traffic: dict, seed: int, make_weights) -> dict:
    """The numbers ``correct`` rests on, each beside its limit.

    - ``served_gap_max``: over every served token of the sampled
      requests, how far its logit lies below the reference's best at
      that position (0 when the served token IS the reference's pick);
    - ``bad_streams``: finished streams that did not deliver exactly
      the tokens asked for, or a token outside the vocabulary (limit
      0: an exact count)."""
    from reference import gpt2

    by_id = {r["id"]: r for r in requests}
    bad = 0
    for rec in records:
        if rec["finished"] and (
                len(rec["tokens"]) != by_id[rec["id"]]["max_tokens"]
                or not all(0 <= t < cfg["vocab_size"]
                           for t in rec["tokens"])):
            bad += 1
    limits = traffic["limits"]
    sample = pick_sample(records, seed, traffic["check_requests"])
    out = {"bad_streams": {"value": bad, "limit": limits["bad_streams"]},
           "checked_requests": len(sample),
           "checked_tokens": sum(len(r["tokens"]) for r in sample)}
    if not sample:
        out["served_gap_max"] = {"value": None,
                                 "limit": limits["served_gap_max"]}
        return out
    w = make_weights()
    worst = 0.0
    for rec in sample:
        gaps = gpt2.served_gaps(
            w, by_id[rec["id"]]["prompt"], rec["tokens"], cfg["n_head"],
            cfg["layer_norm_epsilon"], pad_to=cfg["n_positions"])
        worst = max(worst, float(gaps.max()))
    out["served_gap_max"] = {"value": worst,
                             "limit": limits["served_gap_max"]}
    return out


# ---------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------

async def _http(port: int, payload: dict) -> tuple[int, bytes]:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        body = json.dumps(payload).encode()
        writer.write(
            f"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        return int(head.split(b" ", 2)[1]), await reader.read()
    finally:
        writer.close()


async def _sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


async def warm_up(port: int, cfg: dict, chunk: int, seed: int) -> None:
    """Both programs, every shape the traffic uses: prompts of more
    than one chunk, a few decode steps."""
    rng = np.random.default_rng(int(seed) + 2)
    warm = [_http(port, {
        "prompt": rng.integers(0, cfg["vocab_size"], chunk + 40).tolist(),
        "max_tokens": 4, "stream": False}) for _ in range(2)]
    for status, body in await asyncio.gather(*warm):
        if status != 200:
            raise RuntimeError(f"warm-up answered {status}: {body[:200]!r}")


async def offer(ctx, port: int, requests: list[dict], preroll: float,
                seconds: float, trace: bool) -> dict:
    """One pass of the open loop against a running front door: start
    the generator process, let it pre-roll, hold the window open for
    ``seconds``, wait for the generator to drop what still streams.
    Returns what was read at the window's edges and the client's
    records."""
    loop = asyncio.get_running_loop()
    traffic = ctx.traffic
    plan_path = ctx.out_dir / "plan.json"
    rec_path = ctx.out_dir / "client.json"
    state: dict = {}
    t0 = time.monotonic() + float(traffic.get("lead_s", 1.5))
    open_at, close_at = t0 + preroll, t0 + preroll + seconds
    plan_path.write_text(json.dumps({
        "host": "127.0.0.1", "port": port, "t0": t0,
        "abort_at": close_at + 0.05, "requests": requests}))
    child = subprocess.Popen(
        [sys.executable, str(CLIENT), str(plan_path), str(rec_path)],
        stdout=subprocess.DEVNULL)
    try:
        await _sleep_until(open_at)
        state["opened_at"] = time.monotonic()
        state["registry_open"] = program.registry_snapshot()
        compiles_open = ctx.compiles.count
        if trace:
            await _sleep_until(open_at + float(traffic["trace_at_s"]))
            await loop.run_in_executor(None, ctx.start_trace)
            await asyncio.sleep(float(traffic["trace_s"]))
            await loop.run_in_executor(None, ctx.stop_trace)
        await _sleep_until(close_at)
        state["registry_close"] = program.registry_snapshot()
        state["compiles_in_window"] = ctx.compiles.count - compiles_open
        state["memory"] = ctx.memory_peak()
        rc = await loop.run_in_executor(None, child.wait, 60)
        if rc != 0:
            raise RuntimeError(f"load generator exited {rc}")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    by_id = {r["id"]: r for r in requests}
    state["records"] = json.loads(rec_path.read_text())["records"]
    for rec in state["records"]:
        rec["prompt_len"] = len(by_id[rec["id"]]["prompt"])
    return state


def run(ctx) -> dict:
    """``ctx``: the harness's :class:`run.Context`."""
    import jax

    cfg, traffic, seconds = ctx.cfg, ctx.traffic, ctx.seconds
    out_dir = ctx.out_dir
    program.set_telemetry(ctx.trace)
    batcher, frontend, conf = program.build_serve(
        cfg, traffic["serving"], ctx.seed)
    preroll = float(traffic["preroll_s"])
    requests = loadplan.make_requests(traffic, ctx.seed, preroll + seconds,
                                      cfg["vocab_size"])
    state: dict = {}

    async def scenario() -> None:
        await frontend.start()
        try:
            await warm_up(frontend.port, cfg,
                          conf.prefill_chunk_pages * conf.page_size,
                          ctx.seed)
            gc.collect()
            gc.freeze()
            state.update(await offer(ctx, frontend.port, requests, preroll,
                                     seconds, ctx.trace))
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
    win = client_numbers(records, lo, hi)
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
        lambda: weights.generate(cfg, ctx.seed, jax.numpy.bfloat16))
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
            for q in GAP_PERCENTILES},
        "gap_modes": gap_modes(win["gaps"]),
        "gap_p50_ms_by_5s": gap_median_by_slice(records, lo, hi),
        "gap_histogram_10ms": gap_histogram(win["gaps"]),
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
