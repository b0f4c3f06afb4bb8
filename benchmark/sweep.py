"""Find a serve cell's knee: one ladder of rates in one process, warm.

    python benchmark/sweep.py --workload gpt2-xl.serve-chat-r80 \
        --rates 3,4,5,6,7,8,9,10,12 --seconds 30 --seed 5

The server is built and warmed once; each rate then gets its own pass of
the open loop (the cell's mix, pre-roll and all), after which whatever
still runs is dropped and the engine drains. One line per rate: tokens
offered and delivered per second in the window, the front door's queue
at the window's edges, occupancy, preemptions, refusals, how late the
generator sent (``gen_late_p99_ms``: a generator that falls behind is a
knee of the benchmark's, not the server's), how long a request lived,
and the pooled gap percentiles with the histogram and its two modes
(``jobs/serve.py::gap_modes``) — enough to read off the knee (the
highest rate at which delivered keeps up with offered and the queue
does not grow) and to see where a percentile sits between the gap's
modes. ``<out>/sweep.json`` keeps the rows with a 1 ms histogram each.
The rate written into a traffic file comes from here, once (``rate`` =
``share`` x ``knee_rps``, README.md); a run never searches.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

sys.path.insert(0, str(HERE / "layer_metrics"))

import run as harness  # noqa: E402
from _lib import registry_delta  # noqa: E402


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--seed", type=int, default=5)
    parser.add_argument("--root", default=str(harness.ROOT))
    parser.add_argument("--preroll", type=float,
                        help="seconds of pre-roll (default: the mix's)")
    parser.add_argument("--out", help="directory for sweep.json "
                        "(default: <root>/.bench_out/sweep)")
    parser.add_argument("--allow-cpu", action="store_true",
                        help="rehearsal only: nothing printed is a rate")
    args = parser.parse_args()

    import jax

    from jobs import serve
    from loadgen import plan as loadplan
    import program

    root = Path(args.root)
    _, cell, cfg, traffic = harness.resolve(args.workload, root)
    devices = jax.devices() if args.allow_cpu \
        else harness.find_devices(cell["chips"])
    out_dir = Path(args.out) if args.out else root / ".bench_out" / "sweep"
    out_dir.mkdir(parents=True, exist_ok=True)
    ctx = harness.Context(
        cell=args.workload, cfg=cfg, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=False, chips=cell["chips"],
        out_dir=out_dir, t_start=harness.T_START,
        compiles=harness.CompileCount(), devices=devices[:cell["chips"]])
    program.set_telemetry(True)     # occupancy comes from the registry
    batcher, frontend, conf = program.build_serve(
        cfg, traffic["serving"], args.seed)
    preroll = float(traffic["preroll_s"]) if args.preroll is None \
        else args.preroll
    rows = []

    async def ladder() -> None:
        await frontend.start()
        try:
            await serve.warm_up(frontend.port, cfg,
                                conf.prefill_chunk_pages * conf.page_size,
                                args.seed)
            gc.collect()
            for rate in (float(r) for r in args.rates.split(",")):
                mix = {**traffic, "rate": rate}
                requests = loadplan.make_requests(
                    mix, args.seed, preroll + args.seconds,
                    cfg["vocab_size"])
                queue = {}

                async def watch_queue():
                    await asyncio.sleep(float(traffic.get("lead_s", 1.5))
                                        + preroll)
                    queue["open"] = batcher.queue_depth
                    await asyncio.sleep(args.seconds - 0.2)
                    queue["close"] = batcher.queue_depth

                watcher = asyncio.create_task(watch_queue())
                state = await serve.offer(
                    dataclasses.replace(ctx, traffic=mix), frontend.port,
                    requests, preroll, args.seconds, False)
                await watcher
                while batcher.has_work:      # dropped streams drain
                    await asyncio.sleep(0.2)
                lo, hi = preroll, preroll + args.seconds
                win = serve.client_numbers(state["records"], lo, hi)
                by_id = {r["id"]: r for r in requests}
                offered = sum(by_id[r["id"]]["max_tokens"]
                              for r in state["records"]
                              if lo <= r["due"] < hi) / args.seconds
                steps = registry_delta(
                    state, "span_seconds{name=decode_step}_count")
                toks = registry_delta(state, "serving_decode_tokens_total")
                pct = {str(q): round(loadplan.pooled_percentile(
                    win["gaps"], q) * 1e3, 2)
                    for q in serve.GAP_PERCENTILES} \
                    if win["gaps"] else {}
                lived = serve.lifetimes(state["records"], lo, hi)
                row = {
                    "rate": rate, "requests": len(requests),
                    "offered_tok_s": round(offered, 1),
                    "delivered_tok_s": round(
                        win["tokens_in"] / args.seconds, 1),
                    "queue_open": queue.get("open"),
                    "queue_close": queue.get("close"),
                    "occupancy": round(toks / steps, 2) if steps else None,
                    "decode_steps_s": round(steps / args.seconds, 2),
                    "preemptions": registry_delta(
                        state, "serving_preemptions_total") or 0,
                    "refused": sum(r["status"] not in (None, 200)
                                   for r in state["records"]),
                    "gen_late_p99_ms": round(loadplan.pooled_percentile(
                        win["late"], 99) * 1e3, 3) if win["late"] else None,
                    "lifetime_s": {str(q): round(loadplan.pooled_percentile(
                        lived, q), 3) for q in (50, 90)} if lived else {},
                    "ttft_p50_ms": round(loadplan.pooled_percentile(
                        win["ttfts"], 50) * 1e3, 1) if win["ttfts"] else None,
                    "n_gaps": len(win["gaps"]), "gap_ms": pct,
                    "gap_modes": serve.gap_modes(win["gaps"]),
                    "gap_histogram_10ms": serve.gap_histogram(win["gaps"]),
                }
                print(json.dumps(row), flush=True)
                rows.append({**row, "gap_histogram_1ms":
                             serve.gap_histogram(win["gaps"], 1.0)})
                (out_dir / "sweep.json").write_text(
                    json.dumps(rows, indent=1))
        finally:
            await frontend.stop(drain=False)

    asyncio.run(ladder())


if __name__ == "__main__":
    main()
