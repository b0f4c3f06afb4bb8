"""The load generator: a process of its own that never imports JAX.

``python client.py <plan.json> <out.json>``. The plan holds the
server's address, the schedule's origin ``t0`` and the instant
``abort_at`` at which whatever is still streaming is dropped (both on
``time.monotonic()``, which every process of one Linux machine shares),
and the requests with their due times. Each request is sent when due,
whatever happened to the others (an open loop), streamed over
``POST /v1/completions``, and every token is stamped on THIS process's
clock as its SSE event arrives — so thirty streaming clients share no
interpreter lock with the engine's pump. All times written out are
seconds since ``t0``.
"""
from __future__ import annotations

import asyncio
import gc
import json
import sys
import time


async def one_request(plan: dict, req: dict, rec: dict) -> None:
    t0 = plan["t0"]
    delay = t0 + req["due"] - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    rec["sent"] = time.monotonic() - t0
    reader, writer = await asyncio.open_connection(plan["host"], plan["port"])
    try:
        body = json.dumps({"prompt": req["prompt"],
                           "max_tokens": req["max_tokens"],
                           "stream": True}).encode()
        writer.write(
            f"POST /v1/completions HTTP/1.1\r\nHost: bench\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        rec["status"] = int(head.split(b" ", 2)[1])
        if rec["status"] != 200:
            rec["error"] = (await reader.read())[:200].decode("utf-8", "replace")
            return
        while True:
            line = await reader.readline()
            now = time.monotonic() - t0
            if not line:
                rec["error"] = "stream closed without [DONE]"
                return
            line = line.strip()
            if line == b"data: [DONE]":
                rec["finished"] = True
                return
            if line.startswith(b"data: "):
                choice = json.loads(line[6:])["choices"][0]
                for tok in choice["token_ids"]:
                    rec["times"].append(now)
                    rec["tokens"].append(tok)
                if choice.get("finish_reason") == "error":
                    rec["error"] = "engine error mid-stream"
    finally:
        writer.close()


async def run(plan: dict) -> list[dict]:
    records = [{"id": r["id"], "due": r["due"], "sent": None,
                "status": None, "times": [], "tokens": [],
                "finished": False, "error": None, "aborted": False}
               for r in plan["requests"]]
    tasks = [asyncio.create_task(one_request(plan, req, rec))
             for req, rec in zip(plan["requests"], records)]
    await asyncio.sleep(max(plan["abort_at"] - time.monotonic(), 0))
    for task, rec in zip(tasks, records):
        if not task.done():
            rec["aborted"] = True
            task.cancel()
    for task, rec in zip(tasks, records):
        try:
            await task
        except asyncio.CancelledError:
            pass
        except (OSError, asyncio.IncompleteReadError, ValueError) as exc:
            rec["error"] = f"{type(exc).__name__}: {exc}"
    return records


def main(argv: list[str]) -> int:
    plan_path, out_path = argv
    with open(plan_path) as fh:
        plan = json.load(fh)
    gc.collect()
    gc.freeze()
    gc.disable()
    records = asyncio.run(run(plan))
    with open(out_path, "w") as fh:
        json.dump({"t0": plan["t0"], "records": records}, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
