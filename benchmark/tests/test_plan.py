"""The traffic generator: fixed multisets, seeded order, pooled tails."""
import json
import math
from pathlib import Path

import numpy as np
import pytest

from loadgen import plan

BENCH = Path(plan.__file__).parent.parent
TRAFFIC = BENCH / "traffic"
CHAT = json.loads((TRAFFIC / "serve-chat-r80.json").read_text())


def lengths(requests):
    return [(len(r["prompt"]), r["max_tokens"]) for r in requests]


# 65 s: the horizon of the cell as PR 23 pitched it (72 requests); the
# second is a run of the cell as it stands: pre-roll + run_seconds, some
# hundreds of requests
@pytest.mark.parametrize("horizon", [65.0, CHAT["preroll_s"] + 40.0])
def test_two_seeds_offer_the_same_multiset_in_another_order(horizon):
    a = plan.make_requests(CHAT, 3, horizon, 50257)
    b = plan.make_requests(CHAT, 2**31 + 12345, horizon, 50257)
    assert len(a) == len(b) == round(CHAT["rate"] * horizon)
    assert sorted(lengths(a)) == sorted(lengths(b))
    assert lengths(a) != lengths(b)
    gaps = lambda rs: sorted(
        [round(y["due"] - x["due"], 9) for x, y in zip(rs, rs[1:])]
        + [round(horizon - rs[-1]["due"], 9)])
    assert gaps(a) == gaps(b)               # the same arrival gaps too
    assert a[0]["prompt"] != b[0]["prompt"] or lengths(a)[0] != lengths(b)[0]


def test_a_window_of_the_chat_cell_holds_some_hundreds_of_requests():
    # a pooled p95 wants some hundreds of requests in the window: at
    # 0.8 x the knee of PR 25's server that is 250-350 in 40 s
    assert 250 <= round(CHAT["rate"] * 40.0) <= 350


def serve_mixes():
    """Every serve mix a cell of ``BENCHMARK.json`` uses, and the one
    kept ready beside them."""
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = {w["traffic"] for w in manifest["workloads"]}
    names.add("serve-chat-sat")
    out = []
    for name in sorted(names):
        mix = json.loads((TRAFFIC / f"{name}.json").read_text())
        if mix["job"] == "serve":
            out.append(pytest.param(name, mix, id=name))
    return out


@pytest.mark.parametrize("name, mix", serve_mixes())
def test_a_serve_mixs_rate_is_its_share_of_the_knee(name, mix):
    """``rate`` = ``share`` x ``knee_rps`` to two significant figures,
    with the commit the knee was swept at beside it: when a PR moves
    the server, the next reader sees what the rate rested on."""
    want = mix["share"] * mix["knee_rps"]
    digits = 1 - math.floor(math.log10(want))
    assert mix["rate"] == round(want, digits)
    assert isinstance(mix["knee_commit"], str) and mix["knee_commit"]
    assert str(mix["knee_rps"]) in mix["rate_is"]
    if name.endswith("-r80"):
        assert 0.7 <= mix["share"] <= 0.9
    if name.endswith("-sat"):
        assert mix["share"] > 1.0


def test_the_same_seed_gives_the_same_requests():
    assert plan.make_requests(CHAT, 77, 40.0, 50257) == \
        plan.make_requests(CHAT, 77, 40.0, 50257)


def test_lengths_keep_to_the_file_and_the_models_positions():
    reqs = plan.make_requests(CHAT, 5, 200.0, 50257)
    p, o = CHAT["prompt"], CHAT["output"]
    for plen, olen in lengths(reqs):
        assert p["min"] <= plen <= p["max"]
        assert 1 <= olen <= o["max"]
        assert plen + olen <= CHAT["max_positions"]
    assert abs(np.median([l[0] for l in lengths(reqs)]) - p["median"]) < 8
    assert reqs[0]["due"] == 0.0
    assert reqs[-1]["due"] < 200.0
    assert all(0 <= t < 50257 for r in reqs for t in r["prompt"])


def test_percentile_is_pooled_over_all_gaps():
    # two requests with very different gap counts: the pooled p95 is
    # the p95 of ALL gaps, not a mean or median of per-request tails
    many, few = [0.1] * 95, [0.5] * 5
    assert plan.pooled_percentile(many + few, 50) == 0.1
    pooled = plan.pooled_percentile(many + few, 95)
    per_request = np.mean([plan.pooled_percentile(many, 95),
                           plan.pooled_percentile(few, 95)])
    assert 0.1 <= pooled <= 0.5 and pooled != per_request
    assert plan.pooled_percentile(many + few, 99) == 0.5


def test_the_kept_saturated_mix_is_the_chat_mix_at_another_rate():
    # serve-chat-sat.json is in no cell yet (PERF.md, open questions):
    # it stays ready, the same mix through the same one ordering path
    sat = json.loads((Path(plan.__file__).parent.parent / "traffic"
                      / "serve-chat-sat.json").read_text())
    differ = {k for k in set(sat) | set(CHAT) if sat.get(k) != CHAT.get(k)}
    assert differ == {"rate", "rate_is", "share", "status"}
    reqs = plan.make_requests(sat, 9, 65.0, 50257)
    assert len(reqs) == round(sat["rate"] * 65.0)
    assert sorted(lengths(reqs)) == sorted(lengths(
        plan.make_requests(sat, 10, 65.0, 50257)))


def test_a_refused_request_is_recorded_and_the_generator_goes_on():
    """Above the knee the front door answers 429 once its queue is
    full: the generator records the status and the body's start, and
    ends normally (the sweep's 10 requests/s rung crashed it once)."""
    import asyncio
    import time

    from loadgen import client

    async def scenario():
        async def refuse(reader, writer):
            await reader.readuntil(b"\r\n\r\n")
            body = b'{"error": "queue full (64 waiting)"}'
            writer.write(b"HTTP/1.1 429 Too Many Requests\r\n"
                         b"Retry-After: 1\r\nContent-Length: "
                         + str(len(body)).encode() + b"\r\n\r\n" + body)
            await writer.drain()
            writer.close()

        server = await asyncio.start_server(refuse, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        plan_ = {"host": "127.0.0.1", "port": port,
                 "t0": time.monotonic(), "abort_at": time.monotonic() + 1.0,
                 "requests": [{"id": i, "due": 0.05 * i, "prompt": [1, 2],
                               "max_tokens": 4} for i in range(3)]}
        try:
            return await client.run(plan_)
        finally:
            server.close()
            await server.wait_closed()

    records = asyncio.run(scenario())
    assert [r["status"] for r in records] == [429, 429, 429]
    assert all("queue full" in r["error"] for r in records)
    assert not any(r["finished"] or r["aborted"] for r in records)
