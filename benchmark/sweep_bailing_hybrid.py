"""``sweep.py``'s ladder of rates for a cell of the Bailing hybrid
family.

    python benchmark/sweep_bailing_hybrid.py \
        --workload ling-3.0-flash.serve-longout-r80 \
        --rates 2,2.5,3,3.5,4 --seconds 30 --seed 5 --preroll 60

As ``sweep_qwen3_next.py``: ``sweep.py`` builds its server through
``program.build_serve`` and may not be edited; this puts the family's
own ``build_serve`` in that one place and runs ``sweep.main`` as it
stands: the same rungs, rows and ``sweep.json``. Each rung's row is
preceded by a line with the requests seated at its window's two edges
(``jobs/serve_bailing_hybrid.occupancy``): whether the pre-roll let
occupancy level at that rate. A rung wants to be longer than a
request's life (``--seconds`` 90 or more for this cell's outputs).
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import program  # noqa: E402
import program_bailing_hybrid  # noqa: E402
import run as harness  # noqa: E402
import sweep  # noqa: E402


def main() -> None:
    def build_serve(cfg: dict, serving_block: dict, seed: int):
        # the MLA layer's tables' width comes from the mix: found again
        # from the cell, since sweep hands over no more
        name = sys.argv[sys.argv.index("--workload") + 1]
        root = Path(sys.argv[sys.argv.index("--root") + 1]) \
            if "--root" in sys.argv else harness.ROOT
        traffic = harness.resolve(name, root)[3]
        return program_bailing_hybrid.build_serve(
            cfg, serving_block, seed, traffic["max_positions"])

    from jobs import serve, serve_bailing_hybrid

    offer = serve.offer

    async def offer_and_count(ctx, port, requests, preroll, seconds, trace):
        state = await offer(ctx, port, requests, preroll, seconds, trace)
        seated = [serve_bailing_hybrid.occupancy(state["records"], at)
                  for at in (preroll, preroll + seconds)]
        print(json.dumps({"rate": ctx.traffic["rate"],
                          "occupancy_at_window": seated}), flush=True)
        return state

    program.build_serve = build_serve
    serve.offer = offer_and_count
    sweep.main()


if __name__ == "__main__":
    main()
