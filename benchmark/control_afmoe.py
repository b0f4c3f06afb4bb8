"""Show that an AFMoE serve cell's ``correct`` can fail: ``control.py``
for the ``afmoe`` family, at the cell's own size, on the chip.

    python benchmark/control_afmoe.py \
        --workload trinity-large-preview.serve-longctx-r80 --seeds 3,5 \
        [--seconds 15]

``control.py`` reads its serve cells through ``reference/gpt2.py`` and
``weights.py`` and may not be edited. Per seed this makes one ordinary
run of the cell and then, on the sample that run checked
(``jobs/serve_afmoe.sample``: a twice-wrapped ring and a decode over a
recycled page among it), reads through the harness's own comparison:

- **program**: the served tokens' gaps under the float32 reference's
  best, the widest and the 99th percentile (what ``correct`` rests
  on);
- **control_fp8**: the reference put in the program's place and
  computed in float8 e4m3 (``reference.afmoe.fp8`` on both operands of
  every matrix product, the router's included), the nearest precision
  under the bfloat16 the configuration states: the widest gap of the
  token IT puts first;
- **altered_token**: every served token altered (``t + 1``).

``verdicts`` must read True for the program and False for the other
two, or the limit does not hold. The limit in the traffic file is set
between the program's largest reading over the seeds and the control's
smallest (PERF.md gives both).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import control  # noqa: E402
import run as harness  # noqa: E402


def serve_control(records: list[dict], requests: list[dict], cfg: dict,
                  traffic: dict, seed: int, w: dict) -> dict:
    from jobs import serve_afmoe
    from reference import afmoe as ref

    import numpy as np

    by_id = {r["id"]: r for r in requests}
    got, low, off = [], [], []
    pad = traffic["max_positions"]
    serving = traffic["serving"]
    for rec in serve_afmoe.sample(
            records, seed, traffic["check_requests"],
            serve_afmoe.ring_span(cfg, serving), serving["page_size"]):
        prompt, served = by_id[rec["id"]]["prompt"], rec["tokens"]
        got.append(ref.served_gaps(w, prompt, served, cfg, pad_to=pad))
        low.append(ref.control_gaps(w, prompt, served, cfg, ref.fp8,
                                     pad_to=pad))
        off.append(ref.served_gaps(
            w, prompt, [(t + 1) % cfg["vocab_size"] for t in served], cfg,
            pad_to=pad))
    limits = traffic["limits"]

    def held(gaps) -> dict:
        gaps = np.concatenate([np.asarray(g) for g in gaps])
        return {"served_gap_max": {"value": float(gaps.max()),
                                   "limit": limits["served_gap_max"]},
                "served_gap_p99": {"value": float(np.percentile(gaps, 99)),
                                   "limit": limits["served_gap_p99"]},
                "tokens_off_best": int((gaps > 0).sum()),
                "tokens": int(gaps.size)}

    return {"program": held(got), "control_fp8": held(low),
            "altered_token": held(off)}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=15.0)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    import weights_afmoe
    from jobs import serve_afmoe

    _, _, cfg, traffic = harness.resolve(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.execute(args.workload, seed, args.seconds, False)
        requests = serve_afmoe.plan_requests(
            traffic, seed, float(traffic["preroll_s"]), args.seconds,
            cfg["vocab_size"])
        w = weights_afmoe.generate(cfg, seed, jnp.bfloat16)
        row = serve_control(out["records"], requests, cfg, traffic, seed, w)
        row["run"] = out["line"]["compared"]
        del w
        jax.clear_caches()
        print(json.dumps({"workload": args.workload, "seed": seed, **row,
                          "verdicts": control.verdicts(row)}), flush=True)


if __name__ == "__main__":
    main()
