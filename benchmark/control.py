"""Show that ``correct`` can fail: the control and the planted faults,
at a cell's own size, on the chip.

    python benchmark/control.py --workload <cell> --seeds 3,5,8 [--seconds 15]

The benchmark's own runs never run this. It reads, per seed, the numbers
a cell is compared on — once for the program (serve cells; a train
cell's program readings come with every ordinary run) and once for

- the **control**: the reference put in the program's place and computed
  in float8 e4m3 (``reference.gpt2.fp8``), the nearest precision under
  the bfloat16 the configurations state;
- a train cell's **faults**, planted in the reference put in the
  program's place: half of the batch left out (the mean taken over the
  rest), and the exchange between chips left out (each chip's gradient
  is of its own rows only: one chip's share of the batch). A step that
  returns its state unchanged reads exactly 1 on ``delta_gap`` by the
  measure's definition and needs no run;
- a serve cell's **fault**: every token altered where it is produced
  (``t + 1``, as ``tests/test_run.py`` plants it under the batcher: the
  engine keeps its own tokens, the reader gets the altered ones), read
  on the sample the run checked.

Every reading goes through the harness's own comparison: each row holds
the numbers beside the cell's limits, and ``verdicts`` says what
``run.verdict`` makes of each set — False for the control and for every
fault, or the limits do not hold. A limit in a traffic file is set
between the largest program reading and the smallest of these (PERF.md
gives the readings). ``tests/`` keeps the same comparisons at a size a
test run can hold.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as harness  # noqa: E402


def train_readings(cfg: dict, traffic: dict, seed: int, devices,
                   shares=(("half_batch", 2), ("no_exchange", None))) -> dict:
    """The float32 reference, then the control and each fault compared
    against it exactly as a run compares the program."""
    from jobs import train
    from reference import gpt2

    recipe = traffic["recipe"]
    hyper = train.hyper_of(recipe)
    batch = int(recipe["loader"]["batch_size"])
    pool = train.make_batches(seed, int(traffic["n_batches"]), batch,
                              cfg["n_positions"], cfg["vocab_size"])
    first = pool[:train.N_FOLLOWED]
    rows = int(traffic.get("reference_block_rows", 4))
    want = train.reference_readings(cfg, seed, first, hyper, devices,
                                    block_rows=rows)
    out = {"reference": {"losses": want["losses"]}}
    got = train.reference_readings(cfg, seed, first, hyper, devices,
                                   quant=gpt2.fp8, block_rows=rows)
    out["control_fp8"] = train.compare(got, want, traffic["limits"])
    for name, divisor in shares:
        divisor = divisor or max(len(devices), 2)
        got = train.reference_readings(
            cfg, seed, first[:, :batch // divisor], hyper, devices,
            block_rows=rows)
        out[name] = train.compare(got, want, traffic["limits"])
    return out


def serve_control(records: list[dict], requests: list[dict], cfg: dict,
                  traffic: dict, seed: int, w: dict) -> dict:
    """On the sample a run checks: the program's widest gap, the
    widest gap of the token the float8 forward puts first, and the
    widest gap once every served token is altered, each beside the
    cell's limit."""
    from jobs import serve
    from reference import gpt2

    by_id = {r["id"]: r for r in requests}
    program_gap = control_gap = altered_gap = 0.0
    flips = tokens = 0
    eps, pad = cfg["layer_norm_epsilon"], cfg["n_positions"]
    for rec in serve.pick_sample(records, seed, traffic["check_requests"]):
        prompt = by_id[rec["id"]]["prompt"]
        args = (w, prompt, rec["tokens"], cfg["n_head"])
        got = gpt2.served_gaps(*args, eps, pad_to=pad)
        low = gpt2.control_gaps(*args, gpt2.fp8, eps, pad_to=pad)
        off = gpt2.served_gaps(
            w, prompt, [(t + 1) % cfg["vocab_size"] for t in rec["tokens"]],
            cfg["n_head"], eps, pad_to=pad)
        program_gap = max(program_gap, float(got.max()))
        control_gap = max(control_gap, float(low.max()))
        altered_gap = max(altered_gap, float(off.max()))
        flips += int((low > 0).sum())
        tokens += len(rec["tokens"])
    limit = traffic["limits"]["served_gap_max"]
    held = lambda value: {"served_gap_max": {"value": value, "limit": limit}}
    return {"program": held(program_gap),
            "control_fp8": {**held(control_gap),
                            "tokens_off_best": flips, "tokens": tokens},
            "altered_token": held(altered_gap)}


def verdicts(row: dict) -> dict:
    """``run.verdict`` on each set of compared numbers in a row."""
    return {name: harness.verdict(checks) for name, checks in row.items()
            if isinstance(checks, dict) and any(
                isinstance(c, dict) and "limit" in c
                for c in checks.values())}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=15.0)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    import weights
    from loadgen import plan as loadplan

    _, cell, cfg, traffic = harness.resolve(args.workload)
    devices = harness.find_devices(cell["chips"])[:cell["chips"]]
    for seed in (int(s) for s in args.seeds.split(",")):
        if traffic["job"] == "train":
            row = train_readings(cfg, traffic, seed, devices)
        else:
            out = harness.execute(args.workload, seed, args.seconds, False)
            requests = loadplan.make_requests(
                traffic, seed, float(traffic["preroll_s"]) + args.seconds,
                cfg["vocab_size"])
            w = weights.generate(cfg, seed, jnp.bfloat16)
            row = serve_control(out["records"], requests, cfg, traffic,
                                seed, w)
            row["run"] = out["line"]["compared"]
            del w
            jax.clear_caches()
        print(json.dumps({"workload": args.workload, "seed": seed, **row,
                          "verdicts": verdicts(row)}), flush=True)


if __name__ == "__main__":
    main()
