"""Rehearse a run here, without the chip: the whole of ``run.execute``
at a toy size on whatever devices JAX has (the CPU), from the toy
manifest under ``tests/tiny``. Nothing it prints is a measurement.

    JAX_PLATFORMS=cpu python benchmark/tests/rehearse.py gpt2-tiny.serve-tiny
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python benchmark/tests/rehearse.py gpt2-tiny.train-tiny-fsdp4

A CPU's trace has no device plane, so ``--trace 1`` ends in "the trace
holds no device operation" here: the traced half is rehearsed by
``test_trace_reduce.py`` on the traces kept beside it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent
sys.path.insert(0, str(TESTS.parent))

import flops  # noqa: E402
import run  # noqa: E402

TINY = TESTS / "tiny"


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("workload")
    parser.add_argument("--seed", type=int, default=2**31 + 11)
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args()
    import jax

    out = run.execute(args.workload, args.seed, args.seconds,
                      bool(args.trace), root=TINY, devices=jax.devices(),
                      peaks=flops.peaks_of("TPU v5 lite"))
    print(json.dumps(out["checks"]), file=sys.stderr)
    print(json.dumps(out["line"]))


if __name__ == "__main__":
    main()
