"""Record the small device trace the reduction's test reads
(``tests/data/tiny_tpu.xplane.pb``): on the chip, four runs of a toy
program under a ``toy_step`` span with a sleep between them, so the
trace has busy stretches, idle gaps and a host span over each.

    python benchmark/tests/record_fixture.py <out-dir>
"""
from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import trace_reduce  # noqa: E402


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def toy(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.01
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    toy(x).block_until_ready()
    work = Path(out) / "fixture_trace"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(work), profiler_options=options)
    for _ in range(4):
        with jax.profiler.TraceAnnotation("toy_step"):
            toy(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    found = trace_reduce.find_xplane(str(work))
    shutil.copy(found, Path(out) / "tiny_tpu.xplane.pb")
    (Path(out) / "tiny_tpu.txt").write_text(trace_reduce.describe(found, 12))
    shutil.rmtree(work)


if __name__ == "__main__":
    main(sys.argv[1])
