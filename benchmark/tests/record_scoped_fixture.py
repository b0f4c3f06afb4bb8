"""Record the small scoped device trace that ``test_xplane_scopes.py``
reads (``tests/data/scoped_tpu.xplane.pb``): on the chip, three runs of
a toy train step (a scan of checkpointed blocks under ``attn_core`` /
``mlp``, a ``loss``, an ``optimizer``, differentiated, so the trace
holds forward, backward and recomputed ops) and three of a toy decode
program (``attn_core`` with a ``kv_write`` nested in it, and a
transposed copy under no scope), each under a host span with a sleep
between them. The module names end in ``step_fn`` / ``decode_fn`` as
the program's own do.

    python benchmark/tests/record_scoped_fixture.py <out-dir>
"""
from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import trace_reduce  # noqa: E402
import xplane_scopes  # noqa: E402


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp

    def block(x, w):
        with jax.named_scope("attn_core"):
            s = jnp.tanh(x @ w)
        with jax.named_scope("mlp"):
            return x + jax.nn.gelu(s @ w) * 0.01

    def toy_step_fn(x, ws):
        def loss_of(ws):
            h, _ = jax.lax.scan(
                lambda h, w: (jax.checkpoint(block)(h, w), None), x, ws)
            with jax.named_scope("loss"):
                return jnp.mean(jnp.square(h.astype(jnp.float32)))

        loss, grads = jax.value_and_grad(loss_of)(ws)
        with jax.named_scope("optimizer"):
            ws = ws - 0.01 * grads
        return loss, ws

    def toy_decode_fn(x, cache, w, pos):
        with jax.named_scope("attn_core"):
            with jax.named_scope("kv_write"):
                cache = jax.lax.dynamic_update_slice(
                    cache, x[:1], (pos, 0))
            x = jnp.tanh((x @ cache.T) @ cache) @ w
        return x, jnp.transpose(cache).copy()        # no scope

    step, decode = jax.jit(toy_step_fn), jax.jit(toy_decode_fn)
    x = jnp.ones((512, 512), jnp.bfloat16)
    ws = jnp.full((3, 512, 512), 0.01, jnp.bfloat16)
    cache = jnp.zeros((1024, 512), jnp.bfloat16)
    jax.block_until_ready(step(x, ws))
    jax.block_until_ready(decode(x, cache, ws[0], 3))
    work = Path(out) / "fixture_trace"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(work), profiler_options=options)
    for i in range(3):
        with jax.profiler.TraceAnnotation("train_step"):
            jax.block_until_ready(step(x, ws))
        time.sleep(0.002)
        with jax.profiler.TraceAnnotation("decode_step"):
            jax.block_until_ready(decode(x, cache, ws[0], i))
        time.sleep(0.002)
    jax.profiler.stop_trace()
    found = trace_reduce.find_xplane(str(work))
    shutil.copy(found, Path(out) / "scoped_tpu.xplane.pb")
    (Path(out) / "scoped_tpu.txt").write_text(
        xplane_scopes.report(xplane_scopes.load(Path(found))))
    shutil.rmtree(work)


if __name__ == "__main__":
    main(sys.argv[1])
