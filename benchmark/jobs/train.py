"""The ``train`` job: the GPT recipe's compiled step, timed.

Set-up builds ONE object — the recipe's step with its state, as a
user's YAML builds it — and drives it from the seed through its first
three steps on three batches whose rows all differ; the readings the
reference is compared on (each loss, the first gradient as the
optimizer got it, the parameters' change) are taken there. The window
then keeps calling that same object for ``--seconds``: all tokens of
all steps that complete, over the whole elapsed time, the last step
closed by ``block_until_ready``. Afterwards memory is read, the state
is freed, and the float32 reference follows the same three steps.
"""
from __future__ import annotations

import gc
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import program  # noqa: E402
import weights  # noqa: E402

N_FOLLOWED = 3


def hyper_of(recipe: dict) -> dict:
    """The optimizer the cell's recipe block states, as the reference
    wants it."""
    optim, sched = recipe["optim"], recipe["scheduler"]
    b1, b2 = (float(x) for x in str(optim["betas"]).split(","))
    return {"lr": float(optim["lr"]), "b1": b1, "b2": b2,
            "adam_eps": float(optim.get("eps", 1e-8)),
            "weight_decay": float(optim["weight_decay"]),
            "clip": float(recipe["clip"]),
            "warmup": int(sched["warmup"]),
            "initial_multiplier": float(
                sched.get("initial_multiplier", 4e-2))}


def make_batches(seed: int, n: int, batch: int, seq: int,
                 vocab: int) -> np.ndarray:
    """``n`` batches of ``batch`` rows of ``seq + 1`` token ids, every
    row its own draw from the seed."""
    rng = np.random.default_rng(int(seed))
    return rng.integers(0, vocab, (n, batch, seq + 1), dtype=np.int32)


def worst_leaf_gap(got: dict, want: dict, skip=()) -> tuple[float, str]:
    """The widest gap between the program's norm and the reference's
    over the leaves, each measured against the reference's norm of
    that leaf or of the median leaf, whichever is larger (some leaves
    are all but zero)."""
    median = float(np.median([want[k] for k in want]))
    worst, where = 0.0, ""
    for k in want:
        if k in skip:
            continue
        gap = abs(got[k] - want[k]) / max(want[k], median)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def compare(got: dict, want: dict, limits: dict) -> dict:
    """Program readings against reference readings, each number beside
    its limit. Leaves whose reference gradient is under a thousandth
    of the median leaf's move under Adam by round-off alone and are
    left out of the change (a rule on the gradient, not on names)."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], want["losses"]))
    grad_gap, grad_leaf = worst_leaf_gap(got["grad_norms"],
                                         want["grad_norms"])
    median = float(np.median(list(want["grad_norms"].values())))
    dead = [k for k, v in want["grad_norms"].items() if v < 1e-3 * median]
    delta_gap, delta_leaf = worst_leaf_gap(
        got["delta_norms"], want["delta_norms"], skip=dead)
    return {
        "loss_gap": {"value": loss_gap, "limit": limits["loss_gap"]},
        "grad_gap": {"value": grad_gap, "limit": limits["grad_gap"],
                     "leaf": grad_leaf},
        "delta_gap": {"value": delta_gap, "limit": limits["delta_gap"],
                      "leaf": delta_leaf},
        "leaves_left_out_of_delta": dead,
    }


def flat_shardings(cfg: dict, devices):
    """Where the reference's flat leaves live: on one device as they
    are, on several each leaf split along its first axis that divides
    (memory only; the arithmetic is the same plain float32)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("r",))
    n = len(devices)

    def spec(shape):
        for axis, size in enumerate(shape):
            if n > 1 and size % n == 0:
                return P(*([None] * axis + ["r"]))
        return P()

    shardings = {k: NamedSharding(mesh, spec(s))
                 for k, s in weights.shapes(cfg).items()}
    rows = NamedSharding(mesh, P("r") if n > 1 else P())
    return shardings, rows, NamedSharding(mesh, P())


def reference_readings(cfg: dict, seed: int, batches: np.ndarray,
                       hyper: dict, devices, quant=None,
                       block_rows: int = 4) -> dict:
    """The float32 reference over the same first steps (``quant`` is
    the control's hook, see ``reference/gpt2.py``)."""
    import jax
    import jax.numpy as jnp

    from reference import gpt2

    shardings, rows, whole = flat_shardings(cfg, devices)
    w0 = weights.generate(cfg, seed, jnp.float32, out_shardings=shardings)
    step_rows = block_rows * len(devices)
    prepared = []
    for tokens in batches:
        blocks = []
        for r in range(0, tokens.shape[0], step_rows):
            blk = tokens[r:r + step_rows]
            put = rows if len(blk) % len(devices) == 0 else whole
            blocks.append((jax.device_put(blk[:, :-1], put),
                           jax.device_put(blk[:, 1:], put)))
        prepared.append(blocks)
    with jax.default_matmul_precision("highest"):
        return gpt2.follow(w0, prepared, cfg["n_head"], hyper,
                           eps=cfg["layer_norm_epsilon"], quant=quant)


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    cfg, traffic, seconds = ctx.cfg, ctx.traffic, ctx.seconds
    recipe = traffic["recipe"]
    batch, seq = int(recipe["loader"]["batch_size"]), cfg["n_positions"]
    hyper = hyper_of(recipe)
    pool = make_batches(ctx.seed, int(traffic["n_batches"]), batch, seq,
                        cfg["vocab_size"])

    state, step, shard, mesh = program.build_train(cfg, recipe, ctx.seed)
    devices = list(mesh.devices.flat)
    from reference.gpt2 import leaf_norms

    host = lambda tree: {k: float(v) for k, v in tree.items()}

    got = {"losses": []}
    with mesh:
        for i in range(N_FOLLOWED):
            state, metrics = step(state, shard(pool[i]))
            got["losses"].append(float(metrics["loss"]))
            if i == 0:
                # Adam's first moment after one step is (1 - b1) g
                mu = host(leaf_norms(program.first_moment(state)))
                got["grad_norms"] = {k: v / (1.0 - hyper["b1"])
                                     for k, v in mu.items()}
        p0 = weights.generate(
            cfg, ctx.seed, jnp.float32, arrange=program.arrange,
            out_shardings=jax.tree.map(lambda x: x.sharding, state.params))
        delta = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
            state.params, p0)
        got["delta_norms"] = host(leaf_norms(program.flatten(delta)))
        del p0, delta
        memory_program = ctx.program_bytes(step, state, shard(pool[0]))

        def take(state, n):
            return step(state, shard(pool[(N_FOLLOWED + n) % len(pool)]))

        jax.block_until_ready(state)
        gc.collect()
        gc.freeze()
        compiles_open = ctx.compiles.count
        t_open = time.monotonic()
        setup_s = t_open - ctx.t_start
        n, pending, traced_steps, paused = 0, None, 0, 0.0
        trace_at = int(traffic.get("trace_at_step", 5))
        while True:
            if ctx.trace and n == trace_at:
                jax.block_until_ready(state)
                t_pause = time.monotonic()
                ctx.start_trace()
                paused += time.monotonic() - t_pause
                for _ in range(int(traffic["trace_steps"])):
                    state, metrics = take(state, n)
                    n += 1
                    traced_steps += 1
                jax.block_until_ready(state)
                t_pause = time.monotonic()
                ctx.stop_trace()
                paused += time.monotonic() - t_pause
                pending = None
            state, metrics = take(state, n)
            n += 1
            # one step stays in flight: the device never waits for the
            # host, the host never runs more than a step ahead
            if pending is not None:
                jax.block_until_ready(pending)
            pending = metrics["loss"]
            if time.monotonic() - t_open >= seconds:
                break
        jax.block_until_ready(state)
        elapsed = time.monotonic() - t_open
    compiles_in_window = ctx.compiles.count - compiles_open
    last_loss = float(metrics["loss"])
    memory = max(ctx.memory_peak(), memory_program)

    del state, step, metrics, pending
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()

    want = reference_readings(
        cfg, ctx.seed, pool[:N_FOLLOWED], hyper, devices,
        block_rows=int(traffic.get("reference_block_rows", 4)))
    checks = compare(got, want, traffic["limits"])
    tokens = n * batch * seq
    log = {"cell": ctx.cell, "seed": ctx.seed, "steps": n,
           "elapsed_s": elapsed, "tokens": tokens,
           "program": got, "reference": want, "last_loss": last_loss,
           "compiles_in_window": compiles_in_window,
           "memory_program_bytes": memory_program}
    return {
        "e2e": {"train_tok_s": tokens / elapsed, "setup_s": setup_s},
        "checks": checks,
        "attempted": n, "failed": 0,
        "memory_peak_bytes": memory,
        "compiles_in_window": compiles_in_window,
        "log": log,
        # a traced run's whole-step share leaves out the seconds in which
        # the profiler started and stopped (no step could run in them)
        "layers": {"steps": n, "elapsed": elapsed - paused, "tokens": tokens,
                   "batch": batch, "seq": seq,
                   "traced_steps": traced_steps,
                   "n_devices": len(devices)},
    }
