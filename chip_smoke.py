"""Prove on demand that the program runs on the chip.

``python chip_smoke.py`` (one TPU chip, one process) drives the two
main paths through the entry points a user calls, at the full
published width of GPT-2 small (``GPTConfig()`` defaults, bf16,
random weights from ``--seed``):

- **train**: ``examples/lm/gpt/gpt.py::main`` with the recipe's own
  ``Config`` from ``gpt.yml``, model block set to GPT-2 small, batch
  16, a few steps, then its KV-cache ``generate`` sample; plus a
  ``workers="process"`` loader whose children must stay off the chip;
- **train_long**: the same recipe at the ``gpt-long.yml`` widths
  (S=8192, rope, GQA, chunked head) — the pallas flash kernel must be
  IN the lowered step;
- **resnet**: three ``make_step`` steps of ResNet-50 at 224², bf16;
- **serve**: ``ServingConfig.make`` -> ``frontend.make`` -> real HTTP
  requests to ``/v1/completions`` on localhost, once per decode
  backend (``xla``, then ``pallas``), tokens compared across backends
  and against dense ``jit_generate``.

``python chip_smoke.py --chips 4`` runs ONLY the cross-chip paths and
their one-device twins: the recipe on a ``dp:2,fsdp:2`` mesh, a ZeRO-2
step on ``dp:4``, and ``tp: 4`` serving.

Every phase prints one JSON line; any failed check raises, so the
exit code is non-zero and the last line is never written. The last
line of stdout is exactly ``{"ok": true, "device": {...}}`` with the
device as JAX reports it. Without a TPU the script fails at once. It
prints no utilisation and assumes no peak rate: it is a proof that
the system starts, not a benchmark.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from torchbooster_tpu.config import BaseConfig, CommsConfig, ServingConfig

REPO = Path(__file__).resolve().parent
GPT_RECIPE = REPO / "examples" / "lm" / "gpt"


@dataclass
class Blocks(BaseConfig):
    """chip_smoke.yml: the ``serving:`` and ``comms:`` blocks a user's
    config would carry beside the recipe's own."""

    serving: ServingConfig
    comms: CommsConfig


GPT2_SMALL = dict(vocab=50257, n_layers=12, d_model=768, n_heads=12,
                  seq_len=1024)
SERVE_PROMPT_LENS = (32, 96, 200, 330, 450, 580, 700)
SERVE_NEW_TOKENS = (48, 32, 64, 40, 32, 56, 48)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ---------------------------------------------------------------------
# observation: compile events, memory, the recipe's compiled step
# ---------------------------------------------------------------------

class CompileLog:
    """Backend-compile seconds and persistent-cache traffic, from
    JAX's own monitoring events, reset per phase."""

    def __init__(self):
        import jax

        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def reset(self) -> None:
        self.seconds, self.compiles = 0.0, 0
        self.cache_requests, self.cache_hits = 0, 0

    def _secs(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phase:
    """Time one phase and print its line; a raised check leaves no
    line and ends the run."""

    def __init__(self, name: str, log: CompileLog):
        self.name, self.log, self.fields = name, log, {}

    def __enter__(self):
        self.log.reset()
        self.t0 = time.perf_counter()
        return self.fields

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        import jax

        gc.collect()
        stats = jax.devices()[0].memory_stats() or {}
        emit(self.name, ok=True,
             wall_s=round(time.perf_counter() - self.t0, 2),
             compile_s=round(self.log.seconds, 2),
             compiles=self.log.compiles,
             cache_requests=self.log.cache_requests,
             cache_hits=self.log.cache_hits,
             peak_bytes_in_use=stats.get("peak_bytes_in_use"),
             bytes_in_use=stats.get("bytes_in_use"),
             **self.fields)
        return False


class StepProbe:
    """See what the recipe's compiled train step sees, without a knob
    in the recipe: while active, ``utils.make_step`` returns the real
    jitted step wrapped to record each loss, the backend compiles each
    call caused, the latest state and batch, and (``lower=True``) the
    lowered text of the first call."""

    def __init__(self, log: CompileLog, lower: bool = False):
        self.log, self.lower, self.text = log, lower, None
        self.step = self.state = self.batch = None
        self.losses: list = []
        self.compiles: list[int] = []

    def __enter__(self):
        import torchbooster_tpu.utils as utils

        self._utils, self._make_step = utils, utils.make_step

        def make_step(*args, **kwargs):
            self.step = self._make_step(*args, **kwargs)

            def probed(state, batch):
                if self.lower and self.text is None:
                    self.text = self.step.lower(state, batch).as_text()
                before = self.log.compiles
                state, metrics = self.step(state, batch)
                self.compiles.append(self.log.compiles - before)
                self.losses.append(metrics["loss"])
                self.state, self.batch = state, batch
                return state, metrics

            return probed

        utils.make_step = make_step
        return self

    def __exit__(self, *exc):
        self._utils.make_step = self._make_step
        return False

    def host_losses(self) -> list[float]:
        return [float(np.asarray(x)) for x in self.losses]

    def check_compiled_once(self) -> int:
        """Backend compiles, not ``_cache_size()``: the second call
        adds a jit-cache entry for the step's own output shardings
        without compiling anything."""
        check(self.compiles[0] == 1 and not any(self.compiles[1:]),
              f"step compiles per call: {self.compiles}")
        return sum(self.compiles)


def devices_of(tree) -> set:
    """Every device holding a shard of any array leaf of ``tree``."""
    import jax

    return {shard.device for leaf in jax.tree.leaves(tree)
            if hasattr(leaf, "addressable_shards")
            for shard in leaf.addressable_shards}


def check(cond: bool, message: str) -> None:
    if not cond:
        raise AssertionError(message)


def check_losses(losses: list[float], vocab: int | None = None) -> None:
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss: {losses}")
    if vocab is not None:
        check(abs(losses[0] - math.log(vocab)) < 0.5,
              f"first loss {losses[0]:.3f} is not near ln({vocab}) = "
              f"{math.log(vocab):.3f}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")


# ---------------------------------------------------------------------
# the GPT recipe, driven as ``python gpt.py`` drives it
# ---------------------------------------------------------------------

def load_recipe():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke_gpt_recipe", GPT_RECIPE / "gpt.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def recipe_conf(recipe, yml: str, *, seed: int, model: dict, batch: int,
                n_iter: int, sample_tokens: int = 0):
    """The recipe's own Config from its own YAML, cut to a few steps
    on synthetic tokens; ``model`` overrides fields of its model
    block."""
    conf = recipe.Config.load(GPT_RECIPE / yml)
    for key, value in model.items():
        setattr(conf.model, key, value)
    conf.seed = seed
    conf.n_iter = conf.scheduler.n_iter = n_iter
    conf.scheduler.warmup = 2
    conf.log_every, conf.save_every, conf.eval_batches = 1, 0, 0
    conf.sample_tokens = sample_tokens
    conf.loader.batch_size = batch
    conf.dataset.name = "synthetic_lm"
    conf.dataset.n_examples = max(4 * batch * n_iter, 64)
    return conf


def run_recipe(recipe, conf, log: CompileLog, *, lower: bool = False):
    """``utils.boost()`` + ``dist.launch(main, ...)`` exactly as the
    recipe's ``__main__`` does, from the recipe's directory."""
    import torchbooster_tpu.distributed as dist
    import torchbooster_tpu.utils as utils

    cwd = os.getcwd()
    os.chdir(GPT_RECIPE)
    try:
        utils.boost()
        with StepProbe(log, lower=lower) as probe:
            results = dist.launch(
                recipe.main, conf.env.n_devices, conf.env.n_machine,
                conf.env.machine_rank, conf.env.dist_url, args=(conf,))
    finally:
        os.chdir(cwd)
    probe.check_compiled_once()
    return results, probe


class BackendProbe:
    """A dataset whose items say which JAX backend the loader child
    that built them sees (1 = cpu)."""

    def __len__(self) -> int:
        return 16

    def __getitem__(self, index: int) -> np.ndarray:
        import jax

        return np.int32(jax.default_backend() == "cpu")


def loader_children_stay_off_chip() -> int:
    """``workers="process"`` children re-import the dataset's modules
    and may use JAX; the chip belongs to this process, so they must
    land on the CPU (data/pipeline.py ``_worker_init``)."""
    from torchbooster_tpu.data import DataLoader

    loader = DataLoader(BackendProbe(), batch_size=4, shuffle=False,
                        num_workers=2, workers="process")
    try:
        seen = np.concatenate([np.asarray(b).ravel() for b in loader])
    finally:
        loader.close()
    check(len(seen) == 16 and bool(seen.all()),
          f"a loader child did not report the cpu backend: {seen}")
    return len(seen)


def phase_train(fields: dict, log: CompileLog, platform: str, *, seed: int = 0,
                model: dict = GPT2_SMALL, batch: int = 16, n_iter: int = 8,
                sample_tokens: int = 16) -> None:
    import jax

    recipe = load_recipe()
    conf = recipe_conf(recipe, "gpt.yml", seed=seed, model=model,
                       batch=batch, n_iter=n_iter,
                       sample_tokens=sample_tokens)
    results, probe = run_recipe(recipe, conf, log)
    losses = probe.host_losses()
    check(len(losses) == n_iter, f"{len(losses)} steps, want {n_iter}")
    check_losses(losses, vocab=model["vocab"])
    platforms = {d.platform for d in devices_of(probe.state.params)}
    check(platforms == {platform},
          f"params live on {platforms}, want {{{platform!r}}}")
    sample = results["sample"]
    check(len(sample) == 8 + sample_tokens
          and all(0 <= t < model["vocab"] for t in sample),
          f"bad KV-cache sample: {sample}")
    n_params = sum(x.size for x in jax.tree.leaves(probe.state.params))
    fields.update(losses=[round(x, 4) for x in losses],
                  n_params=int(n_params), step_compiles=sum(probe.compiles),
                  sample_len=len(sample),
                  loader_child_items=loader_children_stay_off_chip())


def phase_train_long(fields: dict, log: CompileLog, platform: str, *, seed: int = 0,
                     model: dict | None = None, batch: int = 2,
                     n_iter: int = 2, expect_kernel: bool = True) -> None:
    from torchbooster_tpu.ops.attention import flash_auto_engaged

    recipe = load_recipe()
    conf = recipe_conf(recipe, "gpt-long.yml", seed=seed,
                       model=model or {}, batch=batch, n_iter=n_iter)
    # the one chip: no sp axis to shard the sequence over
    conf.env.mesh, conf.env.distributed = "dp", False
    seq_len = conf.model.seq_len
    if expect_kernel:
        # the r3 failure: this dispatch silently took the reference
        # path on the chip
        check(flash_auto_engaged(seq_len),
              f"flash_auto_engaged({seq_len}) is False on {platform}")
    _, probe = run_recipe(recipe, conf, log, lower=True)
    losses = probe.host_losses()
    check_losses(losses, vocab=conf.model.vocab)
    kernel = "tpu_custom_call" in probe.text
    if expect_kernel:
        check(kernel, "no tpu_custom_call in the lowered S=%d step: the "
                      "flash kernel is not on the path" % seq_len)
    fields.update(losses=[round(x, 4) for x in losses], seq_len=seq_len,
                  batch=batch, flash_auto_engaged=flash_auto_engaged(seq_len),
                  tpu_custom_call=kernel, step_compiles=sum(probe.compiles))


# ---------------------------------------------------------------------
# ResNet-50: the BASELINE.json metric's step
# ---------------------------------------------------------------------

def phase_resnet(fields: dict, log: CompileLog, platform: str, *, seed: int = 0,
                 batch: int = 256, image: int = 224, depth: int = 50,
                 n_iter: int = 3) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    import torchbooster_tpu.utils as utils
    from torchbooster_tpu.models.resnet import ResNet
    from torchbooster_tpu.ops.losses import cross_entropy

    utils.boost()
    key = jax.random.PRNGKey(seed)
    params = ResNet.init(key, depth=depth, num_classes=1000,
                         stem="imagenet")

    def loss_fn(params, data, rng):
        del rng
        return cross_entropy(ResNet.apply(params, data["images"]),
                             data["labels"]), {}

    tx = optax.sgd(1e-3, momentum=0.9)
    state = utils.TrainState.create(params, tx, rng=seed)
    with StepProbe(log) as probe:
        step = utils.make_step(loss_fn, tx, compute_dtype=jnp.bfloat16)
    k_img, k_lab = jax.random.split(key)
    data = {"images": jax.random.normal(
                k_img, (batch, image, image, 3), jnp.bfloat16),
            "labels": jax.random.randint(k_lab, (batch,), 0, 1000)}
    for _ in range(n_iter):          # one fixed batch: it must be learnt
        state, _ = step(state, data)
    losses = probe.host_losses()
    check_losses(losses)
    step_compiles = probe.check_compiled_once()
    platforms = {d.platform for d in devices_of(state.params)}
    check(platforms == {platform}, f"params live on {platforms}")
    fields.update(losses=[round(x, 4) for x in losses], batch=batch,
                  image=image, step_compiles=step_compiles)


# ---------------------------------------------------------------------
# serving: YAML block -> engine -> HTTP front door -> real requests
# ---------------------------------------------------------------------

def serving_conf(**overrides) -> ServingConfig:
    conf = Blocks.load(REPO / "chip_smoke.yml").serving
    for key, value in overrides.items():
        setattr(conf, key, value)
    return conf


def decisive_params(cfg, seed: int):
    """Random GPT weights with the tied embedding scaled up, the
    tier-1 parity tests' trick: wider argmax margins, so rounding
    rarely flips a greedy pick."""
    import jax

    from torchbooster_tpu.models.gpt import GPT

    params = GPT.init(jax.random.PRNGKey(seed), cfg)
    return {**params, "wte": {"table": params["wte"]["table"] * 4.0}}


def make_prompts(seed: int, vocab: int, lens) -> list[list[int]]:
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(0, vocab, n)] for n in lens]


def reference_tokens(params, cfg, prompts, new_tokens) -> list[list[int]]:
    """Dense greedy ``jit_generate`` per request, in the engine's
    compute dtype."""
    import jax
    import jax.numpy as jnp

    from torchbooster_tpu.models.gpt import jit_generate

    out = []
    for prompt, n_new in zip(prompts, new_tokens):
        fn = jit_generate(cfg, n_new=n_new, temperature=0.0)
        ids = fn(params, jnp.asarray(prompt, jnp.int32)[None],
                 jax.random.PRNGKey(0))
        out.append([int(t) for t in np.asarray(ids)[0, len(prompt):]])
    return out


async def http(port: int, method: str, path: str,
               payload: dict | None = None) -> tuple[int, bytes]:
    """One HTTP/1.1 exchange with the front door; returns (status,
    whole body). The server closes the connection after answering."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        body = b"" if payload is None else json.dumps(payload).encode()
        writer.write(
            f"{method} {path} HTTP/1.1\r\nHost: chip-smoke\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        head = await reader.readuntil(b"\r\n\r\n")
        return int(head.split(b" ", 2)[1]), await reader.read()
    finally:
        writer.close()


async def complete(port: int, prompt: list[int], n_new: int,
                   stream: bool) -> list[int]:
    status, body = await http(
        port, "POST", "/v1/completions",
        {"prompt": prompt, "max_tokens": n_new, "stream": stream})
    check(status == 200, f"/v1/completions answered {status}: {body[:300]}")
    if not stream:
        return json.loads(body)["choices"][0]["token_ids"]
    tokens, done = [], False
    for line in body.split(b"\n"):
        line = line.strip()
        if line == b"data: [DONE]":
            done = True
        elif line.startswith(b"data: "):
            tokens += json.loads(line[6:])["choices"][0]["token_ids"]
    check(done, "SSE stream ended without [DONE]")
    return tokens


def serve_over_http(conf, params, cfg, prompts, new_tokens) -> dict:
    """Build the engine from the YAML block, start the front door on
    an ephemeral localhost port, send every request (all in flight
    together, every other one streamed), scrape /metrics and /healthz,
    stop cleanly."""
    batcher = conf.make(params, cfg)
    frontend = conf.frontend.make(batcher)

    async def scenario():
        await frontend.start()
        try:
            tokens = await asyncio.wait_for(asyncio.gather(*(
                complete(frontend.port, prompt, n_new, stream=i % 2 == 0)
                for i, (prompt, n_new)
                in enumerate(zip(prompts, new_tokens)))), 600)
            health = await http(frontend.port, "GET", "/healthz")
            prom = await http(frontend.port, "GET", "/metrics")
        finally:
            metrics = await frontend.stop()
        return tokens, health, prom, metrics

    tokens, health, prom, metrics = asyncio.run(scenario())
    engine = batcher.engine
    check(health[0] == 200 and json.loads(health[1])["status"] == "ok",
          f"/healthz: {health}")
    check(prom[0] == 200 and b"serving_ttft_seconds" in prom[1],
          f"/metrics answered {prom[0]} without serving_ttft_seconds")
    for got, n_new in zip(tokens, new_tokens):
        check(len(got) == n_new, f"{len(got)} tokens back, want {n_new}")
    check(metrics["n_requests"] == len(prompts)
          and metrics["n_shed"] == 0 and metrics["n_cancelled"] == 0,
          f"batcher metrics: {metrics}")
    check(engine.decode_compiles == 1 and engine.prefill_compiles <= 2,
          f"decode compiled {engine.decode_compiles}x, prefill "
          f"{engine.prefill_compiles}x")
    engine.tables.check()
    check(engine.tables.n_free_pages == engine.n_pages - 1,
          "pages leaked after every request retired")
    return {"tokens": tokens, "engine": engine, "metrics": metrics}


def agreement(got: list[list[int]], want: list[list[int]]) -> dict:
    """Greedy streams diverge for good after one flipped argmax, so
    agreement is the share of each stream before its first
    divergence."""
    shares, first = [], None
    for i, (g, w) in enumerate(zip(got, want)):
        n = next((k for k, (a, b) in enumerate(zip(g, w)) if a != b),
                 len(w))
        shares.append(n / len(w))
        if n < len(w) and first is None:
            first = {"request": i, "position": n, "got": g[n],
                     "want": w[n]}
    return {"exact_requests": sum(s == 1.0 for s in shares),
            "n_requests": len(shares),
            "mean_prefix_share": round(float(np.mean(shares)), 4),
            "first_divergence": first}


def phase_serve(fields: dict, log: CompileLog, platform: str, *, seed: int = 0,
                model: dict = GPT2_SMALL,
                prompt_lens=SERVE_PROMPT_LENS,
                new_tokens=SERVE_NEW_TOKENS, **geometry) -> None:
    from torchbooster_tpu.models.gpt import GPTConfig
    from torchbooster_tpu.ops._pallas_util import default_interpret

    check(default_interpret() is (platform != "tpu"),
          f"default_interpret() is {default_interpret()} on {platform}")
    cfg = GPTConfig(**model)
    params = decisive_params(cfg, seed)
    prompts = make_prompts(seed, cfg.vocab, prompt_lens)
    want = reference_tokens(params, cfg, prompts, new_tokens)
    served = {}
    for backend in ("xla", "pallas"):
        conf = serving_conf(decode_backend=backend, **geometry)
        check(conf.n_pages - 1 >= conf.max_slots
              * -(-cfg.seq_len // conf.page_size),
              "the pool cannot hold every slot at full context")
        out = serve_over_http(conf, params, cfg, prompts, new_tokens)
        check(devices_of(out["engine"].pool).pop().platform == platform,
              "the KV pool is not on the accelerator")
        served[backend] = out["tokens"]
        fields[f"{backend}_decode_compiles"] = out["engine"].decode_compiles
        fields[f"{backend}_new_tokens"] = out["metrics"]["new_tokens"]
        del out
    across = agreement(served["pallas"], served["xla"])
    dense = agreement(served["xla"], want)
    fields.update(pallas_vs_xla=across, xla_vs_jit_generate=dense,
                  n_requests=len(prompts))
    # bf16 rounding can flip a near-tied argmax between two programs
    # that compute the same logits in another order; a kernel that
    # reads the wrong page cannot agree on most of a stream
    for name, result in (("pallas vs xla", across),
                         ("xla vs jit_generate", dense)):
        check(result["mean_prefix_share"] >= 0.5,
              f"gross token mismatch, {name}: {result}")


# ---------------------------------------------------------------------
# --chips 4: what exists only across chips, and its one-device twin
# ---------------------------------------------------------------------

def phase_mesh_train(fields: dict, log: CompileLog, platform: str, *, seed: int = 0,
                     model: dict = GPT2_SMALL, batch: int = 16,
                     n_iter: int = 3, rtol: float = 5e-3) -> None:
    recipe = load_recipe()
    runs = {}
    for name, distributed in (("sharded", True), ("single", False)):
        conf = recipe_conf(recipe, "gpt.yml", seed=seed, model=model,
                           batch=batch, n_iter=n_iter)
        conf.env.distributed = distributed
        # degrades to one device when not distributed
        conf.env.mesh, conf.env.n_devices = "dp:2,fsdp:2", 4
        _, probe = run_recipe(recipe, conf, log)
        runs[name] = probe.host_losses()
        fields[f"{name}_param_devices"] = len(devices_of(probe.state.params))
        fields[f"{name}_batch_devices"] = len(devices_of(probe.batch))
        del probe
    check(fields["sharded_param_devices"] == 4
          and fields["sharded_batch_devices"] == 4,
          f"the dp:2,fsdp:2 run did not span four devices: {fields}")
    check(fields["single_param_devices"] == 1, f"{fields}")
    check_losses(runs["sharded"], vocab=model["vocab"])
    check(np.allclose(runs["sharded"], runs["single"], rtol=rtol),
          f"sharded {runs['sharded']} vs single {runs['single']}")
    fields.update(sharded_losses=[round(x, 4) for x in runs["sharded"]],
                  single_losses=[round(x, 4) for x in runs["single"]])


def phase_zero2(fields: dict, log: CompileLog, platform: str, *, seed: int = 0,
                model: dict = GPT2_SMALL, batch: int = 16,
                n_iter: int = 2, rtol: float = 5e-3) -> None:
    """One ZeRO-2 step pair on a plain ``dp:4`` mesh through the YAML
    ``comms:`` block, against the replicated optimizer on one
    device."""
    import jax
    import jax.numpy as jnp
    import optax

    from torchbooster_tpu import distributed as dist
    from torchbooster_tpu.models.gpt import GPT, GPTConfig
    from torchbooster_tpu.ops.losses import cross_entropy
    from torchbooster_tpu.utils import TrainState, boost, make_step

    boost()
    cfg = GPTConfig(**model)
    mesh = dist.make_mesh("dp:4", 4)
    sched = Blocks.load(REPO / "chip_smoke.yml").comms.make(mesh=mesh)
    # a bare EnvConfig() has no mesh and silently degenerates to N=1
    check(sched.stage == 2 and sched.n_shards == 4,
          f"comms schedule: stage {sched.stage}, n_shards {sched.n_shards}")
    tx = optax.adamw(3e-4)
    ids = np.random.RandomState(seed).randint(
        0, cfg.vocab, (batch, cfg.seq_len + 1)).astype(np.int32)
    host = {"ids": ids[:, :-1], "labels": ids[:, 1:]}

    def loss_fn(params, data, rng):
        del rng
        logits = GPT.apply(params, data["ids"], cfg,
                           compute_dtype=jnp.bfloat16)
        return cross_entropy(logits, data["labels"]), {}

    def run(state, step, data):
        losses = []
        for _ in range(n_iter):
            state, metrics = step(state, data)
            losses.append(float(np.asarray(metrics["loss"])))
        return state, losses

    init = lambda: GPT.init(jax.random.PRNGKey(seed), cfg)
    state, zero2 = run(sched.create_state(init(), tx),
                       make_step(loss_fn, tx, clip=1.0, comms=sched),
                       dist.shard_batch(dict(host), mesh))
    fields["opt_state_devices"] = len(devices_of(state.opt_state))
    check(fields["opt_state_devices"] == 4,
          "the ZeRO-2 optimizer state does not span four devices")
    del state
    _, single = run(TrainState.create(init(), tx),
                    make_step(loss_fn, tx, clip=1.0),
                    jax.device_put(host, jax.devices()[0]))
    check_losses(zero2, vocab=cfg.vocab)
    check(np.allclose(zero2, single, rtol=rtol),
          f"zero2 {zero2} vs single {single}")
    fields.update(n_shards=sched.n_shards,
                  zero2_losses=[round(x, 4) for x in zero2],
                  single_losses=[round(x, 4) for x in single])


def phase_tp_serve(fields: dict, log: CompileLog, platform: str, *, seed: int = 0,
                   model: dict = GPT2_SMALL,
                   prompt_lens=(48, 200, 450, 700),
                   new_tokens=(32, 32, 32, 32), **geometry) -> None:
    from torchbooster_tpu import distributed as dist
    from torchbooster_tpu.models.gpt import GPTConfig
    from torchbooster_tpu.serving import Request

    cfg = GPTConfig(**model)
    params = decisive_params(cfg, seed)
    prompts = make_prompts(seed, cfg.vocab, prompt_lens)
    served = {}
    for tp in (4, 1):
        conf = serving_conf(tp=tp, **geometry)
        mesh = dist.make_mesh("tp:4", 4) if tp > 1 else None
        batcher = conf.make(params, cfg, mesh=mesh)
        requests = [Request(prompt=np.asarray(p, np.int32),
                            max_new_tokens=n)
                    for p, n in zip(prompts, new_tokens)]
        batcher.run(requests)
        engine = batcher.engine
        check(engine.decode_compiles == 1,
              f"tp={tp} decode compiled {engine.decode_compiles}x")
        served[tp] = [[int(t) for t in r.tokens] for r in requests]
        fields[f"tp{tp}_pool_devices"] = len(devices_of(engine.pool))
        fields[f"tp{tp}_qkv_devices"] = len(devices_of(
            engine.params["blocks"]["attn_qkv"]))
        del batcher, engine
    check(fields["tp4_pool_devices"] == 4
          and fields["tp4_qkv_devices"] == 4,
          f"the tp:4 engine does not span four devices: {fields}")
    result = agreement(served[4], served[1])
    fields.update(tp4_vs_tp1=result, n_requests=len(prompts))
    check(result["mean_prefix_share"] >= 0.5,
          f"gross token mismatch, tp=4 vs tp=1: {result}")


# ---------------------------------------------------------------------

def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the cross-chip paths and their "
                             "one-device twins")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    import jax

    from torchbooster_tpu.utils import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax found {platform!r})")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but jax "
                         f"found {len(devices)} device(s)")
    cache_dir = enable_compile_cache()
    before = cache_entries(cache_dir)
    emit("start", jax=jax.__version__, chips=args.chips, seed=args.seed,
         device_kind=devices[0].device_kind, n_devices=len(devices),
         cache_dir=cache_dir, cache_entries=before, cache_warm=before > 0,
         cache_dir_from_env=bool(
             os.environ.get("JAX_COMPILATION_CACHE_DIR")))

    log = CompileLog()
    if args.chips == 1:
        phases = (("train", phase_train), ("train_long", phase_train_long),
                  ("resnet", phase_resnet), ("serve", phase_serve))
    else:
        phases = (("mesh_train", phase_mesh_train), ("zero2", phase_zero2),
                  ("tp_serve", phase_tp_serve))
    for name, phase in phases:
        with Phase(name, log) as fields:
            phase(fields, log, platform, seed=args.seed)
    emit("end", cache_dir=cache_dir, cache_entries_before=before,
         cache_entries_after=cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
