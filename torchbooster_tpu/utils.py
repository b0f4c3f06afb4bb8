"""Training utilities: the compiled train step, PRNG seeding, loaders.

Capability parity with reference ``torchbooster/utils.py`` (251 LoC),
re-designed functional. The reference's ``step(loss, optimizer, ...)``
(ref utils.py:204-252) mutates optimizer/scaler/scheduler in place per
call; here the equivalent is :func:`make_step`, which *builds* a single
jitted ``(state, batch) -> (state, metrics)`` function with gradient
psum over the mesh's data axes, global-norm clipping, schedule advance,
and gradient accumulation compiled in. TrainState donation makes the
update in-place at the XLA level (no reallocation per step).

Symbol map (ref → here):
- ``boost``            (ref :29-45)   → :func:`boost` (XLA/debug knobs)
- ``seed``             (ref :48-64)   → :func:`seed` (+ the ``deterministic``
  flag two reference examples pass but the reference never accepted —
  a latent TypeError there, ref adain.py:192)
- ``freeze``           (ref :67-84)   → :func:`freeze` (zero-out updates
  via optax mask; params are immutable here so freezing is an optimizer
  property, not a param flag)
- ``detach``           (ref :87-103)  → :func:`detach` (stop_gradient)
- ``iter_loader``      (ref :106-132) → :func:`iter_loader`
- ``to_tensor``        (ref :146-178) → :func:`to_array`
- ``stack_dictionaries`` (ref :181-201) → :func:`stack_dictionaries`
- ``step``             (ref :204-252) → :func:`make_step` / :class:`TrainState`
"""
from __future__ import annotations

import logging
import os
import random
from pathlib import Path
from typing import Any, Callable, Iterable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh


# =========================================================================
# Environment knobs (ref boost, utils.py:29-45)
# =========================================================================

# JAX's persistent-cache key leaves HLO metadata out
# (jax_compilation_cache_include_metadata_in_key is false), so a program
# compiled before a ``jax.named_scope`` was added or renamed is a HIT
# for the re-scoped one and comes back with the old op names: a trace
# would attribute device time by a vocabulary the source no longer has.
# The cache therefore lives in a subdirectory named after this number;
# bump it whenever the scope vocabulary (docs/observability.md) changes.
PROGRAM_METADATA_VERSION = 1


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache and return its
    directory: ``m<PROGRAM_METADATA_VERSION>`` under
    ``JAX_COMPILATION_CACHE_DIR`` where that is set, otherwise under
    the fixed ``<checkout>/.jax_cache`` (never a temp dir: a directory
    that moves between runs never hits). The compile-time threshold
    is dropped so the step programs of a short run are kept too.
    Idempotent; called by :func:`boost` and by the serving build, the
    two calls every entry point makes before its first big compile.

    A process pinned to the CPU (``JAX_PLATFORMS=cpu``: tests,
    rehearsals) keeps no cache and gets None: its compiles are short,
    and XLA:CPU's loader logs an error for every entry it reads back.
    The pin is read from the config, never from the backend — this
    runs before ``jax.distributed.initialize`` may."""
    if (jax.config.jax_platforms or "") == "cpu":
        return None
    base = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(__file__).resolve().parent.parent / ".jax_cache")
    path = os.path.join(base, f"m{PROGRAM_METADATA_VERSION}")
    if jax.config.jax_compilation_cache_dir != path:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", path)
        # a compile before this call would have opened the cache at
        # the environment's own directory, and it opens only once
        compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def boost(enable: bool = True) -> None:
    """Performance/debug switch (ref boost utils.py:29-45).

    ``boost(True)`` (default) leaves XLA at full speed and turns on the
    persistent compilation cache (:func:`enable_compile_cache`).
    ``boost(False)`` is debug mode: enables NaN checking and disables
    jit so errors point at python lines — the analogue of the
    reference's anomaly detection (ref utils.py:40-45; its
    cudnn.benchmark knob has no TPU meaning, XLA autotunes by
    default)."""
    if not enable:
        logging.warning("boost disabled: debug_nans on, jit disabled — slow")
    else:
        enable_compile_cache()
    jax.config.update("jax_debug_nans", not enable)
    jax.config.update("jax_disable_jit", not enable)


# Profiler helpers now live in the telemetry subsystem (the canonical
# home: observability/spans.py unifies them with host spans + the
# registry); re-exported here because ``utils.trace(...)`` is the
# documented user surface since the seed.
from torchbooster_tpu.observability.spans import annotate, trace  # noqa: E402,F401


def instrument_step(step_fn: Callable, name: str = "train_step",
                    registry: Any = None) -> Callable:
    """Wrap a compiled ``(state, batch) -> (state, metrics)`` step with
    telemetry: a per-call ``step_seconds`` histogram, a ``steps_total``
    counter (``LogCallback`` derives steps/s from its deltas), and a
    :func:`~torchbooster_tpu.observability.span` so the step groups
    under one label in a captured trace.

    Sync-free by construction: it times the HOST side of each call
    (dispatch + whatever blocking the body itself does) and never
    touches the result — with async dispatch the per-call number is
    dispatch time, but the call *cadence* backpressures on the device
    queue, so the histogram's steady-state mean converges to the true
    device step time without a single added ``block_until_ready`` or
    D2H read. When telemetry is disabled the wrapper is one attribute
    check per call."""
    import functools
    import time as _time

    from torchbooster_tpu.observability import get_registry, span

    reg = registry if registry is not None else get_registry()
    hist = reg.histogram("step_seconds",
                         "host wall time per train-step dispatch")
    count = reg.counter("steps_total", "train steps dispatched")

    @functools.wraps(step_fn)
    def wrapped(*args: Any, **kwargs: Any) -> Any:
        if not reg.enabled:
            return step_fn(*args, **kwargs)
        t0 = _time.perf_counter()
        with span(name, reg):
            out = step_fn(*args, **kwargs)
        hist.observe(_time.perf_counter() - t0, step=name)
        count.inc(1, step=name)
        return out

    return wrapped


def seed(value: int = 42, deterministic: bool = True) -> jax.Array:
    """Seed python/numpy RNGs and return the root PRNG key
    (ref seed utils.py:48-64). Determinism needs no flags here: JAX
    randomness is deterministic by construction via explicit key
    threading, and XLA:TPU reductions are deterministic by default —
    the CUDA-side knobs the reference sets (CUBLAS_WORKSPACE_CONFIG +
    use_deterministic_algorithms, ref utils.py:59-64) have no TPU
    analogue to toggle. The ``deterministic`` kwarg is accepted for the
    call-signature the reference examples expect but its API lacked
    (latent TypeError at ref adain.py:192); it is a no-op by design."""
    del deterministic
    random.seed(value)
    np.random.seed(value)
    return jax.random.PRNGKey(value)


# =========================================================================
# Pytree helpers (ref freeze/detach/to_tensor/stack_dictionaries)
# =========================================================================

def freeze(labels: Callable[[str], bool],
           tx: optax.GradientTransformation) -> optax.GradientTransformation:
    """Freeze parameters under any optimizer (ref freeze utils.py:67-84
    sets requires_grad=False; params are immutable pytrees here, so
    freezing is an optimizer property). ``labels(path_str)`` returns
    True for *frozen* paths; those get zero updates while ``tx`` drives
    the rest. Wrapping the whole optimizer (rather than zeroing grads
    in front of it) is required for bit-identical frozen params:
    decoupled weight decay (adamw) would otherwise still shrink them."""
    from torchbooster_tpu.parallel.sharding import path_str

    def label_fn(params: Any) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "frozen" if labels(path_str(path)) else "train",
            params)

    return optax.multi_transform(
        {"train": tx, "frozen": optax.set_to_zero()}, label_fn)


def detach(*arrays: Any) -> Any:
    """Stop gradients (ref detach utils.py:87-103: one arg → the value,
    several → a tuple)."""
    out = tuple(jax.tree.map(jax.lax.stop_gradient, a) for a in arrays)
    return out[0] if len(out) == 1 else out


def to_array(data: Any, dtype: Any = None) -> Any:
    """Convert lists / dict-likes / namedtuples of numbers into numpy
    arrays ready for device_put (ref to_tensor utils.py:146-178 — the
    HF-tokenizer-output-friendly converter)."""
    if hasattr(data, "_asdict"):
        data = data._asdict()
    if isinstance(data, dict):
        return {k: to_array(v, dtype) for k, v in data.items()}
    arr = np.asarray(data)
    if dtype is not None:
        arr = arr.astype(dtype)
    return arr


def stack_dictionaries(dicts: Sequence[dict]) -> dict:
    """List-of-dicts → dict-of-stacked-arrays (ref utils.py:181-201)."""
    if not dicts:
        return {}
    return {
        key: np.stack([to_array(d[key]) for d in dicts])
        for key in dicts[0]
    }


def iter_loader(loader: Iterable) -> Iterator[tuple[int, Any]]:
    """Infinite epoch-tracking iterator over a loader → yields
    ``(epoch, batch)`` enabling iteration-count-based training
    (ref iter_loader utils.py:106-132)."""
    epoch = 0
    while True:
        for batch in loader:
            yield epoch, batch
        epoch += 1


# =========================================================================
# TrainState + the compiled step (ref step, utils.py:204-252)
# =========================================================================

class TrainState(struct.PyTreeNode):
    """The full training state threaded through the compiled step:
    params, optimizer state, step count, PRNG key — everything the
    reference keeps as mutable objects (model buffers, optimizer
    internals, scheduler step, ref callbacks.py:42-72) plus accumulated
    gradients when ``accumulate`` is used."""

    params: Any
    opt_state: Any
    step: jax.Array
    rng: jax.Array
    grad_acc: Any = None
    # exponential moving average of params (sampling weights for
    # diffusion/GAN-style training); updated inside the compiled step
    # when make_step(ema_decay=...) is set, checkpointed with the rest
    ema: Any = None
    # gradient-communication state (int8 error-feedback residuals;
    # see torchbooster_tpu.comms) — populated by
    # GradComms.create_state, None/{} otherwise; checkpointed with
    # the rest like every other leaf
    comms: Any = None

    @classmethod
    def create(cls, params: Any, tx: optax.GradientTransformation,
               rng: jax.Array | int = 0,
               accumulate: bool = False,
               ema: bool = False) -> "TrainState":
        if isinstance(rng, int):
            rng = jax.random.PRNGKey(rng)
        grad_acc = jax.tree.map(jnp.zeros_like, params) if accumulate else None
        ema_tree = jax.tree.map(jnp.array, params) if ema else None
        return cls(params=params, opt_state=tx.init(params),
                   step=jnp.zeros((), jnp.int32), rng=rng,
                   grad_acc=grad_acc, ema=ema_tree)


def _clip_by_global_norm(grads: Any, clip: float) -> Any:
    norm = optax.global_norm(grads)
    scale = jnp.minimum(1.0, clip / (norm + 1e-6))
    return jax.tree.map(lambda g: g * scale, grads)


def make_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    clip: float | None = None,
    accumulate_every: int = 1,
    mesh: Mesh | None = None,
    compute_dtype: Any = None,
    has_aux: bool = True,
    donate: bool = True,
    rules: Any = None,
    ema_decay: float | None = None,
    comms: Any = None,
) -> Callable:
    """Build the jitted train step — the functional replacement for the
    reference's per-call ``utils.step`` (ref utils.py:204-252).

    ``loss_fn(params, batch, rng) -> loss`` (or ``(loss, aux)`` when
    ``has_aux``). The returned function has signature
    ``(state, batch) -> (state, metrics)`` and compiles in:

    - forward + backward (``value_and_grad``),
    - gradient mean over data-parallel shards — implicit: batch is
      sharded over dp/fsdp, params replicated/sharded, so XLA inserts
      the psum exactly where DDP's bucketed allreduce sat
      (ref config.py:178 / SURVEY §3.3),
    - optional global-norm clipping (ref utils.py:243-246),
    - gradient accumulation every ``accumulate_every`` microbatches
      (ref accumulate flag, utils.py:233-235) via state.grad_acc,
    - optimizer + schedule advance (ref utils.py:248-251; the schedule
      is baked into ``tx`` via inject_hyperparams),
    - fresh PRNG key split per step.

    No GradScaler: bf16 on TPU needs no loss scaling (SURVEY §7
    precision note); master weights stay fp32, casts happen in
    ``loss_fn`` via ``compute_dtype``.

    Sharding: without ``rules``, layouts propagate from the (already
    placed) state/batch inputs via jit's inference — correct for the
    shipped models, which pin their own internal layouts with
    ``with_sharding_constraint``. Pass ``mesh`` AND ``rules`` (a model's
    ``SHARDING_RULES``) to additionally constrain gradients and updated
    params to the rule layout inside the compiled step — this pins the
    layout for models with no internal constrainers, so fsdp/tp cannot
    silently degrade to whatever XLA guesses.

    Gradient communication: pass ``comms`` (a
    :class:`~torchbooster_tpu.comms.GradComms`, built from the YAML
    ``comms:`` block) to replace the implicit fp32 gradient psum with
    an explicit sync over the data axes — ``mode: fp32`` (the control
    arm), ``bf16``/``int8`` (quantized wire formats with
    error-feedback residuals carried in ``state.comms``), and/or
    ``zero1: true`` (optimizer state reduce-scattered across replicas,
    updated params all-gathered). Build states with
    ``comms.create_state(params, tx)``. Explicit modes require
    replicated params (no ``rules``); ``zero1`` is incompatible with
    ``accumulate_every > 1`` (the accumulator would need the same
    scatter layout — keep the implicit path there). The returned step
    exports its modeled per-collective bytes through the
    ``comms_bytes_total`` counter when telemetry is enabled.

    A :class:`~torchbooster_tpu.comms.schedule.CommsSchedule` with
    ``stage >= 2`` extends the ladder: ZeRO-2 reduce-scatters the
    gradients bucket-by-bucket (inside backward when ``overlap``),
    ZeRO-3 additionally keeps params sharded at rest and all-gathers
    them just in time in forward — see
    :mod:`torchbooster_tpu.comms.schedule`. Same constraints as
    ``zero1`` plus: no gradient accumulation, elementwise optimizers
    only.
    """
    accumulate = accumulate_every > 1

    if rules is not None and mesh is None:
        raise ValueError("make_step(rules=...) needs mesh= as well")
    explicit = comms is not None and comms.mode != "implicit"
    zero1 = bool(comms is not None and comms.zero1)
    # ZeRO ladder: stage 0/1 rides the original explicit/zero1 paths
    # below bit-for-bit; stage >= 2 (ZeRO-2/3, optionally overlapped)
    # dispatches to the comms.schedule step — one fused shard_map over
    # fwd+bwd+sharded update (torchbooster_tpu/comms/schedule.py)
    stage = int(getattr(comms, "stage", 1 if zero1 else 0))
    if (explicit or zero1) and rules is not None:
        raise ValueError(
            "make_step(comms=...) explicit modes / zero1 need fully "
            "replicated params — rules= is the model-parallel path; "
            "use comms mode: implicit with it")
    if zero1 and accumulate:
        raise ValueError(
            "comms zero1 does not compose with accumulate_every > 1 "
            "(the accumulator would need the scatter layout); "
            "accumulate on the implicit path instead")

    def _pin(tree: Any) -> Any:
        """Constrain a param-shaped pytree to the rule layout."""
        if rules is None or mesh is None:
            return tree
        from torchbooster_tpu.parallel.sharding import (
            make_param_specs, make_shardings)

        specs = make_param_specs(tree, rules, mesh=mesh)
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            make_shardings(specs, mesh))

    def _cast(tree: Any) -> Any:
        return jax.tree.map(
            lambda x: x.astype(compute_dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    def _ema(state: TrainState, params: Any, boundary: Any = None) -> Any:
        """The EMA of the updated params (None where the state keeps
        none); ``boundary``: decay only on this micro-step."""
        if ema_decay is None or state.ema is None:
            return state.ema
        # bias-corrected decay ramp: early steps track params closely
        # instead of the init snapshot
        d = jnp.minimum(ema_decay,
                        (1.0 + state.step) / (10.0 + state.step))
        if boundary is not None:
            d = jnp.where(boundary, d, 1.0)
        return jax.tree.map(lambda e, p: e * d + (1.0 - d) * p,
                            state.ema, params)

    def step_fn(state: TrainState, batch: Any) -> tuple[TrainState, dict]:
        rng, step_rng = jax.random.split(state.rng)
        batch_cast = batch if compute_dtype is None else _cast(batch)

        if compute_dtype is None:
            diff_fn = loss_fn
        else:
            # mixed precision, TPU-style: fp32 master params, bf16
            # compute — the whole fwd+bwd runs on the MXU in bf16 (cast
            # inside the differentiated fn so its grad is fp32 w.r.t.
            # the masters), no loss scaling needed (SURVEY §7)
            def cast_loss_fn(params: Any, batch: Any, rng: jax.Array):
                return loss_fn(_cast(params), batch, rng)

            diff_fn = cast_loss_fn
        comms_state = state.comms
        if stage >= 2:
            # ZeRO-2/3: per-bucket reduce-scatter (inside backward
            # when the schedule overlaps), elementwise update on this
            # replica's flat shard, params re-gathered (stage 2) or
            # kept sharded at rest (stage 3)
            from torchbooster_tpu.comms.schedule import sharded_step

            (loss, aux), params, opt_state, comms_state = sharded_step(
                comms, diff_fn, tx, clip, state.params,
                state.opt_state, state.comms or {}, batch_cast,
                step_rng, has_aux=has_aux)
            with jax.named_scope("optimizer"):
                ema = _ema(state, params)
            new_state = state.replace(
                params=params, opt_state=opt_state,
                step=state.step + 1, rng=rng, ema=ema,
                comms=comms_state)
            return new_state, {"loss": loss, **aux}
        if explicit:
            # per-replica fwd+bwd under shard_map, then the explicit
            # sync in the configured wire format; with zero1 the sync
            # stops at the reduce-scatter and grads come back as this
            # replica's flat chunk (torchbooster_tpu.comms.quantized)
            from torchbooster_tpu.comms.quantized import (
                value_and_grad_sync)

            (loss, aux), grads, comms_state = value_and_grad_sync(
                diff_fn, state.params, state.comms or {}, batch_cast,
                step_rng, comms, has_aux=has_aux, scatter=zero1)
        else:
            grad_fn = jax.value_and_grad(diff_fn, has_aux=has_aux)
            if has_aux:
                (loss, aux), grads = grad_fn(state.params, batch_cast,
                                             step_rng)
            else:
                loss, grads = grad_fn(state.params, batch_cast, step_rng)
                aux = {}
        grads = grads if zero1 else _pin(grads)

        if zero1:
            # cross-replica sharded weight update: local optimizer
            # shard + updated-param all-gather (comms.zero); clipping
            # happens inside (global norm via scalar psum)
            from torchbooster_tpu.comms.zero import sharded_update

            with jax.named_scope("optimizer"):
                params, opt_state = sharded_update(
                    tx, comms, clip, grads, state.opt_state,
                    state.params, scattered=explicit)
                ema = _ema(state, params)
            new_state = state.replace(
                params=params, opt_state=opt_state,
                step=state.step + 1, rng=rng, ema=ema,
                comms=comms_state)
            return new_state, {"loss": loss, **aux}

        boundary = (state.step + 1) % accumulate_every == 0
        with jax.named_scope("optimizer"):
            if accumulate:
                grad_acc = jax.tree.map(jnp.add, state.grad_acc, grads)

                def apply(_):
                    grads_avg = jax.tree.map(
                        lambda g: g / accumulate_every, grad_acc)
                    if clip is not None:
                        grads_clipped = _clip_by_global_norm(grads_avg,
                                                             clip)
                    else:
                        grads_clipped = grads_avg
                    updates, opt_state = tx.update(
                        grads_clipped, state.opt_state, state.params)
                    params = optax.apply_updates(state.params, updates)
                    zeros = jax.tree.map(jnp.zeros_like, grad_acc)
                    return params, opt_state, zeros

                def hold(_):
                    return state.params, state.opt_state, grad_acc

                params, opt_state, grad_acc = jax.lax.cond(
                    boundary, apply, hold, None)
            else:
                if clip is not None:
                    grads = _clip_by_global_norm(grads, clip)
                updates, opt_state = tx.update(grads, state.opt_state,
                                               state.params)
                params = optax.apply_updates(state.params, updates)
                grad_acc = state.grad_acc
            # under accumulation, params only change on boundary
            # micro-steps — decaying on hold steps would shrink the
            # effective half-life by accumulate_every
            ema = _ema(state, params, boundary if accumulate else None)

        new_state = state.replace(
            params=_pin(params), opt_state=opt_state, step=state.step + 1,
            rng=rng, grad_acc=grad_acc, ema=ema, comms=comms_state)
        metrics = {"loss": loss, **aux}
        return new_state, metrics

    # Without rules, sharding propagates from the (already placed)
    # state/batch inputs via jit's inference; with rules, _pin holds
    # grads and updated params to the declared layout inside the step.
    donate_argnums = (0,) if donate else ()
    jitted = jax.jit(step_fn, donate_argnums=donate_argnums)
    if comms is None:
        return jitted
    return _instrument_comms(jitted, comms)


def _instrument_comms(jitted: Callable, comms: Any) -> Callable:
    """Export the step's modeled per-collective bytes through the
    ``comms_bytes_total`` counter. Host-side constants only (the
    traffic model is static per compiled step) — one dict walk per
    call when telemetry is on, a single attribute check when off. The
    jit cache handle passes through so RecompileSentinel keeps
    working on the wrapped step."""
    import functools

    from torchbooster_tpu.observability import get_registry

    cache: dict[str, Any] = {}

    @functools.wraps(jitted)
    def stepped(state: Any, batch: Any) -> Any:
        reg = get_registry()
        if reg.enabled and "traffic" not in cache:
            # param count read BEFORE the call: the step donates its
            # state, so these buffers are gone afterwards
            n_params = sum(
                int(leaf.size) for leaf in jax.tree.leaves(state.params)
                if hasattr(leaf, "size"))
            cache["traffic"] = comms.step_traffic(n_params)
        out = jitted(state, batch)
        if reg.enabled and "traffic" in cache:
            from torchbooster_tpu.comms.accounting import (
                record_step_traffic)

            record_step_traffic(cache["traffic"], reg)
        return out

    stepped._cache_size = jitted._cache_size  # type: ignore[attr-defined]
    stepped.lower = jitted.lower              # type: ignore[attr-defined]
    return stepped


def make_eval_step(loss_fn: Callable, has_aux: bool = True,
                   compute_dtype: Any = None) -> Callable:
    """Jitted eval step: ``(params, batch, rng) -> metrics`` (the
    reference had no eval helper; examples hand-rolled it)."""

    def eval_fn(params: Any, batch: Any, rng: jax.Array) -> dict:
        if compute_dtype is not None:
            batch = jax.tree.map(
                lambda x: x.astype(compute_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, batch)
        out = loss_fn(params, batch, rng)
        if has_aux:
            loss, aux = out
        else:
            loss, aux = out, {}
        return {"loss": loss, **aux}

    return jax.jit(eval_fn)


__all__ = [
    "TrainState", "annotate", "boost", "detach", "enable_compile_cache",
    "freeze",
    "instrument_step", "iter_loader", "make_step", "make_eval_step",
    "seed", "stack_dictionaries", "to_array", "trace",
]
