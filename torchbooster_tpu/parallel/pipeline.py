"""Pipeline parallelism: GPipe schedule over a ``pp`` mesh axis.

No reference counterpart (the reference is DP-only, SURVEY §2.12); this
completes the parallelism matrix (dp/fsdp/tp/sp/ep/pp). The design is
SPMD, not host-orchestrated: layer-stacked parameters shard their
leading axis over ``pp`` (each device holds ``L/P`` contiguous layers),
and one ``shard_map`` kernel runs the classic GPipe schedule — at tick
``t`` stage ``i`` processes microbatch ``t - i``, then rotates its
activation to stage ``i+1`` with a single ``ppermute`` ring step. The
bubble is the usual ``P - 1`` ticks; all shapes are static, so the
whole schedule compiles to one XLA while-loop with a collective-permute
per tick.

Differentiable end to end: ``jax.grad`` through the kernel yields the
reverse schedule automatically (ppermute transposes to the reverse
ring), so ``pipeline_apply`` drops into a jitted train step unchanged.

Cost model (honest limits at scale):

- **Inactive-tick compute**: every stage runs its layers on every tick
  and discards inactive results via ``jnp.where`` — SPMD has one
  program, so the bubble ticks still burn MXU. Overhead factor is
  (m + P − 1)/m of the ideal schedule's FLOPs: ~2× at m = P; at
  m = 4P (the default when the batch divides) it is 1.25 − 1/(4P),
  i.e. +18.75% at P = 4 approaching +25% for deep pipelines; m = 8P
  approaches +12.5%. Raise ``n_microbatches`` to buy efficiency with
  smaller per-microbatch matmuls.
- **Why not 1F1B**: in this SPMD one-program design every stage runs
  its layers every tick regardless of schedule, so 1F1B's classic win
  over GPipe — fewer in-flight microbatches, hence less LIVE
  activation memory — is its only applicable benefit, and
  ``jax.checkpoint`` over the stage body already bounds activations
  at O(saved-dots) per microbatch. The bubble FLOPs are identical
  under both schedules here; raising ``n_microbatches`` (default 4P)
  is the lever that actually buys MXU back. A manually-scheduled
  interleaved 1F1B with a hand-written backward would shrink the
  bubble below (m + P − 1)/m only by interleaving *virtual stages*
  (more layers-per-device splits) — worthwhile only on real multi-pod
  topologies, and measurable there before building it.
- **Epilogue broadcast**: finished microbatches live on the last
  stage; the mask + ``psum`` broadcasts the (B, ...) output across the
  pp axis — one all-reduce of the output activation per call. For
  LM training (output feeds a loss computed identically everywhere)
  this is the layout jit wants anyway; a ``ppermute``-to-stage-0
  epilogue would save ICI bytes when only one host consumes the
  result. Measured at dryrun scale this is noise; revisit against a
  profile before hand-optimizing.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _default_microbatches(batch: int, n_stages: int,
                          dp_size: int) -> int:
    """Deepest default schedule the batch supports, up to 4 stages'
    worth: the SPMD GPipe bubble burns (m + P − 1)/m of the ideal
    FLOPs — ~2× at m = P but 1.25 − 1/(4P) (≤ +25%) at m = 4P — so
    prefer 4P and degrade to the largest multiple of P the batch
    actually divides (each microbatch must also split over the data
    axes)."""
    for mult in (4, 3, 2):
        m = mult * n_stages
        if batch % m == 0 and (batch // m) % dp_size == 0:
            return m
    return n_stages


def pipeline_apply(
    layer_fn: Callable[..., jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh: Mesh,
    axis: str = "pp",
    n_microbatches: int | None = None,
    batch_axes: tuple[str, ...] | None = None,
    with_mb_index: bool = False,
    with_aux: bool = False,
    param_specs: Any | None = None,
    x_spec: P | None = None,
    aux_axes: tuple[str, ...] = (),
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Run ``layer_fn`` over ``L`` stacked layers, pipelined over the
    mesh's ``axis``.

    ``layer_fn(layer_params, x) -> x`` applies ONE layer (a pytree leaf
    slice of ``stacked_params``'s leading axis). ``x`` is the full batch
    ``(B, ...)``; it is split into ``n_microbatches`` (default: up to
    4× the pipeline depth, the deepest schedule the batch divides —
    ``_default_microbatches``) along axis 0. ``B`` must divide evenly
    and ``L`` must divide the ``axis`` size.

    ``with_mb_index=True`` calls ``layer_fn(layer_params, x, mb_index)``
    with the (traced) index of the microbatch being processed — for
    per-microbatch state like independent dropout streams (without it,
    stochastic layers would draw IDENTICAL noise for every microbatch,
    noise the un-pipelined full-batch forward draws independently).

    ``with_aux=True``: ``layer_fn`` additionally returns a scalar aux
    loss (MoE load balance); ``pipeline_apply`` returns ``(out, aux)``
    where aux is the SUM over layers of the MEAN over microbatches —
    the microbatch-granular estimator of the full-batch aux (batch
    statistics like expert load fractions are computed per microbatch
    here, so the value is close to, not bitwise-equal to, the
    un-pipelined one). ``aux_axes``: extra MANUAL mesh axes the
    layer_fn's aux varies over (a sequence-parallel axis with
    per-shard routing) — the aux is pmean'd over them ONCE here, so
    the returned scalar is collective-uniform; pmean is linear, so
    grads are identical to reducing inside every layer.

    ``batch_axes`` are the mesh axes the per-microbatch batch dimension
    shards over — default: whichever of ``dp``/``fsdp`` the mesh has.
    Note the ZeRO-style interaction: when the rule table STORES stage
    weights sharded over ``fsdp``, the kernel's in_specs (replicated
    across the data axes) make shard_map gather them at use — sharded
    at rest, whole during the step — without any extra machinery.
    Each data-parallel group then runs its own pp ring on its own batch
    slice, so dp×pp composes with no replicated compute; pass ``()`` to
    replicate instead. ``B / n_microbatches`` must divide by the product
    of the batch axes.

    Returns the full-batch output, identical (up to float reassociation)
    to sequentially scanning the layers on one device — EXCEPT for
    layers whose math depends on batch-level statistics: those see one
    microbatch (one dp slice of it) at a time. Concretely, MoE capacity
    and token-drop decisions are made per microbatch-slice, so at tight
    capacity factors a different token set overflows than in the
    un-pipelined forward (ample capacity → bitwise-matching outputs;
    the aux estimator differs regardless — see ``with_aux``).
    """
    n_stages = mesh.shape[axis]
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_layers % n_stages:
        raise ValueError(f"{n_layers} layers not divisible by "
                         f"{n_stages} pipeline stages")
    batch = x.shape[0]
    if batch_axes is None:
        batch_axes = tuple(a for a in ("dp", "fsdp")
                           if a in mesh.axis_names and a != axis)
    dp_size = int(np.prod([mesh.shape[a] for a in batch_axes])) \
        if batch_axes else 1
    m = n_microbatches or _default_microbatches(batch, n_stages, dp_size)
    if batch % m:
        raise ValueError(f"batch {batch} not divisible by {m} microbatches")
    if (batch // m) % dp_size:
        raise ValueError(
            f"microbatch size {batch // m} not divisible by data-axes "
            f"product {dp_size} ({batch_axes})")
    x_mb = x.reshape(m, batch // m, *x.shape[1:])

    # params shard their layer axis over pp (replicating across the data
    # axes); microbatches shard their batch dim over the data axes, so
    # each dp group drives an independent pp ring on its own slice.
    # ``param_specs`` overrides the default for callers that ALSO shard
    # within-layer dims over a manual axis (tensor parallelism — the
    # layer_fn is then responsible for the matching collectives).
    if param_specs is None:
        param_specs = jax.tree.map(lambda _: P(axis), stacked_params)
    # ``x_spec`` overrides the microbatch layout for callers that ALSO
    # shard activation dims over a manual axis (sequence parallelism:
    # P(None, data, "sp", ...) — the layer_fn then runs the matching
    # collectives, e.g. a ring attention body). The leading entry is
    # the microbatch axis and must stay unsharded.
    if x_spec is not None:
        if len(x_spec) and x_spec[0] is not None:
            # a sharded microbatch axis would make the kernel's global
            # dynamic_index_in_dim clamp out of local range — silently
            # re-feeding the last local microbatch instead of erroring
            raise ValueError(
                f"x_spec {x_spec} shards the leading (microbatch) "
                "axis; it must stay unsharded")
        for entry in x_spec:
            axes = entry if isinstance(entry, (tuple, list)) else (entry,)
            if axis in axes:
                # activations must replicate across pp: the ring hands
                # each stage's output to the next as ITS input — a
                # pp-sharded activation would silently mix batch slices
                raise ValueError(
                    f"x_spec {x_spec} shards over the pipeline axis "
                    f"{axis!r}; activations must replicate across it")
    mb_spec = P(None, batch_axes or None) if x_spec is None else x_spec

    def kernel(stage_params: Any, x_mb: jax.Array) -> jax.Array:
        stage = jax.lax.axis_index(axis)
        right = [(j, (j + 1) % n_stages) for j in range(n_stages)]

        def run_stage(carry_x: jax.Array, mb_idx: jax.Array):
            def one(carry, layer_params):
                x, aux = carry
                args = (layer_params, x, mb_idx) if with_mb_index \
                    else (layer_params, x)
                y = layer_fn(*args)
                if with_aux:
                    y, layer_aux = y
                    aux = aux + layer_aux
                return (y, aux), None

            (out, aux), _ = jax.lax.scan(
                one, (carry_x, jnp.zeros((), jnp.float32)), stage_params)
            return out, aux

        def tick(t: int, state: tuple) -> tuple:
            held, out, aux_sum = state
            mb_index = t - stage
            active = (mb_index >= 0) & (mb_index < m)
            # stage 0 pulls a fresh microbatch; others use the activation
            # received over the ring on the previous tick
            fresh = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, m - 1), 0, keepdims=False)
            x_in = jnp.where(stage == 0, fresh, held)
            y, aux = run_stage(x_in, jnp.clip(mb_index, 0, m - 1))
            y = jnp.where(active, y, x_in)
            aux_sum = aux_sum + jnp.where(active, aux, 0.0)
            # the final stage banks its finished microbatch
            write = active & (stage == n_stages - 1)
            slot = jnp.clip(mb_index, 0, m - 1)
            banked = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(write, y, jax.lax.dynamic_index_in_dim(
                    out, slot, 0, keepdims=False)), slot, 0)
            held = jax.lax.ppermute(y, axis, right)
            return held, banked, aux_sum

        held = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
        out = jnp.zeros_like(x_mb)
        _, out, aux_sum = jax.lax.fori_loop(
            0, m + n_stages - 1, tick,
            (held, out, jnp.zeros((), jnp.float32)))
        # results live on the last stage; mask + psum broadcasts them
        out = out * jnp.where(stage == n_stages - 1, 1.0, 0.0).astype(out.dtype)
        out = jax.lax.psum(out, axis)
        if with_aux:
            # each (stage, microbatch) pair contributed once; psum over
            # pp sums the stages (mean over the batch axes so every
            # data group agrees), /m gives mean-over-microbatches
            aux = jax.lax.psum(aux_sum, axis) / m
            if batch_axes:
                aux = jax.lax.pmean(aux, batch_axes)
            if aux_axes:
                aux = jax.lax.pmean(aux, aux_axes)
            return out, aux
        return out

    out_specs = (mb_spec, P()) if with_aux else mb_spec
    mapped = jax.shard_map(kernel, mesh=mesh,
                           in_specs=(param_specs, mb_spec),
                           out_specs=out_specs, check_vma=False)
    if with_aux:
        out_mb, aux = mapped(stacked_params, x_mb)
        return out_mb.reshape(batch, *x.shape[1:]), aux
    out_mb = mapped(stacked_params, x_mb)
    return out_mb.reshape(batch, *x.shape[1:])


__all__ = ["pipeline_apply"]
