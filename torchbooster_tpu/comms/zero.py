"""ZeRO-1: the optimizer update sharded across data-parallel replicas.

Plain data parallelism duplicates the weight update: every replica
holds the full optimizer state (2 extra fp32 copies of the params for
adam) and computes the identical update N times. "Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training"
(PAPERS.md) showed the fix: reduce-*scatter* the gradients so each
replica owns 1/N of them, update only that shard (1/N of the
optimizer state in HBM), then all-gather the updated parameters —
same wire bytes as the all-reduce it replaces, optimizer-state memory
divided by the DP degree.

Layout here: parameters ravel into ONE flat fp32 vector padded to a
multiple of ``n_shards * bucket_size`` (so the chunks quantized
collectives trade stay bucket-aligned). The optimizer state is built
over that flat vector and sharded over the data axes with the same
``PartitionSpec`` machinery the rest of the stack uses
(:mod:`torchbooster_tpu.parallel.sharding` conventions): every leaf
whose leading dim equals the padded length gets ``P(axes)``, scalars
(schedule counts, injected hyperparams) replicate.

The flat layout REQUIRES an elementwise, structure-agnostic
transformation — sgd / adam / adamw / lion (unmasked) update a shard
bit-identically to the replicated update of the same elements, which
the parity tests pin. Transformations that look at per-LEAF structure
silently change semantics on one flat leaf: a
``decay_matrices_only`` mask sees a 1-D vector and turns weight decay
OFF everywhere, lamb's per-leaf trust ratio becomes a per-shard-norm
ratio, adafactor loses its low-rank factoring. Keep those on the
implicit path (``zero1: false``).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["init_opt_state", "opt_state_specs", "padded_size",
           "sharded_update"]


def padded_size(n_params: int, n_shards: int, bucket_size: int) -> int:
    """Flat length padded so every replica's chunk is a whole number
    of quantization buckets. Padding is zeros end to end: zero grads
    into any optax elementwise state produce zero updates, so the pad
    region stays inert and is sliced off before unravel."""
    multiple = n_shards * bucket_size
    return n_params + (-n_params) % multiple


def opt_state_specs(opt_state: Any, padded: int,
                    axes: tuple[str, ...]) -> Any:
    """PartitionSpec pytree for a flat-built optax state: leaves with
    the padded flat leading dim (adam m/v, momentum traces) shard over
    the data axes, everything else (counts, injected hyperparams)
    replicates."""
    from torchbooster_tpu.comms.quantized import data_spec

    def spec(leaf: Any) -> P:
        if hasattr(leaf, "ndim") and leaf.ndim >= 1 \
                and leaf.shape[0] == padded:
            return data_spec(axes)
        return P()

    return jax.tree.map(spec, opt_state)


def init_opt_state(tx: optax.GradientTransformation, params: Any,
                   mesh: Mesh, axes: tuple[str, ...],
                   bucket_size: int) -> Any:
    """``tx.init`` over the flat padded parameter vector, placed
    sharded over the data axes — the ZeRO-1 replacement for
    ``tx.init(params)``. Per-replica HBM for adam drops from 2 full
    param copies to 2/N — including AT INIT: the state is built under
    a jit with sharded out_shardings, so the full replicated tree
    (the exact footprint ZeRO-1 exists to avoid) is never
    materialized on one device."""
    flat, _ = ravel_pytree(params)
    padded = padded_size(flat.size, _axes_size(mesh, axes), bucket_size)
    flat_p = jnp.pad(flat, (0, padded - flat.size))
    abstract = jax.eval_shape(tx.init, flat_p)
    specs = opt_state_specs(abstract, padded, axes)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    try:
        return jax.jit(tx.init, out_shardings=shardings)(flat_p)
    except TypeError:  # pragma: no cover — jax without out_shardings
        opt_state = tx.init(flat_p)
        return jax.tree.map(
            lambda leaf, sh: jax.device_put(leaf, sh),
            opt_state, shardings, is_leaf=lambda x: x is None)


def _axes_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def sharded_update(
    tx: optax.GradientTransformation,
    comms: Any,
    clip: float | None,
    grads: Any,
    opt_state: Any,
    params: Any,
    scattered: bool = False,
) -> tuple[Any, Any]:
    """One ZeRO-1 optimizer step (traced inside the compiled train
    step): slice this replica's gradient chunk (``scattered=True``
    means ``grads`` is already the flat reduce-scatter output from
    ``quantized.value_and_grad_sync``; otherwise it is a replicated
    pytree and the slice is free), update the local optimizer-state
    shard, and all-gather the updated flat parameters. Global-norm
    clipping composes via a scalar psum of per-shard sum-of-squares —
    identical math to ``utils._clip_by_global_norm``.

    Returns ``(new_params, new_opt_state)`` with params unraveled to
    the original pytree (replicated) and the optimizer state still
    sharded."""
    mesh, axes = comms.mesh, comms.axes
    sizes = tuple(mesh.shape[a] for a in axes)
    n = comms.n_shards
    flat_n = sum(int(leaf.size) for leaf in jax.tree.leaves(params))
    padded = comms.padded_size(flat_n)   # single derivation source
    chunk = padded // n
    _check_flat_state(opt_state, padded)

    specs = opt_state_specs(opt_state, padded, axes)

    def body(params, grads_in, opt_shard):
        from torchbooster_tpu.comms.quantized import linear_index

        idx = linear_index(axes, sizes)
        flat_p, unravel = ravel_pytree(params)
        flat_p = jnp.pad(flat_p, (0, padded - flat_n))
        start = (idx * chunk).astype(jnp.int32)
        p_shard = jax.lax.dynamic_slice(flat_p, (start,), (chunk,))
        if scattered:
            g_shard = grads_in
        else:
            flat_g, _ = ravel_pytree(grads_in)
            flat_g = jnp.pad(flat_g, (0, padded - flat_n))
            g_shard = jax.lax.dynamic_slice(flat_g, (start,), (chunk,))
        if clip is not None:
            # pad region is zero → contributes nothing to the norm
            norm = jnp.sqrt(jax.lax.psum(jnp.sum(g_shard * g_shard),
                                         axes))
            g_shard = g_shard * jnp.minimum(1.0, clip / (norm + 1e-6))
        updates, new_opt = tx.update(g_shard, opt_shard, p_shard)
        new_shard = optax.apply_updates(p_shard, updates)
        gathered = jax.lax.all_gather(new_shard, axes, tiled=True)
        return unravel(gathered[:flat_n]), new_opt

    from torchbooster_tpu.comms.quantized import data_spec

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), data_spec(axes) if scattered else P(), specs),
        out_specs=(P(), specs),
        check_vma=False)
    return mapped(params, grads, opt_state)


def _check_flat_state(opt_state: Any, padded: int) -> None:
    """Fail with a pointer instead of a shape soup when the state was
    built by plain ``TrainState.create`` (per-leaf trees) rather than
    :func:`init_opt_state` / ``GradComms.create_state``."""
    flat_leaves = [leaf for leaf in jax.tree.leaves(opt_state)
                   if hasattr(leaf, "ndim") and leaf.ndim >= 1
                   and leaf.shape[0] == padded]
    if not flat_leaves and any(
            hasattr(leaf, "ndim") and leaf.ndim >= 1
            for leaf in jax.tree.leaves(opt_state)):
        raise ValueError(
            "zero1 needs a flat sharded optimizer state — build the "
            "TrainState with GradComms.create_state(params, tx) (or "
            "comms.zero.init_opt_state), not TrainState.create")
