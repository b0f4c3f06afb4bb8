"""The declarative comms schedule: ZeRO-2/3 + backward-overlapped sync.

PR 3 stopped the training ladder at ZeRO-1: optimizer state sharded,
but full gradients still materialize on every replica and every grad
byte waits for the LAST backward op before it moves (one synchronous
bucketed sync at the end of backward). This module finishes the ladder
from the cross-replica weight-update sharding paper (PAPERS.md, arxiv
2004.13336) and makes the communication overlap with backward compute:

- **stage 2 (ZeRO-2)** — gradients reduce-scatter bucket-by-bucket
  *during* backward, directly into the flat ``P(dp)`` shard the ZeRO-1
  optimizer already owns. The mechanism is a per-bucket ``custom_vjp``
  hook: forward is the identity on that bucket's parameter leaves;
  backward intercepts the bucket's cotangent (its gradients, available
  the moment that slice of backward finishes) and reduce-scatters it in
  the configured wire format. The scattered chunk and the new int8
  error-feedback residual ride OUT of the backward pass as cotangents
  of zero-valued "token" inputs — no side channels, traces cleanly,
  ``jax.checkpoint``-compatible. Because each bucket's collective
  depends only on that bucket's grads, XLA's scheduler can move bucket
  k's bytes while bucket k-1 (the earlier layers) is still
  differentiating.
- **stage 3 (ZeRO-3)** — parameters shard at rest: ``TrainState
  .params`` is one flat padded fp32 vector sharded ``P(dp)``
  (per-replica param HBM ÷ N, same assertion surface as the ZeRO-1
  optimizer state). Forward all-gathers each bucket just in time
  through a ``custom_vjp`` gather hook whose backward IS the gradient
  reduce-scatter (the transpose of an all-gather), so ZeRO-3 subsumes
  ZeRO-2's overlapped grad sync for free; the gather is wrapped in
  ``jax.checkpoint`` so backward re-gathers instead of keeping the
  full gathered params alive (XLA may CSE the re-gather back into one
  all-gather when the buffer is live anyway — the accounting model
  prices what the compiled HLO actually contains).

Layout: parameters partition into **comm buckets** (whole leaves,
greedily grouped to ``bucket_mb``), each bucket padded to a multiple
of ``n_shards * bucket_size`` so the chunks quantized collectives
trade stay quantization-bucket-aligned. The global flat vector is the
concatenation of the padded buckets; replica *r*'s shard is the
concatenation of chunk *r* of every bucket. The optimizer update is
elementwise (the same structure-agnostic contract ZeRO-1 documents),
so this permuted layout is update-equivalent to the ZeRO-1 global
ravel — the parity tests pin it against the replicated optimizer.

Error feedback composes: the int8 phase-1 residual stays PER-SHARD
(each replica carries only its own ``(1, total_padded)`` row, sliced
per bucket inside the hooks), and the overlap-off tail sync derives
the exact same per-bucket RNG (``fold_in(sync_rng, bucket)``), so
overlap on/off is a pure scheduling choice: the loss trajectories are
element-for-element identical (test-pinned).

Front door: the ``comms:`` YAML block's schedule keys
(``stage``/``wire``/``overlap``/``bucket_mb``) build a
:class:`CommsSchedule` via :func:`make_schedule`;
``utils.make_step(comms=...)`` consumes it and
``CommsSchedule.create_state`` builds the matching
:class:`~torchbooster_tpu.utils.TrainState`. Legacy ``mode``/``zero1``
keys shim onto stages 0/1 unchanged (bit-for-bit the PR 3 paths).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from torchbooster_tpu.comms import GradComms, MODES, make_grad_comms

__all__ = ["BucketPlan", "CommsSchedule", "STAGES", "WIRES",
           "as_schedule", "make_schedule"]

STAGES = (0, 1, 2, 3)
WIRES = ("fp32", "bf16", "int8")


def _pad_to(n: int, multiple: int) -> int:
    return n + (-n) % multiple


# =========================================================================
# BucketPlan: the static leaf → comm-bucket partition
# =========================================================================

@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static partition of a parameter pytree into comm buckets.

    Everything here is trace-time metadata (python ints and the
    treedef) — the plan never holds arrays. Built once per
    (params, schedule) pair by :meth:`build`; the grouping depends
    only on leaf sizes and ``bucket_mb`` (never on the shard count),
    so plans built for different data-parallel worlds agree on the
    bucket boundaries — the property the different-dp checkpoint
    restore relies on.
    """

    n_shards: int
    bucket_size: int                       # quantization bucket (elems)
    treedef: Any
    shapes: tuple[tuple[int, ...], ...]    # per leaf
    dtypes: tuple[Any, ...]
    raw: tuple[int, ...]                   # per-bucket unpadded elems
    padded: tuple[int, ...]                # per-bucket padded elems
    spans: tuple[tuple[int, int], ...]     # per-bucket [leaf_lo, leaf_hi)

    @classmethod
    def build(cls, params: Any, n_shards: int, bucket_size: int,
              bucket_mb: float) -> "BucketPlan":
        leaves, treedef = jax.tree.flatten(params)
        if not leaves:
            raise ValueError("BucketPlan.build: empty parameter pytree")
        sizes = [int(np.prod(leaf.shape)) if leaf.shape else 1
                 for leaf in leaves]
        limit = float("inf") if bucket_mb <= 0 else bucket_mb * 1e6 / 4.0
        spans, raw = [], []
        lo, acc = 0, 0
        for i, size in enumerate(sizes):
            if acc > 0 and acc + size > limit:
                spans.append((lo, i))
                raw.append(acc)
                lo, acc = i, 0
            acc += size
        spans.append((lo, len(sizes)))
        raw.append(acc)
        multiple = max(1, n_shards) * bucket_size
        padded = tuple(_pad_to(r, multiple) for r in raw)
        return cls(n_shards=max(1, n_shards), bucket_size=bucket_size,
                   treedef=treedef,
                   shapes=tuple(tuple(leaf.shape) for leaf in leaves),
                   dtypes=tuple(leaf.dtype for leaf in leaves),
                   raw=tuple(raw), padded=padded, spans=tuple(spans))

    # ---- derived geometry (python ints, trace-static) ----

    @property
    def n_buckets(self) -> int:
        return len(self.raw)

    @property
    def total_raw(self) -> int:
        return sum(self.raw)

    @property
    def total_padded(self) -> int:
        return sum(self.padded)

    @property
    def chunks(self) -> tuple[int, ...]:
        """Per-bucket chunk (one replica's slice of that bucket)."""
        return tuple(p // self.n_shards for p in self.padded)

    @property
    def shard_size(self) -> int:
        return self.total_padded // self.n_shards

    def full_offset(self, b: int) -> int:
        return sum(self.padded[:b])

    def shard_offset(self, b: int) -> int:
        return sum(self.chunks[:b])

    # ---- traced packing/unpacking (jnp) ----

    def _bucket_leaves(self, b: int, leaves: list) -> list:
        lo, hi = self.spans[b]
        return leaves[lo:hi]

    def ravel_bucket(self, b: int, bucket_leaves: list) -> jax.Array:
        """Concat-ravel one bucket's leaves to fp32 and zero-pad to
        the bucket's padded size (pad is inert end-to-end: zero grads
        → zero updates → zero params, like the ZeRO-1 global pad)."""
        flat = jnp.concatenate(
            [leaf.reshape(-1).astype(jnp.float32)
             for leaf in bucket_leaves])
        return jnp.pad(flat, (0, self.padded[b] - self.raw[b]))

    def unravel_bucket(self, b: int, flat: jax.Array) -> list:
        lo, hi = self.spans[b]
        out, off = [], 0
        for shape, dtype in zip(self.shapes[lo:hi], self.dtypes[lo:hi]):
            size = int(np.prod(shape)) if shape else 1
            out.append(flat[off:off + size].reshape(shape).astype(dtype))
            off += size
        return out

    def pack(self, params: Any) -> jax.Array:
        """Full flat padded vector ``(total_padded,)`` in SHARD-MAJOR
        layout — ``flat[r·S : (r+1)·S]`` is replica *r*'s shard, which
        is the concat of its chunk of every bucket. This is what makes
        a plain leading-dim ``P(dp)`` sharding hand each replica
        exactly the chunks :meth:`pack_shard` / the gather hooks
        address — the at-rest form of ZeRO-3 params and the init input
        for the flat optimizer state."""
        leaves = jax.tree.leaves(params)
        buckets = [self.ravel_bucket(b, self._bucket_leaves(b, leaves))
                   for b in range(self.n_buckets)]
        shards = []
        for r in range(self.n_shards):
            shards.extend(bucket[r * c:(r + 1) * c]
                          for bucket, c in zip(buckets, self.chunks))
        return jnp.concatenate(shards)

    def pack_shard(self, params: Any, idx: jax.Array) -> jax.Array:
        """Replica ``idx``'s shard ``(shard_size,)`` of :meth:`pack`,
        sliced bucket-by-bucket (shard_map body code: ``idx`` is this
        replica's :func:`~torchbooster_tpu.comms.quantized
        .linear_index`)."""
        leaves = jax.tree.leaves(params)
        parts = []
        for b in range(self.n_buckets):
            flat = self.ravel_bucket(b, self._bucket_leaves(b, leaves))
            start = (idx * self.chunks[b]).astype(jnp.int32)
            parts.append(jax.lax.dynamic_slice(
                flat, (start,), (self.chunks[b],)))
        return jnp.concatenate(parts)

    def unpack(self, flat: jax.Array) -> Any:
        """Inverse of :meth:`pack` (full shard-major vector →
        parameter pytree)."""
        S = self.shard_size
        leaves = []
        for b in range(self.n_buckets):
            off, c = self.shard_offset(b), self.chunks[b]
            bucket = jnp.concatenate(
                [flat[r * S + off: r * S + off + c]
                 for r in range(self.n_shards)])
            leaves.extend(self.unravel_bucket(b, bucket))
        return jax.tree.unflatten(self.treedef, leaves)

    def gather_params(self, shard: jax.Array,
                      axes: tuple[str, ...]) -> Any:
        """shard_map body code: per-bucket tiled all-gather of this
        replica's chunks back to the full (replicated) pytree — the
        ZeRO-2 tail param gather."""
        leaves = []
        for b in range(self.n_buckets):
            off = self.shard_offset(b)
            full = jax.lax.all_gather(
                shard[off:off + self.chunks[b]], axes, tiled=True)
            leaves.extend(self.unravel_bucket(b, full))
        return jax.tree.unflatten(self.treedef, leaves)

    # ---- host-side (numpy) repacking for checkpoint resharding ----

    def strip_pads_host(self, flat: np.ndarray) -> np.ndarray:
        """``(total_padded,)`` shard-major host vector →
        ``(total_raw,)`` raw elements in bucket order (pads dropped) —
        the world-size-INDEPENDENT form checkpoints reshard through."""
        S = self.shard_size
        parts = []
        for b in range(self.n_buckets):
            off, c = self.shard_offset(b), self.chunks[b]
            bucket = np.concatenate(
                [flat[r * S + off: r * S + off + c]
                 for r in range(self.n_shards)])
            parts.append(bucket[:self.raw[b]])
        return np.concatenate(parts)

    def with_pads_host(self, raw: np.ndarray) -> np.ndarray:
        """Inverse of :meth:`strip_pads_host` for THIS plan's world
        size — the restore-on-a-different-dp repacking step."""
        buckets, off = [], 0
        for r, p in zip(self.raw, self.padded):
            buckets.append(np.concatenate(
                [raw[off:off + r], np.zeros(p - r, dtype=raw.dtype)]))
            off += r
        shards = []
        for rep in range(self.n_shards):
            shards.extend(bucket[rep * c:(rep + 1) * c]
                          for bucket, c in zip(buckets, self.chunks))
        return np.concatenate(shards)


# =========================================================================
# The per-bucket backward hooks
# =========================================================================

def _scatter_bucket(flat: jax.Array, ef: jax.Array | None,
                    rng: jax.Array, wire: str, axes: tuple[str, ...],
                    n: int, bucket_size: int
                    ) -> tuple[jax.Array, jax.Array | None]:
    """Reduce-scatter one bucket's local padded gradient in ``wire``
    format; returns ``(this replica's chunk of the mean, new error-
    feedback residual or None)``. Thin wrapper over
    :func:`~torchbooster_tpu.comms.quantized.reduce_flat` so the wire
    formats (and their HLO-validated byte accounting) stay
    single-sourced."""
    from torchbooster_tpu.comms.quantized import reduce_flat

    red, new_ef, _ = reduce_flat(flat, axes, n, wire, bucket_size, rng,
                                 ef, None, scatter=True)
    return red, new_ef


def _zero_like_cot(x: Any) -> Any:
    """A zero cotangent of ``x``'s type — float0 for integer primals
    (PRNG keys)."""
    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(jnp.shape(x), jax.dtypes.float0)


def _make_stage2_hook(plan: BucketPlan, b: int, wire: str,
                      axes: tuple[str, ...]) -> Callable:
    """Identity on bucket ``b``'s leaves whose BACKWARD reduce-scatters
    the bucket's cotangent the moment it exists. The scattered chunk
    (and, for int8, the new residual) leave the backward pass as the
    cotangents of the zero-valued token inputs; the parameter
    cotangent is zeroed (the grads have moved into the shard — nothing
    upstream should see them again)."""
    n, bucket = plan.n_shards, plan.bucket_size

    @jax.custom_vjp
    def hook(xs, t_chunk, t_ef, ef, rng):
        return xs

    def fwd(xs, t_chunk, t_ef, ef, rng):
        return xs, (ef, rng)

    def bwd(res, g):
        ef, rng = res
        flat = plan.ravel_bucket(b, list(g))
        chunk, new_ef = _scatter_bucket(flat, ef, rng, wire, axes, n,
                                        bucket)
        if new_ef is None:
            new_ef = jnp.zeros((0,), jnp.float32)
        return (tuple(jnp.zeros_like(x) for x in g), chunk, new_ef,
                _zero_like_cot(ef) if ef is not None
                else jnp.zeros((0,), jnp.float32),
                _zero_like_cot(rng))

    hook.defvjp(fwd, bwd)
    return hook


def _make_gather_hook(plan: BucketPlan, b: int, wire: str,
                      axes: tuple[str, ...]) -> Callable:
    """ZeRO-3's just-in-time param materialization for bucket ``b``:
    forward all-gathers this replica's chunk into the full padded
    bucket; backward IS the wire-format gradient reduce-scatter (the
    all-gather's transpose), so the chunk cotangent lands directly on
    the flat shard ``value_and_grad`` differentiates. Wrapped in
    ``jax.checkpoint`` by the caller so backward re-gathers instead of
    holding the gathered bucket across the whole forward."""
    n, bucket = plan.n_shards, plan.bucket_size

    @jax.custom_vjp
    def hook(chunk, t_ef, ef, rng):
        return jax.lax.all_gather(chunk, axes, tiled=True)

    def fwd(chunk, t_ef, ef, rng):
        return hook(chunk, t_ef, ef, rng), (ef, rng)

    def bwd(res, g):
        ef, rng = res
        chunk, new_ef = _scatter_bucket(g, ef, rng, wire, axes, n,
                                        bucket)
        if new_ef is None:
            new_ef = jnp.zeros((0,), jnp.float32)
        return (chunk, new_ef,
                _zero_like_cot(ef) if ef is not None
                else jnp.zeros((0,), jnp.float32),
                _zero_like_cot(rng))

    hook.defvjp(fwd, bwd)
    return hook


def _ef_slices(plan: BucketPlan, ef_row: jax.Array | None) -> list:
    """This replica's error-feedback row sliced per bucket (static
    offsets), or Nones when the wire carries no residual."""
    if ef_row is None:
        return [None] * plan.n_buckets
    out = []
    for b in range(plan.n_buckets):
        off = plan.full_offset(b)
        out.append(ef_row[off:off + plan.padded[b]])
    return out


def _bucket_rngs(plan: BucketPlan, sync_rng: jax.Array) -> list:
    """One stochastic-rounding key per bucket — derived identically by
    the overlapped hooks and the overlap-off tail sync, which is what
    makes overlap a pure scheduling choice (trajectory-identical)."""
    return [jax.random.fold_in(sync_rng, b)
            for b in range(plan.n_buckets)]


def hooked_params(plan: BucketPlan, params: Any, tokens: dict,
                  ef_row: jax.Array | None, sync_rng: jax.Array,
                  wire: str, axes: tuple[str, ...]) -> Any:
    """Stage-2 overlap: rebuild the parameter pytree with every bucket
    routed through its backward reduce-scatter hook."""
    leaves = jax.tree.leaves(params)
    efs = _ef_slices(plan, ef_row)
    rngs = _bucket_rngs(plan, sync_rng)
    out: list = []
    for b in range(plan.n_buckets):
        tok = tokens[f"b{b}"]
        hook = _make_stage2_hook(plan, b, wire, axes)
        ef = efs[b] if efs[b] is not None else jnp.zeros((0,),
                                                        jnp.float32)
        hooked = hook(tuple(plan._bucket_leaves(b, leaves)),
                      tok["g"], tok["ef"], ef, rngs[b])
        out.extend(hooked)
    return jax.tree.unflatten(plan.treedef, out)


def gathered_params(plan: BucketPlan, shard: jax.Array, tokens: dict,
                    ef_row: jax.Array | None, sync_rng: jax.Array,
                    wire: str, axes: tuple[str, ...]) -> Any:
    """Stage-3 forward: materialize the full pytree from the flat
    shard, bucket by bucket, through the gather hooks (backward =
    reduce-scatter + re-gather under ``jax.checkpoint``)."""
    efs = _ef_slices(plan, ef_row)
    rngs = _bucket_rngs(plan, sync_rng)
    leaves: list = []
    for b in range(plan.n_buckets):
        off = plan.shard_offset(b)
        chunk = shard[off:off + plan.chunks[b]]
        tok = tokens[f"b{b}"]
        hook = _make_gather_hook(plan, b, wire, axes)
        ef = efs[b] if efs[b] is not None else jnp.zeros((0,),
                                                        jnp.float32)
        full = jax.checkpoint(hook)(chunk, tok["ef"], ef, rngs[b])
        leaves.extend(plan.unravel_bucket(b, full))
    return jax.tree.unflatten(plan.treedef, leaves)


def _zero_tokens(plan: BucketPlan, int8: bool) -> dict:
    """Zero-valued token inputs whose cotangents carry the scattered
    chunks (stage 2) and new residuals (int8) out of backward."""
    toks = {}
    for b in range(plan.n_buckets):
        toks[f"b{b}"] = {
            "g": jnp.zeros((plan.chunks[b],), jnp.float32),
            "ef": jnp.zeros((plan.padded[b],) if int8 else (0,),
                            jnp.float32),
        }
    return toks


def scatter_grads(plan: BucketPlan, grads: Any,
                  ef_row: jax.Array | None, sync_rng: jax.Array,
                  wire: str, axes: tuple[str, ...]
                  ) -> tuple[jax.Array, jax.Array | None]:
    """The overlap-off tail sync: same per-bucket reduce-scatter (same
    wire, same per-bucket RNG and residual slices) issued after
    backward completes — element-for-element what the hooks compute,
    minus the chance to hide any byte."""
    leaves = jax.tree.leaves(grads)
    efs = _ef_slices(plan, ef_row)
    rngs = _bucket_rngs(plan, sync_rng)
    parts, new_efs = [], []
    for b in range(plan.n_buckets):
        flat = plan.ravel_bucket(b, plan._bucket_leaves(b, leaves))
        chunk, new_ef = _scatter_bucket(flat, efs[b], rngs[b], wire,
                                        axes, plan.n_shards,
                                        plan.bucket_size)
        parts.append(chunk)
        if new_ef is not None:
            new_efs.append(new_ef)
    g_shard = jnp.concatenate(parts)
    return g_shard, (jnp.concatenate(new_efs) if new_efs else None)


# =========================================================================
# CommsSchedule
# =========================================================================

@dataclasses.dataclass(frozen=True)
class CommsSchedule(GradComms):
    """The full gradient-communication plan: ZeRO stage, wire format,
    overlap, and bucketing — the declarative promotion of the ad-hoc
    ``make_step(comms=)`` modes. ``stage``/``wire``/``overlap`` are
    the composition axes (the YAML ``comms:`` schedule block);
    ``mode``/``zero1`` are kept consistent with them so every legacy
    consumer (and the stage ≤ 1 paths, which are bit-for-bit PR 3's)
    keeps working. Build with :func:`make_schedule` (validated), not
    the raw constructor."""

    stage: int = 0
    overlap: bool = False
    bucket_mb: float = 4.0

    @property
    def wire(self) -> str:
        """The gradient wire format (``implicit`` only via the legacy
        ``mode`` shim, stages 0-1)."""
        return self.mode

    def plan(self, params: Any = None) -> BucketPlan:
        """The (cached) bucket plan for this schedule. Needs a
        parameter pytree the first time — ``create_state`` builds and
        caches it; a restored stage-3 state (flat params, no pytree)
        requires :meth:`attach_plan` with a template first."""
        cached = getattr(self, "_plan", None)
        if cached is not None:
            return cached
        if params is None:
            raise ValueError(
                "CommsSchedule has no bucket plan yet — build states "
                "with create_state(params, tx), or attach_plan(params)"
                " with a template pytree first")
        bucket_mb = self.bucket_mb if self.stage >= 2 else 0.0
        built = BucketPlan.build(params, self.n_shards,
                                 self.bucket_size, bucket_mb)
        object.__setattr__(self, "_plan", built)
        return built

    def attach_plan(self, params: Any) -> BucketPlan:
        """Explicitly (re)build the bucket plan from a template pytree
        — the restore-side entry point."""
        object.__setattr__(self, "_plan", None)
        return self.plan(params)

    def init_state(self, params: Any) -> dict:
        if self.stage < 2:
            return super().init_state(params)
        if self.wire != "int8":
            return {}
        from torchbooster_tpu.comms.quantized import data_spec

        plan = self.plan(params)
        sharding = NamedSharding(self.mesh, data_spec(self.axes))
        return {"ef1": jax.device_put(
            jnp.zeros((self.n_shards, plan.total_padded), jnp.float32),
            sharding)}

    def create_state(self, params: Any, tx: Any, rng: Any = 0,
                     accumulate: bool = False, ema: bool = False):
        """Stage ≥ 2 states: flat dp-sharded optimizer state (like
        ZeRO-1) and, for stage 3, params stored AS the flat shard —
        per-replica param HBM ÷ N from the first byte (packed under a
        jit with sharded out_shardings, so the full vector never lands
        on one device)."""
        if self.stage < 2:
            return super().create_state(params, tx, rng=rng,
                                        accumulate=accumulate, ema=ema)
        if accumulate:
            raise ValueError(
                "comms stage >= 2 does not compose with gradient "
                "accumulation (the accumulator would need the scatter "
                "layout); accumulate on the implicit path instead")
        from torchbooster_tpu.comms import _noop_transform
        from torchbooster_tpu.comms.quantized import data_spec
        from torchbooster_tpu.utils import TrainState

        # defensive copy — same aliasing/donation hazard create_state
        # documents for ZeRO-1
        params = jax.tree.map(
            lambda l: jnp.array(l) if hasattr(l, "ndim") else l, params)
        plan = self.plan(params)
        sharded = NamedSharding(self.mesh, data_spec(self.axes))
        replicated = NamedSharding(self.mesh, P())

        state = TrainState.create(params, _noop_transform(), rng=rng,
                                  ema=ema)
        try:
            flat = jax.jit(plan.pack, out_shardings=sharded)(params)
        except TypeError:  # pragma: no cover — jax w/o out_shardings
            flat = jax.device_put(plan.pack(params), sharded)
        abstract = jax.eval_shape(tx.init, flat)
        from torchbooster_tpu.comms.zero import opt_state_specs

        specs = opt_state_specs(abstract, plan.total_padded, self.axes)
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        try:
            opt_state = jax.jit(tx.init, out_shardings=shardings)(flat)
        except TypeError:  # pragma: no cover
            opt_state = jax.tree.map(
                lambda leaf, sh: jax.device_put(leaf, sh),
                tx.init(flat), shardings, is_leaf=lambda x: x is None)

        if self.stage >= 3:
            placed_params: Any = flat
            ema_tree = jnp.array(flat) if ema else None
        else:
            placed_params = jax.tree.map(
                lambda l: jax.device_put(l, replicated)
                if hasattr(l, "ndim") else l, state.params)
            ema_tree = None
            if ema:
                ema_tree = jax.tree.map(
                    lambda l: jax.device_put(jnp.array(l), replicated),
                    placed_params)
        state = state.replace(
            params=placed_params, opt_state=opt_state, ema=ema_tree,
            step=jax.device_put(state.step, replicated),
            rng=jax.device_put(state.rng, replicated),
            comms=self.init_state(params))
        return state

    def gather_params(self, state_or_flat: Any) -> Any:
        """Host/jit helper: materialize the full parameter pytree from
        a stage-3 flat shard (or a ``TrainState`` holding one) — the
        eval/export/checkpoint-template path. Stage ≤ 2 states pass
        through unchanged."""
        flat = getattr(state_or_flat, "params", state_or_flat)
        if self.stage < 3:
            return flat
        plan = self.plan()
        return plan.unpack(jnp.asarray(flat))

    def step_traffic(self, n_params: int) -> dict:
        from torchbooster_tpu.comms import accounting

        plan = getattr(self, "_plan", None)
        return accounting.step_traffic(
            n_params, self.n_shards, self.mode, self.zero1,
            self.bucket_size, stage=self.stage, overlap=self.overlap,
            padded=plan.total_padded if plan is not None else None)


def make_schedule(mesh: Any, stage: int = 0, wire: str = "fp32",
                  overlap: bool = False, bucket_mb: float = 4.0,
                  bucket_size: int = 512) -> CommsSchedule:
    """Validated :class:`CommsSchedule` constructor — the workhorse
    behind ``CommsConfig.make``'s schedule keys. Errors name the YAML
    keys so a bad block is a one-line fix."""
    if stage not in STAGES:
        raise ValueError(
            f"comms.stage: {stage!r} — expected one of {STAGES}")
    if wire not in WIRES and wire != "implicit":
        raise ValueError(
            f"comms.wire: {wire!r} — expected one of {WIRES}")
    if wire == "implicit" and stage >= 2:
        raise ValueError(
            f"comms.stage: {stage} needs an explicit wire format (the "
            f"reduce-scatter is explicit); set comms.wire to one of "
            f"{WIRES}")
    if overlap and stage < 2:
        raise ValueError(
            f"comms.overlap: true needs comms.stage: 2 or 3 (got "
            f"comms.stage: {stage}) — stages 0/1 sync once at the "
            f"tail; only the per-bucket backward reduce-scatter "
            f"overlaps")
    if bucket_mb <= 0:
        raise ValueError(
            f"comms.bucket_mb must be positive, got {bucket_mb}")
    # stage 3 has no serialized variant: the gather hooks' backward IS
    # the reduce-scatter, inside backward by construction — normalize
    # so the schedule reports the truth instead of carrying a knob
    # whose overlap-off A/B arm would silently compile the same program
    if stage == 3:
        overlap = True
    # mesh/mode validation is shared with the legacy constructor —
    # same pure-data-parallel-mesh and bucket_size rules
    make_grad_comms(mesh, mode=wire if wire in MODES else "fp32",
                    zero1=stage >= 1, bucket_size=bucket_size)
    return CommsSchedule(mesh=mesh, mode=wire, zero1=stage >= 1,
                         bucket_size=int(bucket_size), stage=int(stage),
                         overlap=bool(overlap),
                         bucket_mb=float(bucket_mb))


def as_schedule(comms: Any) -> CommsSchedule:
    """Normalize a legacy :class:`GradComms` (or a schedule) to a
    :class:`CommsSchedule` — the ``mode``/``zero1`` → stage mapping
    the config shim documents."""
    if isinstance(comms, CommsSchedule):
        return comms
    return CommsSchedule(mesh=comms.mesh, mode=comms.mode,
                         zero1=comms.zero1,
                         bucket_size=comms.bucket_size,
                         stage=1 if comms.zero1 else 0, overlap=False)


# =========================================================================
# The stage-2/3 compiled step body
# =========================================================================

def sharded_step(
    sched: CommsSchedule,
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    clip: float | None,
    params: Any,
    opt_state: Any,
    comms_state: dict,
    batch: Any,
    rng: jax.Array,
    has_aux: bool = True,
) -> tuple[tuple[jax.Array, dict], Any, Any, dict]:
    """One ZeRO-2/3 train step (traced inside ``make_step``'s jit):
    per-replica fwd+bwd under ONE shard_map over the data axes, the
    gradient reduce-scatter issued per bucket (inside backward when
    ``overlap`` — the hooks — or at the tail otherwise, identical
    math), the elementwise optimizer update on this replica's flat
    shard, and the params either re-gathered (stage 2, replicated
    out) or kept as the shard (stage 3).

    Returns ``((loss, aux), new_params, new_opt_state,
    new_comms_state)`` with loss/aux pmean'd."""
    from torchbooster_tpu.comms.quantized import data_spec, linear_index
    from torchbooster_tpu.comms.zero import (_check_flat_state,
                                             opt_state_specs)

    mesh, axes = sched.mesh, sched.axes
    sizes = tuple(mesh.shape[a] for a in axes)
    n = sched.n_shards
    wire, stage, overlap = sched.wire, sched.stage, sched.overlap
    int8 = wire == "int8"
    plan = sched.plan(params if stage == 2 else None)
    _check_flat_state(opt_state, plan.total_padded)
    specs = opt_state_specs(opt_state, plan.total_padded, axes)
    dspec = data_spec(axes)
    param_spec = dspec if stage >= 3 else P()
    comms_spec = jax.tree.map(lambda _: dspec, comms_state)

    def body(params, opt_shard, comms_state, batch, rng):
        idx = linear_index(axes, sizes)
        local_rng = jax.random.fold_in(rng, idx)
        sync_rng = jax.random.fold_in(rng, n + idx)
        ef_row = None
        if int8:
            ef_row = comms_state["ef1"].reshape(-1)
        tokens = _zero_tokens(plan, int8)

        def call_loss(p):
            out = loss_fn(p, batch, local_rng)
            return out if has_aux else (out, {})

        new_ef = None
        if stage >= 3:
            def wrapped(shard, tokens):
                full = gathered_params(plan, shard, tokens, ef_row,
                                       sync_rng, wire, axes)
                return call_loss(full)

            (loss, aux), (g_shard, gtok) = jax.value_and_grad(
                wrapped, argnums=(0, 1), has_aux=True)(params, tokens)
            if int8:
                new_ef = jnp.concatenate(
                    [gtok[f"b{b}"]["ef"] for b in range(plan.n_buckets)])
            p_shard = params
        elif overlap:
            def wrapped(p, tokens):
                hooked = hooked_params(plan, p, tokens, ef_row,
                                       sync_rng, wire, axes)
                return call_loss(hooked)

            (loss, aux), gtok = jax.value_and_grad(
                wrapped, argnums=1, has_aux=True)(params, tokens)
            g_shard = jnp.concatenate(
                [gtok[f"b{b}"]["g"] for b in range(plan.n_buckets)])
            if int8:
                new_ef = jnp.concatenate(
                    [gtok[f"b{b}"]["ef"] for b in range(plan.n_buckets)])
            p_shard = plan.pack_shard(params, idx)
        else:
            (loss, aux), grads = jax.value_and_grad(
                call_loss, has_aux=True)(params)
            g_shard, new_ef = scatter_grads(plan, grads, ef_row,
                                            sync_rng, wire, axes)
            p_shard = plan.pack_shard(params, idx)

        new_comms = {}
        if int8 and new_ef is not None:
            new_comms = {"ef1": new_ef[None]}
        if clip is not None:
            # pad regions are zero → contribute nothing to the norm
            norm = jnp.sqrt(jax.lax.psum(jnp.sum(g_shard * g_shard),
                                         axes))
            g_shard = g_shard * jnp.minimum(1.0, clip / (norm + 1e-6))
        updates, new_opt = tx.update(g_shard, opt_shard, p_shard)
        new_shard = optax.apply_updates(p_shard, updates)
        if stage >= 3:
            params_out: Any = new_shard
        else:
            params_out = plan.gather_params(new_shard, axes)
        loss = jax.lax.pmean(loss, axes)
        aux = jax.tree.map(lambda a: jax.lax.pmean(a, axes), aux)
        return (loss, aux), params_out, new_opt, new_comms

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_spec, specs, comms_spec, dspec, P()),
        out_specs=((P(), P()), param_spec, specs, comms_spec),
        check_vma=False)
    return mapped(params, opt_state, comms_state, batch, rng)
