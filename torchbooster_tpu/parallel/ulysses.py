"""All-to-all (Ulysses-style) sequence parallelism: the second SP
strategy next to ring attention (parallel/ring.py).

Layout dance: q/k/v arrive sequence-sharded (each device holds an
S/sp slice of every head). One ``all_to_all`` per tensor re-shards
them head-wise — afterwards each device holds the FULL sequence for
H/sp heads — so attention is one dense local call with ordinary causal
masking (and, on TPU, the pallas flash kernel: the all-to-all form is
the only SP strategy that can use it, because the kernel needs the
whole key sequence on-device). A final all-to-all restores sequence
sharding for the rest of the network.

Trade-offs vs the ring (when a mesh has a real ``sp`` axis):

- ring: O(S/sp) activation memory per device, K/V circulate in ``sp``
  ppermute hops overlapped with compute; works for any head count; on
  TPU the per-chunk body IS the pallas flash kernel (ring-flash, with
  log-sum-exp chunk merging), blocked-XLA online softmax elsewhere.
- all-to-all: 4 collectives total (3 in, 1 out) moving O(S/sp·H·D)
  each, attention runs on full S locally (flash-friendly, exact tril
  mask), but needs H % (sp·tp) == 0 and the full-S attention working
  set must fit one device.

Grouped-query attention composes without inflating the wire: when the
grouped K/V head count divides the mesh layout, K/V ride the
collectives UN-expanded (n_heads/kv_heads × less ICI traffic and ring
transfer) and stay grouped into the local attention (the flash kernel
reads grouped tiles natively; the XLA reference expands internally);
otherwise the front door falls back to pre-expansion, so any
head-count combination stays correct.

Heuristic (``sequence_attention(strategy="auto")``): all-to-all when
the head counts divide, ring otherwise — matching the published
guidance (Ulysses for H ≥ sp, ring for extreme S or few heads).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _ulysses_local(q: jax.Array, k: jax.Array, v: jax.Array, *, axis: str,
                   causal: bool, sm_scale: float, impl: str,
                   rep: int) -> jax.Array:
    """Per-device body under shard_map: q (B, S_loc, Hq_loc, D) and
    k/v (B, S_loc, Hkv_loc, D) sequence shards; returns the q-shaped
    attention output, sequence-sharded again."""
    from torchbooster_tpu.ops.attention import attention

    # seq-sharded → head-sharded: split heads, gather seq. q and the
    # (stacked) k/v pair reshard separately when head counts differ;
    # grouped K/V stay grouped through the wire AND into the local
    # attention — the dispatcher (flash kernel included) reads grouped
    # widths natively, so the expansion never materializes.
    if rep == 1:
        qkv = lax.all_to_all(jnp.stack([q, k, v]), axis, split_axis=3,
                             concat_axis=2, tiled=True)
        qh, kh, vh = qkv
    else:
        qh = lax.all_to_all(q, axis, split_axis=2, concat_axis=1,
                            tiled=True)
        kv = lax.all_to_all(jnp.stack([k, v]), axis, split_axis=3,
                            concat_axis=2, tiled=True)
        # stay grouped INTO the local attention too: the dispatcher
        # (and the flash kernel) handle grouped widths natively
        kh, vh = kv[0], kv[1]
    out = attention(qh, kh, vh, causal=causal, sm_scale=sm_scale, impl=impl)
    # head-sharded → seq-sharded: split seq (1), gather heads (2)
    return lax.all_to_all(out, axis, split_axis=1, concat_axis=2, tiled=True)


def _validate_heads(q: jax.Array, k: jax.Array) -> int:
    n_heads, kv_heads = q.shape[2], k.shape[2]
    if n_heads % kv_heads:
        raise ValueError(f"query heads ({n_heads}) not divisible by "
                         f"kv heads ({kv_heads})")
    return n_heads // kv_heads


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                      causal: bool = True, sm_scale: float | None = None,
                      axis: str = "sp", impl: str = "auto") -> jax.Array:
    """Exact attention over (B, S, H, D) with S sharded on ``axis``.

    Same contract as :func:`parallel.ring.ring_attention` (drop-in);
    requires the per-device head counts (query AND grouped k/v) to
    divide by the ``sp`` size. ``impl`` feeds the local attention
    dispatch ("auto" engages the flash kernel on TPU from S≥512).
    """
    *_, n_heads, head_dim = q.shape
    rep = _validate_heads(q, k)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    sp_size = mesh.shape[axis]
    tp_size = mesh.shape.get("tp", 1)
    for name, heads in (("query", n_heads), ("kv", k.shape[2])):
        if heads % tp_size or (heads // tp_size) % sp_size:
            raise ValueError(
                f"ulysses_attention needs {name} heads ({heads}) "
                f"divisible by tp·sp ({tp_size}·{sp_size}); expand K/V "
                "first or use ring_attention")

    data = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names) or None
    tp = "tp" if "tp" in mesh.axis_names else None
    spec = P(data, axis, tp, None)

    body = functools.partial(_ulysses_local, axis=axis, causal=causal,
                             sm_scale=sm_scale, impl=impl, rep=rep)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def sequence_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                       causal: bool = True, sm_scale: float | None = None,
                       axis: str = "sp", strategy: str = "auto",
                       impl: str = "auto") -> jax.Array:
    """One front door for sequence-parallel attention.

    ``strategy``: "ring", "ulysses", or "auto" (all-to-all whenever the
    head counts divide — it is never slower on TPU meshes where both
    apply, and unlocks the flash kernel; ring is the fallback that
    always works). K/V may carry fewer (grouped) heads than q: they
    stay grouped across the collectives when the mesh layout divides,
    and are pre-expanded otherwise. ``impl`` feeds both strategies'
    local body dispatch: the all-to-all's full-sequence attention, or
    the ring's per-chunk body (pallas ring-flash on TPU, blocked-XLA
    online softmax otherwise — parallel/ring.py).
    """
    from torchbooster_tpu.parallel.ring import ring_attention

    rep = _validate_heads(q, k)
    n_heads, kv_heads = q.shape[2], k.shape[2]
    sp_size = mesh.shape[axis]
    tp_size = mesh.shape.get("tp", 1)

    def divides(heads: int, with_sp: bool) -> bool:
        return heads % tp_size == 0 and (
            not with_sp or (heads // tp_size) % sp_size == 0)

    if strategy == "auto":
        strategy = "ulysses" if divides(n_heads, True) else "ring"
        # GQA wire cost: if grouped K/V fit the ring but would need
        # rep-times expansion to ride the all-to-alls, the ring moves
        # far fewer bytes — prefer it (the "ulysses never slower"
        # rationale assumed K/V at query width)
        if (strategy == "ulysses" and rep > 1
                and not divides(kv_heads, True)
                and divides(kv_heads, False)):
            strategy = "ring"
    # grouped K/V must fit the strategy's layout; expand as a fallback
    grouped_ok = (divides(kv_heads, strategy == "ulysses")
                  if rep > 1 else True)
    if rep > 1 and not grouped_ok:
        from torchbooster_tpu.ops.attention import expand_kv_heads

        k, v = expand_kv_heads(k, rep), expand_kv_heads(v, rep)
    if strategy == "ulysses":
        return ulysses_attention(q, k, v, mesh, causal=causal,
                                 sm_scale=sm_scale, axis=axis, impl=impl)
    if strategy == "ring":
        return ring_attention(q, k, v, mesh, causal=causal,
                              sm_scale=sm_scale, axis=axis, impl=impl)
    raise ValueError(f"unknown sequence-parallel strategy {strategy!r}")


__all__ = ["sequence_attention", "ulysses_attention"]
