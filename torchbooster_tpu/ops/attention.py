"""Attention dispatch: one call site, backend-appropriate kernel.

``attention(q, k, v, causal=...)`` takes (B, S, H, D) tensors and
routes to the pallas flash kernel on TPU (ops.flash_attention) or the
fused-by-XLA jnp reference elsewhere. The reference implementation is
also the numerical ground truth for kernel tests.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def expand_kv_heads(kv: jax.Array, rep: int) -> jax.Array:
    """THE grouped→query head-expansion convention: block-repeat on the
    head axis, so query head ``h`` reads grouped head ``h // rep``.
    Every consumer (reference math, SP fallbacks, GPT cache) and the
    flash kernels' ``b // rep`` index maps assume exactly this ordering
    — keep it in one place."""
    return kv if rep == 1 else jnp.repeat(kv, rep, axis=2)


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True,
                  sm_scale: float | None = None) -> jax.Array:
    """Plain attention over (B, S, H, D): softmax(QKᵀ/√d + mask)V.
    Softmax in fp32 regardless of compute dtype (bf16 scores lose too
    much around the max). Grouped (GQA) k/v expand to the query head
    count here — the reference path has no grouped math."""
    *_, head_dim = q.shape
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k, v = expand_kv_heads(k, rep), expand_kv_heads(v, rep)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores * sm_scale
    if causal:
        seq_q, seq_k = scores.shape[-2:]
        mask = jnp.tril(jnp.ones((seq_q, seq_k), bool), seq_k - seq_q)
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Where the kernel starts to win on a v5e, forward + backward at head
# size 64 and 16k tokens a call (PERF.md section 6, PR 31): 3.4 ms
# against mha_reference's 5.4 at S=512, 3.7 against 3.0 at S=256; the
# forward alone crosses at the same length
FLASH_MIN_SEQ = 512


def flash_auto_engaged(seq_len_q: int, seq_len_kv: int | None = None) -> bool:
    """THE predicate ``attention(impl="auto")`` evaluates — exposed so
    callers (chip_smoke.py's dispatch assertion) test the real
    dispatch rather than a lookalike check that can drift from it."""
    from torchbooster_tpu.ops.flash_attention import tileable

    if seq_len_kv is None:
        seq_len_kv = seq_len_q
    return (_on_tpu() and seq_len_q >= FLASH_MIN_SEQ
            and tileable(seq_len_q) and tileable(seq_len_kv))


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True, sm_scale: float | None = None,
              impl: str = "auto",
              mesh: jax.sharding.Mesh | None = None) -> jax.Array:
    """(B, S, H, D) attention. ``impl``: "auto", "flash",
    "flash_interpret" (CPU-debuggable kernel), or "reference".

    "auto" picks by measured crossover on v5e (:data:`FLASH_MIN_SEQ`):
    from there up the pallas flash kernel keeps the (S, S) scores in
    VMEM, below it XLA's fused reference is faster. Off-TPU always
    reference. Each call counts its choice, at trace time, in
    ``attention_dispatch_total{impl}``.

    ``mesh``: the mesh of a caller traced under plain ``jit`` (the
    partitioner cannot split a ``pallas_call``): the kernel then runs
    per device under ``shard_map``, batch over the mesh's ``dp`` /
    ``fsdp`` axes and heads over ``tp`` where they divide, sequence
    whole. Callers already inside a ``shard_map`` (the pipeline, the
    ``sp`` strategies) pass none."""
    from torchbooster_tpu.observability import get_registry

    if impl == "auto":
        impl = ("flash" if flash_auto_engaged(q.shape[1], k.shape[1])
                else "reference")
    get_registry().counter(
        "attention_dispatch_total",
        "attention() calls traced, by the implementation chosen",
    ).inc(impl="reference" if impl == "reference" else "flash")
    if impl == "reference":
        return mha_reference(q, k, v, causal, sm_scale)
    local = functools.partial(_flash_folded, causal=causal,
                              sm_scale=sm_scale,
                              interpret=impl == "flash_interpret")
    if mesh is not None and mesh.size > 1:
        axes = mesh.axis_names
        data = tuple(a for a in ("dp", "fsdp") if a in axes) or None
        tp = ("tp" if "tp" in axes and q.shape[2] % mesh.shape["tp"] == 0
              and k.shape[2] % mesh.shape["tp"] == 0 else None)
        spec = jax.sharding.PartitionSpec(data, None, tp, None)
        local = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                              out_specs=spec, check_vma=False)
    return local(q, k, v)


def _flash_folded(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool, sm_scale: float | None,
                  interpret: bool) -> jax.Array:
    from torchbooster_tpu.ops.flash_attention import flash_attention

    b, s_q, h, d = q.shape
    s_kv, h_kv = k.shape[1], k.shape[2]
    # fold heads into batch: kernel grid parallelizes over B*H. Grouped
    # (GQA) k/v fold at their OWN width — the kernel indexes grouped
    # tiles directly (ops/flash_attention.py), so the expansion never
    # exists in HBM
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s_q, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h_kv, s_kv, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h_kv, s_kv, d)
    out = flash_attention(qf, kf, vf, causal=causal, sm_scale=sm_scale,
                          interpret=interpret)
    return out.reshape(b, h, s_q, d).transpose(0, 2, 1, 3)


__all__ = ["attention", "expand_kv_heads", "flash_auto_engaged",
           "mha_reference"]
