"""Tensor-parallel serving: the paged engine's head-sharded mesh layer.

Per-chip decode is HBM-bandwidth-bound on KV bytes (docs/performance.md
roofline), so the one way the serving engine tracks the hardware past a
single chip is dividing those bytes: shard the ATTENTION of every
compiled serving step — Q/K/V/O projections, the KV page pool, the
decode pool sweep, the pallas table walk, and the fused speculative
verify — over a ``tp`` (heads) mesh axis, Megatron-style. Everything
host-side stays exactly as it is: block tables, refcounts, the prefix
index, and all seat/retire/evict/CoW scheduling are replicated VALUES,
so every chip walks the same tables over its own head shard and the
engine's bookkeeping does not change at all.

Layout (the SNIPPETS partition-spec table, narrowed to serving):

- ``attn_qkv`` — column-parallel over tp with RANK-MAJOR columns
  (``qkv_to_tp_major``: rank i holds ``[q_i | k_i | v_i]``, its
  contiguous head subset of each section — a contiguous split of the
  canonical ``[q | k | v]`` stack would hand rank 0 all of q);
- ``attn_proj`` — row-parallel over tp (input rows follow the local
  heads), ONE psum before the replicated bias — the single cross-chip
  collective of a serving step (:func:`step_traffic` prices it;
  ``comms/accounting.xla_collective_traffic`` verifies the compiled
  step agrees);
- the KV page pool — sharded on its ``kv_heads`` axis: each chip's
  pool shard holds its local KV-head slice of EVERY page, so
  bytes/step per chip are the single-chip engine's ÷ tp;
- everything else — embeddings, MLP, LM head, sampling — replicated
  compute over replicated weights (serving decode is KV-bytes-bound,
  not weight-bound; redundant MLP math costs no wire and keeps the
  collective count at exactly one).

GQA shards by KV-HEAD GROUPS: query heads follow their group (local
query head j on rank i is global head ``i·H/tp + j``, whose group is
local group ``j // rep`` of rank i's KV slice), which is why ``tp``
must divide ``n_kv_heads`` — MHA degenerates to ``tp | n_heads``.
The pallas kernel path shards the same way with NO kernel changes:
``kernel_args()`` work lists are sharding-oblivious host values, so
the in-kernel page walk runs per-shard over the heads-sliced pool.

``tp=1`` never reaches this module's wrappers: the engine keeps its
un-wrapped jits and the compiled artifacts are bit-for-bit the
single-chip engine's.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from torchbooster_tpu.parallel.sharding import path_str

# the page pool (kv_pages.make_pool): (n_layers, n_pages, page_size,
# kv_width), heads and head dim merged into one minor row that is
# padded per tp shard — so sharding the row IS sharding the KV heads,
# each rank's slice contiguous (an int8 pool's scales end in kv_heads,
# so one spec serves both leaves)
POOL_SPEC = P(None, None, None, "tp")
REP = P()


def check_tp(tp: int, cfg: Any, mesh: Mesh | None) -> None:
    """Loud, number-carrying validation of a serving ``tp`` request —
    shared by ``ServingConfig`` (YAML-time) and the engine ctor
    (build-time) so both fail with the same story.

    Rejects: non-positive ``tp``; ``tp`` that does not divide the
    KV-head count (``n_kv_heads`` under GQA — query heads follow
    their group — or ``n_heads`` under MHA); a ``tp > 1`` build with
    no committed mesh; a mesh without a ``tp`` axis; and a mesh whose
    ``tp`` axis size differs from ``tp`` (the shard_map split must be
    exact — a bigger axis silently under-using chips is as wrong as a
    smaller one over-asking)."""
    if tp < 1:
        raise ValueError(f"serving.tp must be >= 1, got {tp}")
    if tp == 1:
        return
    if cfg.n_kv_heads and cfg.kv_heads % tp:
        raise ValueError(
            f"serving.tp={tp} does not divide n_kv_heads="
            f"{cfg.kv_heads}: GQA shards by KV-head groups (query "
            "heads follow their group), so tp must divide the "
            "KV-head count")
    if cfg.n_heads % tp:        # MHA (n_kv_heads unset): kv == heads
        raise ValueError(
            f"serving.tp={tp} does not divide n_heads={cfg.n_heads}: "
            "tensor-parallel serving shards attention by heads")
    if mesh is None:
        raise ValueError(
            f"serving.tp={tp} needs a committed mesh with a 'tp' "
            f"axis of size {tp} (e.g. make_mesh('tp:{tp}')); got no "
            "mesh — the engine will not guess a device topology")
    if "tp" not in mesh.axis_names:
        raise ValueError(
            f"serving.tp={tp} but the mesh axes {mesh.axis_names} "
            "have no 'tp' axis to shard heads over")
    size = mesh.shape["tp"]
    if tp > size:
        raise ValueError(
            f"serving.tp={tp} exceeds the mesh's tp axis size "
            f"{size}: there are not enough chips on the axis")
    if tp != size:
        raise ValueError(
            f"serving.tp={tp} mismatches the mesh's tp axis size "
            f"{size}: the head shard_map split must be exact — "
            f"commit a mesh with tp:{tp}")


def param_specs(params: Any) -> Any:
    """PartitionSpec pytree for the serving engine's params at tp>1:
    qkv column-parallel (rank-major columns — the caller permuted with
    ``qkv_to_tp_major`` first), O-projection row-parallel, everything
    else (embeddings, MLP, norms, LM head, the ``_tp_major`` marker
    leaf) replicated. Leading ``None`` is the stacked layer axis.

    Quantized weights (models/quant.py) shard their SCALES alongside
    their kernels, per the SNIPPETS partition-spec table: qkv's
    qkernel/qscale follow the column split (out axis — both the int8
    per-channel ``(L, 1, out)`` and int4 per-group ``(L, G, out)``
    scale shapes carry out last); attn_proj's qkernel follows the row
    split (input axis — int4's packed bytes and groups both live
    there, so its ``(L, G, d)`` qscale row-shards too), while the
    int8 per-OUTPUT-channel proj scale ``(L, 1, d)`` is the same for
    every row shard and stays replicated (the scale multiply commutes
    with the psum)."""

    def assign(path: tuple, leaf: Any) -> P:
        name = path_str(path)
        if name.endswith("attn_qkv/kernel") \
                or name.endswith("attn_qkv/qkernel") \
                or name.endswith("attn_qkv/qscale"):
            return P(None, None, "tp")
        if name.endswith("attn_qkv/bias"):
            return P(None, "tp")
        if name.endswith("attn_proj/kernel") \
                or name.endswith("attn_proj/qkernel"):
            return P(None, "tp", None)
        if name.endswith("attn_proj/qscale"):
            # int4 group scales ride the (row-sharded) input axis;
            # the int8 per-channel scale's input axis is 1 — nothing
            # to shard, every rank applies the same channel scales
            return P(None, "tp", None) if leaf.shape[1] > 1 else P()
        return P()

    return jax.tree_util.tree_map_with_path(assign, params)


def place(params: Any, pool: dict, mesh: Mesh) -> tuple[Any, dict]:
    """One-time device placement of (tp-major) params and the page
    pool onto the mesh — engine construction only, never per step:
    after this the jitted steps see correctly-laid-out operands and
    move nothing."""
    specs = param_specs(params)
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    pool = jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, POOL_SPEC)),
        pool)
    return params, pool


def shard_engine_fn(fn, mesh: Mesh, pspecs: Any, n_host_args: int,
                    n_rep_out: int):
    """Wrap one engine step function (``_chunk_fn`` / ``_decode_fn`` /
    the verify fn) in shard_map over the tp axis AND jit it with the
    engine's donation + pinned output shardings. Argument convention
    (shared by all three): ``(params, pool_k, pool_v, *host_args)``
    in — the packed operand buffer, the rng key, then what a mode
    rides beside them — and ``(*replicated_outputs, pool_k, pool_v)``
    out, the new key first — pools sharded on KV heads, the buffer,
    the key and every operand beside them replicated, and
    the post-psum outputs replicated by construction (``check_vma=
    False``: the pallas table walk inside defeats the static
    replication checker; the token-parity tests are the behavioral
    check).

    ``out_shardings`` is pinned to the SAME NamedShardings
    :func:`place` committed at construction: without the pin, a
    step's output pool carries a differently-EXPRESSED (but
    layout-identical) sharding than the placed input pool did, so the
    executable's second call registers a spurious extra jit-cache
    entry — no retrace, no recompile, but the ``*_compiles``
    observables (the zero-recompile contract's proof, and the flight
    recorder's recompile flag) would read 2 where nothing was ever
    rebuilt. Donation mirrors the single-chip engine: the pool is
    updated in place every call."""
    in_specs = (pspecs, POOL_SPEC, POOL_SPEC) + (REP,) * n_host_args
    out_specs = (REP,) * n_rep_out + (POOL_SPEC, POOL_SPEC)
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    pool_ns = NamedSharding(mesh, POOL_SPEC)
    rep_ns = NamedSharding(mesh, REP)
    return jax.jit(sharded, donate_argnums=(1, 2),
                   out_shardings=(rep_ns,) * n_rep_out
                   + (pool_ns, pool_ns))


def step_traffic(tp: int, cfg: Any, max_slots: int, compute_dtype: Any,
                 s_q: int = 1) -> dict:
    """Closed-form per-chip wire bytes of ONE serving step's
    decode-output psum — the tensor-parallel analogue of
    ``comms/accounting.step_traffic``, priced with the same ring
    all-reduce convention (``2·(N-1)/N·B``).

    The sharded step has exactly ONE collective: the psum of the
    row-parallel O-projection's partial products, payload
    ``max_slots · s_q · d_model`` activations in compute dtype
    (``s_q=1`` decode, ``1 + draft_len`` speculative verify). It sits
    inside the layer scan, so the compiled module carries ONE
    all-reduce instruction executed ``n_layers`` times per step —
    ``per_layer_wire_bytes`` is what ``xla_collective_traffic`` would
    read off the HLO, ``wire_bytes`` the per-step total the
    ``serving_tp_bytes_total`` counter accumulates.
    """
    if tp <= 1:
        return {"tp": max(tp, 1), "payload_bytes": 0,
                "per_layer_wire_bytes": 0.0, "wire_bytes": 0.0,
                "psums_per_step": 0}
    import jax.numpy as jnp

    payload = max_slots * s_q * cfg.d_model * jnp.dtype(
        compute_dtype).itemsize
    per_layer = 2 * (tp - 1) / tp * payload
    return {"tp": tp, "payload_bytes": payload,
            "per_layer_wire_bytes": round(per_layer, 1),
            "wire_bytes": round(cfg.n_layers * per_layer, 1),
            "psums_per_step": cfg.n_layers}


__all__ = ["POOL_SPEC", "check_tp", "param_specs", "place",
           "shard_engine_fn", "step_traffic"]
