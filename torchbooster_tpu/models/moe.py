"""Mixture-of-Experts layer with expert parallelism (GShard-style).

No reference counterpart (the reference has no transformer at all,
SURVEY §5.7); this is the ``ep`` mesh axis made real. Tokens are routed
top-k into per-expert capacity buffers — by flat-index scatter/gather
(default; O(T·d + E·C·d) peak memory) or by the GShard one-hot
dispatch/combine einsums (the O(T·E·C) parity oracle) — the expert MLPs
run as one batched einsum over the stacked expert weights, and results
combine back weighted by the gate. Everything has static shapes — XLA
turns the expert-axis sharding (``P("ep", ...)``) into the collective
pair around the expert compute; there is no host-side routing.

Design notes (TPU-first):
- capacity is static: ``C = ceil(k·T/E · capacity_factor)`` — overflow
  tokens drop (standard GShard semantics), keeping shapes compile-time
  constant.
- the auxiliary load-balance loss (Switch/GShard ``mean(frac·prob)·E``)
  is returned alongside the output; recipes add it to the task loss.
- position-in-expert is computed with a cumsum over tokens — O(T·E)
  on the VPU, no sort; the default dispatch then moves tokens by flat
  1-D scatter-add / gather (whose transposes are each other, so the
  path is differentiable for free).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchbooster_tpu.models import layers as L

# rules fragment for a stacked-MoE block (leading axis = scan layer);
# experts shard over ep, hidden over tp — the (E, C, d) expert batch
# (scatter-buffer reshape, or the oracle's dispatch einsum) meets the
# P("ep", ...) weights in the expert matmuls, where XLA places the
# resharding collective
SHARDING_RULES = [
    (r"moe_gate/kernel", P(None, None, None)),
    (r"moe_fc1/kernel", P(None, "ep", None, "tp")),
    (r"moe_fc1/bias", P(None, "ep", "tp")),
    (r"moe_fc2/kernel", P(None, "ep", "tp", None)),
    (r"moe_fc2/bias", P(None, "ep", None)),
]


def moe_init(rng: jax.Array, n_experts: int, d_model: int, hidden: int,
             std: float = 0.02, out_std: float | None = None,
             dtype: Any = jnp.float32) -> dict:
    """Stacked expert MLP + gate: fc1 (E, d, h), fc2 (E, h, d)."""
    k_gate, k1, k2 = jax.random.split(rng, 3)
    out_std = std if out_std is None else out_std
    return {
        "moe_gate": L.dense_init(k_gate, d_model, n_experts, std=std,
                                 use_bias=False, dtype=dtype),
        "moe_fc1": {
            "kernel": std * jax.random.normal(
                k1, (n_experts, d_model, hidden), dtype),
            "bias": jnp.zeros((n_experts, hidden), dtype),
        },
        "moe_fc2": {
            "kernel": out_std * jax.random.normal(
                k2, (n_experts, hidden, d_model), dtype),
            "bias": jnp.zeros((n_experts, d_model), dtype),
        },
    }


def moe_apply(params: dict, x: jax.Array, top_k: int = 2,
              capacity_factor: float = 1.25,
              activation=jax.nn.gelu,
              impl: str = "scatter",
              reduce=None,
              ep: tuple[str, int] | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """(B, S, d) → ((B, S, d), aux_loss). Top-``top_k`` routing with
    static per-expert capacity; dropped tokens pass through as zeros
    (the residual connection around the block carries them).

    ``impl``:
    - ``"scatter"`` (default): tokens scatter into the (E·C, d) expert
      buffer by flat slot index and gather back out — peak routing
      memory is O(T·d + E·C·d); no (T, E, C) tensor ever exists, so
      long sequences (T=16k+) stay cheap.
    - ``"einsum"``: the GShard one-hot dispatch/combine einsums —
      O(T·E·C) memory. Kept as the parity oracle for the scatter path.

    ``reduce``: MANUAL tensor parallelism over the expert hidden dim,
    for shard_map callers (the pipeline): ``params`` then hold per-rank
    slices — fc1 kernel/bias column-split over hidden, fc2 kernel
    row-split — and ``reduce`` (a psum over the tp axis) runs between
    the fc2 matmul and its bias, exactly like the dense blocks'
    ``_row_dense``. Routing is token-level math on the (replicated)
    activations, so every tp rank computes identical dispatch and only
    the expert MLP hidden is split. The auto-SPMD paths leave this
    None and let XLA place the collectives from SHARDING_RULES.

    ``ep=(axis, size)``: MANUAL expert parallelism for shard_map
    callers — ``params``' expert tensors hold this rank's ``E/size``
    expert slice (the gate stays global/replicated). Because the
    activations are replicated across ep within a stage, NO all-to-all
    is needed: every rank computes the identical GLOBAL routing
    (capacity stays ``k·T/E_global·cf`` — exactly the unsharded
    semantics), scatters only the tokens destined to ITS experts, runs
    its expert slice, and one psum over ``axis`` combines each token's
    top-k contributions (each expert lives on exactly one rank).
    Scatter impl only. Composes with ``reduce`` (tp splits each local
    expert's hidden)."""
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    t = tokens.shape[0]
    n_experts = params["moe_gate"]["kernel"].shape[-1]
    capacity = int((top_k * t / n_experts) * capacity_factor + 0.5)
    capacity = max(capacity, top_k)

    gate_logits = L.dense(params["moe_gate"], tokens)      # (T, E)
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    # top-k selection, one expert at a time (k is tiny and static):
    # per round, each token's expert id, gate weight, position within
    # that expert's capacity buffer, and whether it fit
    rounds: list[tuple[jax.Array, jax.Array, jax.Array, jax.Array]] = []
    remaining = probs
    # position counters per expert accumulate across the k rounds
    fill = jnp.zeros((n_experts,), jnp.int32)
    for _ in range(top_k):
        expert = jnp.argmax(remaining, axis=-1)            # (T,)
        weight = jnp.take_along_axis(
            remaining, expert[:, None], axis=-1)[:, 0]     # (T,)
        onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)
        # position of each token within its chosen expert's buffer
        position = jnp.cumsum(onehot, axis=0) - 1 + fill[None, :]
        pos = jnp.sum(position * onehot, axis=-1)          # (T,)
        keep = pos < capacity
        rounds.append((expert, weight, pos, keep))
        fill = fill + jnp.sum(onehot, axis=0)
        remaining = remaining * (1.0 - onehot.astype(jnp.float32))

    def expert_mlps(expert_in: jax.Array) -> jax.Array:
        # expert MLPs over the stacked weights — one batched matmul
        # pair; under manual tp the hidden dim is a per-rank slice and
        # ``reduce`` sums the partial fc2 products before the bias
        h = jnp.einsum("ecd,edh->ech", expert_in,
                       params["moe_fc1"]["kernel"].astype(x.dtype))
        h = activation(
            h + params["moe_fc1"]["bias"].astype(x.dtype)[:, None, :])
        expert_out = jnp.einsum("ech,ehd->ecd", h,
                                params["moe_fc2"]["kernel"].astype(x.dtype))
        if reduce is not None:
            expert_out = reduce(expert_out)
        return expert_out + \
            params["moe_fc2"]["bias"].astype(x.dtype)[:, None, :]

    if impl == "scatter":
        # flat slot id e·C + c; each (token, round) owns at most one
        # slot and no two tokens share one, so scatter-add never
        # collides. Dropped tokens get an out-of-range id and vanish
        # via mode="drop" / gather fill — the transposes (gather /
        # scatter-add) make the whole path differentiable. Under
        # manual ep, slots index the LOCAL expert slice and routes to
        # other ranks' experts are out-of-range here (they land on
        # their own rank; the psum below re-assembles every token).
        if ep is not None:
            ep_axis, ep_size = ep
            local_e = params["moe_fc1"]["kernel"].shape[0]
            if local_e * ep_size != n_experts:
                # a full-E (or differently factored) expert tree with
                # ep set would silently mis-route tokens via a wrong
                # rank offset — fail loudly instead
                raise ValueError(
                    f"moe_apply(ep=({ep_axis!r}, {ep_size})): local "
                    f"expert slice {local_e} x {ep_size} != gate's "
                    f"{n_experts} experts")
            lo = jax.lax.axis_index(ep_axis) * local_e
        else:
            local_e, lo = n_experts, 0
        flat = jnp.zeros((local_e * capacity, d), x.dtype)
        dsts = []
        for expert, weight, pos, keep in rounds:
            local_idx = expert - lo
            ok = keep & (local_idx >= 0) & (local_idx < local_e)
            dst = jnp.where(ok, local_idx * capacity + pos,
                            local_e * capacity)
            dsts.append(dst)
            flat = flat.at[dst].add(tokens, mode="drop")
        expert_out = expert_mlps(flat.reshape(local_e, capacity, d))
        flat_out = expert_out.reshape(local_e * capacity, d)
        out = jnp.zeros((t, d), x.dtype)
        for (expert, weight, pos, keep), dst in zip(rounds, dsts):
            gathered = flat_out.at[dst].get(mode="fill", fill_value=0)
            out = out + weight.astype(x.dtype)[:, None] * gathered
        if ep is not None:
            out = jax.lax.psum(out, ep_axis)
    elif impl == "einsum":
        if ep is not None:
            raise ValueError(
                "manual ep is wired for the scatter impl only (the "
                "einsum oracle is a global-dispatch parity check)")
        combine = jnp.zeros((t, n_experts, capacity), jnp.float32)
        dispatch = jnp.zeros((t, n_experts, capacity), jnp.bool_)
        for expert, weight, pos, keep in rounds:
            onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.float32)
            pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
            slot = onehot[:, :, None] * pos_oh[:, None, :]
            slot = slot * keep[:, None, None].astype(jnp.float32)
            combine = combine + weight[:, None, None] * slot
            dispatch = dispatch | (slot > 0)
        # dispatch: (T, E, C) × (T, d) → per-expert batches (E, C, d)
        expert_in = jnp.einsum(
            "tec,td->ecd", dispatch.astype(x.dtype), tokens)
        expert_out = expert_mlps(expert_in)
        # combine back, gate-weighted
        out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    else:
        raise ValueError(f"unknown moe impl {impl!r}")

    # Switch-style load-balance loss: E * mean_e(frac_tokens * mean_prob)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, n_experts, dtype=jnp.float32),
                    axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = n_experts * jnp.sum(frac * mean_prob)

    return out.reshape(b, s, d), aux_loss


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tile(size: int, limit: int, unit: int) -> int:
    """Largest multiple of ``unit`` that divides ``size`` and is at
    most ``limit``; ``size`` itself where none is."""
    return max((t for t in range(unit, min(size, limit) + 1, unit)
                if size % t == 0), default=size)


def grouped_matmul(rows: jax.Array, kernels: jax.Array,
                   sizes: jax.Array) -> jax.Array:
    """``rows (m, k)`` sorted by group, ``kernels (G, k, n)``, ``sizes
    (G,)`` int32 -> ``(m, n)``: rows ``[sum(sizes[:g]), +sizes[g])``
    times ``kernels[g]``; a group of size 0 is not read, rows behind
    the last group are left to the caller. ONE grouped product.

    On a TPU this is the pallas grouped-matmul kernel that ships with
    JAX (``megablox.gmm``: visits only (row tile, group) pairs that
    exist); elsewhere ``jax.lax.ragged_dot``, the same contract. The
    choice is the platform's, not a knob: XLA:TPU lowers ``ragged_dot``
    to a kernel of its own that drops the op's name stack — its time
    lands under NO ``jax.named_scope``, which blinds every per-scope
    reading of the trace — and reads the experts at a third of the
    memory bandwidth (PERF.md, PR 28). Tiles: the whole contraction
    where it is at most 2048 wide, output tiles of up to ~4 MB of
    kernel, row tiles of 128 (or what divides ``m``)."""
    m, k = rows.shape
    n = kernels.shape[-1]
    tm = _tile(m, 128, 8)
    if not _on_tpu() or m % tm or tm % 8:
        return jax.lax.ragged_dot(rows, kernels, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tk = _tile(k, 2048, 128)
    tn = _tile(n, max((1 << 21) // tk // 128 * 128, 128), 128)
    return gmm(rows, kernels, sizes, rows.dtype, (tm, tk, tn))


def group_limited(biased: jax.Array, n_group: int,
                  topk_group: int) -> jax.Array:
    """``biased (T, E)`` with every expert outside its token's
    ``topk_group`` best groups set to ``-inf``: the ``E`` experts are
    ``n_group`` groups of ``E / n_group`` in order, and a group scores
    the sum of its two highest values."""
    t, e = biased.shape
    grouped = biased.reshape(t, n_group, e // n_group)
    _, best = jax.lax.top_k(jax.lax.top_k(grouped, 2)[0].sum(-1),
                            topk_group)
    kept = jnp.any(best[..., None] == jnp.arange(n_group), axis=-2)
    return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(t, e)


def moe_route(params: dict, tokens: jax.Array, top_k: int,
              scaling: float = 1.0, eps: float = 1e-6,
              scoring: str = "sigmoid", n_group: int = 1,
              topk_group: int = 1
              ) -> tuple[jax.Array, jax.Array]:
    """Routing in float32, by ``scoring``:

    - ``"sigmoid"``: ``s = sigmoid(u @ W_g)``; the ``top_k`` experts
      are chosen by ``s + b`` (``moe_bias``, a load-balancing buffer
      that steers SELECTION only) and weighted by the unbiased ``s``;
    - ``"softmax"``: ``s = softmax(u @ W_g)`` over all the router's
      experts, the ``top_k`` chosen by ``s`` (no bias).

    Either way the weights are renormalised over the chosen ones: ``w
    = scaling * s[sel] / (sum(s[sel]) + eps)``. ``n_group > 1`` (sigmoid
    only) limits the selection by GROUP (DeepSeek-V3's ``noaux_tc``):
    the experts form ``n_group`` equal groups in order, a group scores
    the sum of its two highest ``s + b``, and the ``top_k`` are chosen
    among the ``topk_group`` best groups only (:func:`group_limited`).
    ``tokens (T, d)`` -> ``(sel (T, k) int32, w (T, k) float32)``."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"moe_route: unknown scoring {scoring!r}")
    if n_group > 1 and scoring != "sigmoid":
        raise ValueError("moe_route: group-limited selection is "
                         "sigmoid scoring's (noaux_tc)")
    gate = params["moe_gate"]["kernel"].astype(jnp.float32)
    logits = jnp.dot(tokens.astype(jnp.float32), gate,
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        _, sel = jax.lax.top_k(scores, top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        biased = scores + params["moe_bias"].astype(jnp.float32)
        if n_group > 1:
            biased = group_limited(biased, n_group, topk_group)
        _, sel = jax.lax.top_k(biased, top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = scaling * w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return sel.astype(jnp.int32), w


def moe_dropless(params: dict, x: jax.Array, top_k: int,
                 scaling: float = 1.0, valid: jax.Array | None = None,
                 first_group: jax.Array | int = 0,
                 route_on: jax.Array | None = None,
                 held: tuple[int, int] | None = None,
                 tp: tuple | None = None, route_eps: float = 1e-6,
                 scoring: str = "sigmoid", n_group: int = 1,
                 topk_group: int = 1
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(B, S, d) -> ((B, S, d), tokens per expert (E,) int32, pairs
    elsewhere () int32): the DROPLESS expert layer — every selected
    (token, expert) pair is computed whatever the imbalance, and
    nothing else is. The ``T*k``
    pairs are sorted by expert and each projection of the bias-free
    SwiGLU experts (``moe_fc1`` / ``moe_fc3`` ``(E, d, h)``,
    ``moe_fc2`` ``(E, h, d)``) is ONE grouped matrix product over the
    sorted rows (:func:`grouped_matmul`: the group sizes are the
    tokens per expert, traced values) — no ``(T, E, C)`` tensor, no
    per-expert buffer, no product of an expert with a token it was
    not given; an expert nobody chose is not read. The same code
    serves a decode batch of one token a slot and a prefill chunk.

    The expert kernels may hold MORE groups than this layer's ``E``
    experts — the experts of several layers stacked on the leading
    axis, ``(layers * E, d, h)`` — with ``first_group`` (a traced
    value) saying where this layer's begin: the other layers' groups
    get size 0 and are never touched. That is how a layer inside a
    ``lax.scan`` reads its experts where they lie: sliced out of a
    stacked array for the grouped product's kernel, they would be
    COPIED every step (compiled for the v5e: 234 MB a matrix).

    ``route_on``: the tokens as the ROUTER reads them where that is
    not ``x`` — the float32 normed activations before they are
    rounded to the experts' compute dtype (a score decides a top-k:
    rounding its input flips near-ties).

    ``valid (B, S)`` marks real tokens (a dead slot, a chunk's pad):
    the others are sorted behind every group, take no expert's time
    and count for nothing. Routing follows :func:`moe_route`
    (``scoring``: sigmoid with a selection bias, or softmax;
    ``n_group`` / ``topk_group``: the selection limited by group). The
    count is a program output the serving engine reads
    (``serving_moe_*``, docs/observability.md).

    ``held = (first, n)``: this device's SHARE of the experts — one
    expert-parallel rank's. The router keeps its width (``moe_gate``
    scores all ``E``), the top-k and its renormalisation run over all
    of them wherever they live, and the kernels hold only experts
    ``[first, first + n)``: a pair whose expert is not held is sorted
    behind every group exactly as a dead token's is, takes no expert's
    time and adds nothing to the output — the part of the layer's
    result that THIS rank's experts give. The counts are then of the
    experts held ``(n,)``, and the third output counts the live pairs
    that went elsewhere (0 without ``held``). On one device the layer
    runs without its exchange; summing the ranks' outputs (the
    combine of expert parallelism) is the caller's, and nothing here
    stands in for an absent rank.

    ``tp`` sharding of this path does not exist yet (ROADMAP M1) and
    raises."""
    if tp is not None:
        raise NotImplementedError(
            "moe_dropless: tp sharding of the dropless expert layer "
            "is not implemented")
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    n_groups = params["moe_fc1"]["kernel"].shape[0]
    # experts a pair can land on HERE: the router's width, or the share
    n_experts = params["moe_gate"]["kernel"].shape[-1] if held is None \
        else held[1]
    with jax.named_scope("moe_route"):
        sel, w = moe_route(
            params, tokens if route_on is None
            else route_on.reshape(b * s, d), top_k, scaling, route_eps,
            scoring, n_group, topk_group)
    with jax.named_scope("moe_experts"):
        pair_expert = sel.reshape(-1)                    # (T*k,)
        live = None if valid is None \
            else jnp.repeat(valid.reshape(-1), top_k)
        elsewhere = jnp.zeros((), jnp.int32)
        if held is not None:
            local = pair_expert - held[0]
            away = (local < 0) | (local >= n_experts)
            pair_expert = jnp.where(away, n_experts, local)
            elsewhere = jnp.sum(away if live is None else away & live,
                                dtype=jnp.int32)
        if live is not None:
            pair_expert = jnp.where(live, pair_expert, n_experts)
        order = jnp.argsort(pair_expert, stable=True)
        counts = jnp.bincount(pair_expert, length=n_experts + 1
                              )[:n_experts].astype(jnp.int32)
        rows = tokens[order // top_k]                    # (T*k, d)
        sizes = counts if n_groups == n_experts else \
            jax.lax.dynamic_update_slice(
                jnp.zeros((n_groups,), jnp.int32), counts, (first_group,))
        grouped = lambda a, name: grouped_matmul(
            a, params[name]["kernel"].astype(a.dtype), sizes)
        h = jax.nn.silu(grouped(rows, "moe_fc1")) \
            * grouped(rows, "moe_fc3")
        y = grouped(h, "moe_fc2")
        # rows behind the last group were given to no expert
        y = jnp.where((jnp.arange(y.shape[0]) < jnp.sum(counts))[:, None],
                      y, 0)
        # back to (token, choice) order, weighted sum over the choices
        y = y[jnp.argsort(order)].reshape(b * s, top_k, d)
        out = jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)
    return out.astype(x.dtype).reshape(b, s, d), counts, elsewhere


__all__ = ["SHARDING_RULES", "group_limited", "grouped_matmul",
           "moe_apply", "moe_dropless", "moe_init", "moe_route"]
