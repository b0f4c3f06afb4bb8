"""Pallas step of the decode lanes' delta rule: ONE token of the gated
delta-rule recurrence for each live slot, its float32 state ``S`` read
once and written once, in place in the stacked slot-state array.

Why a kernel and not the XLA step (``models/qwen3_next.delta_step``
over every slot, then a select by liveness): XLA reads a layer's whole
state twice (the two reductions ``S^T k`` and ``S^T q``) and writes it
once with a select, whatever is live — three passes over every slot's
2 MiB a layer. Here the bytes follow the live slots:

- The state is read IN PLACE: the operand is the stacked array
  ``(layers, slots, heads, d_k, d_v)`` itself, aliased to the output;
  the layer index and the compacted live slots ride as
  scalar-prefetch operands, and a slot's whole ``(heads, d_k, d_v)``
  block is the grid step's block. A slot that is not live has no step:
  its block is neither read nor written.
- The grid is the slots, live ones first. Its tail repeats the last
  live slot's block index, so the pipeline neither fetches nor writes
  it again and the kernel leaves the step's output buffer as the last
  live step left it. With no live slot at all the one block visited
  (slot 0's) is copied through unchanged.
- Per head, on the vector unit in float32: ``S^T k`` and ``S^T q`` as
  sums over ``d_k`` of ``S`` scaled by the key's and query's columns
  (``q`` and ``k`` arrive transposed, ``(d_k, heads)`` a slot, so a
  head's column is a lane slice), then ``S <- e^g S + k (beta (v -
  e^g S^T k))^T`` and ``o = e^g S^T q + delta (k . q)``: the new state
  is written and never read back, as in ``delta_step``.

The same call serves the per-channel rule of Kimi Delta Attention
(:func:`kda_lanes`, models/bailing_hybrid.py): each key channel has a
decay of its own, arriving like ``q`` and ``k`` as a ``(d_k, heads)``
column block a slot, and ``S^T (e^g * k)`` stands for the decayed
state's product, so ``S`` is still read once and written once.

On CPU the kernels run in interpret mode (``_pallas_util``), so the
tier-1 tests (tests/test_qwen3_next.py, tests/test_bailing_hybrid.py)
hold them to ``delta_step`` and ``kda_step``;
tests/test_tpu_aot_compile.py compiles the cell's programs for the
described v5e with the state in place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torchbooster_tpu.ops._pallas_util import (
    CompilerParams as _CompilerParams,
    resolve_interpret as _resolve_interpret,
)

VMEM_LIMIT = 32 * 2**20


def _lanes_kernel(li_ref, order_ref, n_ref, qt_ref, kt_ref, v_ref, per_ref,
                  s_ref, o0_ref, s_out, o_out):
    """One slot's heads: ``qt`` / ``kt (d_k, heads)``, ``v (heads,
    d_v)``, ``per (3, heads, d_v)`` (a head's decay ``e^g``, ``beta``
    and ``k . q``, each across ``d_v`` lanes), ``s (heads, d_k,
    d_v)``."""
    del li_ref, order_ref, o0_ref
    i, n = pl.program_id(0), n_ref[0]

    @pl.when(i < n)
    def _live():
        for h in range(s_ref.shape[0]):
            s = s_ref[h]                                # (d_k, d_v)
            kc, qc = kt_ref[:, h:h + 1], qt_ref[:, h:h + 1]
            # a head's scalars arrive as rows: Mosaic broadcasts a row
            # down the sublanes or a column across the lanes, not both
            decay, beta, kq = (per_ref[j, h:h + 1, :] for j in range(3))
            kv = jnp.sum(s * kc, axis=0, keepdims=True)     # (1, d_v)
            sq = jnp.sum(s * qc, axis=0, keepdims=True)
            delta = beta * (v_ref[h:h + 1, :] - decay * kv)
            s_out[h] = decay * s + kc * delta
            o_out[h:h + 1, :] = decay * sq + delta * kq

    @pl.when(n == 0)
    def _none():
        s_out[...] = s_ref[...]
        o_out[...] = jnp.zeros_like(o_out)


def _kda_kernel(li_ref, order_ref, n_ref, qt_ref, kt_ref, at_ref, v_ref,
                per_ref, s_ref, o0_ref, s_out, o_out):
    """One slot's heads of the per-channel rule: ``qt`` / ``kt`` /
    ``at (d_k, heads)`` (``at``: each key channel's decay ``e^g``),
    ``v (heads, d_v)``, ``per (2, heads, d_v)`` (a head's ``beta`` and
    ``k . q`` across ``d_v`` lanes), ``s (heads, d_k, d_v)``."""
    del li_ref, order_ref, o0_ref
    i, n = pl.program_id(0), n_ref[0]

    @pl.when(i < n)
    def _live():
        for h in range(s_ref.shape[0]):
            s = s_ref[h]                                # (d_k, d_v)
            kc, qc = kt_ref[:, h:h + 1], qt_ref[:, h:h + 1]
            ac = at_ref[:, h:h + 1]
            beta, kq = (per_ref[j, h:h + 1, :] for j in range(2))
            # (Diag(a) S)^T x = S^T (a * x): the decayed state is
            # never formed before it is written
            kv = jnp.sum(s * (ac * kc), axis=0, keepdims=True)  # (1, d_v)
            sq = jnp.sum(s * (ac * qc), axis=0, keepdims=True)
            delta = beta * (v_ref[h:h + 1, :] - kv)
            s_out[h] = ac * s + kc * delta
            o_out[h:h + 1, :] = sq + delta * kq

    @pl.when(n == 0)
    def _none():
        s_out[...] = s_ref[...]
        o_out[...] = jnp.zeros_like(o_out)


def _lanes_call(kernel, name: str, state, layer, cols, v, scalars, live,
                interpret):
    """The one ``pallas_call`` both rules run: ``cols`` are the ``(slots,
    heads, d_k)`` vectors a head reads as columns (handed in transposed,
    ``(d_k, heads)`` a slot), ``scalars()`` its ``(slots, heads)``
    scalars, handed in as rows across ``d_v`` lanes (made after the
    columns, the order the delta rule's programs were pinned in,
    tests/test_tpu_aot_compile.py); the grid is the
    slots, live ones first, the state read and written in place (module
    docstring)."""
    _, n_slots, heads, dk, dv = state.shape
    live = jnp.asarray(live, bool)
    n = jnp.sum(live, dtype=jnp.int32)
    # live slots first, in slot order; the tail repeats the last live one
    first = jnp.argsort(~live, stable=True).astype(jnp.int32)
    order = jnp.where(jnp.arange(n_slots) < n, first,
                      first[jnp.maximum(n - 1, 0)])
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    cols = [jnp.swapaxes(f32(t), 1, 2) for t in cols]
    rows = scalars()
    per = jnp.broadcast_to(jnp.stack(rows, axis=1)[..., None],
                           (n_slots, len(rows), heads, dv))

    slot = lambda *shape: pl.BlockSpec(
        (None, *shape), lambda i, li, order, n: (order[i],) + (0,) * len(shape))
    whole = pl.BlockSpec((None, None, heads, dk, dv),
                         lambda i, li, order, n: (li[0], order[i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_slots,),
        in_specs=[slot(dk, heads)] * len(cols)
        + [slot(heads, dv), slot(per.shape[1], heads, dv), whole,
           slot(heads, dv)],
        out_specs=[whole, slot(heads, dv)],
    )
    at = 3 + len(cols) + 2
    state, o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((n_slots, heads, dv), jnp.float32)],
        # operands count the three scalar-prefetch ones: the state and
        # the zeroed output after it are written in place
        input_output_aliases={at: 0, at + 1: 1},
        name=name,
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=_resolve_interpret(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, n.reshape(1),
      *cols, f32(v), per, state,
      jnp.zeros((n_slots, heads, dv), jnp.float32))
    return o, state


def delta_lanes(state: jax.Array, layer, q: jax.Array, k: jax.Array,
                v: jax.Array, g: jax.Array, beta: jax.Array,
                live: jax.Array, *, interpret: bool | None = None):
    """One token of the delta rule for the live slots of layer ``layer``
    of ``state``, in place.

    - ``state (layers, slots, heads, d_k, d_v)`` float32: the stacked
      slot state; its other layers and the slots that are not live
      come back as they were;
    - ``layer``: the layer's index into it (a traced scalar in a scan);
    - ``q``, ``k (slots, heads, d_k)``, ``v (slots, heads, d_v)``, ``g``,
      ``beta (slots, heads)``: the token's query, key, value, log-decay
      and write strength, float32;
    - ``live (slots,)``: False -> the slot is skipped.

    Returns ``(o (slots, heads, d_v) float32 — zeros where a slot is
    not live —, state)``."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    return _lanes_call(
        _lanes_kernel, "delta_lanes", state, layer, (q, k), v,
        lambda: [jnp.exp(f32(g)), f32(beta),
                 jnp.sum(f32(k) * f32(q), axis=-1)], live, interpret)


def kda_lanes(state: jax.Array, layer, q: jax.Array, k: jax.Array,
              v: jax.Array, g: jax.Array, beta: jax.Array,
              live: jax.Array, *, interpret: bool | None = None):
    """:func:`delta_lanes` for the PER-CHANNEL rule (Kimi Delta
    Attention, models/bailing_hybrid.py): ``g (slots, heads, d_k)``
    decays each key channel on its own, ``S <- Diag(e^g) S``, then ``S
    <- S + k (beta (v - S^T k))^T`` and ``o = S^T q``. As one pass over
    ``S``: ``o = S^T (a q) + delta (k . q)`` and ``S <- Diag(a) S + k
    delta^T`` with ``a = e^g`` and ``delta = beta (v - S^T (a k))``.
    Every other operand, and what comes back, as :func:`delta_lanes`'s."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    return _lanes_call(
        _kda_kernel, "kda_lanes", state, layer, (q, k, jnp.exp(f32(g))), v,
        lambda: [f32(beta), jnp.sum(f32(k) * f32(q), axis=-1)], live,
        interpret)


__all__ = ["delta_lanes", "kda_lanes"]
