"""GPT-style transformer LM — the north-star model (SURVEY §6: stretch
GPT-2 config; the reference has no transformer at all, SURVEY §5.7).

TPU-first design:
- **scan over layers**: block params are stacked on a leading layer
  axis and the forward is one ``lax.scan`` — O(1) compile time in
  depth, and the natural substrate for pipeline stages later;
- **remat**: ``remat=True`` wraps the scanned block in
  ``jax.checkpoint`` — activations are recomputed in backward, trading
  MXU FLOPs for HBM (SURVEY's "jax.checkpoint" guidance);
- **Megatron-style tp rules**: qkv/fc1 column-parallel, proj/fc2
  row-parallel — XLA inserts exactly one psum per row-parallel matmul;
  ``fsdp`` shards the other dim (ZeRO-style), ``sp`` shards the
  sequence axis of activations;
- attention runs through :func:`torchbooster_tpu.ops.attention`
  (pallas flash kernel on TPU, GQA-native) or, when the mesh has a
  real ``sp`` axis, :func:`parallel.ulysses.sequence_attention`
  (auto-picked ring / all-to-all strategy per ``cfg.sp_strategy``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from torchbooster_tpu.models import layers as L
from torchbooster_tpu.models.quant import (
    dequant_kernel as _dequant_kernel,
    qmatmul as _qmatmul,
)
from torchbooster_tpu.models.torch_interop import to_numpy as _np
from torchbooster_tpu.ops.attention import attention


@dataclass(frozen=True)
class GPTConfig:
    vocab: int = 50257
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    # grouped-query attention: 0 → = n_heads (standard MHA). Fewer KV
    # heads shrink the decode KV cache (and its HBM traffic) by
    # n_heads / n_kv_heads; training K/V stay GROUPED end to end — the
    # flash kernel indexes grouped tiles natively and SP collectives
    # carry grouped width (ops/flash_attention.py, parallel/ulysses.py)
    n_kv_heads: int = 0
    seq_len: int = 1024
    mlp_ratio: int = 4
    # dropout on the embedding sum and each residual-branch output
    # (GPT-2's training regularization). Active only when ``apply`` is
    # given a ``dropout_rng`` — eval/generate paths never pass one, so
    # they stay deterministic. Attention-probability dropout is
    # deliberately NOT implemented: it cannot ride the flash kernel
    # (the probs never exist in HBM) and would silently change math
    # between the flash and reference paths.
    dropout: float = 0.0
    tie_embeddings: bool = True
    # MoE: n_experts > 0 replaces every block's MLP with a top-k routed
    # expert layer (models/moe.py) sharded over the ``ep`` mesh axis
    n_experts: int = 0
    top_k: int = 2
    capacity_factor: float = 1.25
    # sequence-parallel attention strategy when the mesh has a real
    # ``sp`` axis: "ring" (ppermute online-softmax, any head count),
    # "ulysses" (all-to-all head resharding, flash-capable), or "auto"
    # (ulysses when heads divide, else ring — parallel/ulysses.py)
    sp_strategy: str = "auto"
    # position encoding: "learned" (GPT-2 wpe table) or "rope" (rotary
    # — relative attention, no table; q/k rotate by absolute position
    # before every attention flavor, so flash/ring/ulysses/KV-cache
    # paths are unchanged)
    pos: str = "learned"
    rope_base: float = 10_000.0
    # MLP flavor: "gelu" (GPT-2) or "swiglu" (gated, hidden 2/3·ratio·d
    # so params match); MoE blocks (n_experts>0) keep their own experts
    mlp: str = "gelu"

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads


# path-regex → PartitionSpec (leading None = the stacked layer axis).
# Consumed by parallel.sharding.make_param_specs; axes not in the mesh
# are filtered out, so the same table serves dp-only through dp+fsdp+tp.
SHARDING_RULES = [
    # replicated: any sharding of the table forces XLA into involuntary
    # full-remat reshards around the token gather (and, tied, the head
    # matmul) because gather output wants the activation layout
    # P(data, sp, None); at GPT-2 scale the table is small next to the
    # blocks, so replication is the fast layout
    (r"wte/table", P()),
    (r"wpe/table", P(None, None)),
    # the leading axis of every block tensor is the stacked LAYER axis:
    # on a pp mesh each stage stores only its own L/pp layers (the
    # pipeline kernel's P("pp") layout); _filter_spec drops "pp" on
    # meshes without the axis, so dp/fsdp/tp meshes are unchanged
    (r"attn_qkv/kernel", P("pp", "fsdp", "tp")),
    (r"attn_qkv/bias", P("pp", "tp")),
    (r"attn_proj/kernel", P("pp", "tp", "fsdp")),
    (r"mlp_fc1/kernel", P("pp", "fsdp", "tp")),
    (r"mlp_fc1/bias", P("pp", "tp")),
    (r"mlp_fc3/kernel", P("pp", "fsdp", "tp")),
    (r"mlp_fc3/bias", P("pp", "tp")),
    (r"mlp_fc2/kernel", P("pp", "tp", "fsdp")),
    (r"head/kernel", P("fsdp", "tp")),
    # MoE blocks: experts over ep, hidden over tp (models/moe.py)
    (r"moe_gate/kernel", P("pp")),
    (r"moe_fc1/kernel", P("pp", "ep", None, "tp")),
    (r"moe_fc1/bias", P("pp", "ep", "tp")),
    (r"moe_fc2/kernel", P("pp", "ep", "tp", None)),
    (r"moe_fc2/bias", P("pp", "ep", None)),
    # layer norms and any other stacked block leaf: layer axis over pp
    (r"blocks/", P("pp")),
    (r".*", P()),
]

# activations: batch over data axes, sequence over sp
def batch_spec() -> P:
    return P(("dp", "fsdp"), "sp")


def _block_init(rng: jax.Array, cfg: GPTConfig, dtype: Any) -> dict:
    ks = jax.random.split(rng, 4)
    d, h = cfg.d_model, cfg.mlp_ratio * cfg.d_model
    # GPT-2 init: N(0, 0.02), residual projections scaled by 1/√(2L)
    res_std = 0.02 / (2 * cfg.n_layers) ** 0.5
    head_dim = d // cfg.n_heads
    qkv_out = d + 2 * cfg.kv_heads * head_dim
    block = {
        "ln1": L.norm_init(d, dtype),
        "attn_qkv": L.dense_init(ks[0], d, qkv_out, std=0.02, dtype=dtype),
        "attn_proj": L.dense_init(ks[1], d, d, std=res_std, dtype=dtype),
        "ln2": L.norm_init(d, dtype),
    }
    if cfg.n_experts > 0:
        from torchbooster_tpu.models.moe import moe_init

        block.update(moe_init(ks[2], cfg.n_experts, d, h, std=0.02,
                              out_std=res_std, dtype=dtype))
    elif cfg.mlp == "swiglu":
        # gate (fc1) and value (fc3) as separate params so each shards
        # cleanly over tp (an interleaved (d, 2h) kernel would slice
        # across the sharded dim); hidden 2/3·(ratio·d) keeps the param
        # count at the gelu MLP's, rounded up to a multiple of 8 so the
        # tp rule divides (and lanes stay aligned); the extra key is
        # fold_in-derived so gelu/MoE init streams stay bit-identical
        hs = max((-(-2 * h // 3) + 7) // 8 * 8, 8)
        block.update({
            "mlp_fc1": L.dense_init(ks[2], d, hs, std=0.02, dtype=dtype),
            "mlp_fc3": L.dense_init(jax.random.fold_in(ks[2], 1), d, hs,
                                    std=0.02, dtype=dtype),
            "mlp_fc2": L.dense_init(ks[3], hs, d, std=res_std,
                                    dtype=dtype),
        })
    else:
        block.update({
            "mlp_fc1": L.dense_init(ks[2], d, h, std=0.02, dtype=dtype),
            "mlp_fc2": L.dense_init(ks[3], h, d, std=res_std, dtype=dtype),
        })
    return block


class GPT:
    """``init(rng, cfg)`` → params (blocks stacked over layer axis);
    ``apply(params, ids, cfg)`` → logits (B, S, vocab)."""

    Config = GPTConfig
    SHARDING_RULES = SHARDING_RULES

    @staticmethod
    def init(rng: jax.Array, cfg: GPTConfig = GPTConfig(),
             dtype: Any = jnp.float32) -> dict:
        if cfg.n_heads % cfg.kv_heads:
            raise ValueError(
                f"n_heads={cfg.n_heads} not divisible by "
                f"n_kv_heads={cfg.kv_heads}")
        if cfg.pos not in ("learned", "rope"):
            # a typo'd "rotary" must not silently train learned positions
            raise ValueError(f"unknown pos {cfg.pos!r}; use 'learned' "
                             f"or 'rope'")
        if cfg.mlp not in ("gelu", "swiglu"):
            raise ValueError(f"unknown mlp {cfg.mlp!r}; use 'gelu' "
                             f"or 'swiglu'")
        if not 0.0 <= cfg.dropout < 1.0:
            raise ValueError(
                f"dropout must be in [0, 1), got {cfg.dropout}")
        k_wte, k_wpe, k_blocks, k_head = jax.random.split(rng, 4)
        blocks = jax.vmap(
            lambda k: _block_init(k, cfg, dtype)
        )(jax.random.split(k_blocks, cfg.n_layers))
        params = {
            "wte": L.embedding_init(k_wte, cfg.vocab, cfg.d_model,
                                    dtype=dtype),
            "blocks": blocks,
            "ln_f": L.norm_init(cfg.d_model, dtype),
        }
        if cfg.pos != "rope":   # rope has no position table
            params["wpe"] = L.embedding_init(k_wpe, cfg.seq_len,
                                             cfg.d_model, std=0.01,
                                             dtype=dtype)
        if not cfg.tie_embeddings:
            params["head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab,
                                          use_bias=False, std=0.02,
                                          dtype=dtype)
        return params

    @staticmethod
    def apply(params: dict, ids: jax.Array,
              cfg: GPTConfig = GPTConfig(),
              mesh: Mesh | None = None,
              compute_dtype: Any = jnp.bfloat16,
              remat: bool = True,
              attn_impl: str = "auto",
              return_aux: bool = False,
              return_hidden: bool = False,
              dropout_rng: jax.Array | None = None,
              qkv_tp_major: bool = False) -> jax.Array:
        """``dropout_rng``: pass the step's rng (make_step splits a
        fresh one per step and hands it to the loss fn) to activate
        ``cfg.dropout``; omit it (eval, generate) for the
        deterministic forward. ``qkv_tp_major``: the params' stacked
        qkv columns are already rank-major for this mesh's tp axis
        (``qkv_to_tp_major`` applied at placement) — skips the
        per-step re-permute on the pp×tp path; only meaningful there,
        and loud anywhere else (the canonical math would silently read
        scrambled columns)."""
        b, s = ids.shape
        _check_pos(params, cfg, allow_tp_major=qkv_tp_major)
        if s > cfg.seq_len:
            # jnp.take would silently fill NaN embeddings for positions
            # beyond the wpe table; shapes are static, so fail loudly
            raise ValueError(
                f"sequence length {s} exceeds cfg.seq_len={cfg.seq_len}")
        constrain = _make_constrainer(mesh)

        drop = cfg.dropout if dropout_rng is not None else 0.0
        if drop:
            k_emb, k_layers = jax.random.split(dropout_rng)
            layer_keys = jax.random.split(k_layers, cfg.n_layers)
        else:
            # unused sentinel keys keep ONE scan body for both modes;
            # XLA dead-code-eliminates them when drop == 0
            k_emb = None
            layer_keys = jax.random.split(jax.random.PRNGKey(0),
                                          cfg.n_layers)

        with jax.named_scope("embed"):
            x = L.embedding(params["wte"], ids, dtype=compute_dtype)
            if "wpe" in params:
                x = x + L.embedding(params["wpe"], jnp.arange(s),
                                    dtype=compute_dtype)
            x = constrain(_dropout(x, drop, k_emb))

        use_sp = (mesh is not None and "sp" in mesh.axis_names
                  and mesh.shape["sp"] > 1)
        use_pp = (mesh is not None and "pp" in mesh.axis_names
                  and mesh.shape["pp"] > 1)
        if qkv_tp_major and not (
                use_pp and mesh.shape.get("tp", 1) > 1):
            raise ValueError(
                "qkv_tp_major=True but the mesh has no active pp+tp "
                "axes — these params' qkv columns are rank-major and "
                "the canonical paths would read them scrambled; "
                "restore with qkv_to_tp_major(..., inverse=True)")
        if qkv_tp_major:
            # the stamp qkv_to_tp_major left must exist AND match this
            # mesh's tp — a never-permuted tree or one permuted for a
            # different tp would slice scrambled columns (ADVICE r5)
            stamped = _qkv_tp_marker(params)
            if stamped != mesh.shape["tp"]:
                raise ValueError(
                    "qkv_tp_major=True but params carry "
                    + ("no _tp_major marker — qkv_to_tp_major was "
                       "never applied" if stamped is None else
                       f"a tp={stamped} marker")
                    + f"; this mesh has tp={mesh.shape['tp']}")
        if use_pp:
            x, aux = _pipelined_blocks(params, x, cfg, mesh, remat,
                                       attn_impl, drop, layer_keys,
                                       use_sp, qkv_tp_major)
            if return_hidden:
                with jax.named_scope("head"):
                    out = L.layer_norm(params["ln_f"], x)
            else:
                out = _lm_head(params, x)
            # same normalization as the scan path: mean over layers
            return (out, aux / max(cfg.n_layers, 1)) if return_aux \
                else out

        def attend(q, k, v):
            if use_sp:
                from torchbooster_tpu.parallel.ulysses import (
                    sequence_attention)

                # grouped K/V go in un-expanded: they ride the SP
                # collectives at kv_heads width and expand only at the
                # local math (pre-expanded fallback when layouts don't
                # divide — parallel/ulysses.py)
                return sequence_attention(q, k, v, mesh=mesh, causal=True,
                                          strategy=cfg.sp_strategy,
                                          impl=attn_impl), None
            # grouped K/V go straight to the dispatcher: the flash
            # kernel indexes grouped tiles natively (expanded K/V never
            # exist in HBM); the XLA reference expands internally. The
            # mesh goes along: this path is traced under plain jit, and
            # the kernel runs per device over the batch axes
            return attention(q, k, v, causal=True, impl=attn_impl,
                             mesh=mesh), None

        def block(carry: tuple, layer_in: tuple) -> tuple[tuple, None]:
            bp, drop_key = layer_in
            x, aux = carry
            x, layer_aux, _ = _block_core(bp, x, cfg, attend, constrain,
                                          dropout=drop,
                                          dropout_key=drop_key)
            return (x, aux + layer_aux), None

        scan_block = jax.checkpoint(
            block, policy=_remat_policy()) if remat else block
        (x, aux), _ = jax.lax.scan(
            lambda carry, layer_in: scan_block(carry, layer_in),
            (x, jnp.zeros((), jnp.float32)),
            (params["blocks"], layer_keys))

        if return_hidden:
            # final-norm hidden states, for the chunked LM-head loss
            # (ops.losses.lm_head_cross_entropy + GPT.head_table) that
            # never materializes the (T, vocab) logits
            with jax.named_scope("head"):
                out = L.layer_norm(params["ln_f"], x)
        else:
            out = _lm_head(params, x)
        if return_aux:
            # mean load-balance loss over layers (0 for dense models)
            return out, aux / max(cfg.n_layers, 1)
        return out

    @staticmethod
    def head_table(params: dict) -> jax.Array:
        """(vocab, d) output-projection table — the ``table`` argument
        of :func:`~torchbooster_tpu.ops.losses.lm_head_cross_entropy`
        (tied: the wte table; untied: the head kernel transposed).
        Quantized trees (models/quant.py) reconstruct full precision
        here — an offline/loss-side consumer, never the decode hot
        path."""
        if "head" in params:
            hp = params["head"]
            if "qkernel" in hp:
                return _dequant_kernel(hp).T
            return hp["kernel"].T
        wte = params["wte"]
        if "qtable" in wte:
            return wte["qtable"].astype(jnp.float32) * wte["qscale"]
        return wte["table"]


def _check_pos(params: dict, cfg: GPTConfig,
               allow_tp_major: bool = False) -> None:
    """A params tree from a rope checkpoint run with pos="learned" (or
    vice versa) would silently train/decode with NO position signal —
    the wpe add keys on the params, the rotation on the config. Make
    the mismatch loud instead. Also rejects tp-major-permuted params
    (the :func:`qkv_to_tp_major` marker, ADVICE r5) on every path that
    reads canonical qkv columns — ``allow_tp_major=True`` only for the
    pp×tp apply path, which checks the marker against the mesh
    itself."""
    has_wpe = "wpe" in params
    if cfg.pos == "rope" and has_wpe:
        raise ValueError("params carry a wpe table but cfg.pos='rope' "
                         "— checkpoint/config mismatch")
    if cfg.pos != "rope" and not has_wpe:
        raise ValueError("params have no wpe table but cfg.pos="
                         f"{cfg.pos!r} — was this checkpoint trained "
                         "with pos='rope'?")
    stamped = _qkv_tp_marker(params)
    if stamped is not None and not allow_tp_major:
        raise ValueError(
            f"params' qkv columns are tp-major for tp={stamped} "
            "(qkv_to_tp_major) but this path reads the canonical "
            "layout — attention would be silently scrambled; restore "
            "with qkv_to_tp_major(..., inverse=True) or run the pp×tp "
            "pipeline with qkv_tp_major=True")


# key prefix of the layout marker qkv_to_tp_major stamps into the
# attn_qkv block dict: f"{_TP_MAJOR_PREFIX}{tp_size}". The tp size
# lives in the KEY (static tree structure — checkable under tracing
# and immune to optimizer updates touching leaf VALUES); the value is
# a zero (n_layers,) float so the leaf scans/shards/optimizes like any
# other stacked block tensor.
_TP_MAJOR_PREFIX = "_tp_major"


def _qkv_tp_marker(params: dict) -> int | None:
    """The tp size :func:`qkv_to_tp_major` stamped on these params, or
    None for the canonical layout."""
    qkv = params.get("blocks", {}).get("attn_qkv", {})
    marks = [k for k in qkv if k.startswith(_TP_MAJOR_PREFIX)]
    if not marks:
        return None
    if len(marks) > 1:
        raise ValueError(
            f"params carry multiple tp-major markers {sorted(marks)} — "
            "corrupted layout bookkeeping")
    return int(marks[0][len(_TP_MAJOR_PREFIX):])


def _rope(x: jax.Array, positions: jax.Array,
          base: float = 10_000.0, freqs=None) -> jax.Array:
    """Rotary position embedding (rotate-half form) over (B, S, H, D);
    ``positions`` is (S,) absolute indices shared across the batch, or
    (B, S) per-example indices (continuous batching: every serving
    slot decodes at its OWN depth, so one shared index would rotate
    most slots wrong). Angles in fp32 — bf16 position·frequency
    products alias at long context. ``freqs (D / 2,)``: the
    frequencies themselves where they are not ``base``'s (a scaled
    rope: models/mla_moe.py's YaRN)."""
    half = x.shape[-1] // 2
    if freqs is None:
        freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    if positions.ndim == 1:        # (S, half): broadcast over batch
        cos = jnp.cos(angles)[None, :, None, :]
        sin = jnp.sin(angles)[None, :, None, :]
    else:                          # (B, S, half): per-slot positions
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).astype(x.dtype)


def qkv_tp_permutation(cfg: GPTConfig, tp_size: int):
    """Rank-major column order for the stacked ``[q | k | v]`` qkv
    kernel under tensor parallelism: rank ``i`` of a ``tp_size`` split
    must hold ``[q_i | k_i | v_i]`` (its contiguous head subset of each
    section), but the canonical layout concatenates whole sections — a
    contiguous tp split of it would hand rank 0 all of q and part of k.
    Returns the numpy index array ``perm`` with
    ``tp_major[..., j] = canonical[..., perm[j]]``; invert with
    ``argsort``."""
    import numpy as onp

    head_dim = cfg.d_model // cfg.n_heads
    kv_dim = cfg.kv_heads * head_dim
    sections = onp.split(
        onp.arange(cfg.d_model + 2 * kv_dim),
        [cfg.d_model, cfg.d_model + kv_dim])
    return onp.concatenate([
        onp.concatenate([s.reshape(tp_size, -1)[i] for s in sections])
        for i in range(tp_size)])


def qkv_to_tp_major(params: dict, cfg: GPTConfig, tp_size: int,
                    inverse: bool = False) -> dict:
    """One-time layout transform for pp×tp training: permute the
    stacked qkv kernel/bias columns rank-major (``qkv_tp_permutation``)
    so the rule table's contiguous tp sharding lands each rank's
    ``[q_i | k_i | v_i]`` locally and the pipelined step needs NO
    per-step cross-device re-permute. Apply to params at placement
    time (before ``TrainState.create``/``shard_state``) and pass
    ``qkv_tp_major=True`` to :meth:`GPT.apply`; ``inverse=True``
    restores the canonical layout (e.g. before checkpointing a state
    for a different topology). For a FRESH state, grads/opt-state/EMA
    stay consistent automatically — they follow whatever layout the
    params start in. Resuming a CANONICAL checkpoint whose optimizer
    mirrors are non-zero needs :func:`qkv_state_to_tp_major` instead:
    permuting params alone would misalign adam mu/nu columns.

    The caller must pass the SAME tp size the mesh will have — the
    permute stamps a ``_tp_major<tp>`` marker leaf into the attn_qkv
    dict (ADVICE r5) and the pp×tp apply path checks it against the
    mesh, so a mismatched, double, or missing permute raises instead
    of silently scrambling attention; every canonical-layout path
    (plain apply, generate, the serving engine) rejects marked params
    outright."""
    import numpy as onp

    if cfg.n_heads % tp_size or cfg.kv_heads % tp_size:
        # same precondition the pipelined step enforces for the mesh's
        # tp — without it the permutation would cross head boundaries
        # and "succeed" into silently mis-sliced attention
        raise ValueError(
            f"qkv_to_tp_major needs n_heads ({cfg.n_heads}) and "
            f"kv_heads ({cfg.kv_heads}) divisible by tp ({tp_size})")
    stamped = _qkv_tp_marker(params)
    if inverse and stamped != tp_size:
        raise ValueError(
            f"qkv_to_tp_major(inverse=True, tp_size={tp_size}) on "
            + ("params that were never permuted (no _tp_major marker)"
               if stamped is None else
               f"params permuted for tp={stamped}")
            + " — inverting the wrong permutation scrambles attention")
    if not inverse and stamped is not None:
        raise ValueError(
            f"params are already tp-major (tp={stamped}) — a second "
            "permute would scramble the qkv columns; restore with "
            "inverse=True first")
    perm = qkv_tp_permutation(cfg, tp_size)
    if inverse:
        perm = onp.argsort(perm)
    qkv = params["blocks"]["attn_qkv"]
    # column-layout leaves permute together: the full-precision kernel
    # OR the quantized pair (models/quant.py) — qkernel's out axis is
    # 2 in both formats (int4 packs along the INPUT axis, so the
    # column permute never crosses a packed byte) and qscale's out
    # axis is 2 for both the per-channel (L, 1, out) and per-group
    # (L, G, out) shapes
    new_qkv = {k: v for k, v in qkv.items()
               if k not in ("kernel", "qkernel", "qscale", "bias")
               and not k.startswith(_TP_MAJOR_PREFIX)}
    for key in ("kernel", "qkernel", "qscale"):
        if key in qkv:
            new_qkv[key] = jnp.take(qkv[key], perm, axis=2)
    if "bias" in qkv:
        new_qkv["bias"] = jnp.take(qkv["bias"], perm, axis=1)
    if not inverse:
        # stacked (n_layers,) zeros: scans/shards/checkpoints like any
        # block leaf, and the tp size rides in the KEY so optimizer
        # updates to the value cannot erase the layout fact
        ref = qkv.get("kernel", qkv.get("qkernel"))
        mark_dt = ref.dtype if jnp.issubdtype(ref.dtype,
                                              jnp.floating) \
            else jnp.float32
        new_qkv[f"{_TP_MAJOR_PREFIX}{tp_size}"] = jnp.zeros(
            (ref.shape[0],), mark_dt)
    return {**params,
            "blocks": {**params["blocks"], "attn_qkv": new_qkv}}


def qkv_state_to_tp_major(state: Any, cfg: GPTConfig, tp_size: int,
                          inverse: bool = False) -> Any:
    """:func:`qkv_to_tp_major` for a FULL TrainState — a resumed
    canonical checkpoint carries param-shaped optimizer mirrors (adam
    mu/nu, EMA shadows, grad accumulators) whose qkv columns must
    permute IN LOCKSTEP with the params: permuting only the params
    would divide fresh gradients by another column's second moment,
    silently corrupting the resumed run. Fresh states (zero mirrors)
    are unaffected either way; use this whenever the state predates
    the layout change. ``inverse=True`` restores the canonical layout
    (e.g. before checkpointing for a different topology)."""
    from torchbooster_tpu.parallel.sharding import is_param_shaped

    tf = lambda tree: qkv_to_tp_major(tree, cfg, tp_size,
                                      inverse=inverse)
    is_mirror = lambda leaf: is_param_shaped(leaf, state.params)
    out = state.replace(
        params=tf(state.params),
        opt_state=jax.tree.map(
            lambda leaf: tf(leaf) if is_mirror(leaf) else leaf,
            state.opt_state, is_leaf=is_mirror))
    if getattr(state, "grad_acc", None) is not None:
        out = out.replace(grad_acc=tf(state.grad_acc))
    if getattr(state, "ema", None) is not None:
        out = out.replace(ema=tf(state.ema))
    return out


def _pipelined_blocks(params: dict, x: jax.Array, cfg: GPTConfig,
                      mesh: Mesh, remat: bool, attn_impl: str,
                      drop: float, layer_keys: jax.Array,
                      use_sp: bool,
                      qkv_tp_major: bool = False
                      ) -> tuple[jax.Array, jax.Array]:
    """Route the layer-stacked block scan through the GPipe kernel when
    the mesh has ``pp > 1`` — the blocks were layer-stacked for exactly
    this (parallel/pipeline.py): each pp stage holds ``L/pp`` contiguous
    layers, microbatches ride one ppermute ring, and dp/fsdp batch axes
    compose (each data group drives its own ring). Embedding and LM head
    stay outside the pipeline (they are not layer-stacked). Returns
    (x, aux).

    MoE blocks pipeline too: with an ``ep`` axis in the mesh the
    experts shard across it INSIDE each stage (each rank holds E/ep
    experts, routes its own tokens to them — no all-to-all, the
    activations are ep-replicated — and one psum combines; global
    capacity semantics exactly preserved, ``moe_apply(ep=...)``);
    without ``ep`` experts run replicated within the stage. Either
    way the load-balance aux is the per-microbatch estimator — expert load
    fractions and capacity are computed per microbatch, so aux tracks
    but does not bitwise-match the un-pipelined value. At TIGHT
    capacity factors the drop decisions themselves are per-microbatch,
    so overflowing tokens may differ from the un-pipelined forward
    (pipeline_apply's docstring spells out the contract); with ample
    capacity the logits match bitwise. Under sp the same contract
    tightens one more notch: routing is per SEQUENCE SHARD (each sp
    rank routes its local S/sp tokens with locally-computed capacity —
    tokens never cross sp ranks for expert compute, the standard
    sequence-parallel MoE layout), and the aux is the pmean of the
    per-shard estimators. Ample capacity again gives bitwise-matching
    logits; tight capacity drops a per-(microbatch, shard) token set.

    Tensor parallelism composes INSIDE the pipeline: with ``tp > 1`` in
    the mesh, block weights additionally shard Megatron-style across tp
    (qkv/fc1/fc3 column-parallel with each rank holding its head/hidden
    subset, proj/fc2 row-parallel with an explicit psum —
    ``_block_core(tp=...)``). MoE blocks compose with tp the same way:
    expert hidden splits across tp (fc1 column-, fc2 row-parallel with
    the psum inside ``moe_apply``'s expert matmuls) while routing —
    token-level math on the tp-replicated activations — is computed
    identically on every tp rank. The qkv kernel's output columns are the
    concatenation [q | k | v], so a contiguous tp split would misalign
    with the per-rank [q_i | k_i | v_i] the local math slices — the
    columns must be rank-major. ``qkv_tp_major=True`` declares the
    caller already stored them that way (``qkv_to_tp_major`` at
    placement time — the fast path: zero per-step layout cost);
    otherwise the canonical columns are permuted here, which costs a
    weights-sized cross-device gather of the stacked qkv kernel per
    step when the rule table stored it tp-sharded (fine at test
    scale, the slow default for real pp×tp training).

    Sequence parallelism also composes: with ``sp > 1`` the microbatch
    spec shards the SEQUENCE dim over sp and the attend hook is the
    ring-attention per-device body (parallel/ring.py ``_ring_local`` —
    ppermute online softmax over the manual sp axis, grouped K/V
    un-expanded); rope rotates by the shard's GLOBAL positions and
    dropout keys fold in the sp rank so masks stay independent across
    sequence shards."""
    from torchbooster_tpu.parallel.pipeline import pipeline_apply
    from torchbooster_tpu.parallel.sharding import path_str as _path_str

    tp_size = mesh.shape.get("tp", 1)
    tp = ("tp", tp_size) if tp_size > 1 else None
    ep_size = mesh.shape.get("ep", 1) if cfg.n_experts > 0 else 1
    ep = ("ep", ep_size) if ep_size > 1 else None
    sp_size = mesh.shape["sp"] if use_sp else 1
    blocks = params["blocks"]
    if tp is not None:
        if cfg.n_heads % tp_size or cfg.kv_heads % tp_size:
            raise ValueError(
                f"pp x tp needs n_heads ({cfg.n_heads}) and kv_heads "
                f"({cfg.kv_heads}) divisible by tp ({tp_size})")
        if cfg.n_experts > 0:
            hidden = blocks["moe_fc1"]["kernel"].shape[-1]
            if hidden % tp_size:
                raise ValueError(
                    f"pp x tp MoE needs expert hidden ({hidden}) "
                    f"divisible by tp ({tp_size})")
        if not qkv_tp_major:
            perm = jnp.asarray(qkv_tp_permutation(cfg, tp_size))
            qkv = blocks["attn_qkv"]
            blocks = {**blocks, "attn_qkv": {
                "kernel": jnp.take(qkv["kernel"], perm, axis=2),
                **({"bias": jnp.take(qkv["bias"], perm, axis=1)}
                   if "bias" in qkv else {})}}
    if ep is not None and cfg.n_experts % ep_size:
        raise ValueError(
            f"pp x ep needs n_experts ({cfg.n_experts}) divisible "
            f"by ep ({ep_size})")

    if tp is not None or ep is not None:
        t_ax = "tp" if tp is not None else None
        e_ax = "ep" if ep is not None else None
        col = {"attn_qkv", "mlp_fc1", "mlp_fc3"}   # out dim over tp
        row = {"attn_proj", "mlp_fc2"}             # in dim over tp

        def assign(path: tuple, leaf: Any) -> P:
            name = _path_str(path)
            layer, kind = name.split("/")[0], name.split("/")[-1]
            if kind.startswith(_TP_MAJOR_PREFIX):
                # the layout-marker leaf: stacked (n_layers,) zeros —
                # layer axis over pp like every other block scalar
                return P("pp")
            if layer in col:
                return P("pp", None, t_ax) if kind == "kernel" \
                    else P("pp", t_ax)
            if layer in row and kind == "kernel":
                return P("pp", t_ax, None)
            # expert weights (leading dims: layer, expert): experts
            # over ep (each rank's local slice — moe_apply routes its
            # own tokens, psum combines), hidden over tp — fc1
            # column-parallel, fc2 row-parallel (psum inside
            # moe_apply's expert_mlps); gate replicates (routing is
            # global on every rank)
            if layer == "moe_fc1":
                return P("pp", e_ax, None, t_ax) if kind == "kernel" \
                    else P("pp", e_ax, t_ax)
            if layer == "moe_fc2":
                return P("pp", e_ax, t_ax, None) if kind == "kernel" \
                    else P("pp", e_ax, None)
            return P("pp")

        block_specs = jax.tree_util.tree_map_with_path(assign, blocks)
        param_specs = (block_specs, P("pp"))
    else:
        param_specs = None

    if use_sp:
        import math as _math

        from torchbooster_tpu.parallel.ring import select_ring_body

        head_dim = cfg.d_model // cfg.n_heads
        sm_scale = 1.0 / _math.sqrt(head_dim)

        def attend(q, k, v):
            # per-device ring body, directly: inside the pipeline's
            # shard_map the sp axis is already manual, so the ring's
            # collectives run as-is (no nested shard_map). Body choice
            # is ring_attention's own policy (shared selector — the
            # pipeline must not silently drop the flash kernel at
            # exactly the scale sp targets, and unknown impl names
            # stay loud)
            body = select_ring_body(
                attn_impl, s_loc=q.shape[1], sp_size=sp_size,
                causal=True, sm_scale=sm_scale,
                rep=q.shape[2] // k.shape[2])
            return body(q, k, v), None
    else:
        def attend(q, k, v):
            # plain attention dispatch: inside the pipeline's shard_map
            # the global constrainer must not re-annotate shardings
            return attention(q, k, v, causal=True, impl=attn_impl), None

    def pp_layer(layer_in: tuple, h: jax.Array, mb_idx: jax.Array):
        bp, key = layer_in
        # fold the microbatch index into the layer key: every microbatch
        # must draw an INDEPENDENT dropout mask (the full-batch forward
        # draws one mask over all samples; reusing one key per layer
        # here would correlate the noise m-fold across microbatches);
        # under sp, fold the sequence-shard rank too
        positions = None
        if use_sp:
            shard = jax.lax.axis_index("sp")
            positions = shard * h.shape[1] + jnp.arange(h.shape[1])
            if drop:
                key = jax.random.fold_in(key, shard)
        key = jax.random.fold_in(key, mb_idx) if drop else key
        h, layer_aux, _ = _block_core(
            bp, h, cfg, attend, positions=positions,
            dropout=drop, dropout_key=key, tp=tp, ep=ep)
        return h, layer_aux

    layer = jax.checkpoint(
        pp_layer, policy=_remat_policy()) if remat else pp_layer
    data = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names) \
        or None
    x_spec = P(None, data, "sp") if use_sp else None
    # MoE keeps the shallow m = P schedule: capacity and token-drop
    # decisions are per microbatch-slice, so deepening the default
    # schedule would silently change which tokens overflow at tight
    # capacity factors; dense blocks take the deeper default (less
    # bubble, identical math up to reassociation)
    n_mb = mesh.shape["pp"] if cfg.n_experts > 0 else None
    # per-sequence-shard MoE routing makes each sp rank's aux a LOCAL
    # estimator (a different estimator than the global one — same
    # class of deviation as the per-microbatch granularity above);
    # aux_axes pmeans it once at the pipeline epilogue so the
    # returned scalar is collective-uniform
    return pipeline_apply(layer, (blocks, layer_keys), x, mesh,
                          n_microbatches=n_mb,
                          with_mb_index=True, with_aux=True,
                          param_specs=param_specs, x_spec=x_spec,
                          aux_axes=("sp",) if use_sp else ())


def _dropout(x: jax.Array, rate: float,
             key: jax.Array | None) -> jax.Array:
    """Inverted dropout; identity when ``rate`` is 0 (a static python
    float, so the off path adds zero ops to the compiled graph)."""
    if not rate or key is None:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def _row_dense(params: dict, x: jax.Array, reduce,
               delta: jax.Array | None = None) -> jax.Array:
    """Row-parallel dense: ``reduce`` (a psum over the tp axis, or
    identity) runs BETWEEN the matmul and the bias add — each device
    holds a row slice of the kernel, so partial products sum across
    devices while the (replicated) bias is added exactly once.
    Quantized kernels (``qkernel``, models/quant.py) dequantize inside
    the matmul read; the int8 per-output-channel scale is replicated
    across row shards, so scaling before the psum is exact. ``delta``
    (the LoRA ranked product, serving) adds to the PARTIAL products —
    its own A-factor is row-sliced like the kernel, so it rides the
    same single psum."""
    if "qkernel" in params:
        y = _qmatmul(params, x)
    else:
        y = x @ params["kernel"].astype(x.dtype)
    if delta is not None:
        y = y + delta
    y = reduce(y)
    if "bias" in params:
        y = y + params["bias"].astype(x.dtype)
    return y


def _block_core(bp: dict, x: jax.Array, cfg: GPTConfig, attend,
                constrain=lambda x: x,
                capacity_factor: float | None = None,
                positions: jax.Array | None = None,
                dropout: float = 0.0,
                dropout_key: jax.Array | None = None,
                tp: tuple[str, int] | None = None,
                tp_attn: tuple[str, int] | None = None,
                ep: tuple[str, int] | None = None,
                lora: tuple | None = None
                ) -> tuple[jax.Array, jax.Array, Any]:
    """The transformer block math, shared by every path (training
    forward, prefill, cached decode) so they cannot drift apart.
    ``attend(q, k, v) -> (o, extras)`` supplies the attention flavor;
    ``extras`` passes through (K/V for prefill, updated caches for
    decode). ``positions``: absolute token indices (default
    ``arange(s)``) — consumed only by rope, BEFORE ``attend``, so
    rotated K flows into caches/rings/all-to-alls uniformly.
    ``dropout``/``dropout_key``: residual-branch dropout (training
    forward only; prefill/decode leave the defaults = off).
    ``tp=(axis, size)``: MANUAL tensor parallelism for shard_map
    callers (the pipeline): bp holds per-rank Megatron slices —
    column-parallel qkv/fc1/fc3 (local head/hidden subset), row-
    parallel proj/fc2 (psum over ``axis`` before the bias).
    ``tp_attn=(axis, size)``: MANUAL tensor parallelism over the
    ATTENTION only (the serving engine's layout, serving/tp.py): bp
    holds per-rank qkv/proj slices exactly as under ``tp`` but the
    MLP (and MoE) weights are FULL and every rank computes them
    redundantly with NO reduce — one psum per layer (after the
    O projection) instead of two; mutually exclusive with ``tp``.
    ``ep=(axis, size)``: MANUAL expert parallelism — bp's expert
    tensors hold this rank's slice (``moe_apply(ep=...)``). The
    auto-SPMD paths leave both None and let XLA place the collectives.
    ``lora=((a_qkv, b_qkv, a_proj, b_proj), lane_ids)``: batched
    multi-adapter LoRA deltas (serving/adapters.py) — this LAYER's
    adapter stacks ``(lanes, d, r)`` / ``(lanes, r, qkv_out)`` /
    ``(lanes, d, r)`` / ``(lanes, r, d)`` plus the per-row lane ids
    ``(B,)`` (lane 0 = the all-zero base adapter). Each row's ranked
    products ``h @ A[g] @ B[g]`` add to the qkv and O projections;
    everything is a traced VALUE gather, so adapter churn never
    recompiles. Under ``tp_attn`` the stacks arrive FULL (replicated
    host operands): ``b_qkv`` (rank-major-permuted columns, the
    registry's load-time layout) and ``a_proj`` rows slice to this
    rank's shard at ``axis_index``, so the qkv delta lands on the
    local columns and the proj delta rides the partial products
    through the ONE existing psum. Serving layouts only — the
    training ``tp`` path rejects it.
    Returns (x, aux_loss, extras)."""
    b, s, d = x.shape
    n_heads, kv_heads = cfg.n_heads, cfg.kv_heads
    head_dim = d // n_heads
    reduce = lambda y: y
    attn_reduce = reduce
    if tp is not None and tp_attn is not None:
        raise ValueError("_block_core: tp and tp_attn are mutually "
                         "exclusive manual-parallelism modes")
    if lora is not None and tp is not None:
        raise ValueError(
            "_block_core: lora rides the serving layouts (single-chip "
            "or tp_attn) — the training tp path shards the MLP too "
            "and has no adapter surface")
    if tp is not None:
        tp_axis, tp_size = tp
        n_heads //= tp_size
        kv_heads //= tp_size
        reduce = lambda y: jax.lax.psum(y, tp_axis)
        attn_reduce = reduce
    elif tp_attn is not None:
        tp_axis, tp_size = tp_attn
        n_heads //= tp_size
        kv_heads //= tp_size
        attn_reduce = lambda y: jax.lax.psum(y, tp_axis)
    q_width = n_heads * head_dim
    aux = jnp.zeros((), jnp.float32)

    # the four named scopes below (and embed / head / sample / kv_write
    # / loss / optimizer elsewhere) are the vocabulary the benchmark's
    # trace readers attribute device time by (docs/observability.md);
    # renaming one means bumping utils.PROGRAM_METADATA_VERSION
    with jax.named_scope("attn_qkv"):
        h = L.layer_norm(bp["ln1"], x)
        qkv = L.dense(bp["attn_qkv"], h)
        la_p = lb_p = lane_ids = None
        if lora is not None:
            (la_q, lb_q, la_p, lb_p), lane_ids = lora
            if tp_attn is not None:
                # full replicated stacks -> this rank's shard: b_qkv's
                # columns are rank-major (the registry permuted them at
                # load time to match qkv_to_tp_major's layout), a_proj's
                # input rows follow the local heads
                i = jax.lax.axis_index(tp_axis)
                w_loc = qkv.shape[-1]
                lb_q = jax.lax.dynamic_slice_in_dim(
                    lb_q, i * w_loc, w_loc, axis=2)
                la_p = jax.lax.dynamic_slice_in_dim(
                    la_p, i * q_width, q_width, axis=1)
            dq = jnp.einsum("bsd,bdr->bsr", h,
                            la_q[lane_ids].astype(h.dtype))
            qkv = qkv + jnp.einsum("bsr,bro->bso", dq,
                                   lb_q[lane_ids].astype(h.dtype))
        q = qkv[..., :q_width].reshape(b, s, n_heads, head_dim)
        kv_dim = kv_heads * head_dim
        k = qkv[..., q_width:q_width + kv_dim].reshape(b, s, kv_heads,
                                                       head_dim)
        v = qkv[..., q_width + kv_dim:].reshape(b, s, kv_heads, head_dim)
        if cfg.pos == "rope":
            if positions is None:
                positions = jnp.arange(s)
            q = _rope(q, positions, cfg.rope_base)
            k = _rope(k, positions, cfg.rope_base)
    if dropout and dropout_key is not None:
        k_attn, k_mlp = jax.random.split(dropout_key)
    else:
        k_attn = k_mlp = None
    with jax.named_scope("attn_core"):
        o, extras = attend(q, k, v)
    with jax.named_scope("attn_out"):
        o_flat = o.reshape(b, s, q_width)
        proj_delta = None
        if lora is not None:
            dp = jnp.einsum("bsd,bdr->bsr", o_flat,
                            la_p[lane_ids].astype(o_flat.dtype))
            proj_delta = jnp.einsum("bsr,bro->bso", dp,
                                    lb_p[lane_ids].astype(o_flat.dtype))
        x = constrain(x + _dropout(
            _row_dense(bp["attn_proj"], o_flat, attn_reduce,
                       delta=proj_delta),
            dropout, k_attn))
    with jax.named_scope("mlp"):
        h = L.layer_norm(bp["ln2"], x)
        if cfg.n_experts > 0:
            from torchbooster_tpu.models.moe import moe_apply

            m, aux = moe_apply(
                bp, h, top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor
                if capacity_factor is None else capacity_factor,
                reduce=None if tp is None else reduce, ep=ep)
            x = constrain(x + _dropout(m, dropout, k_mlp))
        elif "mlp_fc3" in bp:   # swiglu: silu(xW1) ⊙ xW3 → W2
            h = jax.nn.silu(L.dense(bp["mlp_fc1"], h)) \
                * L.dense(bp["mlp_fc3"], h)
            x = constrain(x + _dropout(
                _row_dense(bp["mlp_fc2"], h, reduce), dropout, k_mlp))
        else:
            h = jax.nn.gelu(L.dense(bp["mlp_fc1"], h))
            x = constrain(x + _dropout(
                _row_dense(bp["mlp_fc2"], h, reduce), dropout, k_mlp))
    return x, aux, extras


def _quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-(token, head) int8 quantization for the KV cache:
    ``q = round(x / s)`` with ``s = absmax/127`` over the head dim.
    Scales are stored bf16 (1/Dh the elements × 2 bytes ≈ 3% of the
    int8 cache bytes at Dh=64 — fp32 scales would cost 4/Dh ≈ 6%), and
    the QUANTIZATION divides by the rounded bf16 scale so the stored
    pair is exactly self-consistent. Decode HBM reads drop to ~half of
    bf16 *if* XLA folds the widening convert into the dot reads (no
    benchmark cell runs an int8 pool yet: ROADMAP D2). Returns a
    2-tuple ``(int8 values, bf16 scales)`` — scales keep the head dim
    as a trailing 1 for broadcasting."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8).astype(jnp.bfloat16)
    q = jnp.clip(jnp.round(x.astype(jnp.float32)
                           / scale.astype(jnp.float32)),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _grouped_cache_attention(q: jax.Array, cache_k, cache_v,
                             visible: jax.Array, *,
                             state: bool = False):
    """THE cached-attention numerics core, shared by the dense decode
    path (``_cached_block`` → ``jit_generate``, the A/B control) and
    the paged serving engine (serving/engine.py) so the two cannot
    drift. q is (B, S_q, H, Dh); caches are (B, T, H_kv, Dh) token
    axes — either plain arrays (bf16/fp32) or ``(int8 values, bf16
    scales)`` pairs; ``visible`` broadcasts against the (B, g, rep,
    S_q, T) score tensor (False → masked).

    The cache stores only kv_heads (the GQA memory win) and is read
    GROUPED: q folds to (B, S, groups, rep, D) and the einsums
    contract against the grouped cache directly — the decode hot loop
    never materializes the rep-times expansion (its HBM reads dominate
    each step).

    Operands stay in cache dtype with fp32 ACCUMULATION: an explicit
    fp32 astype here makes XLA either materialize an fp32 copy of the
    whole cache per step (2× the HBM traffic decode is roofed on) or
    run the MXU in fp32 mode — narrow inputs +
    preferred_element_type=f32 is the native MXU contract (softmax
    itself stays fp32). One deliberate exception (ADVICE r5): on the
    NON-quantized path the softmax probs stay fp32 into the PV dot —
    probs are tiny next to the cache, V keeps its narrow HBM layout
    and only widens in the dot's fused operand read, and the bf16
    probs downcast was the one numerics loss the decisive-head bf16
    parity test exists to guard. For the int8 cache the per-token scales FACTOR
    OUT of the dots: scores scale by s_k[token] after the QK dot, and
    s_v folds into the (small) probs tensor before the PV dot. The
    int8→dot-dtype convert is written to fuse into the dot's operand
    read (keeping the HBM stream at 1 byte/elem); whether XLA actually
    folds it — vs materializing a widened copy — is exactly what the
    queued decode_int8 A/B row measures. Dot precision follows the
    caller's compute dtype (q.dtype), so fp32 callers keep fp32 dots
    over the dequantized values.

    ``state=False`` returns the normalized (B, S_q, H, Dh) output.
    ``state=True`` returns the flash-style partial-softmax triple
    ``(o_unnorm fp32 (B, S_q, g, rep, Dh), m (B, g, rep, S_q),
    l (B, g, rep, S_q))`` — the paged engine computes one such triple
    per page and combines across each slot's pages with the standard
    online-softmax merge, which is exactly how the same math spreads
    over a token axis that is not contiguous in memory."""
    b, s_q, n_heads, head_dim = q.shape
    quantized = isinstance(cache_k, tuple)
    if quantized:
        ck, ck_s = cache_k
        cv, cv_s = cache_v
    else:
        ck, cv = cache_k, cache_v
    kv_heads = ck.shape[2]
    rep = n_heads // kv_heads
    qg = q.reshape(b, s_q, kv_heads, rep, head_dim)
    dot_t = q.dtype if quantized else ck.dtype
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg.astype(dot_t), ck.astype(dot_t),
        preferred_element_type=jnp.float32) / (head_dim ** 0.5)
    if quantized:
        scores = scores * jnp.transpose(
            ck_s[..., 0], (0, 2, 1))[:, :, None, None, :]
    scores = jnp.where(visible, scores, -1e30)
    if state:
        m = jnp.max(scores, axis=-1)                  # (B, g, rep, S_q)
        probs = jnp.exp(scores - m[..., None])
        l = jnp.sum(probs, axis=-1)
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    if quantized:
        probs = probs * jnp.transpose(
            cv_s[..., 0], (0, 2, 1))[:, :, None, None, :]
        probs = probs.astype(dot_t)
        pv = cv.astype(dot_t)
    else:
        # probs stay fp32 into the PV dot (ADVICE r5 numerics pin):
        # they are the SMALL operand — V is the one that must stay
        # narrow in HBM, and its widening convert is written to fuse
        # into the dot's operand read exactly like the int8 path's
        # (keeping the cache stream at its native byte width)
        pv = cv.astype(jnp.float32)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", probs, pv,
                   preferred_element_type=jnp.float32)
    if state:
        return o, m, l
    return o.astype(q.dtype).reshape(b, s_q, n_heads, head_dim)


def _cached_block(bp: dict, x: jax.Array, cache_k, cache_v,
                  pos: jax.Array, cfg: GPTConfig
                  ) -> tuple[jax.Array, Any, Any]:
    """One decode step through one block: x is (B, 1, d) at position
    ``pos``; K/V caches are (B, S_cache, H, Dh) with entries valid for
    positions < pos — either plain arrays (bf16/fp32) or ``(int8
    values, bf16 scales)`` pairs (the quantized cache, ``cache_dtype=
    "int8"``). Returns (x, cache_k, cache_v) with this token's K/V
    written at ``pos``. MoE capacity floors at n_experts so a decode
    micro-batch never drops tokens (full-sequence drop behavior cannot
    be replicated incrementally anyway)."""
    quantized = isinstance(cache_k, tuple)
    s_cache = (cache_k[0] if quantized else cache_k).shape[1]

    def attend(q, k, v):
        with jax.named_scope("kv_write"):
            if quantized:
                (ck, ck_s), (cv, cv_s) = cache_k, cache_v
                k_q, k_s = _quantize_kv(k)
                v_q, v_s = _quantize_kv(v)
                ck = jax.lax.dynamic_update_slice(ck, k_q,
                                                  (0, pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(cv, v_q,
                                                  (0, pos, 0, 0))
                ck_s = jax.lax.dynamic_update_slice(ck_s, k_s,
                                                    (0, pos, 0, 0))
                cv_s = jax.lax.dynamic_update_slice(cv_s, v_s,
                                                    (0, pos, 0, 0))
                new_k, new_v = (ck, ck_s), (cv, cv_s)
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
                new_k, new_v = ck, cv
        visible = jnp.arange(s_cache)[None, None, None, None, :] <= pos
        o = _grouped_cache_attention(q, new_k, new_v, visible)
        return o, (new_k, new_v)

    x, _, (cache_k, cache_v) = _block_core(
        bp, x, cfg, attend,
        capacity_factor=max(cfg.capacity_factor, float(cfg.n_experts)),
        positions=jnp.asarray(pos)[None])   # rope rotates this token's
    return x, cache_k, cache_v              # q/k at its absolute index


@jax.named_scope("head")
def _lm_head(params: dict, x: jax.Array) -> jax.Array:
    x = L.layer_norm(params["ln_f"], x)
    if "head" in params:
        return L.dense(params["head"], x)
    wte = params["wte"]
    if "qtable" in wte:
        # tied head over the per-row int8 table: the dot streams the
        # 1-byte rows and each row's scale lands on the VOCAB axis of
        # the logits — the transposed analogue of qmatmul's
        # factored-out per-output-channel scale (models/quant.py)
        y = x @ wte["qtable"].astype(x.dtype).T
        return y * wte["qscale"][:, 0].astype(x.dtype)
    return x @ wte["table"].astype(x.dtype).T


@jax.named_scope("sample")
def _mask_logits(logits: jax.Array, mask: jax.Array | None
                 ) -> jax.Array:
    """Constrained-decoding legality mask: forbidden positions drop
    to the dtype's finite minimum (NOT ``-inf`` — an all-masked row
    would turn softmax into NaN; finfo.min keeps it a degenerate but
    finite distribution, and the structured subsystem guarantees at
    least one legal token per live row anyway). ``mask`` broadcasts
    against ``(..., vocab)`` and rides into the compiled decode and
    verify steps as a trailing VALUE operand (serving/engine.py) —
    shape fixed by pool geometry, so zero recompiles. ``None`` (and
    an all-True row) is an exact no-op, which is what keeps
    unconstrained traffic token-identical when the feature is on."""
    if mask is None:
        return logits
    return jnp.where(mask, logits, jnp.finfo(logits.dtype).min)


def _filter_logits(logits: jax.Array, temperature: float,
                   top_k: int | None, top_p: float | None,
                   mask: jax.Array | None = None) -> jax.Array:
    """Temperature-scaled, top-k/top-p-filtered fp32 logits — THE
    sampling distribution every decode flavor draws from, factored out
    of :func:`_make_pick` so the speculative verify step
    (serving/speculative.py) can compute acceptance probabilities over
    the SAME filtered distribution it samples fallbacks from. Filters
    compose in the fixed order the dense path always used: top-k caps
    the candidate set first, then top-p's cumulative mass is measured
    over the top-k-FILTERED distribution (so ``top_k=2, top_p=0.9``
    can keep fewer tokens than either alone, never more). Works on any
    ``(..., vocab)`` shape — the verify step filters a whole
    ``(slots, draft+1, vocab)`` block at once; requires
    ``temperature > 0`` (greedy never builds a distribution).
    ``mask`` (optional, broadcastable boolean legality mask from the
    structured subsystem) applies FIRST via :func:`_mask_logits`, so
    top-k/top-p measure over the constrained candidate set."""
    logits = _mask_logits(logits, mask)
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None or top_p is not None:
        # ONE descending sort serves both filters (this runs per
        # token inside the decode scan)
        desc = jnp.sort(logits, axis=-1)[..., ::-1]
        if top_k is not None:
            logits = jnp.where(logits < desc[..., top_k - 1:top_k],
                               -jnp.inf, logits)
            desc = jnp.where(
                jnp.arange(desc.shape[-1]) < top_k,
                desc, -jnp.inf)
        if top_p is not None:
            probs = jax.nn.softmax(desc, axis=-1)
            # keep while the mass BEFORE a token is < p (top-1
            # always in)
            keep = jnp.cumsum(probs, axis=-1) - probs < top_p
            thresh = jnp.min(jnp.where(keep, desc, jnp.inf),
                             axis=-1, keepdims=True)
            logits = jnp.where(logits >= thresh, logits, -jnp.inf)
    return logits


def _make_pick(temperature: float, top_k: int | None,
               top_p: float | None, dtype: Any):
    """``pick(rng_step, logits) -> ids`` — the next-token rule, shared
    by :func:`generate`'s decode scan and the serving engine's paged
    step (serving/engine.py) so filtering semantics cannot drift.
    Greedy at ``temperature=0`` (plain argmax: ties resolve to the
    LOWEST token id, whatever the logits dtype); otherwise categorical
    over :func:`_filter_logits` — top_p keeps the smallest set of
    tokens whose probability mass reaches p (always at least the top
    token)."""

    @jax.named_scope("sample")
    def pick(rng_step: jax.Array, logits: jax.Array) -> jax.Array:
        if temperature == 0:
            return jnp.argmax(logits, axis=-1).astype(dtype)
        return jax.random.categorical(
            rng_step,
            _filter_logits(logits, temperature, top_k, top_p)
        ).astype(dtype)

    return pick


def _make_branch_pick(temperature: float, top_k: int | None,
                      top_p: float | None, dtype: Any):
    """``pick(keys, logits) -> (ids, logprobs)`` — the PER-BRANCH
    next-token rule of copy-on-write parallel sampling
    (serving/engine.py ``parallel_sampling=True``), built from the
    same knobs as :func:`_make_pick` so filtering semantics cannot
    drift.

    ``keys`` is ``(B, 2)`` — one already-folded PRNG key per slot
    (the engine folds the slot's branch key with its context length,
    so a branch's token at depth d is a pure function of (branch key,
    depth, logits) — token-exact vs an independent single-slot run
    with the same key, whatever else shares the batch). ``logits`` is
    ``(B, vocab)``. Returns the picked ids and their log-probability
    under the distribution actually sampled from: the FILTERED
    distribution at ``temperature > 0`` (what rejection-free
    categorical draws land on), the raw softmax under greedy — the
    per-branch sequence-logprob ``best_of`` ranks by."""

    @jax.named_scope("sample")
    def pick(keys: jax.Array, logits: jax.Array
             ) -> tuple[jax.Array, jax.Array]:
        if temperature == 0:
            ids = jnp.argmax(logits, axis=-1)
            lp = jax.nn.log_softmax(
                logits.astype(jnp.float32), axis=-1)
        else:
            f = _filter_logits(logits, temperature, top_k, top_p)
            ids = jax.vmap(jax.random.categorical)(keys, f)
            lp = jax.nn.log_softmax(f, axis=-1)
        lp = jnp.take_along_axis(lp, ids[:, None], axis=-1)[:, 0]
        return ids.astype(dtype), lp

    return pick


def _make_spec_pick(temperature: float, top_k: int | None,
                    top_p: float | None, dtype: Any):
    """``verify(rng_step, logits, draft) -> (accept, token)`` — the
    PER-POSITION pick + acceptance rule of speculative decoding
    (serving/speculative.py), built from the same knobs as
    :func:`_make_pick` so the two cannot drift.

    ``logits`` is ``(S, K+1, vocab)``: position ``j``'s next-token
    logits after consuming verify input ``j`` (input 0 is the slot's
    pending token, inputs 1..K the drafted tokens). ``draft`` is
    ``(S, K)`` proposed ids, ``-1`` = no proposal (sentinel padding —
    short or absent drafts ride the same fixed-``K`` executable).

    Greedy (``temperature == 0``): ``accept[s, j] = (argmax_j ==
    draft[s, j])`` and ``token`` is the argmax chain — emitting
    ``draft[:a] + [token[a]]`` (``a`` = longest accepted prefix)
    reproduces the non-speculative greedy stream EXACTLY, because each
    position's argmax is conditioned on a confirmed prefix.

    Sampling: standard speculative rejection sampling (Leviathan et
    al. 2023) against the deterministic point-mass prompt-lookup
    draft, over the FILTERED distribution ``p = softmax(
    _filter_logits(...))``: accept ``d_j`` with probability
    ``p_j(d_j)`` (``u < p``); on rejection emit a sample from the
    residual ``max(p_j - q_j, 0)`` renormalized — ``q`` a point mass,
    so that is ``p_j`` with ``d_j`` removed — and a fully-accepted
    chain emits a bonus sample from the untouched ``p_K``. The output
    distribution is exactly the autoregressive sampling distribution.
    Sentinel positions never accept and their fallback token is an
    UNMASKED sample (no proposal to exclude).

    ``parent`` (greedy only) generalizes the chain to a TREE of
    candidate branches (serving/speculative.py tree drafting):
    ``(S, K)`` node indices where draft node ``j`` (verify input
    ``j + 1``) hangs off node ``parent[s, j] ∈ [0, j]`` — node 0 is
    the root/pending token. ``accept[s, j]`` then tests the pick AT
    THE PARENT position against the node's token (the chain is
    ``parent[j] = j``, which reproduces the linear rule bit-for-bit);
    the host walks the accepted tree for the best root-to-leaf path.
    Tree verification under ``temperature > 0`` needs
    without-replacement residual bookkeeping across siblings and is
    rejected loudly (the engine enforces greedy for tree mode)."""

    @jax.named_scope("sample")
    def verify(rng_step: jax.Array, logits: jax.Array,
               draft: jax.Array, parent: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
        k = draft.shape[1]
        valid = draft >= 0
        if temperature == 0:
            picks = jnp.argmax(logits, axis=-1).astype(dtype)
            if parent is None:
                accept = valid & (picks[:, :k] == draft)
            else:
                at_parent = jnp.take_along_axis(picks, parent, axis=1)
                accept = valid & (at_parent == draft)
            return accept, picks
        if parent is not None:
            raise ValueError(
                "tree-structured speculative verification is "
                "greedy-only: sampling acceptance over sibling "
                "branches needs without-replacement residuals "
                "(set temperature=0 for spec_tree)")
        f = _filter_logits(logits, temperature, top_k, top_p)
        probs = jax.nn.softmax(f, axis=-1)
        d_c = jnp.clip(draft, 0, logits.shape[-1] - 1)
        p_d = jnp.take_along_axis(probs[:, :k], d_c[..., None],
                                  axis=-1)[..., 0]
        k_u, k_r, k_b = jax.random.split(rng_step, 3)
        # u in [0, 1): p_d == 1 always accepts, p_d == 0 (draft token
        # filtered out, or sentinel via the valid mask) never does
        u = jax.random.uniform(k_u, draft.shape)
        accept = valid & (u < p_d)
        # residual: the draft token masked OUT of the filtered logits
        # (only where a real proposal exists — sentinels fall back to
        # the plain filtered sample)
        hit_d = (jnp.arange(logits.shape[-1]) == d_c[..., None]) \
            & valid[..., None]
        resid = jax.random.categorical(
            k_r, jnp.where(hit_d, -jnp.inf, f[:, :k])).astype(dtype)
        bonus = jax.random.categorical(k_b, f[:, k]).astype(dtype)
        return accept, jnp.concatenate([resid, bonus[:, None]], axis=1)

    return verify


def _prefill_forward(params: dict, ids: jax.Array, cfg: GPTConfig,
                     compute_dtype: Any
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full prompt forward with per-layer K/V collected as scan
    outputs — the prefill half of every decode flavor (dense
    :func:`generate` and the paged serving engine admit requests
    through this same pass). Returns ``(x, ks, vs)`` with x the final
    hidden states (B, S, d) and ks/vs the GROUPED caches
    (L, B, S, kv_heads, Dh)."""
    s0 = ids.shape[1]
    with jax.named_scope("embed"):
        x = L.embedding(params["wte"], ids, dtype=compute_dtype)
        if "wpe" in params:
            x = x + L.embedding(params["wpe"], jnp.arange(s0),
                                dtype=compute_dtype)

    def prefill_block(x, bp):
        def attend(q, k, v):
            # cache keeps the grouped kv_heads; the dispatcher handles
            # grouped widths natively
            return attention(q, k, v, causal=True), (k, v)

        x, _, kv = _block_core(bp, x, cfg, attend)
        return x, kv

    x, (ks, vs) = jax.lax.scan(prefill_block, x, params["blocks"])
    return x, ks, vs


def generate(params: dict, ids: jax.Array,
             cfg: GPTConfig = GPTConfig(),
             n_new: int = 32,
             rng: jax.Array | None = None,
             temperature: float = 1.0,
             top_k: int | None = None,
             top_p: float | None = None,
             compute_dtype: Any = jnp.bfloat16,
             cache_dtype: Any = None) -> jax.Array:
    """Autoregressive decoding with a static-shape KV cache.

    Prefill runs the full prompt once (collecting per-layer K/V as scan
    outputs), then ``n_new`` tokens decode one at a time — each step is
    O(S_cache) attention against the cache instead of a full O(S²)
    re-forward, and the whole loop is one ``lax.scan`` (compiles once,
    static shapes throughout; SURVEY §7 dynamic-shapes note).

    ``temperature=0`` decodes greedily (no rng needed); otherwise
    ``jax.random.categorical`` samples, with optional ``top_k`` and/or
    ``top_p`` (nucleus) filtering — top_p keeps the smallest set of
    tokens whose probability mass reaches p (always at least the top
    token). Returns (B, S_prompt + n_new) token ids.

    ``cache_dtype``: ``None`` keeps the cache in ``compute_dtype``;
    ``"int8"`` stores symmetric per-(token, head) int8 values + bf16
    scales (``_quantize_kv``) — decode is roofed on reading the cache,
    so this ~halves the per-token HBM traffic at long S_cache for a
    ~0.5% quantization error on the attention output.
    """
    b, s0 = ids.shape
    s_total = s0 + n_new
    if s_total > cfg.seq_len:
        raise ValueError(
            f"prompt {s0} + n_new {n_new} exceeds cfg.seq_len="
            f"{cfg.seq_len}")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng=")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        # top_p=0 would mask EVERY token and categorical would silently
        # emit id 0 forever
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if cache_dtype not in (None, "int8", jnp.int8):
        # fail before the prefill forward, with the other arg checks
        raise ValueError(
            f"cache_dtype must be None or 'int8', got {cache_dtype!r}")
    if n_new == 0:
        return ids
    _check_pos(params, cfg)

    # --- prefill: full prompt forward, K/V collected per layer ---
    x, ks, vs = _prefill_forward(params, ids, cfg, compute_dtype)
    pad = ((0, 0), (0, 0), (0, n_new), (0, 0), (0, 0))
    if cache_dtype in ("int8", jnp.int8):
        kq, ks_sc = _quantize_kv(ks)
        vq, vs_sc = _quantize_kv(vs)
        cache_k = (jnp.pad(kq, pad), jnp.pad(ks_sc, pad))
        cache_v = (jnp.pad(vq, pad), jnp.pad(vs_sc, pad))
    else:
        cache_k = jnp.pad(ks.astype(compute_dtype), pad)  # (L,B,S,H,Dh)
        cache_v = jnp.pad(vs.astype(compute_dtype), pad)

    first_logits = _lm_head(params, x[:, -1:, :])[:, 0]    # (B, vocab)

    pick = _make_pick(temperature, top_k, top_p, ids.dtype)
    rng = jax.random.PRNGKey(0) if rng is None else rng

    def step(carry, _):
        cache_k, cache_v, last_id, pos, rng = carry
        rng, sub = jax.random.split(rng)
        with jax.named_scope("embed"):
            x = L.embedding(params["wte"], last_id[:, None],
                            dtype=compute_dtype)
            if "wpe" in params:
                x = x + L.embedding(params["wpe"], pos[None],
                                    dtype=compute_dtype)

        def layer(x, inputs):
            bp, ck, cv = inputs
            x, ck, cv = _cached_block(bp, x, ck, cv, pos, cfg)
            return x, (ck, cv)

        x, (cache_k, cache_v) = jax.lax.scan(
            layer, x, (params["blocks"], cache_k, cache_v))
        logits = _lm_head(params, x)[:, 0]
        next_id = pick(sub, logits)
        return (cache_k, cache_v, next_id, pos + 1, rng), next_id

    rng, sub = jax.random.split(rng)
    first_id = pick(sub, first_logits)
    carry = (cache_k, cache_v, first_id, jnp.asarray(s0, jnp.int32), rng)
    if n_new > 1:
        _, rest = jax.lax.scan(step, carry, None, length=n_new - 1)
        new_ids = jnp.concatenate([first_id[None], rest], axis=0)
    else:
        new_ids = first_id[None]
    return jnp.concatenate([ids, new_ids.T.astype(ids.dtype)], axis=1)


def jit_generate(cfg: GPTConfig = GPTConfig(),
                 n_new: int = 32,
                 temperature: float = 1.0,
                 top_k: int | None = None,
                 top_p: float | None = None,
                 compute_dtype: Any = jnp.bfloat16,
                 cache_dtype: Any = None):
    """One-compile decode entry: close over the static decode knobs
    (n_new, temperature mode, filters) and jit ONCE — repeated serving
    calls hit the compile cache instead of retracing ``generate``'s
    python wrapper per call (VERDICT r3 missing #4). Returns
    ``fn(params, ids, rng) -> (B, S_prompt + n_new) ids``; a given fn
    compiles once per (batch, prompt-length) shape."""

    @jax.jit
    def fn(params: dict, ids: jax.Array, rng: jax.Array) -> jax.Array:
        return generate(params, ids, cfg, n_new=n_new, rng=rng,
                        temperature=temperature, top_k=top_k,
                        top_p=top_p, compute_dtype=compute_dtype,
                        cache_dtype=cache_dtype)

    return fn


GPT.generate = staticmethod(generate)
GPT.jit_generate = staticmethod(jit_generate)


def load_torch_gpt2(state_dict, n_heads: int | None = None):
    """Build (params, cfg) from a HuggingFace GPT-2 ``state_dict`` —
    the LM counterpart of :func:`models.resnet.load_torch_state` (the
    reference's pretrained-import capability, ref resnet.py:104-112,
    extended to the language-model family).

    Accepts ``GPT2Model`` or ``GPT2LMHeadModel`` checkpoints (with or
    without the ``transformer.`` prefix; torch tensors or numpy
    arrays). HF's Conv1D stores weights as (in, out) — exactly this
    framework's dense ``kernel`` layout, so kernels map without
    transposes; per-layer tensors stack onto the leading layer axis for
    the ``lax.scan`` forward. GPT-2 ties lm_head to wte, so the import
    always produces a tied model. ``n_heads`` defaults from d_model via
    the published GPT-2 family table.

    Numerically exact against ``transformers``' eval-mode forward
    (tests/test_torch_import.py) — both use the tanh-approximate gelu.
    """
    import numpy as _onp

    sd = {(k[12:] if k.startswith("transformer.") else k): v
          for k, v in state_dict.items()}
    n_layers = 1 + max(int(k.split(".")[1]) for k in sd
                       if k.startswith("h."))
    vocab, d_model = _np(sd["wte.weight"]).shape
    n_pos = _np(sd["wpe.weight"]).shape[0]
    if n_heads is None:
        heads_table = {768: 12, 1024: 16, 1280: 20, 1600: 25}
        if d_model not in heads_table:
            raise ValueError(
                f"n_heads not inferable for d_model={d_model}; pass "
                "n_heads= explicitly")
        n_heads = heads_table[d_model]
    cfg = GPTConfig(vocab=vocab, n_layers=n_layers, d_model=d_model,
                    n_heads=n_heads, seq_len=n_pos, tie_embeddings=True)

    def stack(fmt: str):
        return jnp.asarray(_onp.stack(
            [_np(sd[fmt.format(i)]).astype(_onp.float32)
             for i in range(n_layers)]))

    blocks = {
        "ln1": {"scale": stack("h.{}.ln_1.weight"),
                "bias": stack("h.{}.ln_1.bias")},
        "attn_qkv": {"kernel": stack("h.{}.attn.c_attn.weight"),
                     "bias": stack("h.{}.attn.c_attn.bias")},
        "attn_proj": {"kernel": stack("h.{}.attn.c_proj.weight"),
                      "bias": stack("h.{}.attn.c_proj.bias")},
        "ln2": {"scale": stack("h.{}.ln_2.weight"),
                "bias": stack("h.{}.ln_2.bias")},
        "mlp_fc1": {"kernel": stack("h.{}.mlp.c_fc.weight"),
                    "bias": stack("h.{}.mlp.c_fc.bias")},
        "mlp_fc2": {"kernel": stack("h.{}.mlp.c_proj.weight"),
                    "bias": stack("h.{}.mlp.c_proj.bias")},
    }
    params = {
        "wte": {"table": jnp.asarray(
            _np(sd["wte.weight"]).astype(_onp.float32))},
        "wpe": {"table": jnp.asarray(
            _np(sd["wpe.weight"]).astype(_onp.float32))},
        "blocks": blocks,
        "ln_f": {"scale": jnp.asarray(
            _np(sd["ln_f.weight"]).astype(_onp.float32)),
            "bias": jnp.asarray(
                _np(sd["ln_f.bias"]).astype(_onp.float32))},
    }
    return params, cfg


def _remat_policy():
    """What a rematerialised block keeps: matmul outputs, and the two
    tensors the flash kernel hands its backward (a ``pallas_call`` is
    no dot, so without their names the backward would run the forward
    kernel a second time just to rebuild them — 25 MB a layer at GPT-2
    small, batch 16 x S=1024); the cheap elementwise ops are computed
    again — measured ≥ plain full remat on v5e with much less
    recompute."""
    from torchbooster_tpu.ops.flash_attention import RESIDUALS

    policies = jax.checkpoint_policies
    return policies.save_from_both_policies(
        policies.dots_with_no_batch_dims_saveable,
        policies.save_only_these_names(*RESIDUALS))


def _make_constrainer(mesh: Mesh | None):
    if mesh is None:
        return lambda x: x
    axes = mesh.axis_names
    data = tuple(a for a in ("dp", "fsdp") if a in axes) or None
    seq = "sp" if "sp" in axes else None
    spec = P(data, seq)

    def constrain(x: jax.Array) -> jax.Array:
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec))

    return constrain


__all__ = ["GPT", "GPTConfig", "SHARDING_RULES", "batch_spec",
           "jit_generate", "load_torch_gpt2", "qkv_state_to_tp_major",
           "qkv_to_tp_major", "qkv_tp_permutation"]
