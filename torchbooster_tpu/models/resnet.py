"""ResNet family (18/34/50/101) — NHWC, GroupNorm, MXU-friendly.

Capability parity with the reference's ResNet recipe (ref
examples/img_cls/resnet/resnet.py:104-112: torchvision resnet18 with its
fc head swapped for the target class count). The reference imports a
pretrained torch model; here :func:`load_torch_state` imports a
torchvision-convention ``state_dict`` (NCHW OIHW → NHWC HWIO kernels).

**BatchNorm→GroupNorm policy** (documented, not silent): pretrained
torch ResNets carry BatchNorm running statistics, which GroupNorm
cannot reproduce (its stats are data-dependent). The importer therefore
*folds* each BN's running stats + affine into an exact per-channel
affine — ``a = γ/√(σ²+ε)``, ``b = β − μ·a`` — and the model runs those
as frozen-BN affines (``apply(..., norm="affine")``), the standard
formulation for transfer learning (torchvision's own detection models
freeze BN the same way). This makes the import numerically EXACT
against torch's eval-mode forward (tested in
tests/test_torch_import.py). Training from scratch keeps GroupNorm
(``norm="group"``, the default); both modes share one param tree shape.

Design: basic block (two 3×3) for 18/34, bottleneck (1-3-1) for 50/101;
GroupNorm instead of BatchNorm (stateless, no cross-replica sync — see
models/__init__); ``stem="cifar"`` swaps the 7×7/s2+pool ImageNet stem
for the 3×3/s1 CIFAR stem.

``norm="ws"`` selects the **norm-free variant** (NF-ResNet-style scaled
weight standardization — see the NF section below). Its loss surface is
sharper than the normalized model's: pair it with SGD-momentum or set
``OptimizerConfig.agc`` (adaptive gradient clipping, the published
companion) — large adaptive LRs diverge without one of the two.
"""
from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from torchbooster_tpu.models import layers as L
from torchbooster_tpu.models.torch_interop import to_numpy as _np

# depth → (block kind, stage repeats)
_CONFIGS = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
}
_STAGE_WIDTHS = (64, 128, 256, 512)
_GROUPS = 32


def _basic_block_init(rng: jax.Array, cin: int, cout: int, stride: int,
                      dtype: Any) -> dict:
    ks = jax.random.split(rng, 3)
    block = {
        "conv1": L.conv_init(ks[0], 3, cin, cout, use_bias=False, dtype=dtype),
        "norm1": L.norm_init(cout, dtype),
        "conv2": L.conv_init(ks[1], 3, cout, cout, use_bias=False, dtype=dtype),
        "norm2": L.norm_init(cout, dtype),
    }
    if stride != 1 or cin != cout:
        block["proj"] = L.conv_init(ks[2], 1, cin, cout, use_bias=False,
                                    dtype=dtype)
        block["proj_norm"] = L.norm_init(cout, dtype)
    return block


def _norm(params: dict, x: jax.Array, norm: str, relu: bool = False):
    """``norm="group"``: GroupNorm. ``norm="affine"``: frozen-BN
    per-channel affine (same {scale, bias} param shapes — see module
    docstring on the torch-import policy)."""
    if norm == "affine":
        y = x * params["scale"].astype(x.dtype) \
            + params["bias"].astype(x.dtype)
        return jax.nn.relu(y) if relu else y
    return L.group_norm(params, x, _GROUPS, relu=relu)


def _basic_block(params: dict, x: jax.Array, stride: int, norm: str,
                 fused: str | bool = "auto") -> jax.Array:
    # explicit padding=1 (not "SAME"): identical at stride 1, but
    # torch-symmetric at stride 2 — keeps torch imports exact
    y = _conv3x3_norm(params["conv1"], params["norm1"], x, norm,
                      stride=stride, fused=fused, relu=True)
    y = _conv3x3_norm(params["conv2"], params["norm2"], y, norm,
                      stride=1, fused=fused, relu=False)
    if "proj" in params:
        x = _conv1x1_norm(params["proj"], params["proj_norm"], x, norm,
                          relu=False, stride=stride, fused=fused)
    return jax.nn.relu(x + y)


def _bottleneck_init(rng: jax.Array, cin: int, cmid: int, stride: int,
                     dtype: Any) -> dict:
    cout = cmid * 4
    ks = jax.random.split(rng, 4)
    block = {
        "conv1": L.conv_init(ks[0], 1, cin, cmid, use_bias=False, dtype=dtype),
        "norm1": L.norm_init(cmid, dtype),
        "conv2": L.conv_init(ks[1], 3, cmid, cmid, use_bias=False, dtype=dtype),
        "norm2": L.norm_init(cmid, dtype),
        "conv3": L.conv_init(ks[2], 1, cmid, cout, use_bias=False, dtype=dtype),
        "norm3": L.norm_init(cout, dtype),
    }
    if stride != 1 or cin != cout:
        block["proj"] = L.conv_init(ks[3], 1, cin, cout, use_bias=False,
                                    dtype=dtype)
        block["proj_norm"] = L.norm_init(cout, dtype)
    return block


def _use_fused(fused: str | bool, norm: str, x: jax.Array,
               cout: int, three: bool = False) -> bool:
    """Conv+GN fusion gate: explicit True/"interpret" engages the pallas
    kernel when the block fits VMEM (ops/fused_block). "auto" currently
    resolves to the XLA path: the kernel has no number on the current
    stack (ROADMAP D6) — it gets the default when a benchmark cell
    shows it ahead, the dispatch stays honest."""
    if norm != "group" or fused in (False, "auto"):
        return False
    from torchbooster_tpu.ops.fused_block import fits, fits3

    return fits3(x, cout) if three else fits(x, cout)


def _conv1x1_norm(conv_p: dict, norm_p: dict, x: jax.Array, norm: str,
                  relu: bool, stride: int, fused: str | bool) -> jax.Array:
    """1×1 conv + norm(+relu), through the fused pallas kernel when the
    gate passes (one HBM pass instead of three — see ops/fused_block)."""
    cout = conv_p["kernel"].shape[-1]
    if _use_fused(fused, norm, x, cout):
        from torchbooster_tpu.ops.fused_block import conv1x1_gn_relu

        return conv1x1_gn_relu(
            x, conv_p["kernel"], norm_p["scale"], norm_p["bias"],
            groups=_GROUPS, relu=relu, stride=stride,
            interpret=(fused == "interpret"))
    return _norm(norm_p, L.conv(conv_p, x, stride=stride), norm, relu)


def _conv3x3_norm(conv_p: dict, norm_p: dict, x: jax.Array, norm: str,
                  stride: int, fused: str | bool,
                  relu: bool = True) -> jax.Array:
    """3×3 conv + GN (+relu); fused pallas path for the stride-1 body
    (13 of ResNet-50's 16 conv2s and both convs of interior basic
    blocks — stage-entry stride-2 blocks keep XLA)."""
    cout = conv_p["kernel"].shape[-1]
    if stride == 1 and _use_fused(fused, norm, x, cout, three=True):
        from torchbooster_tpu.ops.fused_block import conv3x3_gn_relu

        return conv3x3_gn_relu(
            x, conv_p["kernel"], norm_p["scale"], norm_p["bias"],
            groups=_GROUPS, relu=relu, interpret=(fused == "interpret"))
    return _norm(norm_p, L.conv(conv_p, x, stride=stride, padding=1),
                 norm, relu=relu)


def _stem_s2d(kernel: jax.Array, x: jax.Array) -> jax.Array:
    """The 7×7/s2 ImageNet stem conv as a space-to-depth conv: input
    (B, H, W, 3) repacks to (B, H/2, W/2, 12) and the kernel to
    (4, 4, 12, Cout), turning a 3-input-channel conv (≈2% MXU lane
    fill) into a 12-channel stride-1 conv — the MLPerf-style stem
    repack the r2 ablation prescribed for the 56²/C=64 underfill.
    Exactly conv(x, kernel, stride 2, pad 3) by construction (tested);
    pure jnp re-indexing, so it trains through unchanged."""
    b, h, w, c = x.shape
    kh, kw, _, cout = kernel.shape
    # space-to-depth: S[u, v, (sy, sx, c)] = x[2u+sy, 2v+sx, c]
    s = x.reshape(b, h // 2, 2, w // 2, 2, c)
    s = s.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)
    # kernel repack: out[y,x] = Σ_{ky,kx} in[2y+ky-3, 2x+kx-3]·K[ky,kx]
    # with 2y+ky-3 = 2(y+dy)+sy, sy=(ky-3) mod 2, dy=(ky-3-sy)//2 ∈
    # [-2, 1] → 4×4 taps over the s2d grid, padding (2, 1) per side
    kp = jnp.zeros((4, 4, 4 * c, cout), kernel.dtype)
    for ky in range(kh):
        sy = (ky - 3) % 2
        dy = (ky - 3 - sy) // 2
        for kx in range(kw):
            sx = (kx - 3) % 2
            dx = (kx - 3 - sx) // 2
            # s2d channel block (sy, sx): channels [(sy*2+sx)*c : +c]
            kp = kp.at[dy + 2, dx + 2,
                       (sy * 2 + sx) * c:(sy * 2 + sx + 1) * c,
                       :].set(kernel[ky, kx])
    return jax.lax.conv_general_dilated(
        s, kp.astype(s.dtype), (1, 1), [(2, 1), (2, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bottleneck(params: dict, x: jax.Array, stride: int,
                norm: str, fused: str | bool = "auto") -> jax.Array:
    y = _conv1x1_norm(params["conv1"], params["norm1"], x, norm,
                      relu=True, stride=1, fused=fused)
    y = _conv3x3_norm(params["conv2"], params["norm2"], y, norm,
                      stride=stride, fused=fused)
    y = _conv1x1_norm(params["conv3"], params["norm3"], y, norm,
                      relu=False, stride=1, fused=fused)
    if "proj" in params:
        x = _conv1x1_norm(params["proj"], params["proj_norm"], x, norm,
                          relu=False, stride=stride, fused=fused)
    return jax.nn.relu(x + y)


# ---------------------------------------------------------------------
# Norm-free variant (``norm="ws"``): NF-ResNet-style scaled weight
# standardization. The r2 chip ablation measured activation norms at
# ~30% of the ResNet-50 step (pure HBM traffic: moments + normalize
# passes over every activation); the conv-only step ran ~3 380 img/s vs
# 2 420. Weight standardization moves ALL normalization onto the conv
# kernels — tiny tensors, standardized once per step in the jit — so
# the activation path is conv→(+bias)→relu with zero extra HBM passes.
# This is the published NF(-Res)Net recipe (Brock et al.), designed on
# TPU for exactly this bandwidth reason. The variant reuses the
# existing {scale, bias} norm params as the WS gain and post-conv bias
# (same param tree, same checkpoints); blocks run in pre-activation
# form with analytic variance tracking: h_out = h + α·f(relu(h/β)·γ),
# β² accumulating +α² per block and resetting at transitions — all
# static Python floats, baked at trace time.

# relu gain: Var(γ·relu(z)) = 1 for z ~ N(0, 1)
_GAMMA_RELU = float(np.sqrt(2.0 / (1.0 - 1.0 / np.pi)))
_NF_ALPHA = 0.2


def _ws_kernel(kernel: jax.Array, gain: jax.Array,
               eps: float = 1e-4) -> jax.Array:
    """Scaled weight standardization: per-output-channel zero-mean,
    1/fan-in variance, times the learnable per-channel gain. Stats in
    fp32 (kernels are tiny next to activations)."""
    k = kernel.astype(jnp.float32)
    red = tuple(range(k.ndim - 1))
    mu = k.mean(red, keepdims=True)
    var = k.var(red, keepdims=True)
    fan_in = float(np.prod(k.shape[:-1]))
    w = (k - mu) * jax.lax.rsqrt(var * fan_in + eps)
    return (w * gain.astype(jnp.float32)).astype(kernel.dtype)


def _nf_conv(conv_p: dict, norm_p: dict, x: jax.Array, stride: int = 1,
             padding: Any = 0) -> jax.Array:
    """WS conv + the per-channel bias (the reused norm ``bias``)."""
    y = L.conv({"kernel": _ws_kernel(conv_p["kernel"], norm_p["scale"])},
               x, stride=stride, padding=padding)
    return y + norm_p["bias"].astype(y.dtype)


def _nf_act(x: jax.Array) -> jax.Array:
    return jax.nn.relu(x) * jnp.asarray(_GAMMA_RELU, x.dtype)


def _nf_block(params: dict, x: jax.Array, stride: int,
              beta: float) -> jax.Array:
    """Pre-activation NF residual block (basic or bottleneck by key)."""
    y0 = _nf_act(x / jnp.asarray(beta, x.dtype))
    if "conv3" in params:
        y = _nf_act(_nf_conv(params["conv1"], params["norm1"], y0))
        y = _nf_act(_nf_conv(params["conv2"], params["norm2"], y,
                             stride=stride, padding=1))
        y = _nf_conv(params["conv3"], params["norm3"], y)
    else:
        y = _nf_act(_nf_conv(params["conv1"], params["norm1"], y0,
                             stride=stride, padding=1))
        y = _nf_conv(params["conv2"], params["norm2"], y, padding=1)
    if "proj" in params:
        x = _nf_conv(params["proj"], params["proj_norm"], y0,
                     stride=stride)
    return x + jnp.asarray(_NF_ALPHA, x.dtype) * y


# FSDP/ZeRO layout for the config front door (EnvConfig.make consumes
# this): conv kernels shard their output-channel dim, the head its
# input dim. dp-only meshes filter these away → plain replication.
SHARDING_RULES = [
    (r"(conv[0-9]*|proj)/kernel", jax.sharding.PartitionSpec(
        None, None, None, "fsdp")),
    (r"head/kernel", jax.sharding.PartitionSpec("fsdp", None)),
    (r".*", jax.sharding.PartitionSpec()),
]


class ResNet:
    """``ResNet.init(rng, depth=18/34/50/101, num_classes, stem)`` →
    (params, meta). ``apply(params, x)`` → logits. ``meta`` (block kind,
    repeats, stem) rides inside params under the ``"_meta"``-free
    convention: apply re-derives structure from the params tree itself,
    so params remain a pure array pytree (jit-donatable)."""

    SHARDING_RULES = SHARDING_RULES

    @staticmethod
    def init(rng: jax.Array, depth: int = 18, num_classes: int = 10,
             stem: str = "imagenet", in_channels: int = 3,
             dtype: Any = jnp.float32) -> dict:
        kind, repeats = _CONFIGS[depth]
        ks = iter(jax.random.split(rng, 2 + sum(repeats)))
        stem_kernel, stem_stride = ((7, 2) if stem == "imagenet" else (3, 1))
        params: dict = {
            "stem": {
                "conv": L.conv_init(next(ks), stem_kernel, in_channels, 64,
                                    use_bias=False, dtype=dtype),
                "norm": L.norm_init(64, dtype),
            },
        }
        cin = 64
        for si, (width, n_blocks) in enumerate(zip(_STAGE_WIDTHS, repeats)):
            stage = {}
            for bi in range(n_blocks):
                stride = 2 if (bi == 0 and si > 0) else 1
                if kind == "basic":
                    stage[f"block{bi}"] = _basic_block_init(
                        next(ks), cin, width, stride, dtype)
                    cin = width
                else:
                    stage[f"block{bi}"] = _bottleneck_init(
                        next(ks), cin, width, stride, dtype)
                    cin = width * 4
            params[f"stage{si}"] = stage
        params["head"] = L.dense_init(next(ks), cin, num_classes, dtype=dtype)
        return params

    @staticmethod
    def apply(params: dict, x: jax.Array, train: bool = False,
              rng: jax.Array | None = None,
              pool_stem: bool | None = None,
              norm: str = "group",
              fused: str | bool = "auto",
              stem_s2d: bool = False) -> jax.Array:
        """``fused``: the 1×1-conv+GN pallas kernel (ops/fused_block).
        "auto" currently resolves to the plain XLA path (see
        _use_fused). True forces it on;
        "interpret" is the CPU-debuggable variant for tests.
        ``stem_s2d``: run the 7×7/s2 stem as a space-to-depth conv
        (:func:`_stem_s2d`; opt-in pending chip measurement)."""
        del train, rng
        if norm == "ws":
            if fused not in ("auto", False):
                # the conv+GN pallas kernels have no WS counterpart; a
                # silent ignore would mislabel fused+NF A/B data points
                raise ValueError(
                    "fused conv+GN kernels do not apply to norm='ws' "
                    "(there is no norm in the activation path); drop "
                    "fused= or use norm='group'")
            return _nf_apply(params, x, pool_stem, stem_s2d)
        stem = params["stem"]
        stem_stride = 2 if stem["conv"]["kernel"].shape[0] == 7 else 1
        if pool_stem is None:
            pool_stem = stem_stride == 2
        stem_pad = 3 if stem_stride == 2 else 1
        if stem_s2d and stem_stride == 2 \
                and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
            y = _stem_s2d(stem["conv"]["kernel"], x)
            if "bias" in stem["conv"]:
                y = y + stem["conv"]["bias"].astype(y.dtype)
            x = y
        else:
            x = L.conv(stem["conv"], x, stride=stem_stride,
                       padding=stem_pad)
        x = _norm(stem["norm"], x, norm, relu=True)
        if pool_stem:
            x = L.max_pool(x, 3, 2, padding=1)
        si = 0
        while f"stage{si}" in params:
            stage = params[f"stage{si}"]
            bi = 0
            while f"block{bi}" in stage:
                block = stage[f"block{bi}"]
                stride = 2 if (bi == 0 and si > 0) else 1
                if "conv3" in block:
                    x = _bottleneck(block, x, stride, norm, fused)
                else:
                    x = _basic_block(block, x, stride, norm, fused)
                bi += 1
            si += 1
        x = L.global_avg_pool(x)
        return L.dense(params["head"], x)

    @staticmethod
    def nf_apply(params: dict, x: jax.Array) -> jax.Array:
        """Shorthand for ``apply(params, x, norm="ws")`` — the
        norm-free variant (see the NF section above)."""
        return ResNet.apply(params, x, norm="ws")

    @staticmethod
    def swap_head(params: dict, rng: jax.Array, num_classes: int) -> dict:
        """Transfer-learning head swap (ref resnet.py:111-112 replaces
        ``model.fc``)."""
        din = params["head"]["kernel"].shape[0]
        return {**params, "head": L.dense_init(rng, din, num_classes)}


def _nf_apply(params: dict, x: jax.Array, pool_stem: bool | None,
              stem_s2d: bool) -> jax.Array:
    """Forward for ``norm="ws"``: WS stem, pre-activation NF blocks
    with analytic β tracking, final scaled activation, head."""
    stem = params["stem"]
    stem_stride = 2 if stem["conv"]["kernel"].shape[0] == 7 else 1
    if pool_stem is None:
        pool_stem = stem_stride == 2
    stem_pad = 3 if stem_stride == 2 else 1
    if stem_s2d and stem_stride == 2 \
            and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
        # s2d is exact re-indexing, so it composes with the
        # standardized kernel unchanged
        ws = _ws_kernel(stem["conv"]["kernel"], stem["norm"]["scale"])
        y = _stem_s2d(ws, x)
        x = y + stem["norm"]["bias"].astype(y.dtype)
    else:
        x = _nf_conv(stem["conv"], stem["norm"], x, stride=stem_stride,
                     padding=stem_pad)
    if pool_stem:
        x = L.max_pool(x, 3, 2, padding=1)
    expected_var = 1.0
    si = 0
    while f"stage{si}" in params:
        stage = params[f"stage{si}"]
        bi = 0
        while f"block{bi}" in stage:
            block = stage[f"block{bi}"]
            stride = 2 if (bi == 0 and si > 0) else 1
            x = _nf_block(block, x, stride, float(np.sqrt(expected_var)))
            # a transition block's shortcut re-standardizes the signal
            expected_var = ((1.0 if "proj" in block else expected_var)
                            + _NF_ALPHA ** 2)
            bi += 1
        si += 1
    x = _nf_act(x / jnp.asarray(float(np.sqrt(expected_var)), x.dtype))
    x = L.global_avg_pool(x)
    return L.dense(params["head"], x)


def _fold_bn(sd: Mapping[str, Any], prefix: str,
             eps: float = 1e-5) -> dict:
    """BatchNorm running stats + affine → exact frozen-BN per-channel
    affine (the BatchNorm→GroupNorm policy — see module docstring)."""
    gamma = _np(sd[f"{prefix}.weight"]).astype(np.float32)
    beta = _np(sd[f"{prefix}.bias"]).astype(np.float32)
    mean = _np(sd[f"{prefix}.running_mean"]).astype(np.float32)
    var = _np(sd[f"{prefix}.running_var"]).astype(np.float32)
    a = gamma / np.sqrt(var + eps)
    return {"scale": jnp.asarray(a), "bias": jnp.asarray(beta - mean * a)}


def _conv_kernel(sd: Mapping[str, Any], key: str) -> dict:
    """torch OIHW conv weight → HWIO kernel."""
    return {"kernel": jnp.asarray(
        _np(sd[key]).astype(np.float32).transpose(2, 3, 1, 0))}


def load_torch_state(state_dict: Mapping[str, Any],
                     num_classes: int | None = None,
                     rng: jax.Array | None = None) -> dict:
    """Build ResNet params from a torchvision-convention ``state_dict``
    (the capability behind ref examples/img_cls/resnet/resnet.py:104-112,
    which fine-tunes a pretrained torchvision resnet18).

    Accepts torch tensors or numpy arrays (a ``torch.load``-ed
    checkpoint works without torchvision). Depth and block kind are
    inferred from the keys. BatchNorms are folded to exact frozen-BN
    affines — run the result with ``ResNet.apply(..., norm="affine")``;
    parity with torch's eval-mode forward is exact up to float error.

    ``num_classes`` (with ``rng``) swaps the classifier head for
    transfer learning, mirroring the reference's ``model.fc``
    replacement; omit it to keep the imported 1000-way head.
    """
    sd = state_dict
    params: dict = {"stem": {"conv": _conv_kernel(sd, "conv1.weight"),
                             "norm": _fold_bn(sd, "bn1")}}
    for si in range(4):
        lp = f"layer{si + 1}"
        stage: dict = {}
        bi = 0
        while f"{lp}.{bi}.conv1.weight" in sd:
            bp = f"{lp}.{bi}"
            block = {"conv1": _conv_kernel(sd, f"{bp}.conv1.weight"),
                     "norm1": _fold_bn(sd, f"{bp}.bn1"),
                     "conv2": _conv_kernel(sd, f"{bp}.conv2.weight"),
                     "norm2": _fold_bn(sd, f"{bp}.bn2")}
            if f"{bp}.conv3.weight" in sd:
                block["conv3"] = _conv_kernel(sd, f"{bp}.conv3.weight")
                block["norm3"] = _fold_bn(sd, f"{bp}.bn3")
            if f"{bp}.downsample.0.weight" in sd:
                block["proj"] = _conv_kernel(sd, f"{bp}.downsample.0.weight")
                block["proj_norm"] = _fold_bn(sd, f"{bp}.downsample.1")
            stage[f"block{bi}"] = block
            bi += 1
        params[f"stage{si}"] = stage
    w = _np(sd["fc.weight"]).astype(np.float32)       # (classes, cin)
    params["head"] = {"kernel": jnp.asarray(w.T),
                      "bias": jnp.asarray(
                          _np(sd["fc.bias"]).astype(np.float32))}
    if num_classes is not None and num_classes != w.shape[0]:
        if rng is None:
            raise ValueError("num_classes swap needs an rng")
        params = ResNet.swap_head(params, rng, num_classes)
    return params


__all__ = ["ResNet", "load_torch_state"]
