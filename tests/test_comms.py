"""Gradient-communication subsystem tests on the 8-device virtual CPU
mesh (same harness as tests/test_distributed.py): quantized all-reduce
error bounds, error-feedback drain, ZeRO-1 parity with the replicated
optax update, accounting-vs-XLA agreement, and the zero-recompile
contract."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from torchbooster_tpu import distributed as dist
from torchbooster_tpu.comms import (GradComms, make_grad_comms,
                                    step_traffic, xla_collective_traffic)
from torchbooster_tpu.comms.quantized import (dequantize, quantize,
                                              reduce_flat)
from torchbooster_tpu.config import CommsConfig
from torchbooster_tpu.utils import TrainState, make_step

BUCKET = 64


def _mesh(n=4):
    return dist.make_mesh("dp", n)


def _linear_problem(mesh):
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 8)),
              "b": jnp.zeros((8,))}
    batch = dist.shard_batch(
        {"x": jax.random.normal(jax.random.PRNGKey(1), (32, 16)),
         "y": jax.random.normal(jax.random.PRNGKey(2), (32, 8))}, mesh)

    def loss_fn(p, b, rng):
        pred = b["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - b["y"]) ** 2), {}

    return params, batch, loss_fn


def _run(mesh, comms, loss_fn, params, batch, tx, steps=3, clip=None,
         **mk):
    fresh = jax.tree.map(jnp.array, params)
    if comms is None:
        state = TrainState.create(fresh, tx)
        step = make_step(loss_fn, tx, clip=clip, **mk)
    else:
        state = comms.create_state(fresh, tx)
        step = make_step(loss_fn, tx, clip=clip, comms=comms, **mk)
    losses = []
    for _ in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return state, losses


# =========================================================================
# quantization primitives
# =========================================================================

def test_quantize_roundtrip_error_bound():
    """Per-element dequant error is bounded by one bucket scale
    (stochastic rounding moves at most one quantization level)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (8 * BUCKET,)) * 3.0
    q, scales = quantize(x, BUCKET, jax.random.PRNGKey(1))
    err = np.abs(np.asarray(dequantize(q, scales, BUCKET) - x))
    bound = np.repeat(np.asarray(scales), BUCKET)
    assert (err <= bound + 1e-7).all()
    assert q.dtype == jnp.int8


def test_quantize_stochastic_rounding_unbiased():
    """Repeated quantization of the same value averages back to it."""
    x = jnp.full((BUCKET,), 0.3217)
    # pin the scale with one max element so rounding has a fraction
    x = x.at[0].set(1.0)
    deqs = []
    for k in range(200):
        q, s = quantize(x, BUCKET, jax.random.PRNGKey(k))
        deqs.append(np.asarray(dequantize(q, s, BUCKET)))
    mean = np.stack(deqs).mean(0)
    assert abs(mean[5] - 0.3217) < 1e-3


def test_quantize_zero_bucket():
    q, s = quantize(jnp.zeros((2 * BUCKET,)), BUCKET,
                    jax.random.PRNGKey(0))
    assert not np.asarray(q).any() and not np.asarray(s).any()


# =========================================================================
# int8 all-reduce: error bound vs fp32, error feedback drains
# =========================================================================

def _sync_fn(mesh, mode, n):
    def body(g, ef1, ef2, rng):
        rng = jax.random.fold_in(rng, jax.lax.axis_index("dp"))
        red, nef1, nef2 = reduce_flat(
            g.reshape(-1), ("dp",), n, mode, BUCKET, rng,
            ef1.reshape(-1), ef2)
        return red, nef1[None], nef2

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("dp"), P("dp"), P("dp"), P()),
        out_specs=(P(), P("dp"), P("dp")), check_vma=False))


def test_int8_allreduce_error_bound_vs_fp32():
    """Single-shot int8 mean is within the analytic bound of the fp32
    mean: per element, phase-1 error ≤ mean of per-replica scales and
    phase-2 error ≤ the reduced chunk's scale."""
    n, size = 4, 8 * BUCKET
    mesh = _mesh(n)
    g = jax.random.normal(jax.random.PRNGKey(0), (n, size)) * 2.0
    true_mean = np.asarray(g.mean(0))
    f = _sync_fn(mesh, "int8", n)
    gd = jax.device_put(g, NamedSharding(mesh, P("dp")))
    out, _, _ = f(gd, jnp.zeros((n, size)), jnp.zeros((size,)),
                  jax.random.PRNGKey(1))
    err = np.abs(np.asarray(out) - true_mean).max()
    # every scale ≤ global absmax / 127; two quantizations stack
    bound = 2.5 * np.abs(np.asarray(g)).max() / 127.0
    assert err <= bound, (err, bound)
    # and fp32 mode is exact
    f32 = _sync_fn(mesh, "fp32", n)
    out32, _, _ = f32(gd, jnp.zeros((n, size)), jnp.zeros((size,)),
                      jax.random.PRNGKey(1))
    np.testing.assert_allclose(np.asarray(out32), true_mean, rtol=2e-6,
                               atol=2e-7)


def test_error_feedback_residual_drains():
    """With fixed per-replica gradients, the K-step AVERAGE of the
    compressed all-reduce converges to the true mean (the residual
    carries each step's quantization error into the next, so errors
    cancel instead of repeating) — compressed ≈ fp32 after K steps."""
    n, size = 4, 4 * BUCKET
    mesh = _mesh(n)
    g = jax.random.normal(jax.random.PRNGKey(3), (n, size))
    true_mean = np.asarray(g.mean(0))
    f = _sync_fn(mesh, "int8", n)
    gd = jax.device_put(g, NamedSharding(mesh, P("dp")))
    ef1 = jnp.zeros((n, size))
    ef2 = jnp.zeros((size,))
    acc = np.zeros_like(true_mean)
    single_err = None
    K = 24
    for k in range(K):
        out, ef1, ef2 = f(gd, ef1, ef2, jax.random.PRNGKey(100 + k))
        if single_err is None:
            single_err = np.abs(np.asarray(out) - true_mean).max()
        acc += np.asarray(out)
    avg_err = np.abs(acc / K - true_mean).max()
    assert avg_err < single_err / 4, (avg_err, single_err)
    # residuals themselves stay bounded (no walk-off)
    assert np.abs(np.asarray(ef1)).max() <= \
        np.abs(np.asarray(g)).max() / 64


# =========================================================================
# make_step integration: mode parity
# =========================================================================

def test_explicit_fp32_matches_implicit():
    mesh = _mesh()
    params, batch, loss_fn = _linear_problem(mesh)
    tx = optax.adamw(1e-2)
    ref, l_ref = _run(mesh, None, loss_fn, params, batch, tx)
    comms = make_grad_comms(mesh, mode="fp32")
    got, l_got = _run(mesh, comms, loss_fn, params, batch, tx)
    np.testing.assert_allclose(l_got, l_ref, rtol=1e-6)
    for key in ref.params:
        np.testing.assert_allclose(np.asarray(got.params[key]),
                                   np.asarray(ref.params[key]),
                                   atol=1e-6)


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_compressed_modes_track_fp32(mode):
    mesh = _mesh()
    params, batch, loss_fn = _linear_problem(mesh)
    tx = optax.adamw(1e-2)
    _, l_ref = _run(mesh, None, loss_fn, params, batch, tx, steps=5)
    comms = make_grad_comms(mesh, mode=mode, bucket_size=BUCKET)
    _, l_got = _run(mesh, comms, loss_fn, params, batch, tx, steps=5)
    np.testing.assert_allclose(l_got, l_ref, rtol=5e-3)


# =========================================================================
# ZeRO-1
# =========================================================================

def test_zero1_bit_parity_with_replicated_update():
    """implicit+zero1 computes the identical gradient (XLA's own psum)
    and an elementwise adamw shard update — parity with the replicated
    optax update must be (near-)bitwise."""
    mesh = _mesh()
    params, batch, loss_fn = _linear_problem(mesh)
    tx = optax.adamw(1e-2)
    ref, _ = _run(mesh, None, loss_fn, params, batch, tx)
    comms = make_grad_comms(mesh, zero1=True, bucket_size=BUCKET)
    got, _ = _run(mesh, comms, loss_fn, params, batch, tx)
    for key in ref.params:
        np.testing.assert_array_equal(np.asarray(got.params[key]),
                                      np.asarray(ref.params[key]))


def test_zero1_explicit_fp32_and_clip_parity():
    mesh = _mesh()
    params, batch, loss_fn = _linear_problem(mesh)
    tx = optax.adamw(1e-2)
    ref, _ = _run(mesh, None, loss_fn, params, batch, tx, clip=0.01)
    comms = make_grad_comms(mesh, mode="fp32", zero1=True,
                            bucket_size=BUCKET)
    got, _ = _run(mesh, comms, loss_fn, params, batch, tx, clip=0.01)
    for key in ref.params:
        np.testing.assert_allclose(np.asarray(got.params[key]),
                                   np.asarray(ref.params[key]),
                                   atol=1e-6)


def test_zero1_opt_state_sharded_over_dp():
    """The whole point: adam m/v live sharded, 1/N per replica."""
    mesh = _mesh()
    params, _, _ = _linear_problem(mesh)
    comms = make_grad_comms(mesh, zero1=True, bucket_size=BUCKET)
    state = comms.create_state(jax.tree.map(jnp.array, params),
                               optax.adamw(1e-2))
    flat_leaves = [leaf for leaf in jax.tree.leaves(state.opt_state)
                   if hasattr(leaf, "ndim") and leaf.ndim == 1
                   and leaf.size >= comms.n_shards * BUCKET]
    assert flat_leaves, "no flat sharded optimizer leaves found"
    for leaf in flat_leaves:
        assert leaf.sharding.spec == P("dp"), leaf.sharding
        # each device materializes exactly its chunk
        shard_shapes = {s.data.shape for s in leaf.addressable_shards}
        assert shard_shapes == {(leaf.size // comms.n_shards,)}


def test_zero1_rejects_unsharded_opt_state():
    mesh = _mesh()
    params, batch, loss_fn = _linear_problem(mesh)
    tx = optax.adamw(1e-2)
    comms = make_grad_comms(mesh, zero1=True)
    state = TrainState.create(jax.tree.map(jnp.array, params), tx)
    step = make_step(loss_fn, tx, comms=comms)
    with pytest.raises(ValueError, match="create_state"):
        step(state, batch)


def test_zero1_rejects_accumulation():
    mesh = _mesh()
    comms = make_grad_comms(mesh, zero1=True)
    with pytest.raises(ValueError, match="accumulate"):
        make_step(lambda p, b, r: (0.0, {}), optax.sgd(1e-2),
                  accumulate_every=4, comms=comms)


# =========================================================================
# accounting vs XLA
# =========================================================================

@pytest.mark.parametrize("mode,zero1", [("fp32", False), ("int8", False),
                                        ("fp32", True), ("int8", True)])
def test_accounting_agrees_with_xla(mode, zero1):
    """The static traffic model must price the collectives XLA
    actually compiled into the step within 10%. (bf16 is excluded:
    this CPU backend's float-normalization pass rewrites bf16
    collectives to fp32 — on TPU they ship natively.)"""
    mesh = _mesh()
    params, batch, loss_fn = _linear_problem(mesh)
    tx = optax.adamw(1e-2)
    comms = make_grad_comms(mesh, mode=mode, zero1=zero1,
                            bucket_size=BUCKET)
    state = comms.create_state(jax.tree.map(jnp.array, params), tx)
    step = make_step(loss_fn, tx, comms=comms)
    compiled = step.lower(state, batch).compile()
    xla = xla_collective_traffic(compiled)
    n_params = sum(int(l.size) for l in jax.tree.leaves(params))
    model = step_traffic(n_params, comms.n_shards, mode, zero1, BUCKET)
    assert xla["total_bytes"] > 0
    ratio = xla["total_bytes"] / model["total_bytes"]
    assert 0.9 < ratio < 1.1, (model, xla)


def test_int8_moves_at_least_3_5x_fewer_grad_bytes():
    n_params = 1_000_000
    fp32 = step_traffic(n_params, 8, "fp32", False, 512)
    int8 = step_traffic(n_params, 8, "int8", False, 512)
    assert fp32["grad_bytes"] / int8["grad_bytes"] >= 3.5
    # and the bf16 wire is exactly half of fp32
    bf16 = step_traffic(n_params, 8, "bf16", False, 512)
    assert fp32["grad_bytes"] / bf16["grad_bytes"] == pytest.approx(
        2.0, rel=1e-6)


def test_step_traffic_zero1_breakdown():
    t = step_traffic(1000, 4, "int8", True, 100)
    per = t["per_collective"]
    assert "grad_all_to_all" in per and "param_all_gather" in per
    assert "grad_all_gather" not in per     # params gather instead
    single = step_traffic(1000, 1, "int8", False, 100)
    assert single["total_bytes"] == 0       # N=1: nothing on the wire


def test_comms_bytes_counter_exported():
    from torchbooster_tpu import observability as obs

    mesh = _mesh()
    params, batch, loss_fn = _linear_problem(mesh)
    tx = optax.adamw(1e-2)
    comms = make_grad_comms(mesh, mode="int8", bucket_size=BUCKET)
    state = comms.create_state(jax.tree.map(jnp.array, params), tx)
    step = make_step(loss_fn, tx, comms=comms)
    was = obs.get_registry().enabled
    obs.set_enabled(True)
    try:
        state, _ = step(state, batch)
        state, _ = step(state, batch)
        snap = obs.get_registry().snapshot()
    finally:
        obs.set_enabled(was)
    keys = [k for k in snap if k.startswith("comms_bytes_total")]
    assert any("grad_all_to_all" in k for k in keys), snap.keys()
    n_params = sum(int(l.size) for l in jax.tree.leaves(params))
    expect = comms.step_traffic(n_params)["per_collective"][
        "grad_all_to_all"]
    got = next(v for k, v in snap.items()
               if "grad_all_to_all" in k)
    assert got == pytest.approx(2 * expect)   # two steps


# =========================================================================
# zero-recompile contract
# =========================================================================

@pytest.mark.parametrize("mode,zero1", [("int8", False), ("int8", True),
                                        ("fp32", True)])
def test_zero_recompiles_across_steps(mode, zero1):
    """After the first (compiling) call, steps must be signature-stable
    — no layout or shape leak may retrigger XLA (sentinel-verified,
    on_recompile=raise)."""
    from torchbooster_tpu.observability import RecompileSentinel

    mesh = _mesh()
    params, batch, loss_fn = _linear_problem(mesh)
    tx = optax.adamw(1e-2)
    comms = make_grad_comms(mesh, mode=mode, zero1=zero1,
                            bucket_size=BUCKET)
    state = comms.create_state(jax.tree.map(jnp.array, params), tx)
    step = make_step(loss_fn, tx, comms=comms)
    state, _ = step(state, batch)            # the one budgeted compile
    with RecompileSentinel(step, expected=0, name=f"comms_{mode}",
                           on_recompile="raise"):
        for _ in range(4):
            state, metrics = step(state, batch)
    assert np.isfinite(metrics["loss"])


# =========================================================================
# GPT loss-curve parity (the acceptance pin): int8+EF within 1% of fp32
# =========================================================================

@pytest.mark.slow     # heavy compile/train on CPU (tier-1 time budget)
def test_gpt_int8_loss_within_1pct_of_fp32_after_50_steps():
    from torchbooster_tpu.models.gpt import GPT, GPTConfig
    from torchbooster_tpu.ops.losses import cross_entropy

    cfg = GPTConfig(vocab=256, n_layers=2, d_model=64, n_heads=2,
                    seq_len=32)
    mesh = _mesh()
    params = GPT.init(jax.random.PRNGKey(0), cfg)
    tx = optax.adamw(3e-3)

    def loss_fn(p, b, rng):
        logits = GPT.apply(p, b["ids"], cfg)
        return cross_entropy(logits[:, :-1].reshape(-1, cfg.vocab),
                             b["ids"][:, 1:].reshape(-1)), {}

    def batches(seed):
        rng = np.random.RandomState(seed)
        while True:
            ids = rng.randint(0, cfg.vocab,
                              (8, cfg.seq_len)).astype(np.int32)
            # learnable structure: odd tokens follow even ones
            ids[:, 1::2] = (ids[:, ::2] + 1) % cfg.vocab
            yield dist.shard_batch({"ids": ids}, mesh)

    def run(mode):
        comms = make_grad_comms(mesh, mode=mode, bucket_size=128)
        state = comms.create_state(jax.tree.map(jnp.array, params), tx)
        step = make_step(loss_fn, tx, comms=comms)
        gen = batches(7)
        loss = None
        for _ in range(50):
            state, metrics = step(state, next(gen))
            loss = float(metrics["loss"])
        return loss

    loss_fp32 = run("fp32")
    loss_int8 = run("int8")
    assert loss_int8 == pytest.approx(loss_fp32, rel=0.01), \
        (loss_fp32, loss_int8)


# =========================================================================
# config + construction validation
# =========================================================================

def test_comms_config_yaml_roundtrip(tmp_path):
    path = tmp_path / "comms.yml"
    path.write_text("mode: int8\nzero1: yes\nbucket_size: 256\n")
    conf = CommsConfig.load(path)
    assert (conf.mode, conf.zero1, conf.bucket_size) == ("int8", True,
                                                         256)
    comms = conf.make(mesh=_mesh())
    assert isinstance(comms, GradComms)
    assert comms.mode == "int8" and comms.zero1
    assert comms.axes == ("dp",) and comms.n_shards == 4


def test_comms_config_defaults_are_inert():
    comms = CommsConfig().make(mesh=_mesh())
    assert comms.mode == "implicit" and not comms.zero1
    assert not comms.active


def test_make_grad_comms_validation():
    mesh = _mesh()
    with pytest.raises(ValueError, match="mode"):
        make_grad_comms(mesh, mode="int4")
    with pytest.raises(ValueError, match="bucket_size"):
        make_grad_comms(mesh, mode="int8", bucket_size=0)
    tp_mesh = dist.make_mesh("dp:2,tp:2", 4)
    with pytest.raises(ValueError, match="model-parallel"):
        make_grad_comms(tp_mesh, mode="int8")
    # but implicit mode is fine on any mesh
    assert make_grad_comms(tp_mesh).mode == "implicit"


def test_make_step_rejects_rules_with_explicit_comms():
    mesh = _mesh()
    comms = make_grad_comms(mesh, mode="int8")
    with pytest.raises(ValueError, match="replicated"):
        make_step(lambda p, b, r: (0.0, {}), optax.sgd(1e-2),
                  mesh=mesh, rules=[(r".*", P())], comms=comms)


def test_dp_fsdp_mesh_syncs_over_both_axes():
    """A dp×fsdp mesh (params replicated) treats both as data axes:
    4-way sync over the 2×2 grid matches the replicated grads."""
    mesh = dist.make_mesh("dp:2,fsdp:2", 4)
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 8)),
              "b": jnp.zeros((8,))}
    host_batch = {
        "x": np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                          (32, 16))),
        "y": np.asarray(jax.random.normal(jax.random.PRNGKey(2),
                                          (32, 8)))}

    def loss_fn(p, b, rng):
        pred = b["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - b["y"]) ** 2), {}

    tx = optax.adamw(1e-2)
    ref_mesh = _mesh()
    ref, l_ref = _run(ref_mesh, None, loss_fn, params,
                      dist.shard_batch(dict(host_batch), ref_mesh), tx)
    comms = make_grad_comms(mesh, mode="fp32", zero1=True,
                            bucket_size=BUCKET)
    assert comms.axes == ("dp", "fsdp") and comms.n_shards == 4
    got, l_got = _run(mesh, comms, loss_fn, params,
                      dist.shard_batch(dict(host_batch), mesh), tx)
    np.testing.assert_allclose(l_got, l_ref, rtol=1e-6)
    for key in ref.params:
        np.testing.assert_allclose(np.asarray(got.params[key]),
                                   np.asarray(ref.params[key]),
                                   atol=1e-6)
