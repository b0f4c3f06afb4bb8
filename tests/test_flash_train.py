"""The flash kernel inside the train step: ``GPT.apply`` under
``remat=True`` hands the kernel's output and logsumexp to the backward
by name (no second forward kernel), and under a data mesh the kernel
runs per device over the batch axes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchbooster_tpu.distributed import make_mesh
from torchbooster_tpu.models.gpt import GPT, GPTConfig

CFG = GPTConfig(vocab=64, n_layers=2, d_model=64, n_heads=2, seq_len=128)


def _loss(attn_impl: str, mesh=None):
    def loss(params, ids):
        logits = GPT.apply(params, ids, cfg=CFG, mesh=mesh,
                           compute_dtype=jnp.float32, remat=True,
                           attn_impl=attn_impl)
        return (logits ** 2).mean()
    return loss


def _setup(batch: int = 2):
    params = GPT.init(jax.random.PRNGKey(0), CFG)
    ids = jax.random.randint(jax.random.PRNGKey(1), (batch, CFG.seq_len),
                             0, CFG.vocab)
    return params, ids


def _assert_trees_close(got, want, rtol=2e-3):
    def close(path, g, w):
        w = np.asarray(w)
        np.testing.assert_allclose(
            np.asarray(g), w, rtol=rtol, atol=rtol * float(np.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))

    jax.tree_util.tree_map_with_path(close, got, want)


def _kernels(jaxpr):
    """Every pallas_call equation reachable from ``jaxpr``, scan bodies
    and remat / custom_vjp sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernels(sub)


def test_remat_gradient_matches_reference_and_runs_one_forward_kernel():
    params, ids = _setup()
    want = jax.grad(_loss("reference"))(params, ids)
    grad = jax.grad(_loss("flash_interpret"))
    _assert_trees_close(grad(params, ids), want)
    # the scan traces its body once: one forward kernel in the forward
    # scan, dQ and dK/dV in the backward scan, and no forward there
    names = sorted(eqn.params["name"] for eqn in _kernels(
        jax.make_jaxpr(grad)(params, ids).jaxpr))
    assert names == ["flash_dkv", "flash_dq", "flash_fwd"], names


def test_kernel_runs_per_device_under_a_batch_sharded_mesh():
    mesh = make_mesh("fsdp:8")
    params, ids = _setup(batch=8)
    want = jax.grad(_loss("reference"))(params, ids)
    with mesh:
        got = jax.jit(jax.grad(_loss("flash_interpret", mesh)))(params, ids)
    _assert_trees_close(got, want)
    text = jax.make_jaxpr(jax.grad(_loss("flash_interpret", mesh)))(
        params, ids)
    assert "shard_map" in str(text)


def test_auto_dispatch_agrees_with_the_tiles_the_kernel_picks(monkeypatch):
    """At the train cells' S=1024 the predicate says "kernel", and the
    grid of the kernel it then runs is the one ``tileable`` vouched
    for: the resolved defaults, whole lane tiles. A length those tiles
    cannot cover in lane tiles is refused by the predicate, not left to
    the chip's compiler. Each traced call counts its choice."""
    import importlib

    from torchbooster_tpu.observability import get_registry
    from torchbooster_tpu.ops.flash_attention import (
        STRIP, _block_default, _pick_block, flash_attention, tileable)

    attn_mod = importlib.import_module("torchbooster_tpu.ops.attention")
    monkeypatch.delenv("TB_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("TB_FLASH_BLOCK_K", raising=False)
    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")
    seq = 1024
    assert attn_mod.flash_auto_engaged(seq) and tileable(seq)
    blocks = [_pick_block(_block_default(n), seq, n) for n in "QK"]
    assert all(b % STRIP == 0 for b in blocks)

    q = jnp.zeros((2, seq, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q: flash_attention(q, q, q, interpret=True))(q)
    grids = [tuple(eqn.params["grid_mapping"].grid)
             for eqn in _kernels(jaxpr.jaxpr)]
    assert grids == [(2, seq // blocks[0], seq // blocks[1])]

    # 8 x 125: tiles only 8 wide; 520: one tile, but no lane multiple
    for awkward in (1000, 520):
        assert not tileable(awkward)
        assert not attn_mod.flash_auto_engaged(max(awkward, seq), awkward)

    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "cpu")
    counter = get_registry().counter("attention_dispatch_total")
    get_registry().enabled, was = True, get_registry().enabled
    try:
        before = {i: counter.value(impl=i) for i in ("flash", "reference")}
        x = jnp.zeros((1, 128, 1, 8))
        attn_mod.attention(x, x, x)                # auto, off the TPU
        attn_mod.attention(x, x, x, impl="flash_interpret")
        after = {i: counter.value(impl=i) for i in ("flash", "reference")}
    finally:
        get_registry().enabled = was
    assert {i: after[i] - before[i] for i in after} == {
        "flash": 1, "reference": 1}


def test_attn_fused_share_reads_the_kernels_share_of_attn_core(monkeypatch):
    """The benchmark's reader: device seconds of ``pallas_call`` ops
    under ``attn_core`` over all device seconds under ``attn_core``, in
    runs of ``step_fn`` on the first chip; 0 where XLA computes
    attention, nothing where the scope is not there."""
    import sys
    from pathlib import Path

    bench = Path(__file__).resolve().parent.parent / "benchmark"
    for folder in (bench, bench / "layer_metrics"):
        if str(folder) not in sys.path:
            sys.path.insert(0, str(folder))
    import attn_fused_share
    import xplane_scopes as xs

    us = 1e-6
    stack = "jit(step_fn)/jit(main)/"

    def trace(ops):
        return xs.Scoped(
            ops={"/device:TPU:0": [xs.Event(a * us, b * us, name, tf_op)
                                   for a, b, name, tf_op in ops]},
            modules={"/device:TPU:0": [
                xs.Event(0.0, 100 * us, "jit_step_fn(1)"),
                xs.Event(200 * us, 300 * us, "jit_other(2)")]})

    fused = trace([
        (0, 30, "flash_fwd.1", stack + "jvp(attn_core)/flash_fwd/pallas_call:"),
        (30, 40, "copy.3", stack + "jvp(attn_core)/transpose:"),
        (40, 90, "flash_dkv.1",
         stack + "transpose(jvp(attn_core))/flash_dkv/pallas_call:"),
        (90, 100, "fusion.9", stack + "jvp(mlp)/dot_general:"),
        # another program's kernel: never read
        (200, 300, "flash_fwd.2", "jit(other)/attn_core/pallas_call:")])
    plain = trace([(0, 60, "fusion.1", stack + "jvp(attn_core)/exp:"),
                   (60, 100, "fusion.9", stack + "jvp(mlp)/dot_general:")])
    bare = trace([(0, 100, "fusion.9", stack + "jvp(mlp)/dot_general:")])

    for found, want in ((fused, 100.0 * 80 / 90), (plain, 0.0),
                        (bare, None), (None, None)):
        monkeypatch.setattr(attn_fused_share, "scoped_trace",
                            lambda layers, found=found: found)
        got = attn_fused_share.read("attn_fused_share.train", {})
        assert got == pytest.approx(want) if want is not None \
            else got is None
