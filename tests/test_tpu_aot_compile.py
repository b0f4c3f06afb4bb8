"""Ask the chip's compiler, without the chip.

The TPU compiler is installed beside the CPU backend and compiles for
a chip that is *described*, not attached. These tests hand it the
kernels on ``chip_smoke.py``'s path at the smoke's real shapes, and
the GPT-2-small train step: what Mosaic or XLA:TPU would refuse on
first chip contact (a slice off the tiling, too much VMEM, a program
past 16 GB) fails here, at no chip time. Nothing runs, so this says
nothing about results or speed.

All of it lives in this one file, behind one module-scoped fixture:
only one process may hold the TPU library, and under xdist only the
worker that is handed this file must load it.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host. The persistent compilation cache is
    off while it is in use: a compile for a described chip can be
    written to it but not read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Lower + compile for the described chip; returns (lowered text,
    compiled)."""
    lowered = jax.jit(fn).lower(*args)
    return lowered.as_text(), lowered.compile()


# the serve phase's geometry: 8 slots, GPT-2 heads, page 64, a pool
# that holds every slot at S=1024 (chip_smoke.yml)
SLOTS, HEADS, HEAD_DIM, PAGE, N_PAGES = 8, 12, 64, 64, 129


@pytest.mark.parametrize("s_q,int8_pool", [
    pytest.param(1, False, id="decode-bf16-pool"),
    pytest.param(1, True, id="decode-int8-pool"),
    pytest.param(5, False, id="verify-1+draft_len"),
])
def test_paged_attention_compiles_for_v5e(one_chip, s_q, int8_pool):
    from torchbooster_tpu.ops.paged_attention import paged_attention

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool_shape = (N_PAGES, PAGE, HEADS, HEAD_DIM)
    if int8_pool:
        pool = (arg(pool_shape, jnp.int8),
                arg(pool_shape[:-1] + (1,), jnp.bfloat16))
    else:
        pool = arg(pool_shape, jnp.bfloat16)
    n_work = N_PAGES - 1
    text, _ = _compile(
        functools.partial(paged_attention, page_size=PAGE,
                          interpret=False),
        arg((SLOTS, s_q, HEADS, HEAD_DIM), jnp.bfloat16), pool, pool,
        arg((n_work,), jnp.int32), arg((n_work, 1), jnp.int32),
        arg((n_work,), jnp.int32), arg((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("q_heads,kv_heads,head_dim", [
    pytest.param(12, 12, 64, id="mha-d64"),
    pytest.param(16, 8, 48, id="gpt-long-gqa-d48"),
])
def test_flash_attention_s8192_compiles_for_v5e(
        one_chip, q_heads, kv_heads, head_dim, backward):
    from torchbooster_tpu.ops.flash_attention import flash_attention

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def grads(q, k, v):
        return jax.grad(
            lambda *qkv: flash(*qkv).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    q = jax.ShapeDtypeStruct((q_heads, 8192, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((kv_heads, 8192, head_dim), jnp.bfloat16,
                              sharding=one_chip)
    text, _ = _compile(grads if backward else flash, q, kv, kv)
    assert "tpu_custom_call" in text


def test_gpt2_small_train_step_compiles_and_fits_v5e(one_chip):
    """The smoke's train phase as one program: GPT-2 small, batch 16,
    S=1024, bf16 compute, remat, AdamW, through ``make_step``."""
    import optax

    from torchbooster_tpu.models.gpt import GPT, GPTConfig
    from torchbooster_tpu.ops.losses import cross_entropy
    from torchbooster_tpu.utils import TrainState, make_step

    cfg = GPTConfig()
    tx = optax.adamw(3e-4)

    def loss_fn(params, batch, rng):
        del rng
        logits = GPT.apply(params, batch["ids"], cfg=cfg,
                           compute_dtype=jnp.bfloat16, remat=True)
        return cross_entropy(logits, batch["labels"]), {}

    state = jax.eval_shape(
        lambda: TrainState.create(
            GPT.init(jax.random.PRNGKey(0), cfg), tx, rng=0))
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip), state)
    tokens = jax.ShapeDtypeStruct((16, cfg.seq_len), jnp.int32,
                                  sharding=one_chip)
    compiled = make_step(loss_fn, tx, clip=1.0).lower(
        state, {"ids": tokens, "labels": tokens}).compile()
    memory = compiled.memory_analysis()
    need = (memory.temp_size_in_bytes + memory.argument_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert need < V5E_HBM_BYTES, f"{need / 2**30:.1f} GiB on a 16 GiB chip"
