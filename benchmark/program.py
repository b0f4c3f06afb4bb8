"""The one place where the benchmark touches the system under test.

Everything the benchmark takes from the program comes through here: the
GPT recipe's step built as a user's YAML builds it, the serving stack
built from a ``serving:`` block, and the rearrangement of the
benchmark's flat weights into the tree the program wants. The
yardstick (traffic, reference, reduction, arithmetic) imports none of
this.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

RECIPE = ROOT / "examples" / "lm" / "gpt" / "gpt.py"


def gpt_config(cfg: dict):
    from torchbooster_tpu.models.gpt import GPTConfig

    return GPTConfig(vocab=cfg["vocab_size"], n_layers=cfg["n_layer"],
                     d_model=cfg["n_embd"], n_heads=cfg["n_head"],
                     seq_len=cfg["n_positions"])


def arrange(w: dict) -> dict:
    """The benchmark's flat leaves as ``models/gpt.py``'s param tree."""
    norm = lambda g, b: {"scale": w[g], "bias": w[b]}
    dense = lambda k, b: {"kernel": w[k], "bias": w[b]}
    return {
        "wte": {"table": w["wte"]},
        "wpe": {"table": w["wpe"]},
        "blocks": {
            "ln1": norm("ln1_g", "ln1_b"),
            "attn_qkv": dense("qkv_w", "qkv_b"),
            "attn_proj": dense("proj_w", "proj_b"),
            "ln2": norm("ln2_g", "ln2_b"),
            "mlp_fc1": dense("fc_w", "fc_b"),
            "mlp_fc2": dense("out_w", "out_b"),
        },
        "ln_f": norm("lnf_g", "lnf_b"),
    }


def flatten(tree: dict) -> dict:
    """Inverse of :func:`arrange`: any tree of the program's param
    shape (params, Adam moments, norms of either) by flat leaf name."""
    b = tree["blocks"]
    return {
        "wte": tree["wte"]["table"], "wpe": tree["wpe"]["table"],
        "ln1_g": b["ln1"]["scale"], "ln1_b": b["ln1"]["bias"],
        "qkv_w": b["attn_qkv"]["kernel"], "qkv_b": b["attn_qkv"]["bias"],
        "proj_w": b["attn_proj"]["kernel"],
        "proj_b": b["attn_proj"]["bias"],
        "ln2_g": b["ln2"]["scale"], "ln2_b": b["ln2"]["bias"],
        "fc_w": b["mlp_fc1"]["kernel"], "fc_b": b["mlp_fc1"]["bias"],
        "out_w": b["mlp_fc2"]["kernel"], "out_b": b["mlp_fc2"]["bias"],
        "lnf_g": tree["ln_f"]["scale"], "lnf_b": tree["ln_f"]["bias"],
    }


def load_recipe():
    spec = importlib.util.spec_from_file_location("bench_gpt_recipe", RECIPE)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def enable_compile_cache() -> str | None:
    """The program's own cache switch: ``JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache``."""
    from torchbooster_tpu.utils import enable_compile_cache as enable

    return enable()


class StepBuilt(Exception):
    """Ends ``main`` at the first call of the step it built."""


def build_train(cfg: dict, recipe_block: dict, seed: int):
    """The recipe's step, state and batch layout as
    ``examples/lm/gpt/gpt.py::main`` itself builds them from its YAML.
    ``main(conf)`` is launched as the recipe's ``__main__`` launches it,
    with ``utils.make_step`` wrapped (PR 21's ``chip_smoke.py`` did the
    same): the first call of the step that ``main`` built hands over
    the step, the state and a sharded batch and ends ``main`` there,
    before any step has run. So whatever the recipe does — its
    ``_loss`` with ``chunked_head`` and the MoE term, ``env.make``'s
    layout of the state, the batch's sharding — is what is timed, and
    a change to it moves the cell. The state's weights are then
    replaced by the benchmark's own from the seed, made in the layout
    ``main`` gave its own (Adam's moments start at nought whatever the
    weights are). Returns ``(state, step, shard, mesh)``;
    ``shard(tokens)`` is the recipe's host-batch -> device-batch."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    import torchbooster_tpu.distributed as dist
    import torchbooster_tpu.utils as utils
    from torchbooster_tpu.config import resolve_types

    import weights

    recipe = load_recipe()
    block = dict(recipe_block)
    block["model"] = {"vocab": cfg["vocab_size"], "n_layers": cfg["n_layer"],
                      "d_model": cfg["n_embd"], "n_heads": cfg["n_head"],
                      "seq_len": cfg["n_positions"],
                      **block.get("model", {})}
    block["seed"] = int(seed) % (2**31 - 1)
    conf = recipe.Config(**resolve_types(recipe.Config, block))
    built = {}
    make_step = utils.make_step

    def probed_make_step(*args, **kwargs):
        step = make_step(*args, **kwargs)

        def first_call(state, batch):
            built.update(step=step, state=state, batch=batch)
            raise StepBuilt

        return first_call

    utils.make_step = probed_make_step
    try:
        utils.boost()
        dist.launch(recipe.main, conf.env.n_devices, conf.env.n_machine,
                    conf.env.machine_rank, conf.env.dist_url, args=(conf,))
    except StepBuilt:
        pass
    finally:
        utils.make_step = make_step
    if not built:
        raise RuntimeError("the recipe's main never called its step")
    state, step = built["state"], built["step"]
    sharding = built["batch"]["ids"].sharding
    built.clear()
    gc.collect()        # main's frame and what it held on the device

    own = state.params
    params = weights.generate(
        cfg, seed, jnp.float32, arrange=arrange,
        out_shardings=jax.tree.map(lambda x: x.sharding, own))
    differ = jax.tree.leaves(jax.tree.map(
        lambda a, b: (a.shape, a.dtype) != (b.shape, b.dtype), own, params))
    if any(differ):
        raise RuntimeError("the recipe's parameters are not of the shapes "
                           "and types of the benchmark's weights")
    state = state.replace(params=params)
    del own

    def shard(tokens) -> dict:
        tokens = np.asarray(tokens)
        return {"ids": jax.device_put(
                    np.ascontiguousarray(tokens[:, :-1]), sharding),
                "labels": jax.device_put(
                    np.ascontiguousarray(tokens[:, 1:]), sharding)}

    return state, step, shard, dist.get_mesh(conf.env)


def first_moment(state) -> dict:
    """Adam's first moment out of the recipe's optimizer state
    (``inject_hyperparams`` around ``optax.adamw``), by flat leaf."""
    import optax

    for part in optax.tree_utils.tree_get_all_with_path(
            state.opt_state, "mu"):
        return flatten(part[1])
    raise LookupError("no Adam first moment in the optimizer state")


def build_serve(cfg: dict, serving_block: dict, seed: int):
    """``ServingConfig`` from the cell's ``serving:`` block ->
    ``.make(params, cfg)`` -> ``.frontend.make(batcher)``: the stack a
    user's YAML builds, on bfloat16 weights made from the seed."""
    import jax.numpy as jnp

    from torchbooster_tpu.config import ServingConfig, resolve_types

    import weights

    conf = ServingConfig(**resolve_types(ServingConfig, serving_block))
    enable_compile_cache()
    params = weights.generate(cfg, seed, jnp.bfloat16, arrange=arrange)
    batcher = conf.make(params, gpt_config(cfg))
    return batcher, conf.frontend.make(batcher), conf


def set_telemetry(enabled: bool) -> None:
    """Spans (``decode_step`` ...) and the ``serving_*`` counters are
    only written while the registry is on: the traced run turns it on,
    the timed run leaves it off."""
    from torchbooster_tpu.observability import set_enabled

    set_enabled(enabled)


def registry_snapshot() -> dict:
    from torchbooster_tpu.observability import get_registry

    return dict(get_registry().snapshot())
