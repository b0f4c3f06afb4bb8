"""GPT-2 weights from ``--seed``, in the benchmark's own flat layout.

One jitted call makes every leaf on the device, in the dtype asked for.
Both sides start from here: ``program.py`` rearranges these leaves into
the tree the program wants, and ``reference/gpt2.py`` reads them as
they are — so the reference takes nothing the program has made.

Layout (``L`` layers stacked on the leading axis):
``wte (V,d)  wpe (S,d)  ln1_g ln1_b ln2_g ln2_b (L,d)  qkv_w (L,d,3d)
qkv_b (L,3d)  proj_w (L,d,d)  proj_b (L,d)  fc_w (L,d,4d)  fc_b (L,4d)
out_w (L,4d,d)  out_b (L,d)  lnf_g lnf_b (d,)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_MIX = 2**31 - 1


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key for any whole-number seed (the driver's pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % _MIX)
    return jax.random.fold_in(jax.random.fold_in(key, seed // _MIX), stream)


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    v, s = cfg["vocab_size"], cfg["n_positions"]
    d, n = cfg["n_embd"], cfg["n_layer"]
    return {
        "wte": (v, d), "wpe": (s, d),
        "ln1_g": (n, d), "ln1_b": (n, d),
        "qkv_w": (n, d, 3 * d), "qkv_b": (n, 3 * d),
        "proj_w": (n, d, d), "proj_b": (n, d),
        "ln2_g": (n, d), "ln2_b": (n, d),
        "fc_w": (n, d, 4 * d), "fc_b": (n, 4 * d),
        "out_w": (n, 4 * d, d), "out_b": (n, d),
        "lnf_g": (d,), "lnf_b": (d,),
    }


def _std_of(name: str, cfg: dict) -> tuple[float, float]:
    """(mean, std) of a leaf: GPT-2's N(0, 0.02), residual projections
    scaled by 1/sqrt(2L). Gains and biases get a small spread so that
    a dropped one shows in a gradient; the biases' is kept tiny, for
    summed over 2L layers a larger one becomes a constant direction in
    the residual stream that drags every context to the same token."""
    if name in ("proj_w", "out_w"):
        return 0.0, 0.02 / (2 * cfg["n_layer"]) ** 0.5
    if name == "wpe":
        return 0.0, 0.01
    if name.endswith("_g"):
        return 1.0, 0.02
    if name.endswith("_b"):
        return 0.0, 0.002
    return 0.0, 0.02


def make(cfg: dict, key: jax.Array, dtype=jnp.float32) -> dict:
    """Trace-time body: call under ``jax.jit`` (``generate``)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(cfg).items())):
        mean, std = _std_of(name, cfg)
        leaf = mean + std * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = leaf.astype(dtype)
    return out


def generate(cfg: dict, seed: int, dtype=jnp.float32, *, arrange=None,
             out_shardings=None):
    """All weights in one jitted call. ``arrange`` maps the flat dict
    to another tree inside the same program (the program's layout);
    ``out_shardings`` places the result as it is made."""
    arrange = arrange or (lambda leaves: leaves)

    def build(key):
        return arrange(make(cfg, key, dtype))

    jitted = jax.jit(build, out_shardings=out_shardings)
    return jitted(seed_key(seed))

