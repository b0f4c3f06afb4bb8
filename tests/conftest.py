"""Test environment: force an 8-device virtual CPU mesh.

This is the TPU-world answer to "fake backend" testing (SURVEY §4): all
multi-device sharding/collective tests run on 8 virtual CPU devices, so
the suite needs no TPU hardware. Tests never touch the chip: the
platform is pinned to the CPU here, before the first backend use, and
the chip is exercised by ``chip_smoke.py`` alone.
"""
import os

# zero-egress environment: make HuggingFace resolution fail fast instead
# of stalling in network retries (the offline→synthetic fallback is the
# behavior under test)
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("HF_DATASETS_OFFLINE", "1")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
