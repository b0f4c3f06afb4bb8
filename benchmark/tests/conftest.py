"""The benchmark's own tests: CPU, four virtual devices, never the chip.
Run with ``python -m pytest benchmark/tests`` (not part of ``tests/``)."""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
