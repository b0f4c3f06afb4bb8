"""``chip_smoke.py`` and what bring-up added around it, as far as a
CPU can check: the smoke refuses to run without a TPU, its four-chip
phases are right at toy widths on virtual devices, the compile cache
lands where it should, and the benchmark's ``run.py`` fails loudly
without a chip. The smoke itself only ever passes on the chip."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(args, *, cwd=REPO, drop=(), **env):
    """A fresh interpreter; ``drop`` names inherited variables to
    remove (the suite itself runs under ``JAX_PLATFORMS=cpu``)."""
    full = {k: v for k, v in os.environ.items() if k not in drop}
    full.update(env, PYTHONPATH=str(REPO))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    out = _run([str(REPO / "chip_smoke.py")], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok": true' not in out.stdout
    assert not out.stdout.strip()       # no phase line, no result line


_CACHE_PROBE = (
    "import jax; from torchbooster_tpu.utils import enable_compile_cache;"
    "import json; got = enable_compile_cache();"
    "print(json.dumps([got, jax.config.jax_compilation_cache_dir]))")


@pytest.mark.parametrize("case", ["in_checkout", "env_wins", "cpu_pin"])
def test_compile_cache_placement(tmp_path, case):
    """Unset: ``<checkout>/.jax_cache`` whatever the working
    directory. ``JAX_COMPILATION_CACHE_DIR`` set: that directory and
    nothing else. Under either, the subdirectory named after
    ``PROGRAM_METADATA_VERSION`` (the cache's key leaves the named
    scopes out, so an older program would be served under new names).
    Pinned to the CPU: no cache at all."""
    from torchbooster_tpu.utils import PROGRAM_METADATA_VERSION

    sub = f"m{PROGRAM_METADATA_VERSION}"
    drop = ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")
    if case == "in_checkout":
        want = str(REPO / ".jax_cache" / sub)
        for cwd in (REPO, tmp_path):
            out = _run(["-c", _CACHE_PROBE], cwd=cwd, drop=drop)
            assert out.returncode == 0, out.stderr[-2000:]
            assert json.loads(out.stdout) == [want, want]
    elif case == "env_wins":
        outside = str(tmp_path / "placed_from_outside")
        out = _run(["-c", _CACHE_PROBE], cwd=tmp_path, drop=drop,
                   JAX_COMPILATION_CACHE_DIR=outside)
        assert out.returncode == 0, out.stderr[-2000:]
        want = str(Path(outside) / sub)
        assert json.loads(out.stdout) == [want, want]
        assert not (REPO / "placed_from_outside").exists()
    else:
        out = _run(["-c", _CACHE_PROBE], cwd=tmp_path,
                   drop=("JAX_COMPILATION_CACHE_DIR",),
                   JAX_PLATFORMS="cpu")
        assert out.returncode == 0, out.stderr[-2000:]
        assert json.loads(out.stdout) == [None, None]


def test_four_chip_phases_at_toy_widths_on_virtual_devices(monkeypatch):
    """The ``--chips 4`` code path — recipe on ``dp:2,fsdp:2`` vs one
    device, ZeRO-2 on ``dp:4`` through the YAML block, ``tp: 4``
    serving vs ``tp: 1`` — with its own checks (four distinct devices,
    agreeing losses and tokens), on four of the suite's virtual CPU
    devices."""
    import jax

    monkeypatch.syspath_prepend(str(REPO))
    import chip_smoke

    toy = dict(vocab=211, n_layers=2, d_model=48, n_heads=12, seq_len=128)
    log = chip_smoke.CompileLog()
    platform = jax.devices()[0].platform
    fields: dict = {}
    chip_smoke.phase_mesh_train(fields, log, platform, model=toy, batch=8)
    assert fields["sharded_param_devices"] == 4
    assert fields["sharded_losses"] == pytest.approx(
        fields["single_losses"], rel=1e-3)
    fields = {}
    chip_smoke.phase_zero2(fields, log, platform, model=toy, batch=8)
    assert fields["n_shards"] == 4 and fields["opt_state_devices"] == 4
    fields = {}
    chip_smoke.phase_tp_serve(
        fields, log, platform, model=toy, prompt_lens=(5, 17, 40, 70),
        new_tokens=(8, 8, 8, 8), page_size=16, n_pages=65, max_slots=8)
    assert fields["tp4_pool_devices"] == 4
    assert fields["tp4_vs_tp1"]["exact_requests"] == 4


def test_the_benchmark_refuses_without_an_accelerator():
    """``benchmark/run.py``, the command the driver runs for every
    cell, finds a chip or fails: under ``JAX_PLATFORMS=cpu`` it exits
    non-zero before it builds or measures anything and prints no
    result line, so no CPU number lands under a device metric's name.
    (A fresh interpreter: the seconds it takes are ``import jax``.)"""
    out = _run([str(REPO / "benchmark" / "run.py"), "--workload",
                "gpt2-small.train-s1024", "--seed", "1", "--seconds", "1",
                "--trace", "0"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert not out.stdout.strip()       # no run log, no result line
