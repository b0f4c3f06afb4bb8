"""Real 2-process multi-host runtime test.

The reference's core product is multi-machine launch + rendezvous
(ref distributed.py:110-205 ``launch``/``job``). This test executes the
TPU-native equivalent for real: two OS processes rendezvous through
``jax.distributed.initialize`` (CPU backend, localhost coordinator) and
together run the full stack — launch, barrier, allgather, a distributed
DataLoader feeding a dp-sharded train step through ``_place_global``'s
multi-process branch, and a coordinated orbax save + restore. See
``tests/_multihost_worker.py`` for what runs inside each process.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time
from pathlib import Path

from torchbooster_tpu.distributed import find_free_port

WORKER = Path(__file__).parent / "_multihost_worker.py"
REPO = Path(__file__).parent.parent


def _run_workers(tmp_path, nproc: int, devices_per_proc: int,
                 timeout_s: int = 300) -> None:
    port = find_free_port()
    env = dict(os.environ)
    # fresh interpreters: CPU backend, N virtual devices per process
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count="
                        f"{devices_per_proc}")
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")

    # workers write to files, not pipes: a full 64KB pipe would block a
    # worker mid-write while the test waits on its sibling, and a timeout
    # must still be able to show every rank's output so far
    logs = [tmp_path / f"rank{rank}.log" for rank in range(nproc)]
    procs = []
    for rank in range(nproc):
        with open(logs[rank], "w") as log:
            procs.append(subprocess.Popen(
                [sys.executable, str(WORKER), str(port), str(rank),
                 str(tmp_path / "ckpt"), str(nproc)],
                env=env, stdout=log, stderr=subprocess.STDOUT,
                cwd=str(REPO)))

    def outputs() -> str:
        return "\n---\n".join(
            f"rank {rank}:\n{logs[rank].read_text()}"
            for rank in range(nproc))

    deadline = time.monotonic() + timeout_s
    try:
        for proc in procs:
            proc.wait(timeout=max(deadline - time.monotonic(), 1.0))
    except subprocess.TimeoutExpired:
        for proc in procs:
            proc.kill()
        for proc in procs:
            proc.wait()
        raise AssertionError(
            f"multi-host workers timed out after {timeout_s}s; "
            f"output:\n{outputs()}")
    for rank, proc in enumerate(procs):
        assert proc.returncode == 0, (
            f"rank {rank} exited {proc.returncode}:\n{outputs()}")
        assert f"MULTIHOST_OK rank={rank}" in logs[rank].read_text(), (
            f"rank {rank} missing success marker:\n{outputs()}")


def test_two_process_runtime(tmp_path):
    _run_workers(tmp_path, nproc=2, devices_per_proc=2)


def test_four_process_spanning_mesh(tmp_path):
    """4 processes × 1 device: a dp:2,fsdp:2 mesh splits BOTH axes
    across process boundaries, with fsdp-sharded weights, global batch
    assembly, and a coordinated checkpoint that restores onto the same
    spanning mesh and onto dp:4 (see _multihost_worker.job4;
    VERDICT r4 #6)."""
    _run_workers(tmp_path, nproc=4, devices_per_proc=1, timeout_s=360)
