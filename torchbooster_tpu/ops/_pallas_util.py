"""Shared pallas-kernel plumbing: ONE interpret-mode policy and ONE
CompilerParams spelling for every kernel in ops/.

A kernel copy-pasting ``interpret=False`` silently breaks every CPU
test that reaches it, so both ``ops/flash_attention.py`` and
``ops/paged_attention.py`` resolve an unspecified ``interpret=None``
through :func:`default_interpret` and take their ``CompilerParams``
from here.
"""
from __future__ import annotations

from jax.experimental.pallas.tpu import CompilerParams  # noqa: F401


def default_interpret() -> bool:
    """THE interpret-mode default for pallas kernels: compiled on the
    TPU backend, interpret mode everywhere else — the policy that lets
    the same kernel call sites run under the CPU test mesh and on real
    chips without per-caller plumbing. Callers that pass an explicit
    ``interpret=`` bool always win."""
    from torchbooster_tpu.ops.attention import _on_tpu

    return not _on_tpu()


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` if explicitly given, else :func:`default_interpret`."""
    return bool(default_interpret() if interpret is None else interpret)


__all__ = ["CompilerParams", "default_interpret", "resolve_interpret"]
