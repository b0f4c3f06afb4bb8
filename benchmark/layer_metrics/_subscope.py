"""Device time under a scope that is NOT one of ``xplane_scopes.SCOPES``
— the ones a model nests inside them (``conv_mix`` in ``attn_core``,
``moe_route`` and ``moe_experts`` in ``mlp``). ``scope_of`` files such
an op under the known scope around it, so the known scopes' readers are
unmoved; here the op's whole name stack is searched. None where no run
of the program has an op under the name: a program without the scope."""
from __future__ import annotations

import statistics

from _lib import scoped_trace     # puts benchmark/ on the path
import xplane_scopes


def seconds_by_run(layers: dict, program: str, name: str) -> list[float]:
    """Seconds under ``name`` in each traced run of ``program`` (first
    device); empty where no run has any."""
    trace = scoped_trace(layers)
    if trace is None or not trace.devices:
        return []
    device = trace.devices[0]
    out = []
    for run in xplane_scopes._runs(trace, device, program):
        out.append(sum(op.end - op.start
                       for op in xplane_scopes._ops_in(trace, device, run)
                       if name in op.tf_op.split("/")))
    return out if any(out) else []


def median_ms(layers: dict, program: str, name: str):
    runs = seconds_by_run(layers, program, name)
    return statistics.median(runs) * 1e3 if runs else None


def mean_of(layers: dict, series: str):
    """Mean observation of a registry histogram over the window."""
    from _lib import registry_delta

    total = registry_delta(layers, series + "_sum")
    n = registry_delta(layers, series + "_count")
    return total / n if total is not None and n else None
