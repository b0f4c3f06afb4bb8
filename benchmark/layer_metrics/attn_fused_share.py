"""Share of a train step's device time under the ``attn_core`` scope
that a fused kernel spends (ops whose name stack ends in a
``pallas_call``: the flash kernel's forward, dQ and dK/dV), over the
traced runs of ``step_fn`` on the first chip. 0 where XLA's own
programs compute attention and pass the (S, S) scores through HBM;
None where no op lies under ``attn_core``."""
from _lib import scoped_trace     # puts benchmark/ on the path
import xplane_scopes


def read(name: str, layers: dict):
    trace = scoped_trace(layers)
    if trace is None or not trace.devices:
        return None
    device = trace.devices[0]
    fused = total = 0.0
    for run in xplane_scopes._runs(trace, device, "step_fn"):
        for op in xplane_scopes._ops_in(trace, device, run):
            if xplane_scopes.scope_of(op.tf_op)[0] != "attn_core":
                continue
            total += op.end - op.start
            if "pallas_call" in op.tf_op:
                fused += op.end - op.start
    return 100.0 * fused / total if total else None
