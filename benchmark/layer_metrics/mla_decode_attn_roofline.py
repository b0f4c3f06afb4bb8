"""The decode lanes' latent attention as a share of its roofline, over
the traced stretch: the least time to attend the cached tokens that
were LIVE in it in the absorbed form — read each live row of every
layer once a step (1,280 B as stored), or 64 heads x (576 + 512) x 2
operations a row, whichever is the larger — over ALL device time under
``mla_sweep`` (inside ``attn_core``: the paged kernel over every
slot's own pages) in the traced runs of the programs that decode,
plain (``decode_fn``) and mixed (``chunk_fn``) alike: the lanes'
kernel is the same call in both. The live rows are counted on the same
stretch as the time — every token the client saw decoded inside it,
at the context it read (``traced_context_read``) — because the
kernel's time follows them: the window's mean against a stretch's
time would read over 100 % whenever the stretch held fewer sequences
than the window."""
from _lib import flops
import flops_sarvam_mla as fl
from _sarvam import STEP_PROGRAMS, is_family
from _subscope import seconds_by_run


def read(name: str, layers: dict):
    took = sum(seconds_by_run(layers, STEP_PROGRAMS, "mla_sweep"))
    rows = layers.get("traced_context_read")
    if not (is_family(layers) and took and rows):
        return None
    cfg = layers["cfg"]
    least = flops.roofline_seconds(
        fl.latent_sweep_flops(cfg, rows), fl.latent_read_bytes(cfg, rows),
        layers["peaks"])
    return 100.0 * least / took
