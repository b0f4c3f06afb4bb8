"""``mla_decode_attn_roofline``'s reading for a Bailing hybrid model
(``model_type: bailing_hybrid``; Ling-3.0-flash): the decode lanes'
latent attention as a share of its roofline over the traced stretch —
read each live row of the MLA layers once a step (1,280 B as stored),
or 32 heads x (576 + 512) x 2 operations a row, whichever is the
larger — over ALL device time under ``mla_sweep`` (inside
``attn_core``: the paged kernel over every slot's own pages) in the
traced runs of the plain (``decode_fn``) and mixed (``chunk_fn``)
programs alike. The arithmetic is this family's
(``flops_bailing_hybrid``: one MLA layer in the seven held, where the
sarvam reader counts every layer), and the live rows are counted on
the same stretch as the time (``traced_context_read``). None for a run
of another family or one with nothing to read."""
from _bailing_hybrid import is_family
from _lib import flops
from _sarvam import STEP_PROGRAMS
from _subscope import seconds_by_run

import flops_bailing_hybrid as fl


def read(name: str, layers: dict):
    if not is_family(layers):
        return None
    took = sum(seconds_by_run(layers, STEP_PROGRAMS, "mla_sweep"))
    rows = layers.get("traced_context_read")
    if not (took and rows):
        return None
    cfg = layers["cfg"]
    least = flops.roofline_seconds(
        fl.latent_sweep_flops(cfg, rows), fl.latent_read_bytes(cfg, rows),
        layers["peaks"])
    return 100.0 * least / took
