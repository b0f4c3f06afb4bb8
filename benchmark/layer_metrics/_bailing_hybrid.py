"""Shared by the Bailing hybrid (``model_type: bailing_hybrid``;
Ling-3.0-flash) readers: the slot state's bytes the program counted
(``serving_slot_state_bytes_total{kind, part}``: host arithmetic where
a step is packed, the live lanes' rows and the chunk's slot's), the
plain decode steps, and the share of the routed pairs computed here
(``serving_moe_pairs_total``, ``_sarvam.py``'s reading) — of the
window, or of the traced stretch where the job read the registry at
its edges. Every helper returns None where the run has nothing to
read: a configuration of another family, a program without the
series (the parent's)."""
from __future__ import annotations

from _lib import registry_delta
from _sarvam import PAIRS

STATE = "serving_slot_state_bytes_total"
STEPS = "span_seconds{name=decode_step}_count"


def is_family(layers: dict) -> bool:
    return layers.get("cfg", {}).get("model_type") == "bailing_hybrid"


def edges(layers: dict, stretch: bool) -> dict:
    """``layers`` with the registry's edges of the traced stretch in
    place of the window's where ``stretch`` and the job read them."""
    if stretch and "registry_trace_close" in layers:
        return {**layers, "registry_open": layers["registry_trace_open"],
                "registry_close": layers["registry_trace_close"]}
    return layers


def state_bytes(layers: dict, part: str, kind: str = "kda",
                stretch: bool = False):
    """Growth of the ``kind`` state's bytes of ``part`` (``lanes`` or
    ``chunk``); None where the program has no such series."""
    at = edges(layers, stretch)
    key = "%s{kind=%s}{part=%s}" % (STATE, kind, part)
    if not is_family(layers) or key not in (at.get("registry_close") or {}):
        return None
    return registry_delta(at, key)


def decode_steps(layers: dict, stretch: bool = False):
    return registry_delta(edges(layers, stretch), STEPS)


def pairs_here_share(layers: dict):
    """Share of the routed pairs whose expert is held here, by the
    program's counters over the window's plain decode steps."""
    if not is_family(layers):
        return None
    here = registry_delta(layers, PAIRS % "here")
    away = registry_delta(layers, PAIRS % "elsewhere")
    if here is None or away is None or not here + away:
        return None
    return here / (here + away)
