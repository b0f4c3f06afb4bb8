"""The decode lanes' KDA rule against its roofline, for a Bailing
hybrid model: the bytes of ``S`` the live lanes of a step must read and
write (``serving_slot_state_bytes_total{kind=kda,part=lanes}``, the
program's host arithmetic, over the steps of the traced stretch) at
819 GB/s, over the device time of one run of the plain decode program
(``jit__decode_fn``, median over the traced runs) under
``attn_core/kda_lanes`` — the per-channel recurrence of every KDA
layer, the state's load and its store in place."""
from _bailing_hybrid import decode_steps, state_bytes
from _sarvam import scope_ms


def read(name: str, layers: dict):
    moved = state_bytes(layers, "lanes", stretch=True)
    steps = decode_steps(layers, stretch=True)
    ms = scope_ms(layers, "decode_fn", "kda_lanes")
    if not (moved and steps and ms):
        return None
    least = moved / steps / layers["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (ms * 1e-3)
