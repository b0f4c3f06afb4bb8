"""The PLAIN decode program's share of its roofline, for a
latent-attention (``sarvam_mla``) model that holds a share of its
experts: the least time for what a step NEEDS to read — every
non-expert weight once (the head's slice with them), the held experts
the step's pairs HIT at three matrices each (``serving_moe_experts_hit``:
the program's count, filed by plain steps only), the live latent rows of
the step's sequences in every layer (1,280 B a token and layer as
stored) — or its operations, whichever is the larger, over the device
time of one run of ``jit__decode_fn`` (the mean over the traced runs:
the counts are means too).

Counts and time are of the SAME steps: the job reads the registry at
the traced stretch's edges (``registry_trace_open`` / ``_close``), so
the experts hit and the pairs routed are those of the plain steps the
trace holds. A plain step runs when no prompt is pending, at a lower
occupancy than the window's mean; the window's counts against a
stretch's time would read over 100 % whenever the stretch was quieter
than the window. The sequences a plain step holds are its routed
pairs (here and elsewhere) over ``top_k x expert layers``; a
sequence's live rows are what a token decoded inside the stretch read
(the client's records). None where the stretch held no plain step, or
the run has none of this to read."""
from _lib import flops, statistics, trace_reduce
import flops_sarvam_mla as fl
from _sarvam import PAIRS, is_family


def _stretch_delta(layers: dict, key: str):
    a = layers.get("registry_trace_open")
    b = layers.get("registry_trace_close")
    if a is None or b is None or key not in b:
        return None
    return b[key] - a.get(key, 0.0)


def read(name: str, layers: dict):
    if not is_family(layers):
        return None
    runs = trace_reduce.module_seconds(layers["trace"], "decode_fn")
    steps = _stretch_delta(layers, "serving_moe_experts_hit_count")
    hit = _stretch_delta(layers, "serving_moe_experts_hit_sum")
    here = _stretch_delta(layers, PAIRS % "here")
    away = _stretch_delta(layers, PAIRS % "elsewhere")
    tokens = layers.get("traced_decode_tokens")
    rows = layers.get("traced_context_read")
    if not (runs and steps and hit and here is not None
            and away is not None and tokens and rows):
        return None
    cfg = layers["cfg"]
    n_moe = fl.layer_counts(cfg)["moe"]
    seqs = (here + away) / steps / (cfg["num_experts_per_tok"] * n_moe)
    live = seqs * rows / tokens
    least = flops.roofline_seconds(
        fl.forward_flops(cfg, seqs, live, here / steps, seqs),
        fl.step_bytes(cfg, live, hit / steps),
        layers["peaks"])
    return 100.0 * least / statistics.fmean(runs)
