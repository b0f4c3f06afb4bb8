"""Summarize logs/ab_results.jsonl into a markdown table.

Run over a log of ``bench.py --sub`` rows: prints one row per config (latest ok attempt wins), the headline
value it measured, and the delta vs its family baseline — the exact
evidence the gate-flip policy (bench._ab_best) consumes, rendered for
docs/performance.md.

Usage: python scripts/ab_summary.py [path-to-jsonl]
"""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# config name -> (family, metric key); families share a baseline row
METRICS = {
    "baseline": ("resnet img/s", "value"),
    "fused": ("resnet img/s", "value"),
    "s2d": ("resnet img/s", "value"),
    "fused_s2d": ("resnet img/s", "value"),
    "nf": ("resnet img/s", "value"),
    "nf_s2d": ("resnet img/s", "value"),
    "gpt": ("gpt tok/s", "gpt_tokens_per_sec"),
    "gpt_chunked": ("gpt tok/s", "gpt_tokens_per_sec"),
    "gpt_noremat": ("gpt tok/s", "gpt_tokens_per_sec"),
    "gpt_b32": ("gpt tok/s", "gpt_tokens_per_sec"),
    "gpt_chunked_b32": ("gpt tok/s", "gpt_tokens_per_sec"),
    "gpt_chunked_noremat": ("gpt tok/s", "gpt_tokens_per_sec"),
    "gpt_rope": ("gpt tok/s", "gpt_tokens_per_sec"),
    "gpt_swiglu": ("gpt tok/s", "gpt_tokens_per_sec"),
    "gpt_gqa4": ("gpt tok/s", "gpt_tokens_per_sec"),
    "gpt_long_flash": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "gpt_long_ref": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "gpt_long_b2": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "gpt_long_b4": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "gpt_long_gqa4": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "gpt_long_blk512": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "gpt_long_q2048k512": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "gpt_long_noremat": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "gpt_long_chunked": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "gpt_long_s16k": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "gpt_long_s32k": ("gpt-long tok/s", "gpt_long_tokens_per_sec"),
    "unet": ("unet img/s", "unet_img_per_sec"),
    "loader_thread": ("loader img/s", "loader_img_per_sec"),
    "loader_process": ("loader img/s", "loader_img_per_sec"),
    # serving rows: the in-process dense-geometry control lives in the
    # SAME result dict (serve_dense_* keys), so the paged number is
    # shown with its A/B partner rendered by the generic fallback
    "serve": ("serve tok/s", "serve_tok_s_c2048_kvfull"),
    "serve_int8": ("serve tok/s", "serve_tok_s_c2048_kvfull_int8"),
}
BASELINES = {"resnet img/s": "baseline", "gpt tok/s": "gpt",
             "gpt-long tok/s": "gpt_long_flash",
             "loader img/s": "loader_thread"}


def _fingerprints_comparable(a: dict | None, b: dict | None) -> bool:
    """Two result dicts may be compared unless BOTH carry a
    ``workload_fingerprint`` and the hashes differ — then they served
    different traces and any delta is noise dressed as evidence.
    (Mirror of torchbooster_tpu/serving/loadgen/report.py::
    fingerprints_comparable — duplicated so this summary stays
    importable without jax; tests/test_loadgen.py pins the two
    together.)"""
    fa = (a or {}).get("workload_fingerprint")
    fb = (b or {}).get("workload_fingerprint")
    return fa is None or fb is None or fa == fb


def main() -> None:
    path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        REPO, "logs", "ab_results.jsonl")
    latest: dict[str, dict] = {}
    attempts: dict[str, int] = {}
    try:
        with open(path) as f:
            for ln in f:
                try:
                    e = json.loads(ln)
                except json.JSONDecodeError:
                    continue
                name = e.get("config", "?")
                attempts[name] = attempts.get(name, 0) + 1
                if e.get("status") == "ok":
                    latest[name] = e
    except OSError:
        print(f"no results at {path}")
        return

    print("| config | metric | value | vs family baseline | status |")
    print("|---|---|---|---|---|")
    for name, (family, key) in METRICS.items():
        e = latest.get(name)
        if e is None:
            status = (f"{attempts[name]} failed attempt(s)"
                      if attempts.get(name) else "pending")
            print(f"| {name} | {family} | — | — | {status} |")
            continue
        result = e.get("result") or {}
        value = result.get(key)
        base_e = latest.get(BASELINES[family])
        base_r = (base_e.get("result") or {}) if base_e else {}
        base = base_r.get(key) if base_e else None
        if value and base and name != BASELINES[family]:
            # refuse a delta between arms that served different
            # traces (workload fingerprints present and unequal)
            delta = (f"{(value / base - 1) * 100:+.1f}%"
                     if _fingerprints_comparable(result, base_r)
                     else "refused: fingerprint mismatch")
        else:
            delta = "—"
        extra = ""
        for flag in ("gpt_flash_engaged", "gpt_long_flash_engaged"):
            if flag in (e.get("result") or {}):
                extra = f" flash={e['result'][flag]}"
        print(f"| {name} | {family} | {value} | {delta} "
              f"| ok ({e.get('seconds', '?')}s){extra} |")
    # configs in the log but absent from METRICS (queue entries drift
    # in faster than this table — decode and gpt_chunked_b32 both did):
    # render them raw rather than silently dropping recorded evidence
    multi_key = ("decode", "decode_int8", "cifar_acc", "comms",
                 "comms_cpu8", "zero", "zero_cpu8",
                 "serve_prefix", "serve_prefix_int8",
                 "serve_spec", "serve_spec_int8", "serve_http",
                 "serve_http_prio", "serve_kernel", "serve_kernel_spec",
                 "serve_tp", "serve_tp_pallas",
                 "serve_parallel", "serve_tree",
                 "obs_trace", "replay", "replay_http",
                 "serve_fleet", "serve_fleet_affinity",
                 "serve_spill", "serve_structured", "obs_fleet",
                 "serve_wq", "serve_wq_int4", "serve_lora",
                 "serve_disagg")
    for name in sorted(attempts):
        if name in METRICS or (name in multi_key and name in latest):
            continue  # multi-key ok rows print below; failures fall through
        e = latest.get(name)
        if e is None:
            print(f"| {name} | ? | — | — | "
                  f"{attempts.get(name, 0)} failed attempt(s) |")
        else:
            print(f"| {name} | ? | {json.dumps(e.get('result', {}))} "
                  f"| — | ok ({e.get('seconds', '?')}s) |")
    for name in ("decode", "decode_int8", "cifar_acc"):
        e = latest.get(name)
        if e:
            print(f"\n{name}:",
                  json.dumps(e.get("result", {}), indent=None))

    # serve_prefix rows: the prefix-cache A/B rendered as a cold-vs-
    # hit sub-table (TTFT, hit rate, prefill chunks/compiles, tok/s)
    for name in ("serve_prefix", "serve_prefix_int8"):
        e = latest.get(name)
        if e is None:
            continue
        r = e.get("result") or {}
        sfx = "_int8" if name.endswith("int8") else ""
        print(f"\n{name} (shared frac "
              f"{r.get(f'serve_prefix_shared_frac{sfx}', '?')}, "
              f"hit TTFT ratio "
              f"{r.get(f'serve_prefix_ttft_ratio{sfx}', '?')}x, "
              f"{r.get(f'serve_prefix_hit_pages{sfx}', '?')} hit pages "
              f"~{r.get(f'serve_prefix_prefill_gflops_saved{sfx}', '?')}"
              " GFLOP prefill saved):")
        print("| arm | ttft s | decode tok/s | prefill chunks "
              "| hit rate | prefill compiles |")
        print("|---|---|---|---|---|---|")
        for arm in ("cold", "hit"):
            print(f"| {arm} "
                  f"| {r.get(f'serve_prefix_ttft_{arm}_s{sfx}', '—')} "
                  f"| {r.get(f'serve_prefix_tok_s_{arm}{sfx}', '—')} "
                  f"| {r.get(f'serve_prefix_chunks_{arm}{sfx}', '—')} "
                  f"| {r.get(f'serve_prefix_hit_rate_{arm}{sfx}', '—')} "
                  f"| {r.get(f'serve_prefix_prefill_compiles_{arm}{sfx}', '—')} |")

    # serve_spec rows: the speculative-decoding A/B rendered as an
    # off-vs-on sub-table (decode tok/s, latency) plus the accept
    # stats, compile proof, and greedy-parity bit
    for name in ("serve_spec", "serve_spec_int8"):
        e = latest.get(name)
        if e is None:
            continue
        r = e.get("result") or {}
        sfx = "_int8" if name.endswith("int8") else ""
        print(f"\n{name} (draft_len "
              f"{r.get(f'serve_spec_draft_len{sfx}', '?')}, tok/s "
              f"ratio {r.get(f'serve_spec_tok_s_ratio{sfx}', '?')}x, "
              f"accept rate "
              f"{r.get(f'serve_spec_accept_rate{sfx}', '?')}, mean "
              f"accepted {r.get(f'serve_spec_mean_accepted{sfx}', '?')}"
              f"/step, verify compiles "
              f"{r.get(f'serve_spec_verify_compiles{sfx}', '?')}, "
              f"token parity "
              f"{r.get(f'serve_spec_token_parity{sfx}', '?')}):")
        print("| arm | decode tok/s | mean latency s |")
        print("|---|---|---|")
        for arm in ("off", "on"):
            print(f"| {arm} "
                  f"| {r.get(f'serve_spec_tok_s_{arm}{sfx}', '—')} "
                  f"| {r.get(f'serve_spec_latency_{arm}_s{sfx}', '—')} |")

    # serve_kernel rows: the decode-backend A/B rendered as a
    # per-backend sub-table (tok/s, modeled live-vs-pool MB/step,
    # compile counts) with the measured-vs-modeled ratio headline
    for name in ("serve_kernel", "serve_kernel_spec"):
        e = latest.get(name)
        if e is None:
            continue
        r = e.get("result") or {}
        pre = name
        print(f"\n{name} (tok/s ratio "
              f"{r.get(f'{pre}_tok_s_ratio', '?')}x vs modeled bytes "
              f"ratio {r.get(f'{pre}_modeled_bytes_ratio', '?')}x, "
              f"pool {r.get(f'{pre}_pool_mb_step', '?')} MB/step, "
              f"token parity {r.get(f'{pre}_token_parity', '?')}):")
        print("| backend | decode tok/s | mean latency s "
              "| live MB/step | decode/verify compiles |")
        print("|---|---|---|---|---|")
        for backend in ("xla", "pallas"):
            if f"{pre}_tok_s_{backend}" not in r:
                continue
            print(
                f"| {backend} "
                f"| {r.get(f'{pre}_tok_s_{backend}', '—')} "
                f"| {r.get(f'{pre}_latency_{backend}_s', '—')} "
                f"| {r.get(f'{pre}_live_mb_step_{backend}', '—')} "
                f"| {r.get(f'{pre}_decode_compiles_{backend}', '—')}"
                f"/{r.get(f'{pre}_verify_compiles_{backend}', '—')} |")

    # serve_parallel row: the CoW n-way sampling A/B rendered as a
    # fork-vs-control sub-table (per-completion live MB/step — the
    # amortization headline — prefill chunks, TTFT, tok/s) with the
    # byte-ratio acceptance bit and the parity/compile proof
    e = latest.get("serve_parallel")
    if e is not None:
        r = e.get("result") or {}
        print(f"\nserve_parallel (n {r.get('serve_parallel_n', '?')}, "
              "per-completion byte ratio "
              f"{r.get('serve_parallel_byte_ratio', '?')} "
              "(gate <= 0.5), chunk amortization "
              f"{r.get('serve_parallel_chunk_ratio', '?')}x, "
              f"{r.get('serve_parallel_forks', '?')} forks / "
              f"{r.get('serve_parallel_fork_pages', '?')} shared pages "
              f"/ {r.get('serve_parallel_cow_copies', '?')} CoW "
              "copies, token parity "
              f"{r.get('serve_parallel_token_parity', '?')}):")
        print("| arm | live MB/step/completion | decode tok/s "
              "| ttft s | prefill chunks | decode compiles |")
        print("|---|---|---|---|---|---|")
        for arm in ("ctrl", "fork"):
            print(
                f"| {arm} "
                f"| {r.get(f'serve_parallel_live_mb_per_completion_{arm}', '—')} "
                f"| {r.get(f'serve_parallel_tok_s_{arm}', '—')} "
                f"| {r.get(f'serve_parallel_ttft_{arm}_s', '—')} "
                f"| {r.get(f'serve_parallel_chunks_{arm}', '—')} "
                f"| {r.get(f'serve_parallel_decode_compiles_{arm}', '—')} |")

    # serve_tree row: tree vs linear drafting at the same budget —
    # accepted tokens/step per arm with the tree >= linear verdict
    e = latest.get("serve_tree")
    if e is not None:
        r = e.get("result") or {}
        print("\nserve_tree (draft_len "
              f"{r.get('serve_tree_draft_len', '?')}, width "
              f"{r.get('serve_tree_width', '?')}, win "
              f"{r.get('serve_tree_win', '?')}, token parity "
              f"{r.get('serve_tree_token_parity', '?')}):")
        print("| arm | accepted/step | accept rate | decode tok/s "
              "| verify compiles |")
        print("|---|---|---|---|---|")
        for arm in ("linear", "tree"):
            print(
                f"| {arm} "
                f"| {r.get(f'serve_tree_accepted_per_step_{arm}', '—')} "
                f"| {r.get(f'serve_tree_accept_rate_{arm}', '—')} "
                f"| {r.get(f'serve_tree_tok_s_{arm}', '—')} "
                f"| {r.get(f'serve_tree_verify_compiles_{arm}', '—')} |")

    # serve_tp rows: the tensor-parallel serving A/B rendered as a
    # per-arm sub-table (tok/s, modeled per-chip live MB/step — the
    # ÷tp headline — and modeled psum bytes/step) with the
    # accounting-vs-HLO gate verdict in the header
    for name in ("serve_tp", "serve_tp_pallas"):
        e = latest.get(name)
        if e is None:
            continue
        r = e.get("result") or {}
        pre = name
        print(f"\n{name} (per-chip bytes ratio "
              f"{r.get(f'{pre}_chip_bytes_ratio', '?')}x, token parity "
              f"{r.get(f'{pre}_token_parity', '?')}, psum model-vs-HLO "
              f"ok {r.get(f'{pre}_psum_model_ok', '?')} "
              f"[{r.get(f'{pre}_hlo_psum_ops', '?')} all-reduce, "
              f"{r.get(f'{pre}_hlo_psum_bytes_layer', '?')} vs "
              f"{r.get(f'{pre}_model_psum_bytes_layer', '?')} B/layer]):")
        print("| tp | decode tok/s | mean latency s "
              "| per-chip live MB/step | psum B/step | decode compiles |")
        print("|---|---|---|---|---|---|")
        for tp in r.get(f"{pre}_arms", ()):
            print(
                f"| {tp} "
                f"| {r.get(f'{pre}_tok_s_tp{tp}', '—')} "
                f"| {r.get(f'{pre}_latency_tp{tp}_s', '—')} "
                f"| {r.get(f'{pre}_live_mb_step_chip_tp{tp}', '—')} "
                f"| {r.get(f'{pre}_psum_bytes_step_tp{tp}', '—')} "
                f"| {r.get(f'{pre}_decode_compiles_tp{tp}', '—')} |")

    # serve_http rows: the front-door A/B rendered as a per-class SLO
    # sub-table (client-observed TTFT/TPOT percentiles per arm x
    # class, deadline hit + shed rates, parity + compile proofs); the
    # prio row carries both arms and the p99 win headline
    for name in ("serve_http", "serve_http_prio"):
        e = latest.get(name)
        if e is None:
            continue
        r = e.get("result") or {}
        win = r.get("serve_http_prio_ttft_p99_win")
        print(f"\n{name} (classes {r.get('serve_http_classes', '?')}, "
              f"token parity {r.get('serve_http_token_parity', '?')}"
              + (f", SLO interactive p99 TTFT win {win}x vs FCFS"
                 if win is not None else "") + "):")
        print("| arm | class | ttft p50/p99 s | tpot p50/p99 s "
              "| deadline hit | shed rate | decode compiles |")
        print("|---|---|---|---|---|---|---|")
        for arm in ("fcfs", "slo"):
            if f"serve_http_{arm}_deadline_hit_rate" not in r:
                continue
            for cls in ("interactive", "batch"):
                hit = (r.get(f"serve_http_{arm}_deadline_hit_rate", "—")
                       if cls == "interactive" else "—")
                print(
                    f"| {arm} | {cls} "
                    f"| {r.get(f'serve_http_{arm}_ttft_p50_s_{cls}', '—')}"
                    f"/{r.get(f'serve_http_{arm}_ttft_p99_s_{cls}', '—')} "
                    f"| {r.get(f'serve_http_{arm}_tpot_p50_s_{cls}', '—')}"
                    f"/{r.get(f'serve_http_{arm}_tpot_p99_s_{cls}', '—')} "
                    f"| {hit} "
                    f"| {r.get(f'serve_http_{arm}_shed_rate', '—')} "
                    f"| {r.get(f'serve_http_{arm}_decode_compiles', '—')} |")

    # obs_trace row: the request-tracing A/B rendered as an
    # off-vs-on sub-table (decode tok/s, compile proof) plus the
    # trace-file verdict (Perfetto-loadable, preempted + cancelled
    # request tracks present) and the <3% overhead headline
    e = latest.get("obs_trace")
    if e is not None:
        r = e.get("result") or {}
        print(f"\nobs_trace (overhead "
              f"{r.get('obs_trace_overhead_pct', '?')}% of limit 3%, "
              f"zero new compiles "
              f"{r.get('obs_trace_zero_new_compiles', '?')}, chrome "
              f"valid {r.get('obs_trace_chrome_valid', '?')} with "
              f"preempted/cancelled tracks "
              f"{r.get('obs_trace_has_preempted_track', '?')}/"
              f"{r.get('obs_trace_has_cancelled_track', '?')}, "
              f"verdict ok={r.get('obs_trace_ok', '?')}):")
        print("| arm | decode tok/s | decode/prefill compiles |")
        print("|---|---|---|")
        for arm in ("off", "on"):
            print(f"| tracing {arm} "
                  f"| {r.get(f'obs_trace_tok_s_{arm}', '—')} "
                  f"| {r.get(f'obs_trace_decode_compiles_{arm}', '—')}"
                  f"/{r.get(f'obs_trace_prefill_compiles_{arm}', '—')}"
                  " |")

    # replay rows: the loadgen capture/replay harness — the capture
    # overhead A/B + round-trip verdict, the x1/xN conformance
    # numbers, and the max-sustainable-x capacity headline; the two
    # rows' fingerprints differ by construction (capture vs offered
    # synthetic), so no cross-row delta is ever printed
    e = latest.get("replay")
    if e is not None:
        r = e.get("result") or {}
        print(f"\nreplay (fingerprint "
              f"{r.get('workload_fingerprint', '?')}, capture "
              f"overhead {r.get('replay_capture_overhead_pct', '?')}% "
              f"of limit 3%, zero new compiles "
              f"{r.get('replay_capture_zero_new_compiles', '?')}, "
              f"round trip counts/tokens/cancel "
              f"{r.get('replay_roundtrip_counts_match', '?')}/"
              f"{r.get('replay_roundtrip_tokens_match', '?')}/"
              f"{r.get('replay_roundtrip_cancel_match', '?')}, "
              f"max sustainable x"
              f"{r.get('replay_max_sustainable_x', '?')}, "
              f"verdict ok={r.get('replay_ok', '?')}):")
        print("| arm | goodput tok/s | total tok/s |")
        print("|---|---|---|")
        print(f"| replay x1 "
              f"| {r.get('replay_x1_goodput_tok_s', '—')} "
              f"| {r.get('replay_x1_total_tok_s', '—')} |")
        print(f"| replay x{r.get('replay_xn_speed', '?')} "
              f"| {r.get('replay_xn_goodput_tok_s', '—')} "
              f"| {r.get('replay_xn_total_tok_s', '—')} |")
    e = latest.get("replay_http")
    if e is not None:
        r = e.get("result") or {}
        print(f"\nreplay_http (fingerprint "
              f"{r.get('workload_fingerprint', '?')}, "
              f"x{r.get('replay_http_speed', '?')}, goodput "
              f"{r.get('replay_http_goodput_tok_s', '?')} tok/s, "
              f"deadline hit "
              f"{r.get('replay_http_deadline_hit_rate', '?')}, shed "
              f"{r.get('replay_http_shed_rate', '?')}):")
        print("| class | ttft p50/p99 s | tpot p50/p99 s |")
        print("|---|---|---|")
        for cls in ("interactive", "batch"):
            if f"replay_http_ttft_p50_s_{cls}" not in r:
                continue
            print(
                f"| {cls} "
                f"| {r.get(f'replay_http_ttft_p50_s_{cls}', '—')}"
                f"/{r.get(f'replay_http_ttft_p99_s_{cls}', '—')} "
                f"| {r.get(f'replay_http_tpot_p50_s_{cls}', '—')}"
                f"/{r.get(f'replay_http_tpot_p99_s_{cls}', '—')} |")

    # serve_fleet rows: the engine-fleet router — the 1->N scaling
    # headline (max sustainable x per fleet size) and the
    # affinity-vs-round-robin sub-table (fleet-wide prefix-hit pages,
    # interactive p99 TTFT, goodput, spills) with the parity/compile
    # proofs in the header
    for name in ("serve_fleet", "serve_fleet_affinity"):
        e = latest.get(name)
        if e is None:
            continue
        r = e.get("result") or {}
        scaling = r.get("serve_fleet_scaling_x")
        print(f"\n{name} ({r.get('serve_fleet_replicas', '?')} "
              f"replicas x {r.get('serve_fleet_tenants', '?')} "
              "tenants, fingerprint "
              f"{r.get('workload_fingerprint', '?')}"
              + (f", 1->N scaling {scaling}x (max x"
                 f"{r.get('serve_fleet_max_x_1', '?')} -> x"
                 f"{r.get('serve_fleet_max_x_n', '?')}, gate >= 3)"
                 if scaling is not None else "")
              + f", hit-page ratio "
              f"{r.get('serve_fleet_hit_page_ratio', '?')}x "
              "(gate >= 1.5), interactive p99 TTFT win "
              f"{r.get('serve_fleet_ttft_p99_win', '?')}x, token "
              f"parity {r.get('serve_fleet_token_parity', '?')}, one "
              "compile/replica "
              f"{r.get('serve_fleet_one_compile_per_replica', '?')}, "
              f"verdict ok={r.get('serve_fleet_ok', '?')}):")
        print("| routing | hit pages | ttft p50/p99 s interactive "
              "| goodput tok/s | spills |")
        print("|---|---|---|---|---|")
        for arm in ("affinity", "round_robin"):
            pre = f"serve_fleet_{arm}"
            print(
                f"| {arm} "
                f"| {r.get(f'{pre}_hit_pages', '—')} "
                f"| {r.get(f'{pre}_ttft_p50_s', '—')}"
                f"/{r.get(f'{pre}_ttft_p99_s', '—')} "
                f"| {r.get(f'{pre}_goodput_tok_s', '—')} "
                f"| {r.get(f'{pre}_spills', '—')} |")

    # serve_spill row: the host page spill tier — cold vs HBM-hit vs
    # host-hit TTFT sub-table with the parity/compile/bytes gates in
    # the header and the modeled break-even prefix length
    e = latest.get("serve_spill")
    if e is not None:
        r = e.get("result") or {}
        be = r.get("serve_spill_breakeven_pages")
        print(f"\nserve_spill ({r.get('serve_spill_prefix_pages', '?')}"
              f"-page prefix x {r.get('serve_spill_tenants', '?')} "
              "churn tenants, host/cold TTFT ratio "
              f"{r.get('serve_spill_ttft_ratio', '?')}x (gate >= 1.5)"
              f", token parity {r.get('serve_spill_token_parity', '?')}"
              ", bytes model==measured "
              f"{r.get('serve_spill_bytes_match', '?')} "
              f"({r.get('serve_spill_promoted_bytes', '?')} B), one "
              f"compile {r.get('serve_spill_one_compile', '?')}, "
              "modeled break-even "
              f"{'n/a' if be == -1 else be} pages, verdict "
              f"ok={r.get('serve_spill_ok', '?')}):")
        print("| arm | ttft s | hit pages |")
        print("|---|---|---|")
        print(f"| cold | {r.get('serve_spill_ttft_cold_s', '—')} "
              "| 0 |")
        print(f"| hbm_hit | {r.get('serve_spill_ttft_hbm_s', '—')} "
              f"| {r.get('serve_spill_hbm_hit_pages', '—')} |")
        print(f"| host_hit | {r.get('serve_spill_ttft_host_s', '—')} "
              f"| {r.get('serve_spill_host_hit_pages', '—')} |")

    # serve_structured row: the constrained-decoding A/B — the
    # flag-off baseline vs flag-on-unconstrained (parity + overhead)
    # vs flag-on-constrained (conformance + the one-compile schema-mix
    # proof), gates in the header
    e = latest.get("serve_structured")
    if e is not None:
        r = e.get("result") or {}
        print(f"\nserve_structured "
              f"({r.get('serve_structured_n_constrained', '?')} "
              f"constrained of {r.get('serve_structured_requests', '?')}"
              f" reqs x {r.get('serve_structured_n_schemas', '?')} "
              "schemas, conformance "
              f"{r.get('serve_structured_conformance', '?')} (gate "
              "1.0), flag-on overhead "
              f"{r.get('serve_structured_overhead_pct', '?')}% of "
              "limit 3%, token parity "
              f"{r.get('serve_structured_token_parity', '?')}, one "
              f"compile {r.get('serve_structured_one_compile', '?')}, "
              "verdict "
              f"ok={r.get('serve_structured_ok', '?')}):")
        print("| arm | decode tok/s | masked frac |")
        print("|---|---|---|")
        print(f"| off | {r.get('serve_structured_tok_s_off', '—')} "
              "| — |")
        print(f"| on, unconstrained "
              f"| {r.get('serve_structured_tok_s_plain', '—')} | — |")
        print(f"| on, constrained "
              f"| {r.get('serve_structured_tok_s_on', '—')} "
              f"| {r.get('serve_structured_masked_frac', '—')} |")

    # serve_wq rows: quantized-weight serving, one sub-table row per
    # measured dtype (the serve_wq / serve_wq_int4 rows) — the
    # measured-vs-modeled headline is the whole point: modeled is the
    # weight-stream byte ratio (the gate, >= 1.9), measured is what
    # this chip's decode actually did with it (compute-bound CPU
    # smokes sit near 1.0x; an HBM-bound chip should track modeled)
    wq_rows = [(n, latest[n].get("result") or {})
               for n in ("serve_wq", "serve_wq_int4") if n in latest]
    if wq_rows:
        gates = ", ".join(
            f"{r.get('serve_wq_dtype', '?')}: parity "
            f"{r.get('serve_wq_token_parity', '?')} one compile "
            f"{r.get('serve_wq_one_compile', '?')} "
            f"ok={r.get('serve_wq_ok', '?')}" for _, r in wq_rows)
        d0 = wq_rows[0][1]
        print(f"\nserve_wq (d_model {d0.get('serve_wq_d_model', '?')}"
              f", group {d0.get('serve_wq_group_size', '?')}, modeled"
              " ratio gate >= 1.9; " + gates + "):")
        print("| dtype | bf16 tok/s | quant tok/s | measured ratio "
              "| modeled ratio | match frac |")
        print("|---|---|---|---|---|---|")
        for _, r in wq_rows:
            print(f"| {r.get('serve_wq_dtype', '—')} "
                  f"| {r.get('serve_wq_tok_s_bf16', '—')} "
                  f"| {r.get('serve_wq_tok_s_quant', '—')} "
                  f"| {r.get('serve_wq_measured_ratio', '—')}x "
                  f"| {r.get('serve_wq_modeled_ratio', '—')}x "
                  f"| {r.get('serve_wq_match_frac', '—')} |")

    # serve_lora row: batched multi-LoRA decode — the mixed-adapter
    # batch vs the lora-off control, with the base-parity /
    # distinct-adapters / zero-recompile-churn gates in the header
    e = latest.get("serve_lora")
    if e is not None:
        r = e.get("result") or {}
        print(f"\nserve_lora ({r.get('serve_lora_n_adapters', '?')} "
              f"adapters rank {r.get('serve_lora_rank', '?')} through "
              f"{r.get('serve_lora_max_live', '?')} lanes, "
              f"{r.get('serve_lora_distinct_in_batch', '?')} distinct "
              "in one batch (gate >= 2), base parity "
              f"{r.get('serve_lora_base_parity', '?')}, adapters "
              f"steer {r.get('serve_lora_adapters_differ', '?')}, "
              "decode/load compiles "
              f"{r.get('serve_lora_decode_compiles', '?')}/"
              f"{r.get('serve_lora_load_compiles', '?')} across "
              f"{r.get('serve_lora_loads', '?')} loads + "
              f"{r.get('serve_lora_evictions', '?')} evictions, "
              f"verdict ok={r.get('serve_lora_ok', '?')}):")
        print("| arm | decode tok/s |")
        print("|---|---|")
        print(f"| base (lora off) "
              f"| {r.get('serve_lora_tok_s_base', '—')} |")
        print(f"| mixed adapters "
              f"| {r.get('serve_lora_tok_s_mix', '—')} "
              f"({r.get('serve_lora_overhead_pct', '—')}% overhead) |")

    # serve_disagg row: the prefill/decode split A/B — one unified
    # batcher vs the DisaggPair under longprompt_burst, with the
    # parity / compile / bytes-EQUAL gates in the header and the
    # decode-class p99 TPOT ratio as the headline (gated >= 1.5 only
    # when perf_gated=True, i.e. an accelerator backend ran it —
    # a 1-core CPU host time-slices the two pools and the ratio is
    # reported informationally)
    e = latest.get("serve_disagg")
    if e is not None:
        r = e.get("result") or {}
        print(f"\nserve_disagg ({r.get('serve_disagg_requests', '?')} "
              f"reqs / {r.get('serve_disagg_long_requests', '?')} "
              "long, token parity "
              f"{r.get('serve_disagg_token_parity', '?')}, dense "
              f"parity {r.get('serve_disagg_dense_parity', '?')}, one "
              f"compile {r.get('serve_disagg_one_compile', '?')}, "
              "bytes match "
              f"{r.get('serve_disagg_bytes_match', '?')} "
              f"({r.get('serve_disagg_page_bytes', '?')} == "
              f"{r.get('serve_disagg_modeled_bytes', '?')} modeled), "
              f"perf gated {r.get('serve_disagg_perf_gated', '?')}, "
              f"verdict ok={r.get('serve_disagg_ok', '?')}):")
        print("| arm | decode-class p99 TPOT (ms) | long TTFT (s) |")
        print("|---|---|---|")
        print(f"| unified "
              f"| {r.get('serve_disagg_tpot_p99_uni_ms', '—')} "
              f"| {r.get('serve_disagg_ttft_long_uni_s', '—')} |")
        print(f"| disagg "
              f"| {r.get('serve_disagg_tpot_p99_dis_ms', '—')} "
              f"| {r.get('serve_disagg_ttft_long_dis_s', '—')} |")
        print(f"| ratio "
              f"| {r.get('serve_disagg_tpot_ratio', '—')}x "
              "(gate >= 1.5 when perf gated) | — |")

    # obs_fleet row: the fleet signal-plane A/B — plane off vs on
    # decode tok/s with the <3% headline, the routing byte-identity +
    # compile proofs, the replay_diff --routing rc triple, and the
    # plane's own outputs (alerts fired/resolved, health flaps,
    # audit-ring records)
    e = latest.get("obs_fleet")
    if e is not None:
        r = e.get("result") or {}
        rcs = (f"{r.get('obs_fleet_diff_rc_clean', '?')}/"
               f"{r.get('obs_fleet_diff_rc_mutated', '?')}/"
               f"{r.get('obs_fleet_diff_rc_foreign', '?')}")
        print(f"\nobs_fleet (overhead "
              f"{r.get('obs_fleet_overhead_pct', '?')}% of limit 3%, "
              f"routing identical "
              f"{r.get('obs_fleet_routing_identical', '?')}, zero new "
              f"compiles {r.get('obs_fleet_zero_new_compiles', '?')}, "
              f"replay_diff rcs {rcs} (need 0/1/2), verdict "
              f"ok={r.get('obs_fleet_ok', '?')}):")
        print("| arm | decode tok/s | alerts fired/resolved "
              "| health flaps | audit records |")
        print("|---|---|---|---|---|")
        print(f"| plane off | {r.get('obs_fleet_tok_s_off', '—')} "
              "| — | — | — |")
        print(f"| plane on | {r.get('obs_fleet_tok_s_on', '—')} "
              f"| {r.get('obs_fleet_alerts_fired', '—')}"
              f"/{r.get('obs_fleet_alerts_resolved', '—')} "
              f"| {r.get('obs_fleet_health_flaps', '—')} "
              f"| {r.get('obs_fleet_audit_records', '—')} |")

    # comms rows: bytes-moved + step-time deltas across the gradient
    # sync arms, rendered as a compact sub-table (one row per arm)
    for name in ("comms", "comms_cpu8"):
        e = latest.get(name)
        if e is None:
            continue
        r = e.get("result") or {}
        base = r.get("comms_step_s_implicit")
        print(f"\n{name} (N={r.get('comms_n_devices', '?')} replicas, "
              f"{r.get('comms_n_params', '?')} params; int8-vs-fp32 "
              f"loss delta {r.get('comms_loss_delta_pct', '?')}% after "
              f"{r.get('comms_loss_steps', '?')} steps):")
        print("| arm | step s | vs implicit | grad-sync MB/replica |")
        print("|---|---|---|---|")
        for arm in ("implicit", "fp32", "int8", "int8_zero1"):
            dt = r.get(f"comms_step_s_{arm}")
            if dt is None:
                continue
            delta = (f"{(dt / base - 1) * 100:+.1f}%"
                     if base and arm != "implicit" else "—")
            mb = r.get(f"comms_mbytes_{arm}", "—")
            print(f"| {arm} | {dt} | {delta} | {mb} |")

    # ZeRO-ladder rows: one line per stage arm (step time, wire MB,
    # per-replica persistent-state HBM, loss delta vs zero1) plus the
    # two gates the bench computes (overlap-on <= overlap-off, and
    # reduce-scatter accounting within 10% of the compiled HLO)
    for name in ("zero", "zero_cpu8"):
        e = latest.get(name)
        if e is None:
            continue
        r = e.get("result") or {}
        base = r.get("zero_step_s_zero1")
        print(f"\n{name} (N={r.get('zero_n_devices', '?')} replicas, "
              f"{r.get('zero_n_params', '?')} params, "
              f"{r.get('zero_n_buckets', '?')} buckets; overlap gate "
              f"{r.get('zero_overlap_ok', '?')}, accounting gate "
              f"{r.get('zero_accounting_ok', '?')} "
              f"[rs ratio {r.get('zero_rs_hlo_ratio', '?')}]):")
        print("| arm | step s | vs zero1 | wire MB | state MB/replica "
              "| loss Δ% |")
        print("|---|---|---|---|---|---|")
        for arm in ("zero1", "zero2", "zero2_overlap", "zero2_int8",
                    "zero3"):
            dt = r.get(f"zero_step_s_{arm}")
            if dt is None:
                continue
            delta = (f"{(dt / base - 1) * 100:+.1f}%"
                     if base and arm != "zero1" else "—")
            print(f"| {arm} | {dt} | {delta} "
                  f"| {r.get(f'zero_mbytes_{arm}', '—')} "
                  f"| {r.get(f'zero_state_mb_{arm}', '—')} "
                  f"| {r.get(f'zero_loss_delta_pct_{arm}', '—')} |")


if __name__ == "__main__":
    main()
