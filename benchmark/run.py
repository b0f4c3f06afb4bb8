"""Run one cell of the benchmark once.

``python benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: a new process each time. It finds the cell's chips or
fails (no CPU fallback), sets up, measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints
ONE JSON object as the last line of standard output. Everything else
(the gap histogram, the per-request table, the readings behind
``correct``) goes to earlier lines, to standard error and to
``.bench_out/<cell>/run_log.json``.

The harness is driven by data: the cell, its configuration, its
traffic mix, the job that drives it and its per-layer metrics are
looked up by name in ``BENCHMARK.json``, ``configs/``, ``traffic/``,
``jobs/`` and ``layer_metrics/``; a later PR adds files and entries
and edits nothing here. See ``README.md``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()      # set-up is timed from process start

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class CompileCount:
    """Backend compilations, from JAX's own monitoring events: the
    window must see none."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@dataclass
class Context:
    """What a job gets from the harness."""

    cell: str
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    chips: int
    out_dir: Path
    t_start: float
    compiles: CompileCount
    devices: list = field(default_factory=list)

    @property
    def trace_dir(self) -> Path:
        return self.out_dir / "trace"

    def start_trace(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # spans, not every frame
        jax.profiler.start_trace(str(self.trace_dir),
                                 profiler_options=options)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip, by the runtime's
        allocator (live buffers; see ``program_bytes``)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))

    def program_bytes(self, jitted, *args) -> int:
        """What one compiled program needs on a chip while it runs —
        arguments + outputs - aliased + temporaries, by the compiler's
        ``memory_analysis()``. The allocator's peak above counts live
        buffers and misses a program's temporaries on this runtime
        (PERF.md, PR 21), so a job reports the larger of the two."""
        m = jitted.lower(*args).compile().memory_analysis()
        return int(m.argument_size_in_bytes + m.output_size_in_bytes
                   - m.alias_size_in_bytes + m.temp_size_in_bytes)


def bench_dirs(manifest: dict, root: Path) -> list[Path]:
    """Where files are looked up by name: the manifest's ``paths``
    under ``root``, then this directory."""
    dirs = [root / p for p in manifest["paths"]]
    return dirs if HERE in dirs else dirs + [HERE]


def find_file(dirs: list[Path], kind: str, name: str, suffix: str) -> Path:
    """``<dir>/<kind>/<name><suffix>`` in the first directory that has
    it: a traffic mix, a job or a reader is a file found by its name."""
    if not NAME.match(name):
        raise SystemExit(f"benchmark: {name!r} is no name of a file "
                         f"under {kind}/")
    for folder in dirs:
        if (folder / kind / (name + suffix)).exists():
            return folder / kind / (name + suffix)
    raise SystemExit(f"benchmark: no {kind}/{name}{suffix} under "
                     f"{[str(d) for d in dirs]}")


def resolve(workload: str, root: Path = ROOT):
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in manifest["configs"]
                  if c["name"] == cell["config"])
    cfg = json.loads((root / config["file"]).read_text())
    traffic = json.loads(find_file(bench_dirs(manifest, root), "traffic",
                                   cell["traffic"], ".json").read_text())
    return manifest, cell, cfg, traffic


def metrics_of(manifest: dict, cell: str, kind: str) -> list[dict]:
    """The cell's metrics of ``kind`` (``end_to_end`` / ``per_layer``):
    those that list it under ``workloads``, and those without the key
    (per-layer: where the cell reports the metric they move)."""
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def read_layer_metrics(wanted: list[dict], layers: dict,
                       dirs: list[Path]) -> dict:
    """One small reader per metric family, ``layer_metrics/<name
    before the first dot>.py``; a reader that finds nothing returns
    None and the metric is left out of the line."""
    for folder in dirs:
        if str(folder / "layer_metrics") not in sys.path:
            sys.path.append(str(folder / "layer_metrics"))
    out = {}
    for metric in wanted:
        reader = find_file(dirs, "layer_metrics",
                           metric["name"].split(".")[0], ".py")
        value = load_module(reader).read(metric["name"], layers)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def verdict(checks: dict) -> bool:
    """True when every compared number is there, finite and within its
    limit."""
    compared = [c for c in checks.values() if isinstance(c, dict)]
    return all(c["value"] is not None and math.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in compared)


def find_devices(chips: int) -> list:
    """The cell's chips, or no run: never a CPU standing in."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit("benchmark: JAX found no accelerator")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chip(s), "
                         f"JAX found {len(devices)}")
    return devices


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: Path = ROOT, devices: list | None = None,
            peaks: dict | None = None,
            t_start: float | None = None) -> dict:
    """One run, from resolved names to the result object. ``root``
    holds the ``BENCHMARK.json`` to read; ``devices`` and ``peaks``
    let a test hand over whatever JAX has — the command line always
    goes through :func:`find_devices` and ``peaks.json``."""
    import jax

    import flops
    import trace_reduce
    import xplane_scopes

    manifest, cell, cfg, traffic = resolve(workload, root)
    if devices is None:
        devices = find_devices(cell["chips"])
    if peaks is None:
        peaks = flops.peaks_of(devices[0].device_kind)
    out_dir = root / ".bench_out" / workload
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    ctx = Context(cell=workload, cfg=cfg, traffic=traffic, seed=seed,
                  seconds=seconds, trace=trace, chips=cell["chips"],
                  out_dir=out_dir,
                  t_start=T_START if t_start is None else t_start,
                  compiles=CompileCount(), devices=devices[:cell["chips"]])
    dirs = bench_dirs(manifest, root)
    # the kind of job is a file too: jobs/<traffic's "job">.py
    result = load_module(
        find_file(dirs, "jobs", traffic["job"], ".py")).run(ctx)
    if result["compiles_in_window"]:
        raise SystemExit(f"benchmark: {result['compiles_in_window']} "
                         "compilation(s) inside the measured window")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": verdict(result["checks"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if trace:
        trace_path = trace_reduce.find_xplane(str(ctx.trace_dir))
        reduced = trace_reduce.reduce(trace_path)
        lo, hi = trace_reduce.window_of(reduced)
        device["busy_s"] = trace_reduce.busy_seconds(reduced, lo, hi)
        device["window_s"] = hi - lo
        layers = {**result["layers"], "trace": reduced,
                  "trace_path": trace_path, "cfg": cfg,
                  "peaks": peaks, "chips": cell["chips"],
                  "busy_s": device["busy_s"], "window_s": hi - lo}
        line["metrics"] = read_layer_metrics(
            metrics_of(manifest, workload, "per_layer"), layers, dirs)
        scoped = xplane_scopes.load(trace_path)
        line["breakdown"] = trace_reduce.breakdown(
            reduced, device_ops=xplane_scopes.top_ops(scoped))
        if scoped is not None and scoped.devices:
            # the builder's tables, PERF.md's "where the time goes"
            print(xplane_scopes.report(scoped), file=sys.stderr, flush=True)
        result["log"]["programs"] = trace_reduce.module_summary(reduced)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"]
                 for m in metrics_of(manifest, workload, "end_to_end")}
        line["metrics"] = {name: {"value": float(result["e2e"][name]),
                                  "unit": unit}
                           for name, unit in units.items()}
    line["device"] = device
    line["compared"] = {k: v for k, v in result["checks"].items()
                        if isinstance(v, dict)}
    (out_dir / "run_log.json").write_text(json.dumps(result["log"], indent=1))
    return {"line": line, "log": result["log"], "checks": result["checks"],
            "records": result["layers"].get("records")}


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()

    out = execute(args.workload, args.seed, args.seconds, bool(args.trace))
    log = {k: v for k, v in out["log"].items() if k != "per_request"}
    print(json.dumps({"run_log": log}), flush=True)
    compared = json.dumps(out["checks"])
    print(f"compared {args.workload} seed {args.seed}: {compared}",
          file=sys.stderr, flush=True)
    print(json.dumps(out["line"]), flush=True)


if __name__ == "__main__":
    main()
