"""Device time by ``jax.named_scope``, and the join of the two clocks,
from a raw profiler trace (``.xplane.pb``).

``jax.profiler.ProfileData`` (what ``trace_reduce.py`` reads with)
shows an event's own stats only. The op's name stack — the
``jax.named_scope`` path the program was traced under, e.g.
``jit(step_fn)/transpose(jvp(attn_core))/dot_general`` — is a stat of
the event's *metadata* (``tf_op``), and so is out of its reach. This
module decodes the XSpace wire format itself, as far as it needs and
with nothing but the standard library:

- per device plane (``/device:TPU:n``): the ``XLA Ops`` events with
  start, duration, HLO name and ``tf_op``; the ``XLA Modules`` events
  (one per program run) with their ``run_id``;
- per host plane: the runtime's ``DoEnqueueProgram`` events with the
  same ``run_id``, and every other named event (the program's spans).

What it gives the readers in ``layer_metrics/``:

- :func:`scope_seconds`: per run of the programs whose module name
  matches a pattern, the device seconds under each scope of
  :data:`SCOPES`. An op belongs to the module run that contains it on
  its device, so equal HLO names in two programs never mix; container
  ops (``while``, ``conditional``, ``call``) span their children and
  are left out, as ``trace_reduce.CONTAINER_OP`` leaves them out; an op
  whose ``tf_op`` names no known scope counts as ``unscoped``. Where
  scopes nest (``attn_core/kv_write``) the innermost wins; wrappers
  that JAX's transformations put round a name (``jvp(..)``,
  ``transpose(..)``, ``checkpoint(..)``, ``rematted_computation(..)``)
  are stripped, and :func:`scope_of` also gives the phase: forward,
  backward (a ``transpose(`` encloses it) or recomputed forward.
- :func:`clock_lead_seconds`: the device's clock leads the host's. A
  run cannot start on the device before the host enqueued it, so the
  largest ``enqueue.start - module.start`` over the joined runs is a
  lower bound of the lead, tight wherever the device was idle at the
  launch.
- :func:`idle_by_span`: idle seconds of the first device by the
  *program's* innermost span (not the runtime's events, which is what
  ``trace_reduce.idle_gaps`` names gaps after), the clock lead
  subtracted. A traced run prints it, with the scope table of every
  program and the heaviest ops, to standard error (:func:`report`;
  by hand: ``python benchmark/xplane_scopes.py [trace]``): the
  builder's view for PERF.md section 5.

``run.py`` hands readers the trace's path as ``layers["trace_path"]``
and they give it to :func:`load`. Called without a path (a reader
written before that key was there) it takes the newest ``*.xplane.pb``
under ``<checkout>/.bench_out/*/trace/`` (readers run before ``run.py``
deletes it). Where there is no trace it returns None: the reader then
returns None and the metric is left out of the line. ``run.py`` also
takes its ``breakdown``'s device operations from here
(:func:`top_ops`): program, scope and the end of the name stack beside
the compiler's name, since ``fusion.208`` alone tells the next reader
nothing.
"""
from __future__ import annotations

import bisect
import functools
import re
import statistics
import struct
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce  # noqa: E402  (stdlib only until it reads a trace)

BENCH_OUT = Path(__file__).resolve().parent.parent / ".bench_out"

# the vocabulary of docs/observability.md, letter for letter
SCOPES = ("embed", "attn_qkv", "attn_core", "attn_out", "mlp", "head",
          "loss", "optimizer", "kv_write", "sample")
UNSCOPED = "unscoped"
# the program's own host spans (observability/spans.py::span), the
# tree of docs/observability.md: what an idle gap is named after
PROGRAM_SPANS = ("sched_step", "sched_admit", "prefill_args",
                 "serving_prefill_chunk", "prefill_finish", "sched_grow",
                 "decode_args", "decode_step", "spec_verify_step",
                 "decode_advance", "sched_deliver", "frontend_fanout",
                 "serving_promote", "train_step")

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
CONTAINER_OP = re.compile(r"^%?(while|conditional|call)(\.\d+)?\b")
# what a transformation wraps round a scope's name in the name stack
WRAPPER = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*\((.*)\)$")
ENQUEUE = "DoEnqueueProgram"
RUNTIME_NOISE = re.compile(
    r"::|^PJRT|^\$|semaphore|ReadSyncFlag|CompleteCallbacks"
    r"|ThreadpoolListener|^Pjit|^DoEnqueue|=>")


# ---------------------------------------------------------------------
# the wire format: varints and length-delimited fields
# ---------------------------------------------------------------------

def _fields(buf: memoryview):
    """(field number, wire type, value) of one message: an int for
    varint / fixed fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        number, wire = key >> 3, key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield number, wire, value
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield number, wire, buf[i:i + size]
            i += size
        elif wire == 1:
            yield number, wire, struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wire == 5:
            yield number, wire, struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _text(buf: memoryview) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _stat(buf: memoryview):
    """XStat -> (metadata id, value); ``("ref", id)`` where the value
    is a reference to another stat metadata's name."""
    key, value = 0, None
    for number, wire, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number in (5, 6):
            value = _text(v)
        elif number == 7:
            value = ("ref", v)
    return key, value


# ---------------------------------------------------------------------
# the decoded trace
# ---------------------------------------------------------------------

@dataclass
class Event:
    """One event, in seconds on its plane's clock. ``tf_op`` and
    ``run_id`` are filled where the trace has them."""

    start: float
    end: float
    name: str
    tf_op: str = ""
    run_id: int | None = None


@dataclass
class Scoped:
    """``ops[device]`` and ``modules[device]``: device events sorted by
    start; ``enqueues``: the host's ``DoEnqueueProgram`` events;
    ``spans``: every other host event that is no runtime noise."""

    ops: dict[str, list[Event]] = field(default_factory=dict)
    modules: dict[str, list[Event]] = field(default_factory=dict)
    enqueues: list[Event] = field(default_factory=list)
    spans: list[Event] = field(default_factory=list)

    @property
    def devices(self) -> list[str]:
        return sorted(self.ops)


def _plane(buf: memoryview):
    """XPlane -> (name, lines, event metadata, stat names). An event
    metadata is (name, {stat metadata id: value})."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for number, wire, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            lines.append(v)
        elif number == 4:                      # map<int64, XEventMetadata>
            for n2, _, entry in _fields(v):
                if n2 != 2:
                    continue
                meta_id, meta_name, stats = 0, "", {}
                for n3, _, x in _fields(entry):
                    if n3 == 1:
                        meta_id = x
                    elif n3 == 2:
                        meta_name = _text(x)
                    elif n3 == 5:
                        key, value = _stat(x)
                        stats[key] = value
                event_meta[meta_id] = (meta_name, stats)
        elif number == 5:                      # map<int64, XStatMetadata>
            for n2, _, entry in _fields(v):
                if n2 != 2:
                    continue
                stat_id, stat_name = 0, ""
                for n3, _, x in _fields(entry):
                    if n3 == 1:
                        stat_id = x
                    elif n3 == 2:
                        stat_name = _text(x)
                stat_names[stat_id] = stat_name
    return name, lines, event_meta, stat_names


def _line(buf: memoryview):
    """XLine -> (name, timestamp_ns, [event buffers])."""
    name, timestamp_ns, events = "", 0, []
    for number, wire, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            timestamp_ns = _signed(v)
        elif number == 4:
            events.append(v)
    return name, timestamp_ns, events


def _event(buf: memoryview, timestamp_ns: int, event_meta: dict,
           stat_ids: dict[str, int]) -> Event | None:
    meta_id = offset_ps = duration_ps = 0
    own: dict[int, object] = {}
    for number, wire, v in _fields(buf):
        if number == 1:
            meta_id = v
        elif number == 2:
            offset_ps = _signed(v)
        elif number == 3:
            duration_ps = _signed(v)
        elif number == 4:
            key, value = _stat(v)
            own[key] = value
    if duration_ps <= 0:
        return None
    name, meta_stats = event_meta.get(meta_id, ("", {}))
    start = timestamp_ns * 1e-9 + offset_ps * 1e-12
    tf_op = meta_stats.get(stat_ids.get("tf_op"), "")
    run_id = own.get(stat_ids.get("run_id"))
    return Event(start, start + duration_ps * 1e-12, name,
                 tf_op if isinstance(tf_op, str) else "",
                 run_id if isinstance(run_id, int) else None)


def _plane_name(buf: memoryview) -> str:
    return next((_text(v) for number, _, v in _fields(buf)
                 if number == 2), "")


def decode(data: bytes, all_devices: bool = False) -> Scoped:
    """The serialized XSpace -> :class:`Scoped`. Every reader here
    works on the first device, so the other device planes (three of a
    four-chip host's four) are skipped unless ``all_devices``."""
    out = Scoped()
    planes = [buf for number, _, buf in _fields(memoryview(data))
              if number == 1]
    names = [_plane_name(buf) for buf in planes]
    first = min((n for n in names if DEVICE_PLANE.match(n)), default=None)
    for name, plane_buf in zip(names, planes):
        is_device = bool(DEVICE_PLANE.match(name)) \
            and (all_devices or name == first)
        is_host = name.startswith("/host:") and name != "/host:metadata"
        if not (is_device or is_host):
            continue
        _, lines, event_meta, stat_names = _plane(plane_buf)
        stat_ids = {v: k for k, v in stat_names.items()}
        for line_buf in lines:
            line_name, timestamp_ns, events = _line(line_buf)
            if is_device and line_name not in ("XLA Ops", "XLA Modules"):
                continue
            decoded = [e for e in (
                _event(b, timestamp_ns, event_meta, stat_ids)
                for b in events) if e is not None]
            if is_device and line_name == "XLA Ops":
                out.ops.setdefault(name, []).extend(decoded)
            elif is_device:
                out.modules.setdefault(name, []).extend(decoded)
            else:
                for e in decoded:
                    if e.name == ENQUEUE:
                        out.enqueues.append(e)
                    elif not RUNTIME_NOISE.search(e.name):
                        out.spans.append(e)
    for events in (*out.ops.values(), *out.modules.values(),
                   out.enqueues, out.spans):
        events.sort(key=lambda e: (e.start, e.end))
    for device in out.modules:
        out.ops.setdefault(device, [])
    return out


def newest_trace() -> Path | None:
    """The newest ``*.xplane.pb`` under ``BENCH_OUT/*/trace/``."""
    found = sorted(BENCH_OUT.glob("*/trace/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


_CACHE: dict[Path, tuple[float, Scoped]] = {}


def load(path: Path | str | None = None) -> Scoped | None:
    """The decoded trace at ``path`` (``layers["trace_path"]``), or,
    without one, the newest kept trace; None where there is none.
    Decoded once per file: thirteen readers and ``run.py`` ask."""
    path = newest_trace() if path is None else Path(path)
    if path is None or not path.exists():
        return None
    stamp = path.stat().st_mtime
    hit = _CACHE.get(path)
    if hit is None or hit[0] != stamp:
        _CACHE.clear()
        _CACHE[path] = hit = (stamp, decode(path.read_bytes()))
    return hit[1]


# ---------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------

@functools.lru_cache(maxsize=None)     # a trace has few distinct stacks
def scope_of(tf_op: str) -> tuple[str, str]:
    """(innermost known scope of the name stack, or ``unscoped``; its
    phase). The phase is ``remat`` where a ``rematted_computation``
    component encloses the scope (the forward pass computed again
    inside the backward one), else ``bwd`` where a ``transpose(..)``
    does — round the name itself (``transpose(jvp(attn_core))``) or,
    under a scan, round an outer component
    (``transpose(jvp())/while/body/.../attn_core``) — else ``fwd``."""
    scope, phase, seen = UNSCOPED, "fwd", "fwd"
    for part in tf_op.split("/"):
        if part == "rematted_computation":
            seen = "remat"
        while True:
            found = WRAPPER.match(part)
            if not found:
                break
            if part.startswith("transpose(") and seen == "fwd":
                seen = "bwd"
            part = found.group(1)
        if part in SCOPES:
            scope, phase = part, seen
    return scope, phase


def _runs(trace: Scoped, device: str, pattern: str) -> list[Event]:
    rx = re.compile(pattern)
    return [m for m in trace.modules.get(device, []) if rx.search(m.name)]


def _ops_in(trace: Scoped, device: str, run: Event) -> list[Event]:
    ops = trace.ops.get(device, [])
    lo = bisect.bisect_left(ops, run.start, key=lambda e: e.start)
    out = []
    for op in ops[lo:]:
        if op.start >= run.end:
            break
        if not CONTAINER_OP.match(op.name):
            out.append(op)
    return out


def scope_seconds(trace: Scoped, pattern: str, *,
                  by_phase: bool = False) -> dict[str, list[float]]:
    """``{scope: [seconds in each run]}`` over the runs, on the first
    device, of the programs whose module name matches ``pattern``;
    every scope met in any run has an entry for every run.
    ``by_phase`` files the backward pass's ops as ``<scope>.bwd`` and
    the recomputed forward's as ``<scope>.remat``."""
    if not trace.devices:
        return {}
    device = trace.devices[0]
    per_run = []
    for run in _runs(trace, device, pattern):
        acc: dict[str, float] = defaultdict(float)
        for op in _ops_in(trace, device, run):
            scope, phase = scope_of(op.tf_op)
            if by_phase and phase != "fwd":
                scope += "." + phase
            acc[scope] += op.end - op.start
        per_run.append(acc)
    names = sorted({k for acc in per_run for k in acc})
    return {k: [acc.get(k, 0.0) for acc in per_run] for k in names}


def median_scope_ms(trace: Scoped | None, pattern: str,
                    scopes: tuple[str, ...]) -> float | None:
    """Median over the matching runs of the milliseconds under
    ``scopes`` together; None where no run has any op under them."""
    if trace is None:
        return None
    by_scope = scope_seconds(trace, pattern)
    have = [by_scope[s] for s in scopes if s in by_scope]
    if not have:
        return None
    return statistics.median(sum(run) for run in zip(*have)) * 1e3


def scoped_share(trace: Scoped | None) -> float | None:
    """Device-op seconds under any known scope over all device-op
    seconds of the trace (first device, containers left out), in
    percent; None where no op carries a known scope."""
    if trace is None or not trace.devices:
        return None
    known = every = 0.0
    for op in trace.ops[trace.devices[0]]:
        if CONTAINER_OP.match(op.name):
            continue
        every += op.end - op.start
        if scope_of(op.tf_op)[0] != UNSCOPED:
            known += op.end - op.start
    return 100.0 * known / every if known else None


# ---------------------------------------------------------------------
# the two clocks
# ---------------------------------------------------------------------

def joined_runs(trace: Scoped) -> list[tuple[Event, Event]]:
    """(host enqueue, device module run) pairs that share a
    ``run_id``, first device."""
    if not trace.devices:
        return []
    by_id = {m.run_id: m for m in trace.modules.get(trace.devices[0], [])
             if m.run_id is not None}
    return [(e, by_id[e.run_id]) for e in trace.enqueues
            if e.run_id in by_id]


def clock_lead_seconds(trace: Scoped | None) -> float | None:
    """How far the device's clock leads the host's, at least: the
    largest ``enqueue.start - module.start`` over the joined runs."""
    if trace is None:
        return None
    pairs = joined_runs(trace)
    if not pairs:
        return None
    return max(e.start - m.start for e, m in pairs)


def idle_by_span(trace: Scoped, min_gap: float = 50e-6,
                 names: tuple[str, ...] = PROGRAM_SPANS
                 ) -> dict[str, float]:
    """Idle seconds of the first device between its first and last op,
    by the innermost span of ``names`` that covers the middle of the
    gap (host times moved onto the device's clock by the clock lead);
    ``unattributed`` where none does, ``between_ops`` for the seams
    under ``min_gap``."""
    device = trace.devices[0]
    lead = clock_lead_seconds(trace) or 0.0
    spans = [(s.start - lead, s.end - lead, s.name) for s in trace.spans
             if s.name in names]
    out: dict[str, float] = defaultdict(float)
    reach = None
    for op in trace.ops[device]:
        if reach is not None and op.start - reach > 1e-12:
            gap, mid = op.start - reach, (op.start + reach) / 2
            if gap < min_gap:
                out["between_ops"] += gap
            else:
                cover = [(b - a, n) for a, b, n in spans if a <= mid <= b]
                out[min(cover)[1] if cover else "unattributed"] += gap
        reach = op.end if reach is None else max(reach, op.end)
    return dict(out)


def _bare(part: str) -> str:
    """A name-stack component without the wrappers round it."""
    while True:
        found = WRAPPER.match(part)
        if not found:
            return part
        part = found.group(1)


def op_label(program: str, name: str, tf_op: str) -> str:
    """``<program>/<scope>/<end of the name stack> <HLO name> <type and
    shape>``: what ``breakdown`` calls a device operation. The program
    is the module's name without ``jit_`` and its hash; the scope
    carries its phase as :func:`scope_seconds` files it
    (``attn_core.bwd``); the name stack's end is what follows the
    scope (the last two parts where no scope is known): the primitive,
    and an einsum's spec."""
    scope, phase = scope_of(tf_op)
    parts = [p for p in tf_op.rstrip(":").split("/")
             if p and not p.startswith("jit(")]
    at = max((i for i, p in enumerate(parts) if _bare(p) == scope),
             default=None)
    tail = "/".join(parts[-2:] if at is None else parts[at + 1:])
    program = re.sub(r"^jit_+|\(\d+\)$", "", program)
    if phase != "fwd":
        scope += "." + phase
    return (f"{program}/{scope}/{tail[-60:]} "
            f"{trace_reduce.hlo_label(name)}")[:160]


def top_ops(trace: Scoped | None, top: int = 10) -> list[list]:
    """``[[label, seconds], ...]``: the device operations of the first
    device that took most time, containers left out, each filed under
    the program whose run contains it (equal HLO names in two programs
    never mix) and labelled by :func:`op_label`. Empty where the trace
    has no device plane."""
    if trace is None or not trace.devices:
        return []
    device = trace.devices[0]
    runs = trace.modules.get(device, [])
    starts = [m.start for m in runs]
    # a trace holds few distinct (program, op) pairs and very many
    # events: sum by pair, label the pairs
    by_pair: dict[tuple[str, str, str], float] = defaultdict(float)
    for op in trace.ops[device]:
        if CONTAINER_OP.match(op.name):
            continue
        i = bisect.bisect_right(starts, op.start) - 1
        inside = i >= 0 and op.start < runs[i].end
        by_pair[(runs[i].name if inside else "no_program",
                 op.name, op.tf_op)] += op.end - op.start
    by_op: dict[str, float] = defaultdict(float)
    for pair, seconds in by_pair.items():
        by_op[op_label(*pair)] += seconds
    return [[k, v] for k, v in sorted(by_op.items(),
                                      key=lambda kv: -kv[1])[:top]]


def report(trace: Scoped, top: int = 12) -> str:
    """The builder's tables: per program the median run's seconds by
    scope, the heaviest ops and the heaviest unscoped ones, the
    program's spans, the idle time by program span."""
    rows = []
    device = trace.devices[0]
    programs = sorted({m.name for m in trace.modules.get(device, [])})
    for program in programs:
        by_scope = scope_seconds(trace, re.escape(program),
                                 by_phase=True)
        runs = _runs(trace, device, re.escape(program))
        med = statistics.median(m.end - m.start for m in runs)
        rows.append(f"PROGRAM {program} runs={len(runs)} "
                    f"median_run_ms={med * 1e3:.3f}")
        for scope, secs in sorted(by_scope.items(),
                                  key=lambda kv: -statistics.median(kv[1])):
            rows.append(f"  {scope:<16} {statistics.median(secs) * 1e3:9.3f}"
                        " ms")
    by_op: dict[tuple[str, str, str], float] = defaultdict(float)
    for op in trace.ops[device]:
        if not CONTAINER_OP.match(op.name):
            by_op[(scope_of(op.tf_op)[0], op.name[:160],
                   op.tf_op[-90:])] += op.end - op.start
    # the HLO line names the operands, so an unscoped copy still says
    # whose result it copies
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])
    for title, table in (
            ("TOP OPS", ranked[:top]),
            ("TOP UNSCOPED OPS",
             [kv for kv in ranked if kv[0][0] == UNSCOPED][:top])):
        rows.append(f"{title} (scope, seconds, HLO line, end of tf_op)")
        for (scope, name, tf_op), secs in table:
            rows.append(f"  {scope:<10} {secs:10.6f}  {name}  [{tf_op}]")
    rows.append(f"CLOCK LEAD ms {1e3 * (clock_lead_seconds(trace) or 0):.3f}")
    rows.append("PROGRAM SPANS (count, median ms, total seconds)")
    by_span: dict[str, list[float]] = defaultdict(list)
    for s in trace.spans:
        if s.name in PROGRAM_SPANS:
            by_span[s.name].append(s.end - s.start)
    for name in PROGRAM_SPANS:
        if name in by_span:
            d = by_span[name]
            rows.append(f"  {name:<24} {len(d):6d} "
                        f"{statistics.median(d) * 1e3:10.3f} {sum(d):9.4f}")
    rows.append("IDLE BY PROGRAM SPAN (seconds)")
    for name, secs in sorted(idle_by_span(trace).items(),
                             key=lambda kv: -kv[1]):
        rows.append(f"  {name:<24} {secs:8.4f}")
    return "\n".join(rows)


if __name__ == "__main__":
    found = load(Path(sys.argv[1]) if len(sys.argv) > 1 else None)
    if found is None or not found.devices:
        raise SystemExit("xplane_scopes: no trace with a device plane")
    print(report(found), file=sys.stderr)
