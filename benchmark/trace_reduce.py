"""From a profiler trace (``.xplane.pb``) to intervals and metrics.

Read with nothing but ``jax.profiler.ProfileData``. A trace holds
device planes (``/device:TPU:n``; their ``XLA Ops`` line carries one
event per executed HLO op, ``XLA Modules`` one per program run, named
``jit_<function>(<hash>)``) and host planes (``/host:CPU``; one line per
thread, where the program's ``TraceAnnotation`` spans land). Other
planes (``/device:CUSTOM:...``, ``#Chip0 ...``) are not devices.
Everything here is interval arithmetic on those events, in seconds.

The device's clock and the host's are not the same clock: in the
recorded trace beside the tests a program starts on the device about a
millisecond BEFORE the host call that launched it. So device time is
read from device events alone (a program's run on ``XLA Modules``), and
host spans are used only to name idle gaps that are long against that
offset.

``reduce(path)`` returns a :class:`Trace`; the per-layer readers and
``run.py``'s ``device`` / ``breakdown`` fields take what they need
from it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

NOT_OP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                "Framework Name Scope", "Source code", "Host Offload Ops")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast", re.I)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# ops that only contain other ops (a scan's loop, a branch): their
# children are on the same line, so ranking them would count time twice
CONTAINER_OP = re.compile(r"^(while|conditional|call)(\.\d+)?\b")
# the runtime's own host events: they say nothing of what the program did
RUNTIME_NOISE = re.compile(
    r"::|^PJRT|^\$|semaphore|ReadSyncFlag|CompleteCallbacks|ThreadpoolListener")
HLO_TEXT = re.compile(r"^%(?P<name>[^\s=]+)\s*=\s*(?P<type>\(?[a-z0-9]+\[[^\]]*\])")

Interval = tuple[float, float]


@dataclass
class Trace:
    """``ops[device]`` and ``modules[device]``: (start, end, name) of
    device events; ``spans``: (start, end, name) of host events."""

    ops: dict[str, list[tuple[float, float, str]]] = field(
        default_factory=dict)
    modules: dict[str, list[tuple[float, float, str]]] = field(
        default_factory=dict)
    spans: list[tuple[float, float, str]] = field(default_factory=list)

    @property
    def devices(self) -> list[str]:
        return sorted(self.ops)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def hlo_label(name: str) -> str:
    """An op's short name with its result's type and shape. On the TPU
    an op event is named by its whole HLO line
    (``%copy.29 = bf16[1,256,64,25,64]{...} copy(...)``); the next
    issue's writer sees only these labels, so the shape stays."""
    found = HLO_TEXT.match(name)
    if not found:
        return name[:120]
    return f"{found['name']} {found['type'].lstrip('(')}"[:120]


def reduce(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(data) -> Trace:
    """``data``: a ``jax.profiler.ProfileData``."""
    trace = Trace()
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        is_host = plane.name.startswith("/host:") \
            and plane.name != "/host:metadata"
        if not (is_device or is_host):
            continue
        lines = list(plane.lines)
        if is_device:
            named_ops = [ln for ln in lines if ln.name == "XLA Ops"]
            op_lines = named_ops or [ln for ln in lines
                                     if ln.name not in NOT_OP_LINES]
            ops = trace.ops.setdefault(plane.name, [])
            for ln in op_lines:
                for ev in ln.events:
                    if ev.duration_ns > 0:
                        ops.append((ev.start_ns * 1e-9,
                                    (ev.start_ns + ev.duration_ns) * 1e-9,
                                    hlo_label(ev.name)))
            ops.sort()
            mods = trace.modules.setdefault(plane.name, [])
            for ln in lines:
                if ln.name == "XLA Modules":
                    mods.extend(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                        for ev in ln.events if ev.duration_ns > 0)
            mods.sort()
        else:
            for ln in lines:
                for ev in ln.events:
                    if ev.duration_ns > 0 \
                            and not RUNTIME_NOISE.search(ev.name):
                        trace.spans.append(
                            (ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9,
                             ev.name))
    trace.spans.sort()
    return trace


# ---------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------

def union(intervals) -> list[Interval]:
    """Merge overlapping (start, end) pairs."""
    out: list[list[float]] = []
    for start, end in sorted((i[0], i[1]) for i in intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes) -> list[Interval]:
    """``union(intervals)`` minus ``union(holes)``, in one sweep."""
    out, holes, h = [], union(holes), 0
    for a, b in union(intervals):
        while h < len(holes) and holes[h][1] <= a:
            h += 1
        cur, k = a, h
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append((cur, holes[k][0]))
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def covered(merged: list[Interval], lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` that the sorted, disjoint ``merged``
    intervals cover."""
    i = bisect.bisect_left(merged, (lo, lo))
    if i and merged[i - 1][1] > lo:
        i -= 1
    out = 0.0
    while i < len(merged) and merged[i][0] < hi:
        out += max(min(merged[i][1], hi) - max(merged[i][0], lo), 0.0)
        i += 1
    return out


def window_of(trace: Trace) -> Interval:
    """The traced window: first to last event, host spans and device
    ops alike (the host is recorded from start to stop of the trace,
    so an idle device at either edge still counts as idle)."""
    starts = [ops[0][0] for ops in trace.ops.values() if ops]
    ends = [max(e for _, e, _ in ops) for ops in trace.ops.values() if ops]
    if not starts:
        raise ValueError("the trace holds no device operation")
    if trace.spans:
        starts.append(trace.spans[0][0])
        ends.append(max(e for _, e, _ in trace.spans))
    return min(starts), max(ends)


def busy_seconds(trace: Trace, lo: float | None = None,
                 hi: float | None = None) -> float:
    """Seconds in which an op ran, averaged over the traced devices."""
    if lo is None or hi is None:
        lo, hi = window_of(trace)
    per = [covered(union(ops), lo, hi) for ops in trace.ops.values()]
    return sum(per) / len(per)


def span_seconds(trace: Trace, span_name: str) -> list[float]:
    return [b - a for a, b, name in trace.spans if name == span_name]


def module_seconds(trace: Trace, pattern: str) -> list[float]:
    """Device time of each run of the programs whose module name
    matches ``pattern`` (first device)."""
    rx = re.compile(pattern)
    mods = trace.modules.get(trace.devices[0], [])
    return [b - a for a, b, name in mods if rx.search(name)]


def module_summary(trace: Trace) -> list[dict]:
    """Per program on the first device: runs, median and total device
    seconds — for the run's log, so a reader can check which program a
    per-layer metric read."""
    import statistics

    by_name: dict[str, list[float]] = defaultdict(list)
    for a, b, name in trace.modules.get(trace.devices[0], []):
        by_name[name].append(b - a)
    return [{"module": k, "runs": len(v), "median_s": statistics.median(v),
             "total_s": sum(v)}
            for k, v in sorted(by_name.items(), key=lambda kv: -sum(kv[1]))]


def exposed_collective_seconds(trace: Trace) -> float:
    """Seconds in collective ops during which no other op ran on that
    device, averaged over devices. A container op (a scan's ``while``)
    spans its children, collectives among them, and is no work of its
    own: it hides nothing."""
    per = []
    for ops in trace.ops.values():
        coll = [(a, b) for a, b, n in ops if COLLECTIVE.search(n)]
        rest = [(a, b) for a, b, n in ops
                if not COLLECTIVE.search(n) and not CONTAINER_OP.match(n)]
        per.append(total(subtract(coll, rest)))
    return sum(per) / len(per) if per else 0.0


def idle_gaps(trace: Trace, min_gap: float = 50e-6,
              look_back: int = 400) -> list[tuple[float, float, str]]:
    """The idle intervals of the first device inside the traced
    window, each named after the innermost host span that covers its
    middle (``unattributed`` where none does). Gaps under ``min_gap``
    are the seams between consecutive ops and go under one name."""
    lo, hi = window_of(trace)
    first = trace.devices[0]
    spans = [s for s in trace.spans if s[1] - s[0] >= min_gap]
    starts = [s[0] for s in spans]
    out = []
    for a, b in subtract([(lo, hi)], trace.ops[first]):
        if b - a < min_gap:
            out.append((a, b, "between_ops"))
            continue
        mid, best = (a + b) / 2, None
        idx = bisect.bisect_right(starts, mid)
        for sa, sb, name in spans[max(idx - look_back, 0):idx]:
            if sb >= mid and (best is None or sb - sa < best[0]):
                best = (sb - sa, name)
        out.append((a, b, best[1] if best else "unattributed"))
    return out


def breakdown(trace: Trace, top: int = 10, min_gap: float = 50e-6,
              device_ops: list | None = None) -> dict:
    """The ops that took most device time and the host activities under
    the longest idle time, ``[[name, seconds], ...]``. ``device_ops``:
    the same ranking made where the ops' name stacks can be read
    (``xplane_scopes.top_ops``: program, scope and the end of ``tf_op``
    beside the compiler's name); taken where it is given and not
    empty, else the ops are ranked here by the compiler's names."""
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    if not device_ops:
        by_op: dict[str, float] = defaultdict(float)
        for a, b, name in trace.ops[trace.devices[0]]:
            if not CONTAINER_OP.match(name):
                by_op[name] += b - a
        device_ops = rank(by_op)
    by_gap: dict[str, float] = defaultdict(float)
    for a, b, name in idle_gaps(trace, min_gap):
        by_gap[name] += b - a
    return {"device_ops": device_ops[:top], "idle_gaps": rank(by_gap)}


def describe(path: str, limit: int = 6) -> str:
    """Planes, lines and a few events with their stats: for a human
    looking at a trace by hand before trusting the reduction."""
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(path).planes:
        rows.append(f"PLANE {plane.name}")
        for ln in plane.lines:
            events = list(ln.events)
            rows.append(f"  LINE {ln.name!r} events={len(events)}")
            for ev in events[:limit]:
                try:
                    stats = dict(ev.stats)
                except (TypeError, ValueError):
                    stats = {}
                rows.append(f"    {ev.name!r} start={ev.start_ns:.0f} "
                            f"dur={ev.duration_ns:.0f} {stats}")
    return "\n".join(rows)
