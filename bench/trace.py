"""Reduction from a JAX profiler trace (``.xplane.pb``) to device metrics.

A TPU trace holds one plane per chip, ``/device:TPU:<n>``, whose lines
``XLA Modules`` (one event per program run, named ``jit_<fn>(<id>)``) and
``XLA Ops`` / ``Async XLA Ops`` (one event per HLO instruction, named by
the instruction's text, ``%sort.6 = (s32[..]) sort(...)``) carry start
and duration in nanoseconds.  The host plane ``/host:CPU`` carries the
benchmark's own ``TraceAnnotation`` spans on the same clock.

Everything here works on plain ``Event`` tuples, so a test can feed it a
recorded trace or events it built itself.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINES = ("XLA Ops", "Async XLA Ops")
#: ops that only hold others (a loop and its body): their span covers the
#: gaps between the ops they hold, so they count neither as busy time nor
#: in the breakdown
CONTAINERS = ("while", "conditional", "call")
HOST_PLANE = "/host:CPU"
#: prefix of the benchmark's own host spans (submit / wait / release)
SPAN_PREFIX = "bench."


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """Device and host events of one profiler session."""

    ops: dict[int, list[Event]] = field(default_factory=dict)
    modules: dict[int, list[Event]] = field(default_factory=dict)
    spans: list[Event] = field(default_factory=list)

    @property
    def devices(self) -> list[int]:
        return sorted(set(self.ops) | set(self.modules))


def load(path) -> Trace:
    """Read one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    trace = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            evs = [Event(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            if m and line.name in OP_LINES:
                trace.ops.setdefault(int(m.group(1)), []).extend(
                    e for e in evs if opcode(e.name) not in CONTAINERS)
            elif m and line.name == MODULE_LINE:
                trace.modules.setdefault(int(m.group(1)), []).extend(evs)
            elif plane.name == HOST_PLANE:
                trace.spans.extend(e for e in evs
                                   if e.name.startswith(SPAN_PREFIX))
    for evs in (*trace.ops.values(), *trace.modules.values()):
        evs.sort(key=lambda e: e.start_ns)
    trace.spans.sort(key=lambda e: e.start_ns)
    return trace


# ------------------------------------------------------------------ names


def op_name(text: str) -> str:
    """HLO instruction name without its numeric suffix:
    ``%add_select_fusion.2 = ...`` -> ``add_select_fusion``."""
    name = text.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"(\.\d+)+$", "", name)


def opcode(text: str) -> str:
    """HLO opcode of an instruction's text: ``%x.3 = s32[4]{0:T(1024)}
    all-gather(...)`` -> ``all-gather``.  The shape may be a tuple and may
    hold parentheses of its own, so it is skipped by nesting depth."""
    if " = " not in text:
        return op_name(text)
    rest = text.split(" = ", 1)[1]
    depth, i = 0, 0
    while i < len(rest):
        c = rest[i]
        if c in "({[":
            depth += 1
        elif c in ")}]":
            depth -= 1
        elif c == " " and depth == 0:
            break
        i += 1
    return rest[i:].strip().split("(", 1)[0].strip()


def module_name(text: str) -> str:
    """``jit_job(2775352640231577865)`` -> ``jit_job``."""
    return text.split("(", 1)[0]


# ---------------------------------------------------------------- reduction


def union(events) -> list[tuple[float, float]]:
    """Merged, ordered ``(start, end)`` intervals covered by the events."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if out and e.start_ns <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end_ns)
        else:
            out.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in out]


def busy_ns(trace: Trace, device: int) -> float:
    return sum(b - a for a, b in union(trace.ops.get(device, [])))


def mean_busy_s(trace: Trace, devices) -> float:
    """Seconds in which some operation ran, averaged over ``devices``."""
    return sum(busy_ns(trace, d) for d in devices) / len(devices) / 1e9


def op_seconds(trace: Trace, device: int) -> dict[str, float]:
    """Device seconds per op name (numeric suffix stripped) on one device."""
    tot: dict[str, float] = defaultdict(float)
    for e in trace.ops.get(device, []):
        tot[op_name(e.name)] += e.dur_ns / 1e9
    return dict(tot)


def module_runs(trace: Trace, name: str) -> list[float]:
    """Durations in seconds of every run of the program ``name`` (the
    jitted function's name, e.g. ``job``) on the first device that
    ran it."""
    for d in trace.devices:
        runs = [e.dur_ns / 1e9 for e in trace.modules.get(d, [])
                if module_name(e.name) == f"jit_{name}"]
        if runs:
            return runs
    return []


def top_ops(trace: Trace, device: int, n: int = 10) -> list[list]:
    """The ``n`` op groups that took the most device time."""
    tot = op_seconds(trace, device)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, device: int, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of one device inside the span the
    benchmark's host annotations cover, each named by the host span that
    covers its midpoint (``bench.wait``, ``bench.release``, ...) or
    ``host`` where none does."""
    if not trace.spans:
        return []
    lo = trace.spans[0].start_ns
    hi = max(e.end_ns for e in trace.spans)
    gaps, cursor = [], lo
    for a, b in union(trace.ops.get(device, [])):
        if a > cursor:
            gaps.append((cursor, min(a, hi)))
        cursor = max(cursor, b)
    if hi > cursor:
        gaps.append((cursor, hi))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        label = next((s.name for s in trace.spans
                      if s.start_ns <= mid <= s.end_ns), "host")
        out.append([label, (b - a) / 1e9])
    return out
