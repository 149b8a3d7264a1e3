"""From a profiler trace to numbers: the reduction every PR is measured by.

``jax.profiler`` writes an ``.xplane.pb``; ``load_xplane`` keeps of it what
the metrics read, as plain lists (``Trace``), and ``Trace.to_json`` /
``from_json`` store that form, so the reductions below are tested on a small
recorded trace (``tests/data/``) without a chip.

What a v5e trace holds (looked at by hand, PR 24): a plane ``/device:TPU:<n>``
per chip with the lines ``XLA Modules`` (one event per execution of a jitted
program, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per
HLO op, named by its HLO text, a Pallas kernel as ``%<kernel_name>.<n> =
...custom-call(...)``), and a plane ``/host:CPU`` with a line per thread, on
the same clock.  Times are nanoseconds from the start of the trace.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"
SYNC_MARK = "chipbench_clock_sync"
UNATTRIBUTED = "host:unattributed"


@dataclasses.dataclass
class Trace:
    """``devices``: plane name -> {line name -> [(name, start_ns, dur_ns)]}
    for the two lines above, events in start order.  ``sync_ns``: where the
    host's ``SYNC_MARK`` annotation starts on the trace's clock, if found."""

    devices: dict
    sync_ns: float | None = None

    def line(self, which: str) -> list:
        """Events of one line over all devices."""
        return [e for d in self.devices.values() for e in d.get(which, ())]

    def to_json(self, path: str, name_chars: int | None = None) -> None:
        names: dict = {}
        out: dict = {"sync_ns": self.sync_ns, "devices": {}}
        for plane, lines in self.devices.items():
            out["devices"][plane] = {
                which: [[names.setdefault(n[:name_chars], len(names)), s, d]
                        for n, s, d in evs]
                for which, evs in lines.items()}
        out["names"] = list(names)
        with gzip.open(path, "wt") as f:
            json.dump(out, f, separators=(",", ":"))

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            raw = json.load(f)
        names = raw["names"]
        return cls({plane: {which: [(names[i], s, d) for i, s, d in evs]
                            for which, evs in lines.items()}
                    for plane, lines in raw["devices"].items()},
                   raw.get("sync_ns"))

    def cut(self, start_ns: float, end_ns: float) -> "Trace":
        """The events that lie wholly inside [start_ns, end_ns)."""
        return Trace({plane: {which: [e for e in evs if e[1] >= start_ns
                                      and e[1] + e[2] <= end_ns]
                              for which, evs in lines.items()}
                      for plane, lines in self.devices.items()},
                     self.sync_ns)


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"the profiler left no xplane under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict = {}
    sync_ns = None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    lines[line.name] = sorted(
                        ((e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events), key=lambda e: e[1])
        elif plane.name == "/host:CPU" and sync_ns is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == SYNC_MARK:
                        sync_ns = float(e.start_ns)
                        break
    return Trace(devices, sync_ns)


# --------------------------------------------------------------- reductions
def union_ns(events: list) -> float:
    """Total length of the union of the events' intervals."""
    total, end = 0.0, -1.0
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if s > end:
            total += d
            end = s + d
        elif s + d > end:
            total += s + d - end
            end = s + d
    return total


def span_ns(events: list) -> tuple:
    """(first start, last end) of the events."""
    return (min(e[1] for e in events), max(e[1] + e[2] for e in events))


def busy_and_window_s(trace: Trace) -> tuple:
    """Seconds in which an op ran on the device, averaged over the devices,
    and the traced span (first op start to last op end, the longest over the
    devices).  (0, 0) when no device op is in the trace."""
    busy, spans = [], []
    for lines in trace.devices.values():
        ops = lines.get(OPS) or ()
        if ops:
            busy.append(union_ns(ops))
            lo, hi = span_ns(ops)
            spans.append(hi - lo)
    if not busy:
        return 0.0, 0.0
    return sum(busy) / len(busy) / 1e9, max(spans) / 1e9


def whole_executions(trace: Trace, pattern: str) -> list:
    """Durations (ns) of the executions of the programs whose module name
    matches ``pattern``, leaving out, per device, the first and last event of
    the line: the trace's edges cut those short."""
    rx = re.compile(pattern)
    out = []
    for lines in trace.devices.values():
        mods = lines.get(MODULES) or ()
        out += [d for n, _, d in mods[1:-1] if rx.search(n)]
    return out


def op_seconds(trace: Trace, pattern: str) -> tuple:
    """(events, summed device seconds) of the ops whose name matches."""
    rx = re.compile(pattern)
    hits = [d for n, _, d in trace.line(OPS) if rx.search(n)]
    return len(hits), sum(hits) / 1e9


_SHAPE = re.compile(
    r"\b(pred|[suf]8|[suf]16|bf16|[suf]32|[suf]64)\[([\d,]*)\](\{[^}]*\})?")


def hlo_io_bytes(op_text: str, hbm_only: bool = False):
    """Bytes of the result and the operands of one HLO instruction, from its
    text as the trace prints it (``%name = <result shapes> opcode(<operand
    shapes and names>), ...``): what a kernel that reads each operand once
    and writes its result once moves.  ``hbm_only`` leaves out every buffer
    whose layout carries a memory-space annotation (``S(1)``: the compiler
    placed it in on-chip memory, so it does not cross HBM).  None where the
    text does not parse (cut short, or not an instruction)."""
    _, eq, rest = op_text.partition(" = ")
    m = re.search(r"\s([\w\-]+)\(", rest) if eq else None
    if m is None:
        return None
    # the operand list is the first parenthesis after the opcode (a tuple
    # result's own parentheses come before it, with no word in front)
    depth, close_at = 0, None
    for i in range(m.end() - 1, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                close_at = i
                break
    if close_at is None:
        return None
    total, seen = 0, False
    for dtype, dims, layout in _SHAPE.findall(rest[:close_at]):
        seen = True
        if hbm_only and "S(" in layout:
            continue
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * int(re.sub(r"\D", "", dtype) or 8) // 8  # pred: a byte
    return total if seen else None


def op_bytes_and_seconds(trace: Trace, pattern: str,
                         hbm_only: bool = False) -> tuple:
    """(events, bytes moved, summed device seconds) of the ops whose name
    matches, counting only events whose shapes parse."""
    rx = re.compile(pattern)
    n, moved, secs = 0, 0, 0.0
    for name, _, d in trace.line(OPS):
        if rx.search(name):
            b = hlo_io_bytes(name, hbm_only)
            if b is not None:
                n, moved, secs = n + 1, moved + b, secs + d
    return n, moved, secs / 1e9


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[name, seconds]] of the ops with most summed device time."""
    by_name: dict = {}
    for name, _, d in trace.line(OPS):
        by_name[name] = by_name.get(name, 0.0) + d
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:200], secs / 1e9] for name, secs in top]


def idle_gaps(trace: Trace, n: int = 10, label=None) -> list:
    """[[label, seconds]] of the longest intervals in which no op ran on the
    first device, inside its traced span.  ``label(start_ns, end_ns)`` names
    what the host was doing; without it every gap is ``UNATTRIBUTED``."""
    gaps = []
    for _, lines in sorted(trace.devices.items())[:1]:
        end = None
        for _, s, d in lines.get(OPS) or ():
            if end is not None and s > end:
                gaps.append((s - end, end, s))
            end = s + d if end is None else max(end, s + d)
    gaps.sort(reverse=True)
    return [[label(a, b) if label else UNATTRIBUTED, g / 1e9]
            for g, a, b in gaps[:n]]


# -------------------------------------------------- host phases on the trace
class PhaseRecorder:
    """Stands in for a manager's ``PhaseClock`` during a traced run: passes
    every call through and keeps (phase, start, end) on ``perf_counter_ns``,
    so that a device-idle gap can be named by the host phase open in it.
    The program has no spans of its own yet (ROADMAP A1)."""

    def __init__(self, inner, plane: str):
        self._inner = inner
        self.plane = plane
        self.spans: list = []
        self._t = time.perf_counter_ns()

    def begin(self) -> None:
        self._inner.begin()
        self._t = time.perf_counter_ns()

    def touch(self) -> None:
        self._inner.touch()
        self._t = time.perf_counter_ns()

    def mark(self, phase: str) -> None:
        self._inner.mark(phase)
        now = time.perf_counter_ns()
        self.spans.append((phase, self._t, now))
        self._t = now

    def end(self) -> None:
        self._inner.end()

    def __getattr__(self, name):
        return getattr(self._inner, name)


def phase_labeller(recorders: list, sync_perf_ns: int, sync_trace_ns: float):
    """``label(start_ns, end_ns)`` for ``idle_gaps``: per plane the phase that
    overlaps the gap most, as ``host:<plane>.<phase>+<plane>.<phase>``."""
    shift = sync_trace_ns - sync_perf_ns
    planes = [(r.plane, [(p, a + shift, b + shift) for p, a, b in r.spans])
              for r in recorders]

    def label(start_ns: float, end_ns: float) -> str:
        parts = []
        for plane, spans in planes:
            best, best_overlap = None, 0.0
            for phase, a, b in spans:
                overlap = min(b, end_ns) - max(a, start_ns)
                if overlap > best_overlap:
                    best, best_overlap = phase, overlap
            if best is not None:
                parts.append(f"{plane}.{best}")
        return "host:" + "+".join(parts) if parts else UNATTRIBUTED

    return label
