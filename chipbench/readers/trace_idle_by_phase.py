"""The chip's idle time inside one host phase of one plane, per tick of the
plane, ms: what the chip did not do while the host was in that phase.

Idle is what ``trace_idle_pct`` counts: per chip, the time from its first
op's start to its last op's end in which no ``XLA Ops`` event ran.  The host
phases are the program's own spans on the trace's clock: the phase clock's
annotations ``gptpu/<driver>/<plane>/<phase>`` and, inside them, the parts
``gptpu/<driver>/<plane>/<phase>/<part>`` (``gigapaxos_tpu/obs/phase.py``,
loaded by ``rawtrace.py``).  The value is the idle time that lies inside the
plane's ``phase`` annotations, summed over the slice, divided by the number
of those annotations (one per tick), averaged over the chips.

On stderr, per call: the same split for every phase and part of every plane
the trace holds (the two planes' spans overlap, so the planes are split
apart; the parts lie inside their phase), and the share of all idle time
that no program span covers.  When one plane's phases cover a chip's span,
its phases' idle times add up to that chip's idle time.

Nothing where the harness gave the run no trace (the CPU rehearsal), where
this process's raw trace is not found, or where the trace holds no launch
annotation (a program from before the parts of ``dispatch``, whose traced
runs then print none of the metrics this family of readers serves).
"""

from __future__ import annotations

import sys

import numpy as np

from .. import rawtrace
from .trace_plane_runs import launches

PREFIX = "gptpu/modea/"


def _merge(intervals: list) -> list:
    """The union of ``intervals`` as sorted, disjoint [start, end) pairs."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def idle_gaps(ops: list) -> tuple:
    """(starts, ends) of the intervals inside the span of ``ops`` ((name,
    scope, start, dur) events) in which none of them ran, sorted."""
    if not ops:
        return np.zeros(0), np.zeros(0)
    s = np.array([e[2] for e in ops], dtype=np.float64)
    order = np.argsort(s, kind="stable")
    s = s[order]
    e = s + np.array([ops[k][3] for k in order], dtype=np.float64)
    reach = np.maximum.accumulate(e)  # the latest end of the ops so far
    gap = s[1:] > reach[:-1]
    return reach[:-1][gap], s[1:][gap]


def _idle_before(starts, ends):
    """t -> the idle time before t, for sorted disjoint gaps."""
    before = np.concatenate(([0.0], np.cumsum(ends - starts)))

    def at(t):
        i = np.searchsorted(starts, t, side="right") - 1
        inside = np.minimum(t, ends[np.maximum(i, 0)]) - starts[
            np.maximum(i, 0)]
        return np.where(i >= 0, before[np.maximum(i, 0)] + inside, 0.0)

    return at


def overlap(gaps: tuple, spans: list) -> float:
    """Idle time inside the union of ``spans`` ([start, end) pairs)."""
    merged = np.array(_merge(spans), dtype=np.float64).reshape(-1, 2)
    if not len(gaps[0]) or not len(merged):
        return 0.0
    at = _idle_before(*gaps)
    return float(np.sum(at(merged[:, 1]) - at(merged[:, 0])))


def split(raw) -> tuple:
    """({span name: idle ns inside it, averaged over chips}, {span name:
    number of spans}, idle ns averaged over chips, idle ns no span covers,
    averaged over chips).  A span name is an annotation's name without
    ``PREFIX``: ``<plane>/<phase>`` or ``<plane>/<phase>/<part>``."""
    spans: dict = {}
    for name, s, d in raw.host:
        if name.startswith(PREFIX):
            spans.setdefault(name[len(PREFIX):], []).append((s, s + d))
    every = [iv for v in spans.values() for iv in v]
    chips = [ops for ops in raw.ops.values() if ops]
    inside: dict = {k: 0.0 for k in spans}
    idle = uncovered = 0.0
    for ops in chips:
        gaps = idle_gaps(ops)
        chip_idle = float(np.sum(gaps[1] - gaps[0]))
        idle += chip_idle
        uncovered += chip_idle - overlap(gaps, every)
        for k, ivs in spans.items():
            inside[k] += overlap(gaps, ivs)
    n = max(len(chips), 1)
    return ({k: v / n for k, v in inside.items()},
            {k: len(v) for k, v in spans.items()}, idle / n, uncovered / n)


def read(run, plane: str, phase: str):
    if run.trace is None:
        return None
    raw = rawtrace.of_this_run()
    if raw is None or not launches(raw):
        return None
    inside, counts, idle, uncovered = split(raw)
    key = f"{plane}/{phase}"
    if not counts.get(key):
        return None
    rows = ", ".join(f"{k} {v / 1e6:.3f} ms over {counts[k]} "
                     f"({v / 1e6 / counts[k]:.3f} each)"
                     for k, v in sorted(inside.items()))
    print(f"trace_idle_by_phase: chip idle {idle / 1e6:.3f} ms in the slice, "
          f"{100.0 * uncovered / idle if idle else 0.0:.1f}% of it under no "
          f"program span; inside the spans: {rows}",
          file=sys.stderr, flush=True)
    return inside[key] / 1e6 / counts[key]
