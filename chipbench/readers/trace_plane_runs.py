"""Each run of the tick program on the chip, put down to the plane that
launched it: the wait of a plane's runs behind the chip's queue, ms
(``what="queue"``), or a plane's share of the chip's busy time, %
(``what="share"``).

Both planes of a Mode A node run the same program, so the trace's ``XLA
Modules`` line cannot say whose run is whose.  The host can: the phase
clock (``gigapaxos_tpu/obs/phase.py``) opens the annotation
``gptpu/modea/<plane>/dispatch/launch`` around the call that enqueues a
tick's program(s), on the trace's clock (``rawtrace.py``), and a chip runs
what it is given in the order it was given.  So, per chip, the runs of the
tick program (``TICK``; on four chips the sharded tick, the compaction run
behind it is not matched) are paired in time order, one for one, with the
launches of both planes.  When inside its launch a program was enqueued the
trace does not show, so launches of the two planes that overlap in time may
have enqueued in either order (on v5e chips both were seen, one and four
chips alike): every order of such a group that keeps each plane's own order
is tried against the checks below.  Where more than one fits, the group's
runs are marked as not known to be whose (``_assign`` says why the share
may still count them).

The pairing checks itself against what the host saw, and gives nothing,
with the reason on stderr, where a check fails; it never guesses:

* a run starts after its launch started (``SLACK_NS`` says how far the
  two clocks may disagree);
* a run ends before the completion that waited for it ended: a completion
  is a ``gptpu/modea/<plane>/tally`` annotation (one per completed tick;
  ``_complete_tick``), in the call that launched the tick.  A call that held
  its outbox for the next call would break that, so a window in which a
  plane held one (``tick_completions_total{mode=held}``, none in the
  benchmark's cells) is not paired at all;
* a launch whose completion the trace holds has its run in the trace, and no
  run is left without a launch;
* runs that start before the slice's first launch belong to no launch and
  are dropped; up to ``MAX_BEFORE`` more, launched before the slice and
  still queued at its first launch, may lead the rest.  Exactly one such
  count may pass the checks above.

``what="queue"``: the mean over the plane's runs whose launch is known of
(run start - end of its launch), a run that started before its launch
returned counting 0: what the
program waited behind on the chip (the other plane's program, the inbox
list's scatter, a frontier gather).  ``what="share"``: the device time of
the ops inside the plane's runs over the device time of all ops from the
first paired run's start to the last one's end, per chip, averaged over the
chips.  One line per call goes to stderr.

Nothing where the harness gave the run no trace (the CPU rehearsal), where
this process's raw trace is not found, or where the trace holds no launch
annotation (a program from before the parts of ``dispatch``).
"""

from __future__ import annotations

import bisect
import itertools
import re
import sys

from .. import rawtrace, tracing

TICK = r"^jit__?paxos_tick|^jit_mesh_paxos_tick"
LAUNCH = re.compile(r"^gptpu/modea/([^/]+)/dispatch/launch$")
TALLY = re.compile(r"^gptpu/modea/([^/]+)/tally$")
#: runs of launches from before the slice that may lead the slice's own: a
#: plane has at most two ticks in flight (one held, one dispatched)
MAX_BEFORE = 4
#: how far a run may start before its launch, or end after the completion
#: that waited for it: the profiler aligns the device's clock to the host's,
#: and on a v5e runs were seen to start up to 0.8 ms before the launch that
#: enqueued them opened.  Two ticks' runs lie tens of ms apart
SLACK_NS = 2e6
#: how much further before its launch than any run of a launch that
#: overlapped nothing a run of overlapping launches may start
MARGIN_NS = 250e3
#: the window's counters of outboxes a plane held for its next call
HELD = "tick_completions_total{mode=held,"


class Unpaired(Exception):
    """The pairing rule does not hold on this trace."""


def launches(raw) -> list:
    """[(plane, start_ns, end_ns)] of every launch annotation, in the order
    the launches began."""
    out = []
    for name, s, d in raw.host:
        m = LAUNCH.match(name)
        if m:
            out.append((m.group(1), s, s + d))
    return sorted(out, key=lambda e: e[1])


def held(run) -> dict | None:
    """plane -> outboxes the plane held for a later call in the window, from
    the harness's registry snapshots; None where they do not say."""
    snap0, snap1 = getattr(run, "snap0", None), getattr(run, "snap1", None)
    if not snap0 or not snap1:
        return None
    return {key.rpartition("plane=")[2].rstrip("}"): v - snap0.get(key, 0)
            for key, v in snap1.items() if key.startswith(HELD)}


def completion_ends(raw, ls: list) -> list:
    """For each launch of ``ls``, the end of the completion that waited for
    its program, or None where the trace ends before it.  A plane that holds
    no outbox completes each tick in the call that launched it, with one
    ``tally`` annotation between the launch and the plane's next launch;
    a call with another count means the rule does not hold."""
    tallies: dict = {}
    for name, s, d in raw.host:
        m = TALLY.match(name)
        if m:
            tallies.setdefault(m.group(1), []).append((s, s + d))
    calls: dict = {}
    for i, (plane, s, e) in enumerate(ls):
        calls.setdefault(plane, []).append((s, e, i))
    out: list = [None] * len(ls)
    for plane, cs in calls.items():
        cs.sort()
        ts = tallies.get(plane, [])
        for k, (s, e, i) in enumerate(cs):
            last = k + 1 == len(cs)
            until = float("inf") if last else cs[k + 1][0]
            own = [t_end for t_s, t_end in ts if e <= t_s < until]
            if len(own) > 1 or not (own or last):
                raise Unpaired(f"a {plane} call at {s:.0f} ns completed "
                               f"{len(own)} ticks")
            out[i] = own[0] if own else None
    return out


def _why_not(run, launch: tuple, end, early: float = SLACK_NS) -> str | None:
    """Why ``run`` ((start, dur), or None: not in the trace) cannot be the
    run of ``launch`` whose completion ended at ``end``, or None; a run may
    start ``early`` ns before its launch's start on the trace."""
    plane, l_start, _ = launch
    if run is None:
        return None if end is None else (
            f"the {plane} launch at {l_start:.0f} ns was completed inside the "
            "trace and its run is not in it")
    s, d = run
    if s + early < l_start:
        return f"a run at {s:.0f} ns starts before its {plane} launch"
    if end is not None and s + d > end + SLACK_NS:
        return (f"a run at {s:.0f} ns ends after the {plane} completion that "
                "waited for it")
    return None


def _overlapping(ls: list) -> list:
    """The launches (indices, in the order they began) cut into groups of
    launches that overlap in time, one after another: inside a group the
    trace does not say in which order they enqueued."""
    groups: list = []
    until = float("-inf")
    for i, (_, s, e) in enumerate(ls):
        if groups and s < until:
            groups[-1].append(i)
            until = max(until, e)
        else:
            groups.append([i])
            until = e
    return groups


def _orders(group: list, ls: list):
    """Every order of ``group`` that keeps each plane's own launches in
    theirs (one thread launches a plane's ticks one after another)."""
    for perm in itertools.permutations(group):
        by_plane: dict = {}
        for i in perm:
            by_plane.setdefault(ls[i][0], []).append(i)
        if all(v == sorted(v) for v in by_plane.values()):
            yield perm


def _assign(runs: list, ls: list, ends: list) -> tuple:
    """(the launch, an index into ``ls``, of each run in run order; whether
    that launch is known) or raises ``Unpaired`` where no order of some
    group of overlapping launches fits.  Inside such a group a run may start
    before its launch by no more than the runs of the launches that
    overlapped nothing did (the two clocks' offset in this trace), less
    ``MARGIN_NS``.  Where several orders of a group fit, its runs are given
    in the order its launches began, as far as that fits, and marked not
    known: they are the same program at the same shapes, one per launch, so
    which is whose moves no plane's share of the chip beyond the difference
    of two runs' times, but it does move a run's wait."""
    if len(runs) > len(ls):
        raise Unpaired(f"{len(runs) - len(ls)} run(s) with no launch")
    groups = _overlapping(ls)
    at = [0]
    for g in groups:
        at.append(at[-1] + len(g))
    lead = [ls[g[0]][1] - runs[pos][0] for g, pos in zip(groups, at)
            if len(g) == 1 and pos < len(runs)]
    early = min(max(lead, default=SLACK_NS) + MARGIN_NS, SLACK_NS)
    order: list = []
    known: list = []
    for group, pos in zip(groups, at):
        fits, why = set(), ""
        for perm in _orders(group, ls):
            slots = [runs[pos + k] if pos + k < len(runs) else None
                     for k in range(len(perm))]
            reasons = [_why_not(r, ls[i], ends[i],
                                SLACK_NS if len(group) == 1 else early)
                       for r, i in zip(slots, perm)]
            reason = next((r for r in reasons if r), None)
            if reason is None:
                fits.add(tuple(i for r, i in zip(slots, perm)
                               if r is not None))
            else:
                why = why or reason
        if not fits:
            raise Unpaired(why)
        order += list(min(fits))   # the order they began, where it fits
        order += [i for i in group if i not in order]
        known += [len(fits) == 1] * len(group)
    return order[:len(runs)], known[:len(runs)]


def pair(raw) -> dict:
    """device plane -> [(launch plane, launch start, launch end, run start,
    run duration, whether the launch is known)] in run order; raises
    ``Unpaired`` where the rule does not hold (module docstring)."""
    ls = launches(raw)
    if not ls:
        raise Unpaired("no launch annotation in the trace")
    ends = completion_ends(raw, ls)
    first = ls[0][1]
    rx = re.compile(TICK)
    out = {}
    for dev, mods in sorted(raw.modules.items()):
        runs = [(s, d) for name, s, d in mods
                if rx.search(name) and s + SLACK_NS >= first]
        if not runs:
            continue
        fits, why = {}, []
        for before in range(min(MAX_BEFORE, len(runs)) + 1):
            try:
                fits[before] = _assign(runs[before:], ls, ends)
            except Unpaired as e:
                why.append(f"{before} before the slice: {e}")
        if len(fits) != 1:
            raise Unpaired(f"{dev}: " + (
                f"{len(fits)} pairings fit ({sorted(fits)} runs before the "
                "slice)" if fits else "; ".join(why)))
        (before, (order, known)), = fits.items()
        out[dev] = [(*ls[i], s, d, k) for i, (s, d), k in zip(
            order, runs[before:], known)]
    if not out:
        raise Unpaired("no run of the tick program after the first launch")
    return out


def shares(raw, pairs: dict) -> dict:
    """plane -> its share of the chip's busy time, %, averaged over chips
    (module docstring)."""
    per_chip: dict = {}
    for dev, ps in pairs.items():
        lo = ps[0][3]
        hi = max(s + d for *_, s, d, _ in ps)
        ops = [(n, s, d) for n, _, s, d in raw.ops.get(dev, ())
               if lo <= s < hi]
        busy = tracing.union_ns(ops)
        if busy <= 0:
            continue
        for plane in {p[0] for p in ps}:
            runs = sorted((s, s + d) for p, _, _, s, d, _ in ps
                          if p == plane)
            starts = [s for s, _ in runs]
            inside = [op for op in ops
                      if (j := bisect.bisect_right(starts, op[1]) - 1) >= 0
                      and op[1] < runs[j][1]]
            per_chip.setdefault(plane, []).append(
                100.0 * tracing.union_ns(inside) / busy)
    return {p: sum(v) / len(v) for p, v in per_chip.items()}


def read(run, what: str, plane: str):
    if run.trace is None:
        return None
    raw = rawtrace.of_this_run()
    if raw is None:
        return None
    try:
        kept = held(run)
        if kept is None or any(kept.values()):
            raise Unpaired(f"outboxes held for a later call: {kept}")
        pairs = pair(raw)
    except Unpaired as e:
        print(f"trace_plane_runs: no pairing: {e}", file=sys.stderr,
              flush=True)
        return None
    mine = [p for ps in pairs.values() for p in ps if p[0] == plane]
    n_runs = sum(len(ps) for ps in pairs.values())
    ls = launches(raw)
    overlaps = sum(1 for a, b in zip(ls, ls[1:])
                   if a[0] != b[0] and b[1] < a[2])
    if what == "queue":
        # a run of a group whose order the checks left open has no wait
        waits = [(s - l_end) / 1e6 for _, _, l_end, s, _, k in mine if k]
        if not waits:
            return None
        value = sum(max(0.0, w) for w in waits) / len(waits)
        print(f"trace_plane_runs: {n_runs} runs paired on {len(pairs)} "
              f"chip(s), {len(mine)} of {plane}, {len(waits)} of them to a "
              f"known launch; queue {value:.3f} ms (unclipped "
              f"{sum(waits) / len(waits):.3f} ms, "
              f"{sum(w < 0 for w in waits)} started before the launch "
              f"returned); {overlaps} launch(es) overlapped the other "
              "plane's", file=sys.stderr, flush=True)
        return value
    if what == "share":
        by_plane = shares(raw, pairs)
        print("trace_plane_runs: share of the chip's busy time, "
              + ", ".join(f"{p} {v:.2f}%" for p, v in sorted(
                  by_plane.items()))
              + f" ({n_runs} runs paired on {len(pairs)} chip(s))",
              file=sys.stderr, flush=True)
        # paired, and no run of the plane in the slice: it took none of it
        return by_plane.get(plane, 0.0)
    raise ValueError(f"trace_plane_runs: what={what!r}")
