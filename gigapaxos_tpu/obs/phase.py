"""Per-tick phase clocks for the tick drivers.

A :class:`PhaseClock` lives on a manager and splits each tick into named
host-side phases: ``mark(phase)`` records the wall time since the previous
mark into ``tick_phase_seconds{driver=,plane=,phase=}``.  The timestamps are
host-side (taken at dispatch enqueue and at completion/unpack), so the
always-on mode adds **no device synchronization** — the ``dispatch`` phase
is enqueue cost and the ``tally`` phase absorbs the device wait exactly as
the manager already experiences it.  Beside each phase's wall time the clock
keeps the CPU time its thread spent in it
(``tick_phase_cpu_seconds{driver=,plane=,phase=}``, ``time.thread_time``):
wall minus CPU is the time the thread did not run (the interpreter lock, a
runtime lock, a sleeping system call, the device).  A phase may be split
further into parts (``part(name)``, vocabulary ``DRIVER_PARTS``), each timed
into ``tick_part_seconds{driver=,plane=,phase=,part=}``.  Exact device time
comes from a profiler trace, where both halves of the program's vocabulary
show on one clock:

* each host phase of a driver listed in ``PHASE_RUNS`` is also a
  ``jax.profiler.TraceAnnotation`` named ``gptpu/<driver>/<plane>/<phase>``
  with the phase's true start and end, on the profile's ``/host:CPU`` plane.
  While no profile is being taken it costs one ``TraceMe`` flag check per
  phase.  A part is ``gptpu/<driver>/<plane>/<phase>/<part>``, nested
  inside its phase's annotation;
* each device phase of the tick programs (``ops/tick.py``) runs under a
  ``jax.named_scope`` from ``TICK_SCOPES``, so every ``XLA Ops`` event of a
  tick program names the phase its instruction came from.  XLA fuses across
  scope boundaries and a fusion carries one instruction's metadata, so
  device time splits by op, not by source line.

The canonical vocabularies below are the contract the static coverage test
(``tests/test_obs_coverage.py``) greps driver and tick sources against — add
a phase here AND a ``mark`` (or a scope) there, or tier-1 fails.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional, Tuple

from .metrics import METRICS_ENABLED, Histogram, Registry, registry

# driver name -> the phases its tick MUST mark (coverage-test contract)
DRIVER_PHASES: Dict[str, Tuple[str, ...]] = {
    # paxos/manager.py PaxosManager.tick/_complete_tick
    "modea": ("repair", "intake", "dispatch", "wal_fsync",
              "tally", "execute", "egress", "sweep"),
    # modeb/manager.py ModeBNode.tick (ring_relay: the one-downstream-send
    # payload dissemination hop that replaces payload fan-out under
    # cfg.paxos.ring_dissemination)
    "modeb": ("ingress", "intake", "dispatch", "wal_fsync",
              "tally", "execute", "outbox_pack", "egress", "ring_relay"),
    # chain/manager.py ChainManager.tick
    "chain": ("intake", "dispatch", "wal_fsync", "tally", "execute"),
    # chain/modeb.py ChainModeBNode.tick
    "chain_modeb": ("intake", "dispatch", "wal_fsync",
                    "tally", "execute", "outbox_pack", "egress"),
}

#: driver -> the runs of phases its tick marks in a fixed order, each run
#: back to back: ``begin()`` opens the first run and ``touch()`` the second
#: (the completion of a pipelined tick), and ``mark(p)`` opens ``p``'s
#: successor within its run.  A trace annotation's name is fixed when it
#: opens, while ``mark`` names a phase when it closes, so the clock has to
#: know what comes next; a driver without an entry emits no annotations.
PHASE_RUNS: Dict[str, Tuple[Tuple[str, ...], ...]] = {
    "modea": (("repair", "intake", "dispatch", "wal_fsync"),
              ("tally", "execute", "egress", "sweep")),
}

#: driver -> phase -> the parts of the phase its tick times with
#: ``PhaseClock.part`` (coverage-test contract, like ``DRIVER_PHASES``).
#: ``dispatch`` of a Mode A tick: ``launch`` is the call that enqueues the
#: tick's program(s), ``release`` lets go of the donated inputs and adopts
#: the outputs, ``frontier`` enqueues the sweep frontier and its gather (only
#: on the ticks whose completion sweeps)
DRIVER_PARTS: Dict[str, Dict[str, Tuple[str, ...]]] = {
    "modea": {"dispatch": ("launch", "release", "frontier")},
}

#: the ``jax.named_scope`` names inside the tick programs (``ops/tick.py``):
#: the phases of ``paxos_tick_impl`` under the names its own comments use,
#: the lease and health folds, and the programs around it
TICK_SCOPES: Tuple[str, ...] = (
    "candidacy", "prepare", "intake", "accept", "tally", "decision_sync",
    "execute", "freeze", "repair_summary", "lease_fold", "health_fold",
    "compact_outbox", "sweep_frontier", "frontier_rows",
)


def annotation_name(driver: str, plane: str, phase: str,
                    part: Optional[str] = None) -> str:
    name = f"gptpu/{driver}/{plane}/{phase}"
    return name if part is None else f"{name}/{part}"


class _Part:
    """One part of one phase: a reusable context manager (a part never nests
    in itself) that times its body into its histogram and, while a profile
    is on, opens its annotation inside the phase's."""

    __slots__ = ("_h", "_name", "_annotation", "_t", "_open")

    def __init__(self, h: Histogram, name: str, annotation):
        self._h = h
        self._name = name
        self._annotation = annotation
        self._t = 0.0
        self._open = None

    def __enter__(self):
        if self._annotation is not None and self._annotation.is_enabled():
            self._open = self._annotation(self._name)
            self._open.__enter__()
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self._h.observe(time.perf_counter() - self._t)
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


class PhaseClock:
    """Delta clock over one tick: ``begin`` ... ``mark(p)*`` ... ``end``.

    ``mark`` observes (now - last mark) into the phase histogram, and the
    thread's CPU time over the same span into the phase's CPU histogram, and
    advances the mark.  ``touch`` re-arms the mark without observing — the
    pipelined completion path (``drain_pipeline``) uses it so a deferred
    ``_complete_tick`` doesn't attribute cross-tick idle time to ``tally``.

    While a profile is being taken the phases of a ``PHASE_RUNS`` driver are
    trace annotations too (module docstring).  ``annotation`` is the class
    that makes them (``jax.profiler.TraceAnnotation``; a test substitutes
    its own): a context manager per name, and ``is_enabled()`` says whether
    a profile is on.

    ``part(name)`` is a context manager for a part of the phase that is
    open (the one the next ``mark`` closes; known from ``PHASE_RUNS``).
    """

    __slots__ = ("driver", "plane", "_reg", "_h", "_cpu_h", "_tick_h", "_t",
                 "_c", "_t0", "_first", "_resume", "_next", "_open",
                 "_annotation", "_names", "_phase", "_parts")

    def __init__(self, driver: str, plane: str = "default",
                 reg: Optional[Registry] = None, annotation=None):
        self.driver = driver
        self.plane = plane
        self._reg = registry() if reg is None else reg
        self._h: Dict[str, Histogram] = {}
        self._cpu_h: Dict[str, Histogram] = {}
        runs = PHASE_RUNS.get(driver, ())
        # what begin() and touch() open, what follows each phase (None at
        # the end of a run), and the annotation names, built once
        self._first = runs[0][0] if runs else None
        self._resume = runs[1][0] if len(runs) > 1 else None
        self._next: Dict[str, Optional[str]] = {
            p: q for run in runs for p, q in zip(run, run[1:])}
        self._names = {p: annotation_name(driver, plane, p)
                       for run in runs for p in run}
        self._phase: Optional[str] = None
        self._parts: Dict[Tuple[str, str], _Part] = {}
        self._open = None
        if runs and annotation is None:
            # here and not at import: the package stays importable off JAX
            from jax.profiler import TraceAnnotation as annotation
        self._annotation = annotation
        self._tick_h = self._reg.histogram(
            "tick_seconds", help="whole-tick wall time",
            driver=driver, plane=plane)
        now = time.perf_counter()
        self._t = now
        self._t0 = now
        self._c = time.thread_time()
        # pre-create the declared phases and parts so the scrape shows the
        # full vocabulary (zero-count) from the first tick
        for p in DRIVER_PHASES.get(driver, ()):
            self._phase_h(p)
        for p, parts in DRIVER_PARTS.get(driver, {}).items():
            for part in parts:
                self._part(p, part)

    def _phase_h(self, phase: str) -> Histogram:
        h = self._h.get(phase)
        if h is None:
            h = self._h[phase] = self._reg.histogram(
                "tick_phase_seconds",
                help="host wall time per tick phase",
                driver=self.driver, plane=self.plane, phase=phase)
            self._cpu_h[phase] = self._reg.histogram(
                "tick_phase_cpu_seconds",
                help="CPU time of the ticking thread per tick phase",
                driver=self.driver, plane=self.plane, phase=phase)
        return h

    def _part(self, phase: str, part: str) -> _Part:
        p = self._parts[phase, part] = _Part(
            self._reg.histogram(
                "tick_part_seconds", help="host wall time per part of a "
                "tick phase", driver=self.driver, plane=self.plane,
                phase=phase, part=part),
            annotation_name(self.driver, self.plane, phase, part),
            self._annotation)
        return p

    def _annotate(self, phase: Optional[str]) -> None:
        """Close the open annotation and open ``phase``'s (None: nothing)."""
        self._phase = phase
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        if phase is not None and self._annotation.is_enabled():
            self._open = self._annotation(self._names[phase])
            self._open.__enter__()

    def begin(self) -> None:
        now = time.perf_counter()
        self._t = now
        self._t0 = now
        self._c = time.thread_time()
        self._annotate(self._first)

    def touch(self) -> None:
        self._t = time.perf_counter()
        self._c = time.thread_time()
        self._annotate(self._resume)

    def mark(self, phase: str) -> None:
        now = time.perf_counter()
        cpu = time.thread_time()
        wall = now - self._t
        self._phase_h(phase).observe(wall)
        # the two clocks are read one after the other, so a phase that ran
        # all through can read a few ns more CPU than wall time
        self._cpu_h[phase].observe(min(cpu - self._c, wall))
        self._t = now
        self._c = cpu
        self._annotate(self._next.get(phase))

    def part(self, name: str) -> _Part:
        """The context manager of part ``name`` of the open phase."""
        p = self._parts.get((self._phase, name))
        return p if p is not None else self._part(self._phase, name)

    def end(self) -> None:
        self._tick_h.observe(time.perf_counter() - self._t0)
        self._annotate(None)


class _NullPhaseClock:
    """Compiled-out twin: every method is an empty call."""

    __slots__ = ()
    driver = "null"
    plane = "null"

    def begin(self) -> None:
        pass

    def touch(self) -> None:
        pass

    def mark(self, phase: str) -> None:
        pass

    def end(self) -> None:
        pass

    def part(self, name: str) -> contextlib.nullcontext:
        return _NULL_PART


_NULL_PART = contextlib.nullcontext()
_NULL_CLOCK = _NullPhaseClock()


def phase_clock(driver: str, plane: str = "default"):
    """A PhaseClock on the default registry, or the shared no-op twin
    under ``GPTPU_METRICS=0`` (the bound-at-construction compile-out)."""
    if not METRICS_ENABLED:
        return _NULL_CLOCK
    return PhaseClock(driver, plane)
