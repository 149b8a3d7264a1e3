"""The phase clock one level deeper (``obs/phase.py``): parts of a phase,
and the thread's CPU time beside each phase's wall time.

``PhaseClock.part(name)`` times a part of the open phase into
``tick_part_seconds{driver,plane,phase,part}`` and, while a profile is on,
opens ``gptpu/<driver>/<plane>/<phase>/<part>`` inside the phase's own
annotation; ``mark`` observes the CPU time the ticking thread spent in the
phase into ``tick_phase_cpu_seconds{driver,plane,phase}``.  The trace
readers pair the chip's runs with the ``dispatch/launch`` annotations, and
the benchmark's ``dispatch_launch_ms``, ``dispatch_release_ms`` and
``dispatch_blocked_ms`` read the histograms.
"""

import os
import sys
import time

import pytest

from gigapaxos_tpu.obs import phase
from gigapaxos_tpu.obs.metrics import Registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FIRST, SECOND = phase.PHASE_RUNS["modea"]


class Recorded:
    """A stand-in for ``jax.profiler.TraceAnnotation`` that keeps the order
    in which annotations open and close."""

    events: list = []
    enabled = True

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        Recorded.events.append(("open", self.name))

    def __exit__(self, *exc):
        Recorded.events.append(("close", self.name))


@pytest.fixture
def clock():
    Recorded.events = []
    Recorded.enabled = True
    reg = Registry()
    return phase.PhaseClock("modea", plane="ar", reg=reg,
                            annotation=Recorded), reg


def one(reg, family, **labels):
    want = {k: str(v) for k, v in labels.items()}
    found = [h for h in reg.find(family)
             if all(dict(h.labels).get(k) == v for k, v in want.items())]
    assert len(found) == 1, (family, labels, found)
    return found[0]


def tick(c, dispatch_parts=("launch", "release"), sleep_s=0.002):
    """One tick of the Mode A phases in their order, with the parts of
    ``dispatch`` inside it, each part sleeping ``sleep_s``."""
    c.begin()
    for p in FIRST:
        if p == "dispatch":
            for part in dispatch_parts:
                with c.part(part):
                    time.sleep(sleep_s)
        c.mark(p)
    c.touch()
    for p in SECOND:
        sum(range(2000))
        c.mark(p)
    c.end()


def test_the_parts_are_declared_for_a_phase_of_the_clocks_runs():
    assert phase.DRIVER_PARTS == {
        "modea": {"dispatch": ("launch", "release", "frontier")}}
    for driver, phases in phase.DRIVER_PARTS.items():
        runs = [p for run in phase.PHASE_RUNS[driver] for p in run]
        assert set(phases) <= set(runs), driver


def test_a_part_is_timed_into_its_histogram_inside_its_phase(clock):
    c, reg = clock
    # the declared parts are in the scrape from the first tick
    for part in ("launch", "release", "frontier"):
        assert one(reg, "tick_part_seconds", phase="dispatch", part=part,
                   plane="ar", driver="modea").count == 0
    tick(c, sleep_s=0.003)
    launch = one(reg, "tick_part_seconds", part="launch")
    release = one(reg, "tick_part_seconds", part="release")
    dispatch = one(reg, "tick_phase_seconds", phase="dispatch")
    assert launch.count == release.count == dispatch.count == 1
    assert one(reg, "tick_part_seconds", part="frontier").count == 0
    assert launch.total >= 0.003 and release.total >= 0.003
    assert launch.total + release.total <= dispatch.total


def test_the_cpu_time_of_a_phase_never_exceeds_its_wall_time(clock):
    c, reg = clock
    wall = {p: one(reg, "tick_phase_seconds", phase=p) for p in FIRST + SECOND}
    cpu = {p: one(reg, "tick_phase_cpu_seconds", phase=p)
           for p in FIRST + SECOND}
    for _ in range(5):
        before = {p: (wall[p].total, cpu[p].total) for p in wall}
        tick(c)
        for p in wall:
            w = wall[p].total - before[p][0]
            t = cpu[p].total - before[p][1]
            assert 0 <= t <= w, (p, t, w)
    # a phase that sleeps is mostly time the thread did not run
    assert cpu["dispatch"].total < 0.5 * wall["dispatch"].total
    assert all(h.count == 5 for h in cpu.values())


def test_the_annotations_nest_the_parts_inside_their_phase(clock):
    c, _ = clock
    tick(c, sleep_s=0.0)
    name = "gptpu/modea/ar/{}".format
    want = []
    for p in FIRST:
        want.append(("open", name(p)))
        if p == "dispatch":
            for part in ("launch", "release"):
                want += [("open", name(f"dispatch/{part}")),
                         ("close", name(f"dispatch/{part}"))]
        want.append(("close", name(p)))
    for p in SECOND:
        want += [("open", name(p)), ("close", name(p))]
    assert Recorded.events == want


def test_without_a_profile_a_part_opens_no_annotation_and_still_times(clock):
    c, reg = clock
    Recorded.enabled = False
    tick(c)
    assert Recorded.events == []
    assert one(reg, "tick_part_seconds", part="launch").count == 1


def test_the_null_clock_times_nothing(monkeypatch):
    monkeypatch.setattr(phase, "METRICS_ENABLED", False)
    c = phase.phase_clock("modea", plane="ar")
    assert c is phase._NULL_CLOCK
    part = c.part("launch")
    assert part is c.part("release")     # one shared no-op context
    with part:
        pass
    tick(c)


def test_a_part_works_through_the_harness_pass_through_wrapper(clock):
    tracing = pytest.importorskip("chipbench.tracing")
    c, reg = clock
    rec = tracing.PhaseRecorder(c, "ar")
    tick(rec, sleep_s=0.001)
    assert [p for p, _, _ in rec.spans] == list(FIRST + SECOND)
    assert one(reg, "tick_part_seconds", part="launch").count == 1
    assert ("open", "gptpu/modea/ar/dispatch/release") in Recorded.events


def test_a_managers_tick_times_its_dispatch_in_parts():
    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.models.replicable import KVApp
    from gigapaxos_tpu.obs.metrics import registry
    from gigapaxos_tpu.paxos.manager import PaxosManager

    plane = "t_dispatch_parts"
    cfg = GigapaxosTpuConfig()
    cfg.paxos.compact_outbox = True
    m = PaxosManager(cfg, 3, [KVApp() for _ in range(3)], spill_ns=plane)
    m._sweep_every = 2   # the frontier is dispatched on every other tick
    m.create_paxos_instance("svc", [0, 1, 2])
    for i in range(4):
        m.propose("svc", b"PUT k v%d" % i)
        m.run_ticks(1)
    m.drain_pipeline()
    snap = registry().snapshot()

    def h(family, **labels):
        labels.update(driver="modea", plane=plane)
        key = family + "{" + ",".join(
            f"{k}={v}" for k, v in sorted(labels.items())) + "}"
        return snap[key]

    ticks = m.tick_num
    dispatch = h("tick_phase_seconds", phase="dispatch")
    launch = h("tick_part_seconds", phase="dispatch", part="launch")
    release = h("tick_part_seconds", phase="dispatch", part="release")
    frontier = h("tick_part_seconds", phase="dispatch", part="frontier")
    assert dispatch["count"] == launch["count"] == release["count"] == ticks
    assert 1 <= frontier["count"] <= ticks // 2
    assert launch["sum"] + release["sum"] + frontier["sum"] <= dispatch["sum"]
    for p in phase.DRIVER_PHASES["modea"]:
        wall = h("tick_phase_seconds", phase=p)
        cpu = h("tick_phase_cpu_seconds", phase=p)
        assert cpu["count"] == wall["count"] and cpu["sum"] <= wall["sum"]
