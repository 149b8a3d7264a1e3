"""Mean host wall time of one tick phase, from the program's phase clock.

``tick_phase_seconds{driver,plane,phase}`` is a histogram the manager's
``PhaseClock`` feeds on every tick (host clock, no device sync).  The harness
snapshots the registry at the window's start and end; the mean over the
window is sum delta / count delta.  ``tally`` absorbs the wait for the device.
"""

from __future__ import annotations


def histogram_delta(run, name: str, **labels) -> tuple:
    """(count delta, sum delta in seconds) of one histogram over the window;
    None where either snapshot lacks it."""
    key = name + "{" + ",".join(f"{k}={v}" for k, v in sorted(labels.items())) + "}"
    a, b = run.snap0.get(key), run.snap1.get(key)
    if a is None or b is None:
        return None
    return b["count"] - a["count"], b["sum"] - a["sum"]


def read(run, driver: str, plane: str, phase: str):
    delta = histogram_delta(run, "tick_phase_seconds", driver=driver,
                            plane=plane, phase=phase)
    if delta is None or delta[0] <= 0:
        return None
    return 1e3 * delta[1] / delta[0]
