"""Window length over the ticks one plane completed in it, ms.

The count comes from ``tick_seconds{driver,plane}``, which the phase clock
observes once at the end of every tick."""

from __future__ import annotations

from .phase_mean import histogram_delta


def read(run, driver: str, plane: str):
    delta = histogram_delta(run, "tick_seconds", driver=driver, plane=plane)
    if delta is None or delta[0] <= 0:
        return None
    return 1e3 * run.window_s / delta[0]
