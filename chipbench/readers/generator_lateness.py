"""How late the load generator ran: a percentile of (send instant - due
instant) over the requests due in the window, ms."""

from __future__ import annotations

from .. import stats


def read(run, q: float):
    late = run.load.late_ms()
    if late.size == 0:
        return None
    return stats.percentile(late, q)
