"""The arithmetic of the end-to-end metrics.

Latency is reply-received minus the instant the request was *due* (not the
instant it was sent), so a stalled generator or a stalled system charges the
wait to the requests behind it.  A failed request has no latency sample and
is not in the goodput.
"""

from __future__ import annotations

import numpy as np

#: request status codes in ``Load.status``
PENDING, OK, BUSY, EXPIRED, ERROR = 0, 1, 2, 3, 4
STATUS_NAMES = {PENDING: "pending", OK: "ok", BUSY: "busy",
                EXPIRED: "expired", ERROR: "error"}


def percentile(samples, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; needs at least one sample."""
    a = np.asarray(samples, dtype=np.float64)
    if a.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(a, q))


def end_to_end(due, done, status, in_window, seconds: float) -> dict:
    """Counts and the end-to-end numbers over the requests due in the
    window.  ``due``/``done`` in seconds on one clock; ``status`` as above;
    ``in_window`` a boolean mask."""
    due, done, status = (np.asarray(x) for x in (due, done, status))
    w = np.asarray(in_window, dtype=bool)
    ok = w & (status == OK)
    lat_ms = (done[ok] - due[ok]) * 1e3
    out = {
        "attempted": int(w.sum()),
        "failed": int((w & (status != OK)).sum()),
        "by_status": {STATUS_NAMES[s]: int((w & (status == s)).sum())
                      for s in STATUS_NAMES},
        "samples": int(lat_ms.size),
        "goodput_ops": float(ok.sum() / seconds),
    }
    if lat_ms.size:
        out["commit_p50_ms"] = percentile(lat_ms, 50)
        out["commit_p95_ms"] = percentile(lat_ms, 95)
    return out
