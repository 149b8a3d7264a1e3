"""Mean of one of the program's histograms over the window, ms.

The obs registry (``gigapaxos_tpu/obs/metrics.py``) snapshots every
histogram as ``{"count", "sum", ...}`` under the key ``family{k=v,...}``,
labels in key order, or the bare family name where it has no labels.  The
harness snapshots the registry at the window's start and end; the mean over
the window is sum delta / count delta, summed over every series of the
family that carries the given labels (so ``labels`` may name all of a
series' labels, some, or none).  A family the program does not have, or one
nothing was observed into during the window, gives nothing.
"""

from __future__ import annotations


def series(snap: dict, family: str, labels: dict | None = None):
    """The snapshot's keys that belong to ``family`` and carry ``labels``."""
    want = {str(k): str(v) for k, v in (labels or {}).items()}
    for key in snap:
        name, _, rest = key.partition("{")
        if name != family:
            continue
        have = dict(kv.split("=", 1) for kv in rest.rstrip("}").split(",")
                    if "=" in kv)
        if all(have.get(k) == v for k, v in want.items()):
            yield key


def window(run, family: str, labels: dict | None = None):
    """(count delta, sum delta in seconds, {bucket index: count delta}) of
    the matching histograms over the window; None where the end snapshot
    has none.  A series that appeared during the window counts from zero;
    the buckets are empty where the program's snapshot carries none."""
    count, total, buckets = 0, 0.0, {}
    keys = [k for k in series(run.snap1, family, labels)
            if isinstance(run.snap1[k], dict)]
    if not keys:
        return None
    for key in keys:
        b = run.snap1[key]
        a = run.snap0.get(key) or {"count": 0, "sum": 0.0, "buckets": {}}
        count += b["count"] - a["count"]
        total += b["sum"] - a["sum"]
        before = a.get("buckets") or {}
        for i, c in (b.get("buckets") or {}).items():
            rose = c - before.get(i, 0)
            if rose:
                buckets[int(i)] = buckets.get(int(i), 0) + rose
    return count, total, buckets


def read(run, family: str, labels: dict | None = None):
    w = window(run, family, labels)
    if w is None or w[0] <= 0:
        return None
    return 1e3 * w[1] / w[0]
