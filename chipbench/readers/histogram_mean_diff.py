"""Difference of two histograms' means over the window, ms: what one span
holds that a span inside it does not.

Both means are window deltas over the same two snapshots
(``histogram_mean.window``), so a request counted in one is, but for the few
in flight at the window's edges, counted in the other.  The two means go to
stderr, for a look at the inner one by hand.  Nothing where either is
missing.
"""

from __future__ import annotations

import sys

from .histogram_mean import read as mean_ms


def read(run, outer: dict, inner: dict):
    a = mean_ms(run, outer["family"], outer.get("labels"))
    b = mean_ms(run, inner["family"], inner.get("labels"))
    if a is None or b is None:
        return None
    print(f"histogram_mean_diff: {outer['family']} {a:.3f} ms - "
          f"{inner['family']} {b:.3f} ms", file=sys.stderr, flush=True)
    return a - b
