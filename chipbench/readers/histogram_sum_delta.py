"""Share of the window *not* taken by what a histogram family times, %.

The family's sum delta over the window, over all the series that carry
``labels``, is the seconds spent in what it times; the share reported is
100 x (1 - seconds / window), floored at 0.  It is a share left free and
not the seconds themselves because the seconds read 0 in a healthy run
(nothing compiles inside a window), and a metric that reads 0 cannot be told
from one that found nothing.  The count and the seconds go to stderr.
"""

from __future__ import annotations

import sys

from .histogram_mean import window


def read(run, family: str, labels: dict | None = None):
    w = window(run, family, labels)
    if w is None or run.window_s <= 0:
        return None
    count, seconds, _ = w
    print(f"histogram_sum_delta: {family} {count} observations, "
          f"{seconds:.6f} s in a window of {run.window_s:.3f} s",
          file=sys.stderr, flush=True)
    return max(0.0, 100.0 * (1.0 - seconds / run.window_s))
