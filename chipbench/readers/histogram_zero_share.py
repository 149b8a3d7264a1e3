"""Share of a window's observations of a histogram of plain numbers that
were zero, %.

The program's histograms bucket by ``int(v).bit_length()``, so bucket 0
holds the observations of exactly 0, and a snapshot carries every non-empty
bucket (``histogram_mean.window`` gives their deltas).  It is the share that
were zero and not the mean because the mean reads 0 under any mix that
leaves nothing behind, and a printed 0 cannot be told from a reader that
found nothing (``histogram_sum_delta`` has the same reason); the window's
count, sum and mean go to stderr.  A family the program does not have, or
one nothing was observed into during the window, gives nothing.
"""

from __future__ import annotations

import sys

from .histogram_mean import window


def read(run, family: str, labels: dict | None = None):
    w = window(run, family, labels)
    if w is None or w[0] <= 0:
        return None
    count, total, buckets = w
    print(f"histogram_zero_share: {family} {count} observations, "
          f"{buckets.get(0, 0)} of them 0, sum {total:.0f}, mean "
          f"{total / count:.4f}", file=sys.stderr, flush=True)
    return 100.0 * buckets.get(0, 0) / count
