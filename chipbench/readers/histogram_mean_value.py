"""Mean over the window of a histogram of plain numbers (``unit=""``:
requests, bytes), in the histogram's own unit.

``histogram_mean`` is for histograms of seconds and answers in milliseconds;
this is the same quotient, the window's sum delta over its count delta,
without the x 1,000.  A family the program does not have (a parent commit
from before the counter), or one nothing was observed into during the
window, gives nothing.
"""

from __future__ import annotations

from .histogram_mean import window


def read(run, family: str, labels: dict | None = None):
    w = window(run, family, labels)
    if w is None or w[0] <= 0:
        return None
    return w[1] / w[0]
