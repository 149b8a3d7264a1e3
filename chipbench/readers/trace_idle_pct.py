"""Share of the traced span in which no op ran on the device, %: one minus
the union of the ``XLA Ops`` intervals over the span, averaged over chips."""

from __future__ import annotations

from .. import tracing


def read(run):
    if run.trace is None:
        return None
    busy_s, window_s = tracing.busy_and_window_s(run.trace)
    if window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
