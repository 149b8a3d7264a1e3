"""The longest sample of a histogram family inside the window, ms, to the
bucket: the upper bound of the highest bucket whose count rose between the
two snapshots.

The registry's buckets are powers of two in microseconds, bucket ``i``
holding samples of up to ``2**i - 1`` us, and a snapshot carries the
non-empty ones as ``"buckets": {"<i>": count}``.  A program whose snapshots
carry no buckets gives nothing.
"""

from __future__ import annotations

from .histogram_mean import window


def bucket_upper_ms(i: int) -> float:
    return ((1 << i) - 1) / 1e3


def read(run, family: str, labels: dict | None = None):
    w = window(run, family, labels)
    if w is None or not w[2]:
        return None
    return bucket_upper_ms(max(w[2]))
