"""A memory-bound kernel's share of the chip's HBM bandwidth, %.

Bytes the kernel's calls move through HBM, from the operand and result shapes
of each call as the trace prints them (``tracing.hlo_io_bytes``: each operand
read once, the result written once, which is what ``ops/pallas_gather.py``
says the kernel does; a buffer the compiler placed in on-chip memory, ``S(1)``
in its layout, does not cross HBM and is left out, so the share cannot be
overstated), over the summed device time of those calls, over the peak of the
device kind from ``peaks.json``.  An unknown device kind is an error, not a
default.  Where every buffer sits on chip (small planes) nothing is returned:
the kernel has no HBM roofline there.
"""

from __future__ import annotations

import json
import os

from .. import tracing


def peak(device_kind: str, what: str) -> float:
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"peaks.json has no device kind {device_kind!r}")
    return float(table[device_kind][what])


def read(run, op: str):
    if run.trace is None:
        return None
    calls, moved, seconds = tracing.op_bytes_and_seconds(run.trace, op,
                                                         hbm_only=True)
    if calls == 0 or seconds <= 0 or moved == 0:
        return None
    return 100.0 * moved / seconds / peak(run.device["kind"],
                                          "hbm_bytes_per_s")
