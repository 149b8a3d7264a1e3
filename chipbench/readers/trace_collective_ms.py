"""Device time the chips spend in collective ops per tick, ms.

A program partitioned over several chips carries the collectives the
partitioner (or a ``shard_map`` body) put in: ``all-gather``, ``all-reduce``,
``collective-permute``, ``all-to-all``, each an ``XLA Ops`` event of every
chip that takes part (an asynchronous one as a ``-start`` and a ``-done``
event, both counted).  Per chip this sums the device time of the ops whose
name matches ``op`` and which started inside a whole execution of a program
whose module name matches ``module`` (the trace's edges cut a chip's first and
last execution short, as in ``trace_module_mean``), divides by that chip's
whole executions of the program matching ``per`` (the one a tick dispatches
once), and returns the mean over the chips.  It is time the op held the chip,
not time the chip waited for a peer: the two are one number in the trace.

Nothing is returned where the run has no trace, where no chip shows a whole
execution of ``per`` (a one-chip program under another name, or a commit from
before the programs had these names), or where none of those executions holds
a collective: then the program has none, and a zero would read as a
measurement.
"""

from __future__ import annotations

import bisect
import re

from .. import tracing


def per_tick_ms(trace, module: str, per: str, op: str) -> list:
    """Per chip with a whole execution of ``per``: the ms of matching
    collectives inside the executions of ``module``, per execution of
    ``per``."""
    in_module, counted, wanted = (re.compile(p) for p in (module, per, op))
    out = []
    for lines in trace.devices.values():
        mods = (lines.get(tracing.MODULES) or ())[1:-1]
        ticks = sum(1 for name, _, _ in mods if counted.search(name))
        if not ticks:
            continue
        whole = [(s, s + d) for name, s, d in mods if in_module.search(name)]
        starts = [s for s, _ in whole]
        total = 0.0
        for name, s, d in lines.get(tracing.OPS) or ():
            if not wanted.search(name):
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < whole[i][1]:
                total += d
        out.append(total / ticks / 1e6)
    return out


def read(run, module: str, per: str, op: str):
    if run.trace is None:
        return None
    chips = per_tick_ms(run.trace, module, per, op)
    if not any(chips):
        return None
    return sum(chips) / len(chips)
