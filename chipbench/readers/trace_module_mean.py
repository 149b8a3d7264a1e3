"""Mean device duration of one execution of a jitted program, ms, from the
``XLA Modules`` line of the trace; executions cut by the trace's edges are
left out."""

from __future__ import annotations

from .. import tracing


def read(run, module: str):
    if run.trace is None:
        return None
    whole = tracing.whole_executions(run.trace, module)
    if not whole:
        return None
    return sum(whole) / len(whole) / 1e6
