"""Device time of one program's ops under the program's own scope names, per
execution, ms.

The tick programs trace each phase under a ``jax.named_scope``
(``gigapaxos_tpu/ops/tick.py``; the names are ``TICK_SCOPES`` in
``obs/phase.py``), and the profiler keeps, for every device op, the scope
path of the instruction it came from (``rawtrace.py`` says where).  For the
whole executions of the programs whose module name matches ``module`` (the
trace's edges cut the first and last short, as in ``trace_module_mean``)
this sums the device time of the ops that started inside them and whose
path holds one of ``scopes`` as a component, and divides by the number of
executions.  XLA fuses across scope boundaries and a fusion keeps the
metadata of one instruction, so the split is by op, not by source line.

An op that holds other ops (a ``conditional``: the trace lists it beside the
ops of the branch it ran, which start inside it and carry their own scopes)
is counted in none of the sums: only leaves are, so no time is counted twice.

Per call one line goes to stderr: the time under ``scopes``, under
``beside`` (the program's other scope names, which another metric reads) and
under neither, per execution, with the largest ops under neither; the three
add up to the program's own time.  The time of the ops left out for holding
others stands beside them.

Nothing is returned, never a guess, when the harness gave the run no trace
(``run.trace is None``: its sign that no device metric is to be printed),
when this process's raw trace is not found, when no whole execution
matches, or when no op inside them carries any of ``scopes`` (a program from
before the scopes, or an executable served by the compile cache with another
commit's metadata).
"""

from __future__ import annotations

import bisect
import re
import sys

from .. import rawtrace


def scope_components(path: str) -> list:
    """``jit(f)/phase/jit(g)/op:`` -> [``jit(f)``, ``phase``, ``jit(g)``,
    ``op``]."""
    return [c for c in path.rstrip(":").split("/") if c]


def split_ms(raw, module: str, scopes: list, beside: list) -> tuple | None:
    """(executions, ms under ``scopes``, ms under ``beside``, ms under
    neither, {op name: ms} of the ops under neither, ms of the ops left out
    because they hold other ops), all per execution; None where no whole
    execution matches."""
    rx, wanted, others = re.compile(module), set(scopes), set(beside)
    n, inside, other, none, holders = 0, 0.0, 0.0, 0.0, 0.0
    bare: dict = {}
    for plane, mods in raw.modules.items():
        whole = [(s, s + d) for name, s, d in mods[1:-1] if rx.search(name)]
        if not whole:
            continue
        n += len(whole)
        starts = [s for s, _ in whole]
        ops = raw.ops.get(plane, ())
        for j, (name, path, s, d) in enumerate(ops):
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= whole[i][1]:
                continue
            # in start order: the next op starts before this one has ended
            if j + 1 < len(ops) and ops[j + 1][2] < s + d:
                holders += d
                continue
            parts = scope_components(path)
            if wanted.intersection(parts):
                inside += d
            elif others.intersection(parts):
                other += d
            else:
                none += d
                bare[name] = bare.get(name, 0.0) + d
    if n == 0:
        return None
    per = 1e6 * n
    return (n, inside / per, other / per, none / per,
            {k: v / per for k, v in bare.items()}, holders / per)


def read(run, module: str, scopes: list, beside: list):
    if run.trace is None:
        return None
    raw = rawtrace.of_this_run()
    if raw is None:
        return None
    split = split_ms(raw, module, scopes, beside)
    if split is None:
        return None
    n, inside, other, none, bare, holders = split
    top = sorted(bare.items(), key=lambda kv: -kv[1])[:3]
    print(f"trace_scope_ms: {n} executions of {module}: "
          f"{inside:.3f} ms under {'+'.join(scopes)}, {other:.3f} ms under "
          f"{'+'.join(beside)}, {none:.3f} ms under neither ("
          + "; ".join(f"{v:.3f} {k.split(' = ')[0]}" for k, v in top)
          + f"), sum {inside + other + none:.3f} ms; {holders:.3f} ms of ops "
          "that hold other ops left out",
          file=sys.stderr, flush=True)
    return inside if inside > 0 else None
