"""The plain reference and the comparison that decides ``correct``.

``RefKV`` is a dict per name with the KV app's request semantics, written
independently of ``models/replicable.KVApp`` (copied from ``chip_smoke.py``).
``check_register`` holds one key of one name to what a linearizable store
may show after a set of timed writes; ``check_run`` applies it to every name
a run touched, on every replica.
"""

from __future__ import annotations

import dataclasses
import math


class RefKV:
    def __init__(self):
        self.tables: dict = {}

    def apply(self, name: str, request: bytes) -> bytes:
        op, _, rest = request.decode().partition(" ")
        table = self.tables.setdefault(name, {})
        if op == "PUT":
            key, _, value = rest.partition(" ")
            table[key] = value
            return b"OK"
        if op == "GET":
            return table[rest].encode() if rest in table else b"NF"
        if op == "DEL":
            return b"OK" if table.pop(rest, None) is not None else b"NF"
        raise ValueError(f"reference does not know {request!r}")


@dataclasses.dataclass
class Write:
    """One ``PUT`` as the client saw it.  ``done`` is the instant the reply
    arrived; ``status`` is ``ok``, ``refused`` (answered busy, expired or an
    error: the system says it did not execute it) or ``unknown`` (no reply:
    it may or may not have executed)."""

    value: str
    sent: float
    done: float
    status: str


def allowed_values(writes: list) -> set:
    """The values one key may hold after ``writes``, all to that key, in a
    linearizable store: a write is ruled out when it was refused, or when an
    acknowledged write was sent after it had completed (that one overwrote
    it).  ``None`` (key absent) is allowed while no write was acknowledged."""
    acked = [w for w in writes if w.status == "ok"]
    last_sent = max((w.sent for w in acked), default=-math.inf)
    allowed = set()
    for w in writes:
        if w.status == "refused":
            continue
        done = w.done if w.status == "ok" else math.inf
        if done >= last_sent:
            allowed.add(w.value)
    if not acked:
        allowed.add(None)
    return allowed


def check_register(writes: list, replicas: list, key: str) -> list:
    """Problems with one name: the replicas' tables differ, or the key's
    value is not one a linearizable store may show.  ``replicas`` is one
    dict per replica."""
    problems = []
    if any(t != replicas[0] for t in replicas[1:]):
        problems.append(f"replicas differ: {replicas}")
    held = replicas[0].get(key)
    allowed = allowed_values(writes)
    if held not in allowed:
        problems.append(f"holds {held!r}, a linearizable store may hold "
                        f"{sorted(map(str, allowed))[:4]}")
    extra = set(replicas[0]) - {key}
    if extra:
        problems.append(f"keys nobody wrote: {sorted(extra)[:4]}")
    return problems


def check_run(writes_by_name: dict, tables_of, replies: list, readback: dict,
              key: str) -> list:
    """Every problem a run shows, as strings (empty = correct).

    ``writes_by_name``: service name -> [Write]; ``tables_of(name)`` -> one
    dict per replica; ``replies``: (name, request bytes, reply bytes) of every
    acknowledged request, held to the reference's answer; ``readback``: name
    -> the value a ``GET`` through the client returned after the drain."""
    problems = []
    ref = RefKV()
    for name, request, reply in replies:
        want = ref.apply(name, request)
        if reply != want:
            problems.append(f"{name}: {request[:24]!r} answered {reply!r}, "
                            f"the reference says {want!r}")
    for name, writes in writes_by_name.items():
        for p in check_register(writes, tables_of(name), key):
            problems.append(f"{name}: {p}")
    for name, got in readback.items():
        allowed = allowed_values(writes_by_name[name])
        held = tables_of(name)[0].get(key)
        if got not in allowed or got != held:
            problems.append(f"{name}: GET {key} through the client returned "
                            f"{got!r}; the replicas hold {held!r} and the "
                            f"reference allows {sorted(map(str, allowed))[:4]}")
    return problems
