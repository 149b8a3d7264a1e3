"""The plain reference of the host KV app, and the comparison that decides
``correct`` for a deployment whose every name is a linearizable register
per key.

``RefKV`` is a dict per name with the KV app's request semantics, written
independently of ``models/replicable.KVApp`` (copied from ``chip_smoke.py``,
which keeps its own: it is the original).  ``allowed_values`` is what one
key may hold after a set of timed writes; ``check_reads`` holds the
acknowledged reads of one key to four necessary conditions of
linearizability; ``check_run`` applies both to every name a run touched, on
every replica.  (Until ISSUE 35 this file was ``chipbench/reference.py``,
which stays as a thin import of this one for ``tests/``.)

**What a run can show, not a proof.**  Every written value is unique (the
generators put the request's sequence number in its first 12 characters), so
a read names the write it saw.  An acknowledged read that returned ``v`` (or
not found) is a problem when

- *nobody wrote it*: ``v`` is the value of no write to that key, nor its
  loaded value; or of a write that was refused;
- *from the future*: the write of ``v`` was sent after the read's reply
  arrived;
- *stale*: an acknowledged write was sent after the write of ``v`` completed
  and completed before the read was sent (``v`` was overwritten before the
  read began);
- *going back*: an earlier read (reply received before this one was sent)
  returned a value whose write was sent after the write of ``v`` completed.

The loaded value (or the key's absence) counts as a write that completed
before the run.  A write of unknown status never rules anything out: its
completion is +inf, here as in ``allowed_values``.  Each rule is implied by
linearizability; a history that breaks none of them may still have no
linearization (that search is exponential, and these four catch a stale or
invented answer, which is what a broken read path gives).

**Why a read's reply is not replayed.**  Every acknowledged ``update`` /
``delete`` is replayed through ``RefKV`` in the order sent and must have
answered what the replay answers: a ``PUT`` answers ``OK`` whatever ran
beside it, so the order does not matter (a ``DEL``'s ``OK`` / ``NF`` would
depend on it: no generator deletes yet, and one that does sends one at a
time to a name).  A ``GET``'s answer depends on the commit order of the
requests in flight with it, which is not the send order: five to eight
requests to a hot name are in flight at once through three entry replicas.
The four rules above hold it to every order linearizability allows.
"""

from __future__ import annotations

import bisect
import math

from . import Op

__all__ = ["Op", "RefKV", "allowed_values", "check_name", "check_reads",
           "check_run", "request_of"]


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


def request_of(op: Op) -> bytes:
    """The request bytes of an operation, as the KV app reads them (a test
    holds every generator's payloads to this)."""
    if op.kind == "update":
        return f"PUT {op.key} {op.value}".encode()
    if op.kind == "read":
        return f"GET {op.key}".encode()
    if op.kind == "delete":
        return f"DEL {op.key}".encode()
    raise ValueError(f"reference does not know the kind {op.kind!r}")


def allowed_values(writes: list, loaded=None) -> set:
    """The values one key may hold after ``writes``, all to that key, in a
    linearizable store: a write is ruled out when it was refused, or when an
    acknowledged write was sent after it had completed (that one overwrote
    it).  ``loaded`` (the record loaded before the run; ``None`` = key
    absent) is allowed while no write was acknowledged."""
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
        allowed.add(loaded)
    return allowed


def check_reads(ops: list, loaded=None) -> list:
    """Problems with the acknowledged reads among ``ops``, all to one key of
    one name: the four rules of this file's docstring, at most one problem a
    read (the first rule it breaks).  Sort-based: a read costs two
    bisections."""
    reads = [o for o in ops if o.kind == "read" and o.status == "ok"]
    if not reads:
        return []
    if any(o.kind == "delete" for o in ops):
        return ["reads beside deletes: the read rules need every written "
                "value unique, and a delete writes none"]
    # value -> (sent, completion) of its write; a refused write ran never
    wrote = {loaded: (-math.inf, -math.inf)}
    refused = set()
    for w in ops:
        if w.kind != "update":
            continue
        if w.value in wrote or w.value in refused:
            raise ValueError(f"two writes of {w.value!r}: the generator's "
                             f"values are not unique")
        if w.status == "refused":
            refused.add(w.value)
        else:
            wrote[w.value] = (w.sent,
                              w.done if w.status == "ok" else math.inf)
    acked = sorted((w.sent, w.done) for w in ops
                   if w.kind == "update" and w.status == "ok")
    acked_sent = [s for s, _ in acked]
    # done_after[i]: the earliest completion among acked writes i, i+1, ...
    done_after = [math.inf] * (len(acked) + 1)
    for i in range(len(acked) - 1, -1, -1):
        done_after[i] = min(acked[i][1], done_after[i + 1])

    problems = []
    sound = []  # (the read, its write's sent, its write's completion)
    for r in reads:
        v = None if r.reply == b"NF" else r.reply.decode(errors="replace")
        if v not in wrote:
            why = "a refused write's" if v in refused else "nobody wrote it"
            problems.append(f"read sent {r.sent:.6f} returned {v!r}: {why}")
        elif wrote[v][0] > r.done:
            problems.append(f"read answered {r.done:.6f} returned {v!r}, "
                            f"from the future: written {wrote[v][0]:.6f}")
        else:
            sound.append((r, *wrote[v]))
    sound.sort(key=lambda s: s[0].done)
    arrived = [r.done for r, _, _ in sound]
    # newest[i]: the latest-sent write seen by the first i reads to arrive
    newest = [-math.inf]
    for _, sent, _ in sound:
        newest.append(max(newest[-1], sent))
    for r, _, completed in sound:
        seen = r.reply.decode(errors="replace")
        if done_after[bisect.bisect_right(acked_sent, completed)] < r.sent:
            problems.append(f"read sent {r.sent:.6f} returned {seen!r}, "
                            f"stale: overwritten before the read began")
        elif newest[bisect.bisect_left(arrived, r.sent)] > completed:
            problems.append(f"read sent {r.sent:.6f} returned {seen!r}, "
                            f"going back: an earlier read saw a later write")
    return problems


def check_name(ops: list, replicas: list, loaded: dict) -> list:
    """Problems with one name: the replicas' tables differ; a key holds a
    value that no linearizable store may hold after these writes; a read
    breaks a rule; a key is there that neither the schedule nor the load
    touched.  ``replicas`` is one dict per replica, ``loaded`` the name's
    loaded records."""
    problems = []
    if any(t != replicas[0] for t in replicas[1:]):
        problems.append(f"replicas differ: {replicas}")
    by_key: dict = {key: [] for key in loaded}
    for o in ops:
        by_key.setdefault(o.key, []).append(o)
    for key, key_ops in by_key.items():
        held = replicas[0].get(key)
        allowed = allowed_values([o for o in key_ops if o.kind != "read"],
                                 loaded.get(key))
        if held not in allowed:
            problems.append(f"holds {held!r}, a linearizable store may hold "
                            f"{sorted(map(str, allowed))[:4]}")
        problems.extend(check_reads(key_ops, loaded.get(key)))
    extra = set(replicas[0]) - set(by_key)
    if extra:
        problems.append(f"keys nobody wrote: {sorted(extra)[:4]}")
    return problems


def check_run(ops_by_name: dict, tables_of, readback: dict,
              initial) -> list:
    """Every problem a run shows, as strings (empty = correct).  The
    arguments are ``chipbench/references/__init__.py``'s."""
    problems = []
    for name, ops in ops_by_name.items():
        loaded = initial.get(name, {})
        ref = RefKV()
        ref.tables[name] = dict(loaded)
        for o in ops:
            if o.kind == "read" or o.status != "ok" or o.reply is None:
                continue
            request = request_of(o)
            want = ref.apply(name, request)
            if o.reply != want:
                problems.append(f"{name}: {request[:24]!r} answered "
                                f"{o.reply!r}, the reference says {want!r}")
        tables = tables_of(name)
        for p in check_name(ops, tables, loaded):
            problems.append(f"{name}: {p}")
        for key, got in readback.get(name, {}).items():
            allowed = allowed_values(
                [o for o in ops if o.key == key and o.kind != "read"],
                loaded.get(key))
            held = tables[0].get(key)
            if got not in allowed or got != held:
                problems.append(
                    f"{name}: GET {key} through the client returned {got!r}; "
                    f"the replicas hold {held!r} and the reference allows "
                    f"{sorted(map(str, allowed))[:4]}")
    return problems
