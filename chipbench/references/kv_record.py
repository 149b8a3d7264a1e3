"""The plain reference of a deployment that holds one fixed-width record of
fields per name (YCSB's ``usertable`` row: ``fieldcount`` fields of
``fieldlength`` bytes under one key), and the comparison that decides
``correct`` for it.

``RefRecord`` is the KV app's request semantics with the partial write,
written independently of ``models/replicable.KVApp``: ``PUT <key> <value>``
(YCSB's insert: the whole record), ``GET <key>`` (its read with
``readallfields=true``: the whole record), ``SETRANGE <key> <offset>
<bytes>`` (its update with ``writeallfields=false``: one field, in place;
``NF`` on an absent key, ``ERR`` where the offset is no decimal number or the
range passes the record's end: a record's width is fixed by its insert), and
``DEL <key>``.

**Each field is a linearizable register.**  A name's requests are ordered by
one Paxos group, so the record as a whole is linearizable, and with it every
field.  Updates to different fields of one record commute, so a read's
answer is held field by field: the 1,000-byte reply is cut into its ten
slices, and slice ``i`` is held against the writes of field ``i`` alone by
``kv_register``'s four read rules (nobody wrote it, from the future, stale,
going back); the record the replicas hold after the drain is held the same
way, slice by slice, to what a linearizable store may hold after that
field's writes (``kv_register.allowed_values``; the loaded slice while none
was acknowledged).  A field's written bytes are unique (the generator puts
the request's sequence number in their first 12 characters; a loaded
record's slices are 100 seeded characters each), so a slice names its
write.  What the rules cannot see is in ``kv_register``'s docstring; one
thing more here: a reply that mixes two fields' states in a way no order of
the record's writes gives (field 0 older than a write that field 1's slice
proves was seen later) passes field by field.  The whole-record search is
exponential; a broken ``SETRANGE`` (a skipped, misplaced or torn write)
shows in its own field.

An ``Op`` of an update carries the key and, as its ``value``, the request's
own tail ``"<offset> <bytes>"``; only whole-field writes are known (offset a
multiple of the field width, exactly one field long): the mix sends no
other.
"""

from __future__ import annotations

import re

from . import Op
from .kv_register import allowed_values, check_reads

__all__ = ["FIELD_BYTES", "FIELD_COUNT", "RECORD_KEY", "RefRecord",
           "check_name", "check_run", "request_of"]

#: the record's shape: YCSB ``CoreWorkload``'s defaults (``fieldcount=10``,
#: ``fieldlength=100``), under the one key a name's table holds
RECORD_KEY = "r"
FIELD_COUNT = 10
FIELD_BYTES = 100
RECORD_BYTES = FIELD_COUNT * FIELD_BYTES

_NUMBER = re.compile(r"[0-9]+")


class RefRecord:
    def __init__(self):
        self.tables: dict = {}

    def apply(self, name: str, request: bytes) -> bytes:
        verb, _, rest = request.decode().partition(" ")
        table = self.tables.setdefault(name, {})
        if verb == "PUT":
            key, _, value = rest.partition(" ")
            table[key] = value
            return b"OK"
        if verb == "GET":
            return table[rest].encode() if rest in table else b"NF"
        if verb == "DEL":
            return b"OK" if table.pop(rest, None) is not None else b"NF"
        if verb == "SETRANGE":
            key, _, tail = rest.partition(" ")
            offset, space, data = tail.partition(" ")
            if key not in table:
                return b"NF"
            if not space or not _NUMBER.fullmatch(offset):
                return b"ERR"
            chars = list(table[key])
            at = int(offset)
            if at + len(data) > len(chars):
                return b"ERR"
            for i, c in enumerate(data):
                chars[at + i] = c
            table[key] = "".join(chars)
            return b"OK"
        raise ValueError(f"reference does not know {request!r}")


def request_of(op: Op) -> bytes:
    """The request bytes of an operation, as the KV app reads them (a test
    holds the generator's payloads to this)."""
    if op.kind == "update":
        return f"SETRANGE {op.key} {op.value}".encode()
    if op.kind == "read":
        return f"GET {op.key}".encode()
    raise ValueError(f"reference does not know the kind {op.kind!r}")


def _slices(record: str) -> list:
    return [record[i:i + FIELD_BYTES]
            for i in range(0, RECORD_BYTES, FIELD_BYTES)]


def _by_field(ops: list) -> tuple:
    """(``fields``, problems): ``fields[i]`` holds field ``i``'s operations
    in the order sent, as ``Op``s of a register: an update's ``value`` is the
    field's bytes, an acknowledged read's ``reply`` its slice of the
    answer."""
    fields: list = [[] for _ in range(FIELD_COUNT)]
    problems = []
    for o in ops:
        if o.key != RECORD_KEY:
            problems.append(f"{o.kind} of key {o.key!r}: the record lives "
                            f"under {RECORD_KEY!r}")
        elif o.kind == "update":
            offset, _, data = o.value.partition(" ")
            if (not _NUMBER.fullmatch(offset) or int(offset) % FIELD_BYTES
                    or int(offset) >= RECORD_BYTES
                    or len(data) != FIELD_BYTES):
                problems.append(f"update {o.value[:24]!r}: not one whole "
                                f"field of {FIELD_BYTES}")
                continue
            fields[int(offset) // FIELD_BYTES].append(
                Op("update", o.key, data, o.sent, o.done, o.status))
        elif o.kind != "read":
            problems.append(f"the record reference does not know the kind "
                            f"{o.kind!r}")
        elif o.status == "ok":
            record = (o.reply or b"").decode(errors="replace")
            if len(record) != RECORD_BYTES:
                problems.append(
                    f"read sent {o.sent:.6f} answered {len(record)} bytes "
                    f"({record[:16]!r}), not a record of {RECORD_BYTES}")
                continue
            for i, piece in enumerate(_slices(record)):
                fields[i].append(Op("read", o.key, None, o.sent, o.done,
                                    "ok", piece.encode()))
    return fields, problems


def check_name(ops: list, replicas: list, loaded: dict) -> tuple:
    """(problems, ``allowed``) of one name: the replicas' records differ; a
    key is there beside the record's; the loaded or the held record is not
    ``RECORD_BYTES`` wide; a field's slice of the held record is one no
    linearizable store may hold after that field's writes; a read breaks a
    rule in a field.  ``allowed[i]`` is the set field ``i`` may hold
    (``None`` where the name's shape was already wrong).  ``replicas`` is one
    dict per replica, ``loaded`` the name's loaded records."""
    fields, problems = _by_field(ops)
    if any(t != replicas[0] for t in replicas[1:]):
        held = [t.get(RECORD_KEY) or "" for t in replicas]
        cut = [_slices(h) for h in held]
        differ = [i for i in range(FIELD_COUNT)
                  if any(c[i] != cut[0][i] for c in cut[1:])]
        problems.append(f"replicas differ in fields {differ}: "
                        f"{[h[:24] for h in held]}")
    extra = set(replicas[0]) | set(loaded)
    extra.discard(RECORD_KEY)
    if extra:
        problems.append(f"keys beside the record's: {sorted(extra)[:4]}")
    first, held = loaded.get(RECORD_KEY), replicas[0].get(RECORD_KEY)
    if first is None or len(first) != RECORD_BYTES:
        problems.append(f"loaded {str(first)[:24]!r}: not a record of "
                        f"{RECORD_BYTES}")
        return problems, None
    if held is None or len(held) != RECORD_BYTES:
        problems.append(f"holds {str(held)[:24]!r}: not a record of "
                        f"{RECORD_BYTES}")
        return problems, None
    allowed = []
    for i, (was, now) in enumerate(zip(_slices(first), _slices(held))):
        may = allowed_values([o for o in fields[i] if o.kind == "update"],
                             was)
        allowed.append(may)
        if now not in may:
            problems.append(f"field {i} holds {now[:24]!r}, a linearizable "
                            f"store may hold {sorted(v[:24] for v in may)[:4]}")
        problems.extend(f"field {i}: {p}"
                        for p in check_reads(fields[i], was))
    return problems, allowed


def check_run(ops_by_name: dict, tables_of, readback: dict,
              initial) -> list:
    """Every problem a run shows, as strings (empty = correct).  The
    arguments are ``chipbench/references/__init__.py``'s."""
    problems = []
    for name, ops in ops_by_name.items():
        loaded = initial.get(name, {})
        ref = RefRecord()
        ref.tables[name] = dict(loaded)
        for o in ops:
            if o.kind != "update" or o.status != "ok" or o.reply is None:
                continue
            request = request_of(o)
            want = ref.apply(name, request)
            if o.reply != want:
                problems.append(f"{name}: {request[:24]!r} answered "
                                f"{o.reply!r}, the reference says {want!r}")
        tables = tables_of(name)
        found, allowed = check_name(ops, tables, loaded)
        problems.extend(f"{name}: {p}" for p in found)
        for key, got in readback.get(name, {}).items():
            held = tables[0].get(key)
            fits = (allowed is not None and key == RECORD_KEY
                    and isinstance(got, str) and len(got) == RECORD_BYTES
                    and all(piece in may
                            for piece, may in zip(_slices(got), allowed)))
            if not fits or got != held:
                problems.append(
                    f"{name}: GET {key} through the client returned "
                    f"{str(got)[:24]!r}; the replicas hold "
                    f"{str(held)[:24]!r}"
                    + ("" if fits else ", and a field of it is one no "
                       "linearizable store may hold"))
    return problems
