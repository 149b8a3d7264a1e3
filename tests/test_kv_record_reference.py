"""``chipbench/references/kv_record.check_run`` (ISSUE 36), which decides
``correct`` for the YCSB deployment: a clean history passes, overlapping
writes to different fields of one record included, and each planted fault,
alone in an otherwise clean history, is reported.
"""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import deployment  # noqa: E402
from chipbench.generators.open_poisson import unique_values  # noqa: E402
from chipbench.references import Op, kv_record  # noqa: E402

import numpy as np  # noqa: E402

NAME, OTHER = "bg0", "bg1"
LOADED = {f"bg{i}": {"r": v.decode()} for i, v in enumerate(
    deployment.record_values(36, 3, 1000).tolist())}
FRESH = unique_values(np.random.default_rng(36), 16, 100, 0)


def _with(record: str, **fields) -> str:
    """``record`` with field ``f<i>`` replaced."""
    for f, data in fields.items():
        i = int(f[1:])
        record = record[:100 * i] + data + record[100 * i + 100:]
    return record


def _update(field: int, data: str, sent, done, status="ok", reply=b"OK"):
    return Op("update", "r", f"{100 * field} {data}", sent, done, status,
              reply if status == "ok" else None)


def _read(sent, done, record: str):
    return Op("read", "r", None, sent, done, "ok", record.encode())


def clean() -> dict:
    """One name's history, no fault in it: a write and a read of it; two
    overlapping writes to different fields and a read beside them that saw
    one; a long write that a read sees before it is acknowledged; a write
    nobody answered; a refused one; an untouched second name.  Returns what
    ``check_run`` takes."""
    w = FRESH
    L = LOADED[NAME]["r"]
    ops = [
        _update(3, w[0], 1.0, 1.2),
        _read(1.3, 1.5, _with(L, f3=w[0])),
        _update(3, w[1], 2.0, 2.9),              # long: acknowledged late
        _update(7, w[2], 2.1, 2.2),              # beside it, another field
        _read(2.15, 2.25, _with(L, f3=w[0], f7=w[2])),   # saw one of the two
        _read(2.3, 2.4, _with(L, f3=w[1], f7=w[2])),     # ... then both
        _read(2.5, 2.6, _with(L, f3=w[1], f7=w[2])),
        _update(5, w[3], 3.0, float("nan"), "unknown"),
        _update(1, w[4], 3.1, 3.2, "refused"),
        _read(3.5, 3.6, _with(L, f3=w[1], f7=w[2], f5=w[3])),
    ]
    final = _with(L, f3=w[1], f7=w[2], f5=w[3])
    tables = {NAME: [{"r": final} for _ in range(3)],
              OTHER: [dict(LOADED[OTHER]) for _ in range(3)]}
    return {"ops": {NAME: ops, OTHER: [_read(1.0, 1.1, LOADED[OTHER]["r"])]},
            "tables": tables,
            "readback": {NAME: {"r": final}, OTHER: dict(LOADED[OTHER])}}


def check(h: dict) -> list:
    return kv_record.check_run(h["ops"], lambda n: h["tables"][n],
                               h["readback"], LOADED)


def test_a_clean_history_with_overlapping_field_writes_passes():
    assert check(clean()) == []
    # the write nobody answered may as well not have run
    h = clean()
    L = LOADED[NAME]["r"]
    final = _with(L, f3=FRESH[1], f7=FRESH[2])
    h["tables"][NAME] = [{"r": final} for _ in range(3)]
    h["readback"][NAME] = {"r": final}
    h["ops"][NAME][-1] = _read(3.5, 3.6, final)
    assert check(h) == []
    # two overlapping writes to ONE field: either may be the one that stays
    for stays in (FRESH[5], FRESH[6]):
        h = clean()
        h["ops"][NAME] += [_update(9, FRESH[5], 4.0, 4.3),
                           _update(9, FRESH[6], 4.1, 4.2)]
        final = _with(h["tables"][NAME][0]["r"], f9=stays)
        h["tables"][NAME] = [{"r": final} for _ in range(3)]
        h["readback"][NAME] = {"r": final}
        assert check(h) == []
    # no request at all and nothing loaded: nothing to say
    assert kv_record.check_run({}, lambda n: [], {}, {}) == []


def _planted(fault: str) -> dict:
    h = clean()
    ops, L, w = h["ops"][NAME], LOADED[NAME]["r"], FRESH
    held = h["tables"][NAME][0]["r"]
    if fault == "nobody_wrote_it":
        ops[6] = _read(2.5, 2.6, _with(L, f3=w[1], f7=w[2], f2="x" * 100))
    elif fault == "another_fields_bytes":   # field 3's bytes, seen in field 4
        ops[6] = _read(2.5, 2.6, _with(L, f3=w[1], f7=w[2], f4=w[1]))
    elif fault == "stale":    # w[0] was overwritten (2.9) before the read began
        ops[-1] = _read(3.5, 3.6, _with(held, f3=w[0]))
    elif fault == "going_back":   # the read before saw w[1]; w[1] is not
        ops[6] = _read(2.5, 2.6, _with(L, f3=w[0], f7=w[2]))  # acked yet
    elif fault == "from_the_future":
        ops[1] = _read(1.3, 1.5, _with(L, f3=w[0], f7=w[2]))
    elif fault == "refused_writes_bytes":
        ops[-1] = _read(3.5, 3.6, _with(held, f1=w[4]))
    elif fault == "reply_999_wide":
        ops[6] = _read(2.5, 2.6, _with(L, f3=w[1], f7=w[2])[:999])
    elif fault == "read_not_found":
        ops[6] = Op("read", "r", None, 2.5, 2.6, "ok", b"NF")
    elif fault == "one_replica_differs_in_one_field":
        h["tables"][NAME][1] = {"r": _with(held, f7=L[700:800])}
    elif fault == "lost_acknowledged_update":   # field 7 as loaded, everywhere
        lost = _with(held, f7=L[700:800])
        h["tables"][NAME] = [{"r": lost} for _ in range(3)]
        h["readback"][NAME] = {"r": lost}
    elif fault == "torn_update":   # half of an acknowledged field written
        torn = _with(held, f7=w[2][:50] + L[750:800])
        h["tables"][NAME] = [{"r": torn} for _ in range(3)]
        h["readback"][NAME] = {"r": torn}
    elif fault == "update_answered_err":
        ops[3] = _update(7, w[2], 2.1, 2.2, reply=b"ERR")
    elif fault == "another_key":
        ops.append(Op("read", "k", None, 5.0, 5.1, "ok", b"NF"))
    elif fault == "a_key_beside_the_record":
        for table in h["tables"][NAME]:
            table["k"] = "v"
    elif fault == "record_shorter_than_loaded":
        h["tables"][NAME] = [{"r": held[:900]} for _ in range(3)]
    elif fault == "readback_differs":
        h["readback"][NAME] = {"r": _with(held, f7=L[700:800])}
    elif fault == "readback_unanswered":
        h["readback"][NAME] = {"r": "<no acknowledged GET: None>"}
    elif fault == "untouched_name_changed":
        h["tables"][OTHER][2] = {"r": _with(LOADED[OTHER]["r"], f0="y" * 100)}
    elif fault == "not_a_whole_field":
        ops.append(Op("update", "r", f"150 {w[7]}", 5.0, 5.1, "ok", b"OK"))
    else:
        raise AssertionError(fault)
    return h


@pytest.mark.parametrize("fault,says", [
    ("nobody_wrote_it", "field 2: read sent 2.5"),
    ("another_fields_bytes", "field 4: read sent 2.5"),
    ("stale", "stale: overwritten before the read began"),
    ("going_back", "going back: an earlier read saw a later write"),
    ("from_the_future", "from the future"),
    ("refused_writes_bytes", "a refused write's"),
    ("reply_999_wide", "answered 999 bytes"),
    ("read_not_found", "answered 2 bytes"),
    ("one_replica_differs_in_one_field", "replicas differ in fields [7]"),
    ("lost_acknowledged_update", "field 7 holds"),
    ("torn_update", "field 7 holds"),
    ("update_answered_err", "answered b'ERR', the reference says b'OK'"),
    ("another_key", "the record lives under 'r'"),
    ("a_key_beside_the_record", "keys beside the record's: ['k']"),
    ("record_shorter_than_loaded", "not a record of 1000"),
    ("readback_differs", "GET r through the client returned"),
    ("readback_unanswered", "GET r through the client returned"),
    ("untouched_name_changed", "bg1: replicas differ in fields [0]"),
    ("not_a_whole_field", "not one whole field of 100"),
])
def test_each_planted_fault_alone_is_reported(fault, says):
    problems = check(_planted(fault))
    assert problems and any(says in p for p in problems), problems
    # ... and it is the only thing wrong: the history it was planted in is
    # clean, and every problem names the name it was planted in
    want = OTHER if fault == "untouched_name_changed" else NAME
    assert all(p.startswith(want + ": ") for p in problems), problems


def test_planting_changes_nothing_of_the_clean_history():
    before = copy.deepcopy(clean())
    _planted("lost_acknowledged_update")
    assert check(before) == [] and check(clean()) == []


def test_the_record_reference_imports_nothing_of_the_program():
    with open(kv_record.__file__) as f:
        source = f.read()
    assert "gigapaxos" not in source.replace("``models/replicable.KVApp``", "")
    assert kv_record.RECORD_KEY == "r"
    assert (kv_record.FIELD_COUNT, kv_record.FIELD_BYTES) == (10, 100)
