"""The comparison that decides ``correct`` catches planted faults."""

import time

import pytest

from chipbench import reference
from chipbench.references import Op
from chipbench.references.kv_register import (RefKV, allowed_values,
                                              check_name, check_reads,
                                              check_run, request_of)


def put(value, sent, done, status="ok", key="k"):
    return Op("update", key, value, sent, done, status,
              b"OK" if status == "ok" else None)


def get(saw, sent, done, status="ok", key="k"):
    reply = None if status != "ok" else b"NF" if saw is None else saw.encode()
    return Op("read", key, None, sent, done, status, reply)


def test_refkv_semantics():
    ref = RefKV()
    assert ref.apply("a", b"GET k") == b"NF"
    assert ref.apply("a", b"PUT k v 1") == b"OK"
    assert ref.apply("a", b"GET k") == b"v 1"
    assert ref.apply("b", b"GET k") == b"NF"
    assert ref.apply("a", b"DEL k") == b"OK" and ref.apply("a", b"DEL k") == b"NF"
    assert [request_of(o) for o in (put("v 1", 0, 1), get(None, 0, 1),
                                    Op("delete", "k", None, 0, 1, "ok"))] \
        == [b"PUT k v 1", b"GET k", b"DEL k"]


def test_one_acknowledged_write_must_be_held():
    w = [put("v1", 0.0, 1.0)]
    assert allowed_values(w) == {"v1"}
    assert check_name(w, [{"k": "v1"}] * 3, {}) == []
    assert check_name(w, [{"k": "zz"}] * 3, {})   # planted: wrong value
    assert check_name(w, [{}] * 3, {})            # planted: write lost


def test_only_writes_overlapping_the_last_may_be_held():
    w = [put("old", 0.0, 1.0), put("mid", 1.5, 3.2), put("last", 3.0, 4.0)]
    assert allowed_values(w) == {"mid", "last"}
    assert check_name(w, [{"k": "mid"}] * 3, {}) == []
    assert check_name(w, [{"k": "old"}] * 3, {})  # overwritten for sure


def test_refused_and_unanswered_writes():
    w = [put("a", 0.0, 1.0), put("busy", 2.0, 2.1, "refused"),
         put("lost", 3.0, float("nan"), "unknown")]
    assert allowed_values(w) == {"a", "lost"}   # a refused write never ran
    only_lost = [put("lost", 3.0, float("nan"), "unknown")]
    assert allowed_values(only_lost) == {"lost", None}
    assert allowed_values(only_lost, "loaded") == {"lost", "loaded"}
    assert allowed_values(w, "loaded") == {"a", "lost"}


def test_replicas_must_agree_and_hold_nothing_else():
    w = [put("v1", 0.0, 1.0)]
    assert check_name(w, [{"k": "v1"}, {"k": "v1"}, {}], {})
    assert check_name(w, [{"k": "v1", "j": "x"}] * 3, {})
    # a loaded key that nobody touched must still hold its record
    assert check_name(w, [{"k": "v1", "j": "x"}] * 3, {"j": "x"}) == []
    assert check_name(w, [{"k": "v1"}] * 3, {"j": "x"})
    assert check_name(w, [{"k": "v1", "j": "y"}] * 3, {"j": "x"})


def test_check_run_end_to_end_with_planted_faults():
    ops = {"n1": [put("v1", 0.0, 1.0)], "n2": [put("v2", 0.0, 1.0)]}
    tables = {"n1": [{"k": "v1"}] * 3, "n2": [{"k": "v2"}] * 3}
    assert check_run(ops, tables.__getitem__, {"n1": {"k": "v1"}}, {}) == []
    ops["n1"][0].reply = b"ERR"
    bad_reply = check_run(ops, tables.__getitem__, {}, {})
    assert len(bad_reply) == 1 and "ERR" in bad_reply[0]
    ops["n1"][0].reply = b"OK"
    bad_get = check_run(ops, tables.__getitem__, {"n2": {"k": None}}, {})
    assert len(bad_get) == 1 and "n2" in bad_get[0]
    tables["n2"] = [{"k": "v2"}, {"k": "v2"}, {"k": "stale"}]
    diverged = check_run(ops, tables.__getitem__, {}, {})
    assert len(diverged) == 1 and "replicas differ" in diverged[0]


def test_the_write_only_form_is_the_same_check():
    """``chipbench/reference.py``, which ``tests/`` import: ``Write`` and
    the five-argument ``check_run`` give what ``kv_register`` gives."""
    assert reference.RefKV is RefKV
    writes = {"n1": [reference.Write("v1", 0.0, 1.0, "ok")],
              "n2": [reference.Write("v2", 0.0, 1.0, "ok")]}
    tables = {"n1": [{"k": "v1"}] * 3, "n2": [{"k": "v2"}] * 3}
    replies = [("n1", b"PUT k v1", b"OK"), ("n2", b"PUT k v2", b"OK")]
    assert reference.check_run(writes, tables.__getitem__, replies,
                               {"n1": "v1"}, "k") == []
    bad_reply = reference.check_run(writes, tables.__getitem__,
                                    [("n1", b"PUT k v1", b"ERR")], {}, "k")
    assert len(bad_reply) == 1 and "ERR" in bad_reply[0]
    bad_get = reference.check_run(writes, tables.__getitem__, replies,
                                  {"n2": None}, "k")
    assert len(bad_get) == 1 and "n2" in bad_get[0]
    tables["n2"] = [{"k": "v2"}, {"k": "v2"}, {}]
    dropped = reference.check_run(writes, tables.__getitem__, replies, {}, "k")
    assert len(dropped) == 1 and "replicas differ" in dropped[0]
    # a reply that is no listed write's is refused, not left unchecked
    for stray in (("n1", b"GET k", b"v1"), ("n3", b"PUT k v1", b"OK"),
                  ("n1", b"PUT j v1", b"OK")):
        with pytest.raises(ValueError, match="match no listed write"):
            reference.check_run(writes, tables.__getitem__, replies + [stray],
                                {}, "k")


# ------------------------------------------------ reads: the four rules
def clean_history() -> dict:
    """One name, one key, the record ``L`` loaded.  Five requests overlap
    (sent 1.0-1.4, answered 2.0-2.4) and were committed in the order ``b``,
    read, ``a``, read, ``c``: not the order sent.  Then one write at a time,
    one of unknown status among them, with reads between and across."""
    return {
        "r0": get("L", 0.1, 0.5),             # before any write: the load
        "a": put("a", 1.0, 2.4),
        "r1": get("b", 1.1, 2.0),             # committed after b, before a
        "b": put("b", 1.2, 2.2),
        "r2": get("a", 1.3, 2.3),             # committed after a, before c
        "c": put("c", 1.4, 2.1),
        "r3": get("c", 3.0, 3.5),             # after all five: c was last
        "d": put("d", 4.0, 5.0),
        "r4": get("d", 5.5, 6.0),
        "busy": put("busy", 6.1, 6.2, "refused"),
        "lost": put("lost", 6.5, float("nan"), "unknown"),
        "e": put("e", 6.8, 12.0),             # a long write, and reads in it
        "r5": get("d", 7.0, 7.5),             # before e (and lost) took effect
        "r6": get("e", 9.0, 9.5),
        "r7": get("e", 10.0, 10.5),
        "r8": get("e", 13.0, 13.5),
        "r9": get("e", 14.0, float("nan"), "unknown"),
    }


def _problems(history: dict) -> list:
    ops = sorted(history.values(), key=lambda o: o.sent)
    held = [{"k": "e"}] * 3
    return check_run({"n": ops}, {"n": held}.__getitem__, {"n": {"k": "e"}},
                     {"n": {"k": "L"}})


def _saw(history: dict, read: str, value) -> None:
    history[read].reply = b"NF" if value is None else value.encode()


def _acknowledge(history: dict, write: str, done: float) -> None:
    history[write].status, history[write].done = "ok", done
    history[write].reply = b"OK"


#: each fault alone, planted in the clean history, and the one rule it breaks
FAULTS = {
    "an invented value": (lambda h: _saw(h, "r3", "zz"), "nobody wrote it"),
    "not found beside a loaded record":
        (lambda h: _saw(h, "r0", None), "nobody wrote it"),
    "a refused write's value":
        (lambda h: _saw(h, "r5", "busy"), "a refused write's"),
    "from the future": (lambda h: _saw(h, "r4", "e"), "from the future"),
    "stale: overwritten before the read began":
        (lambda h: _saw(h, "r4", "c"), "stale"),
    "stale: the loaded record after an acknowledged write":
        (lambda h: _saw(h, "r3", "L"), "stale"),
    "stale and going back besides: one problem a read":
        (lambda h: _saw(h, "r8", "d"), "stale"),
    "going back inside a write in flight":
        (lambda h: _saw(h, "r7", "d"), "going back"),
    "an unknown write, acknowledged, rules the older value out":
        (lambda h: _acknowledge(h, "lost", 6.9), "stale"),
}


def test_the_clean_history_with_reads_out_of_send_order_has_no_problem():
    history = clean_history()
    overlapping = [history[k] for k in ("a", "r1", "b", "r2", "c")]
    assert max(o.sent for o in overlapping) < min(o.done for o in overlapping)
    assert _problems(history) == []
    # the unknown write's value would be no problem either: it may have run
    _saw(history, "r5", "lost")
    _saw(history, "r6", "lost")
    assert _problems(history) == []


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_planted_read_fault_is_exactly_one_problem(fault):
    history = clean_history()
    plant, rule = FAULTS[fault]
    plant(history)
    problems = _problems(history)
    assert len(problems) == 1, problems
    assert rule in problems[0], problems


def test_what_the_four_rules_cannot_tell_is_left_alone():
    # a read in flight with a write may see either side of it
    for saw in ("a", "b"):
        ops = [put("a", 0.0, 1.0), put("b", 2.0, 3.0), get(saw, 2.5, 3.5)]
        assert check_reads(ops) == []
    # ... and so may every read after a write of unknown status was sent,
    # in that order alone: once a read saw it, the older value is gone
    lost = [put("a", 0.0, 1.0), put("b", 2.0, float("nan"), "unknown")]
    assert check_reads(lost + [get("a", 5.0, 6.0), get("b", 7.0, 8.0)]) == []
    assert check_reads(lost + [get("a", 5.0, 6.0), get("a", 7.0, 8.0)]) == []
    back = check_reads(lost + [get("b", 5.0, 6.0), get("a", 7.0, 8.0)])
    assert len(back) == 1 and "going back" in back[0]
    # reads that were refused or got no reply say nothing
    assert check_reads([put("a", 0.0, 1.0), get(None, 2.0, 3.0, "refused"),
                        get(None, 2.0, float("nan"), "unknown")]) == []
    with pytest.raises(ValueError):   # the rules need unique values
        check_reads([put("a", 0.0, 1.0), put("a", 2.0, 3.0), get("a", 4, 5)])
    assert "deletes" in check_reads([Op("delete", "k", None, 0.0, 1.0, "ok"),
                                     get(None, 2.0, 3.0)])[0]


def test_twenty_thousand_requests_with_a_hot_name_check_in_under_a_second():
    import numpy as np

    rng = np.random.default_rng(35)
    ops: dict = {}
    tables = {}
    for i in range(20000):
        name = "hot" if i < 800 else f"n{int(rng.integers(15000))}"
        sent = i * 0.001
        if rng.random() < 0.5:
            op = put(f"{i:012d}", sent, sent + 0.15)
            tables[name] = [{"k": op.value}] * 3
        else:   # answers what the last completed write left
            done = [o for o in ops.get(name, []) if o.kind == "update"
                    and o.done < sent]
            op = get(done[-1].value if done else None, sent, sent + 0.15)
        ops.setdefault(name, []).append(op)
    t = time.perf_counter()
    problems = check_run(ops, lambda n: tables.get(n, [{}] * 3), {}, {})
    took = time.perf_counter() - t
    assert problems == [] and took < 1.0, (took, problems[:3])
