"""The comparison that decides ``correct`` catches planted faults."""

from chipbench.reference import (RefKV, Write, allowed_values, check_register,
                                 check_run)


def test_refkv_semantics():
    ref = RefKV()
    assert ref.apply("a", b"GET k") == b"NF"
    assert ref.apply("a", b"PUT k v 1") == b"OK"
    assert ref.apply("a", b"GET k") == b"v 1"
    assert ref.apply("b", b"GET k") == b"NF"
    assert ref.apply("a", b"DEL k") == b"OK" and ref.apply("a", b"DEL k") == b"NF"


def test_one_acknowledged_write_must_be_held():
    w = [Write("v1", 0.0, 1.0, "ok")]
    assert allowed_values(w) == {"v1"}
    assert check_register(w, [{"k": "v1"}] * 3, "k") == []
    assert check_register(w, [{"k": "zz"}] * 3, "k")   # planted: wrong value
    assert check_register(w, [{}] * 3, "k")            # planted: write lost


def test_only_writes_overlapping_the_last_may_be_held():
    w = [Write("old", 0.0, 1.0, "ok"), Write("mid", 1.5, 3.2, "ok"),
         Write("last", 3.0, 4.0, "ok")]
    assert allowed_values(w) == {"mid", "last"}
    assert check_register(w, [{"k": "mid"}] * 3, "k") == []
    assert check_register(w, [{"k": "old"}] * 3, "k")  # overwritten for sure


def test_refused_and_unanswered_writes():
    w = [Write("a", 0.0, 1.0, "ok"), Write("busy", 2.0, 2.1, "refused"),
         Write("lost", 3.0, float("nan"), "unknown")]
    assert allowed_values(w) == {"a", "lost"}   # a refused write never ran
    only_lost = [Write("lost", 3.0, float("nan"), "unknown")]
    assert allowed_values(only_lost) == {"lost", None}


def test_replicas_must_agree_and_hold_nothing_else():
    w = [Write("v1", 0.0, 1.0, "ok")]
    assert check_register(w, [{"k": "v1"}, {"k": "v1"}, {}], "k")
    assert check_register(w, [{"k": "v1", "j": "x"}] * 3, "k")


def test_check_run_end_to_end_with_planted_faults():
    writes = {"n1": [Write("v1", 0.0, 1.0, "ok")],
              "n2": [Write("v2", 0.0, 1.0, "ok")]}
    tables = {"n1": [{"k": "v1"}] * 3, "n2": [{"k": "v2"}] * 3}
    replies = [("n1", b"PUT k v1", b"OK"), ("n2", b"PUT k v2", b"OK")]
    ok = check_run(writes, tables.__getitem__, replies, {"n1": "v1"}, "k")
    assert ok == []
    bad_reply = check_run(writes, tables.__getitem__,
                          [("n1", b"PUT k v1", b"ERR")], {}, "k")
    assert len(bad_reply) == 1 and "ERR" in bad_reply[0]
    bad_get = check_run(writes, tables.__getitem__, replies, {"n2": None}, "k")
    assert len(bad_get) == 1 and "n2" in bad_get[0]
    tables["n2"] = [{"k": "v2"}, {"k": "v2"}, {"k": "stale"}]
    diverged = check_run(writes, tables.__getitem__, replies, {}, "k")
    assert len(diverged) == 1 and "replicas differ" in diverged[0]
