"""``KVApp``'s ``SETRANGE`` (ISSUE 36: YCSB's one-field update), against the
plain reference ``chipbench/references/kv_record.RefRecord``: seeded random
sequences with every answer the request can give; ``checkpoint`` ->
``restore``; and on a small ``InProcessCluster`` the served path (client ->
ActiveReplica -> PaxosManager -> tick -> WAL -> reply), a replay of the
journal after a restart, and the three counters the PR adds beside it.
"""

import contextlib
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import deployment, load, spec  # noqa: E402
from chipbench.references.kv_record import RefRecord  # noqa: E402
from gigapaxos_tpu.models.replicable import KVApp  # noqa: E402
from gigapaxos_tpu.obs.metrics import registry  # noqa: E402

NAMES = ("alice", "bob", "carol")
KEYS = ("r", "s")
ALPHABET = "abcdefghijklmnopqrstuvwxyz0123456789"


def _word(rng, n: int) -> str:
    return "".join(ALPHABET[i] for i in rng.integers(0, len(ALPHABET), n))


def _random_request(rng, widths: dict) -> tuple:
    """(name, request) of one seeded operation; ``widths`` tracks what each
    (name, key) holds so that ranges fall inside, at the end and beyond."""
    name, key = NAMES[rng.integers(3)], KEYS[rng.integers(2)]
    verb = ("PUT", "SETRANGE", "SETRANGE", "SETRANGE", "GET", "DEL"
            )[rng.integers(6)]
    if verb == "PUT":
        value = _word(rng, int(rng.integers(0, 40)))
        widths[name, key] = len(value)
        return name, f"PUT {key} {value}"
    if verb in ("GET", "DEL"):
        if verb == "DEL":
            widths.pop((name, key), None)
        return name, f"{verb} {key}"
    width = widths.get((name, key), 10)
    data = _word(rng, int(rng.integers(0, 12)))
    shape = rng.integers(8)
    if shape == 0:   # exactly up to the end: the last range that fits
        offset = str(max(0, width - len(data)))
    elif shape == 1:  # one past it
        offset = str(width - len(data) + 1)
    elif shape == 2:  # no number at all
        offset = ("x", "-1", "+1", "1.0", "", "1_0", "١")[rng.integers(7)]
    else:
        offset = str(int(rng.integers(0, width + 3)))
    return name, f"SETRANGE {key} {offset} {data}"


@pytest.mark.parametrize("seed", range(12))
def test_setrange_agrees_with_the_reference_on_seeded_sequences(seed):
    rng = np.random.default_rng([seed, 36])
    app, ref, widths, answers = KVApp(), RefRecord(), {}, set()
    for i in range(600):
        name, request = _random_request(rng, widths)
        got = app.execute(name, request.encode(), i)
        assert got == ref.apply(name, request.encode()), (i, request)
        answers.add((request.split(" ", 1)[0], got[:3]
                     if got in (b"OK", b"NF", b"ERR") else b"<v>"))
    # every answer each request can give was met, the three of SETRANGE too
    assert answers >= {("SETRANGE", b"OK"), ("SETRANGE", b"NF"),
                       ("SETRANGE", b"ERR"), ("PUT", b"OK"), ("GET", b"NF"),
                       ("GET", b"<v>"), ("DEL", b"OK"), ("DEL", b"NF")}
    assert {n: t for n, t in app.db.items() if t} == {
        n: t for n, t in ref.tables.items() if t}


@pytest.mark.parametrize("request_,answer,left", [
    ("SETRANGE r 0 AB", b"OK", "AB23456789"),
    ("SETRANGE r 8 AB", b"OK", "01234567AB"),
    ("SETRANGE r 10 ", b"OK", "0123456789"),     # nothing, at the very end
    ("SETRANGE r 9 AB", b"ERR", "0123456789"),   # passes the end: no padding
    ("SETRANGE r 11 ", b"ERR", "0123456789"),
    ("SETRANGE r -1 A", b"ERR", "0123456789"),
    ("SETRANGE r one A", b"ERR", "0123456789"),
    ("SETRANGE r 3", b"ERR", "0123456789"),      # no bytes field at all
    ("SETRANGE r", b"ERR", "0123456789"),
    ("SETRANGE q 0 A", b"NF", "0123456789"),
    ("SETRANGE q x A", b"NF", "0123456789"),     # absent wins over malformed
    ("SETRANGE r 2 a b c", b"OK", "01a b c789"),  # the bytes may hold spaces
])
def test_setrange_answers(request_, answer, left):
    app, ref = KVApp(), RefRecord()
    for target in (lambda q: app.execute("n", q, 1), lambda q: ref.apply("n", q)):
        assert target(b"PUT r 0123456789") == b"OK"
        assert target(request_.encode()) == answer
        assert target(b"GET r") == left.encode()
    assert "q" not in app.db["n"] and "q" not in ref.tables["n"]


def test_a_record_written_field_by_field_survives_checkpoint_and_restore():
    rng = np.random.default_rng(7)
    app = KVApp()
    app.execute("u", b"PUT r " + b"." * 1000, 0)
    fields = {}
    for i in range(40):
        field = int(rng.integers(10))
        fields[field] = _word(rng, 100)
        assert app.execute(
            "u", f"SETRANGE r {100 * field} {fields[field]}".encode(), i
        ) == b"OK"
    record = app.db["u"]["r"]
    assert len(record) == 1000
    for field in range(10):
        assert record[100 * field:100 * field + 100] == fields.get(
            field, "." * 100)
    other = KVApp()
    other.restore("u", app.checkpoint("u"))
    assert other.db["u"] == {"r": record}
    assert other.execute("u", b"GET r", 99) == record.encode()
    assert other.execute("u", b"SETRANGE r 900 " + b"z" * 100, 100) == b"OK"
    assert other.db["u"]["r"] == record[:900] + "z" * 100


# ------------------------------------------------- through the served path
CONFIG = "chipbench/configs/rehearsal-ycsb-a-3r-4k.json"
N_NAMES = 48
DEADLINE_S = 60.0
FAMILIES = ("inbox_deferred_requests", "wal_append_bytes", "app_reply_bytes")


def _ask(client, actives, ops: list, staged_under=None) -> list:
    """Send ``ops`` [(name, request bytes)] at once; the reply bodies.
    ``staged_under``: a manager whose lock is held until all are staged, so
    that one inbox build meets them all (``propose`` stages without it)."""
    from gigapaxos_tpu.reconfiguration import packets as pkt

    got, done = [None] * len(ops), threading.Semaphore(0)

    def on_reply(i, p):
        got[i] = p
        done.release()

    m = staged_under
    with m.lock if m is not None else contextlib.nullcontext():
        for i, (name, request) in enumerate(ops):
            client.send_request(name, request, lambda p, i=i: on_reply(i, p),
                                active=actives[i % len(actives)])
        until = time.monotonic() + DEADLINE_S
        while m is not None and len(m._staged) < len(ops):
            assert time.monotonic() < until, "the requests were not staged"
            time.sleep(0.005)
    for _ in ops:
        assert done.acquire(timeout=DEADLINE_S), "a request got no reply"
    assert all(p.get("ok") for p in got), got
    return [pkt.b64d(p["response"]) or b"" for p in got]


def _histograms() -> dict:
    snap = registry().snapshot()
    return {f: snap[f + "{plane=ar}"] for f in FAMILIES}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Records loaded by ``PUT``, fields written by ``SETRANGE`` through the
    client (the hot name with more than P at once), then a second cluster on
    the same journal."""
    from gigapaxos_tpu.client import ReconfigurableAppClient

    run_dir = str(tmp_path_factory.mktemp("setrange"))
    config = spec.load_config(CONFIG)
    out: dict = {}
    with pytest.MonkeyPatch.context() as env:
        env.setenv("GPTPU_PALLAS", "1")
        env.setenv("GPTPU_PALLAS_INTERPRET", "1")
        cfg = deployment.make_config(config)
        cluster = deployment.build_cluster(config, cfg, run_dir, 600.0)
        client = None
        try:
            names = deployment.populate(cluster, N_NAMES)
            actives = list(cfg.nodes.active_ids())
            client = ReconfigurableAppClient(cfg.nodes)
            ref = RefRecord()
            rng = np.random.default_rng(36)
            puts = [(n, f"PUT r {_word(rng, 1000)}".encode()) for n in names]
            # 3 * P writes of distinct fields to one name at once (their
            # order does not matter), one write each to the others, a bad one
            P = cluster.manager.P
            hot = [(names[0], f"SETRANGE r {100 * (i % 10)} "
                    f"{_word(rng, 100)}".encode()) for i in range(10)]
            assert len(hot) > 2 * P
            cold = [(n, f"SETRANGE r {100 * int(rng.integers(10))} "
                     f"{_word(rng, 100)}".encode()) for n in names[1:]]
            bad = [(names[1], b"SETRANGE r 950 " + b"x" * 100),
                   (names[2], b"SETRANGE q 0 x")]
            before = _histograms()
            replies = []
            for ops in (puts, hot + cold, bad,
                        [(n, b"GET r") for n in names]):
                got = _ask(client, actives, ops,
                           cluster.manager if ops[0] is hot[0] else None)
                want = [ref.apply(n, q) for n, q in ops]
                assert got == want, [(o, g[:20], w[:20]) for o, g, w
                                     in zip(ops, got, want) if g != w]
                replies += got
            out["after"], out["before"] = _histograms(), before
            out["answers"] = set(r for r in replies if len(r) < 4)
            out["tables"] = {n: deployment.replica_tables(cluster, n)
                             for n in names}
            out["reference"] = {n: ref.tables[n] for n in names}
            out["checkpoints"] = {n: cluster.manager.apps[0].checkpoint(
                f"{n}#0") for n in names}
            out["names"] = names
        finally:
            if client is not None:
                client.close()
            cluster.close()
        cfg = deployment.make_config(config)
        cluster = deployment.build_cluster(config, cfg, run_dir, 600.0)
        client = ReconfigurableAppClient(cfg.nodes)
        try:
            out["replayed"] = {n: deployment.replica_tables(cluster, n)
                               for n in names}
            out["read_after_restart"] = load.read_back(
                client, names[:8], list(cfg.nodes.active_ids()), "r",
                DEADLINE_S)
        finally:
            client.close()
            cluster.close()
    return out


def test_setrange_through_the_served_path_equals_the_reference(served):
    assert served["answers"] == {b"OK", b"ERR", b"NF"}
    for name in served["names"]:
        assert served["tables"][name] == [served["reference"][name]] * 3
        assert len(served["tables"][name][0]["r"]) == 1000


def test_the_journal_replays_setrange_after_a_restart(served):
    assert served["replayed"] == served["tables"]
    for name, got in served["read_after_restart"].items():
        assert got == served["reference"][name]["r"]


def test_a_checkpoint_of_a_served_record_restores_it(served):
    app = KVApp()
    for name, blob in served["checkpoints"].items():
        app.restore(name, blob)
        assert app.db[name] == served["reference"][name]


def test_the_three_counters_count_what_the_requests_were(served):
    """``inbox_deferred_requests``, ``wal_append_bytes`` (one observation a
    tick each) and ``app_reply_bytes`` (one a released scalar request)."""
    rose = {f: (served["after"][f]["count"] - served["before"][f]["count"],
                served["after"][f]["sum"] - served["before"][f]["sum"])
            for f in FAMILIES}
    n = len(served["names"])
    # 48 PUTs + 57 SETRANGEs + 2 refused + 48 GETs released; the GETs
    # answered 1,000 bytes, the others OK / ERR / NF
    count, total = rose["app_reply_bytes"]
    assert count == n + (10 + n - 1) + 2 + n
    assert total == 1000 * n + 2 * (n + 10 + n - 1) + 3 + 2
    ticks, deferred = rose["inbox_deferred_requests"]
    # ten writes to one name at once against P = 4 a name a tick: the first
    # inbox that held them all left six behind, the next two
    assert ticks > 4 and deferred >= 6 + 2
    ticks_wal, journaled = rose["wal_append_bytes"]
    assert abs(ticks_wal - ticks) <= 1   # a snapshot may fall inside a tick
    # the bodies were journaled once each: 48 KB of records, 5.7 KB of fields
    bodies = 1006 * n + 115 * (10 + n - 1)
    assert bodies < journaled < 2 * bodies
