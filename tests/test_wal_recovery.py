"""WAL + recovery tests: crash/restart state parity via deterministic replay
(the analog of the reference's kill-and-recover testing around
``PaxosManager.initiateRecovery``, PaxosManager.java:1852-2055)."""

import os

import numpy as np

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.wal.journal import PyJournal, read_journal
from gigapaxos_tpu.wal.logger import PaxosLogger, recover


def mk(tmp_path, ckpt_every=1024):
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 32
    apps = [KVApp() for _ in range(3)]
    wal = PaxosLogger(str(tmp_path), checkpoint_every_ticks=ckpt_every,
                      native=False)
    return cfg, apps, PaxosManager(cfg, 3, apps, wal=wal)


def drive(m, n_names=3, n_reqs=8):
    for g in range(n_names):
        m.create_paxos_instance(f"kv{g}", [0, 1, 2])
    for g in range(n_names):
        for i in range(n_reqs):
            m.propose(f"kv{g}", f"PUT k{i} {g}.{i}".encode())
    m.run_ticks(8)


def test_journal_roundtrip_and_torn_tail(tmp_path):
    p = str(tmp_path / "j.log")
    j = PyJournal(p)
    for i in range(5):
        j.append(f"rec{i}".encode())
    j.close()
    assert read_journal(p) == [f"rec{i}".encode() for i in range(5)]
    # simulate a crash mid-write: append garbage half-record
    with open(p, "ab") as f:
        f.write(b"\x63\x00\x00\x00\xde\xad")
    assert read_journal(p) == [f"rec{i}".encode() for i in range(5)]
    # reopening repairs the tear so new appends stay readable
    j2 = PyJournal(p)
    j2.append(b"after")
    j2.close()
    assert read_journal(p)[-1] == b"after"


def test_recovery_state_parity(tmp_path):
    cfg, apps, m = mk(tmp_path)
    drive(m)
    exec_before = np.array(m.state.exec_slot).copy()
    db_before = [dict(a.db) for a in apps]
    m.wal.close()  # crash

    apps2 = [KVApp() for _ in range(3)]
    m2 = recover(cfg, 3, apps2, str(tmp_path), native=False)
    assert np.array_equal(np.array(m2.state.exec_slot), exec_before)
    assert np.array_equal(np.array(m2.state.bal_num), np.array(m.state.bal_num))
    for r in range(3):
        assert apps2[r].db == db_before[r]
    # recovered manager keeps working and rid space does not collide
    done = []
    rid = m2.propose("kv0", b"PUT post 1", lambda _r, resp: done.append(resp))
    assert rid is not None and rid >= m._next_rid
    m2.run_ticks(3)
    assert done == [b"OK"]
    m2.wal.close()


def test_recovery_with_checkpoint_rollover(tmp_path):
    cfg, apps, m = mk(tmp_path, ckpt_every=4)  # checkpoint every 4 ticks
    drive(m, n_names=2, n_reqs=12)
    snaps = [f for f in os.listdir(tmp_path) if f.startswith("snapshot")]
    assert snaps, "expected at least one checkpoint"
    db_before = dict(apps[0].db)
    m.wal.close()

    apps2 = [KVApp() for _ in range(3)]
    m2 = recover(cfg, 3, apps2, str(tmp_path), native=False)
    assert apps2[0].db == db_before
    assert m2.tick_num == m.tick_num
    m2.wal.close()


def test_recovery_preserves_stop_state(tmp_path):
    cfg, apps, m = mk(tmp_path)
    m.create_paxos_instance("svc", [0, 1, 2])
    m.propose("svc", b"PUT a 1")
    m.propose_stop("svc")
    m.run_ticks(4)
    assert m.is_stopped("svc")
    m.wal.close()

    apps2 = [KVApp() for _ in range(3)]
    m2 = recover(cfg, 3, apps2, str(tmp_path), native=False)
    assert m2.is_stopped("svc")
    # stopped groups reject new work after recovery too (fail-fast None)
    got = []
    assert m2.propose("svc", b"PUT b 2", lambda r, resp: got.append(resp)) is None
    m2.run_ticks(3)
    assert got == [None]
    m2.wal.close()


def test_recovery_idempotent_double_crash(tmp_path):
    cfg, apps, m = mk(tmp_path)
    drive(m, n_names=1, n_reqs=5)
    m.wal.close()
    apps2 = [KVApp() for _ in range(3)]
    m2 = recover(cfg, 3, apps2, str(tmp_path), native=False)
    m2.propose("kv0", b"PUT x y")
    m2.run_ticks(2)
    db = dict(apps2[0].db)
    tick = m2.tick_num
    m2.wal.close()  # crash again
    apps3 = [KVApp() for _ in range(3)]
    m3 = recover(cfg, 3, apps3, str(tmp_path), native=False)
    assert apps3[0].db == db
    assert m3.tick_num == tick
    m3.wal.close()


def test_recovery_preserves_free_list_order(tmp_path):
    """Pause churn reorders the row free-list (LIFO); a checkpoint taken then
    must restore it verbatim, or journaled OP_UNPAUSE replay re-allocates
    different rows than the live run and row-addressed OP_TICK placements
    land on the wrong groups (silently losing committed writes)."""
    cfg, apps, m = mk(tmp_path)
    drive(m, n_names=3, n_reqs=2)  # kv0,kv1,kv2 on rows 0,1,2; quiescent
    m._sweep_outstanding()
    # free rows 0 then 1 -> free list tail is [..., 0, 1], next alloc pops 1
    m._do_pause(["kv0", "kv1"])
    m.wal.log_pause(["kv0", "kv1"])
    m.wal.checkpoint()
    # transparently unpauses kv0 -- live run places it on row 1
    done = []
    m.propose("kv0", b"PUT pk pv", lambda _r, resp: done.append(resp))
    m.run_ticks(3)
    assert done == [b"OK"]
    assert m.rows.row("kv0") == 1
    db_before = [dict(a.db) for a in apps]
    m.wal.close()  # crash after the PUT committed + was acked

    apps2 = [KVApp() for _ in range(3)]
    m2 = recover(cfg, 3, apps2, str(tmp_path), native=False)
    assert m2.rows.row("kv0") == 1  # same row as the live run
    for r in range(3):
        assert apps2[r].db == db_before[r]
    got = []
    m2.propose("kv0", b"GET pk", lambda _r, resp: got.append(resp))
    m2.run_ticks(3)
    assert got == [b"pv"]  # the committed PUT survived recovery
    m2.wal.close()


def test_native_journal_parity(tmp_path):
    """C++ journal writes the byte-identical format (shared reader), repairs
    torn tails, and interoperates with the Python writer."""
    import pytest

    try:
        from gigapaxos_tpu.wal.native_journal import NativeJournal
    except Exception:
        pytest.skip("native toolchain unavailable")
    p = str(tmp_path / "n.log")
    j = NativeJournal(p)
    recs = [b"a", b"bb" * 1000, b"", b"\x00\xff" * 7]
    for r in recs:
        j.append(r)
    j.sync()
    j.close()
    assert read_journal(p) == recs
    # tear + native reopen repairs
    with open(p, "ab") as f:
        f.write(b"\x10\x00\x00\x00bad")
    j2 = NativeJournal(p)
    j2.append(b"post-tear")
    j2.close()
    assert read_journal(p) == recs + [b"post-tear"]
    # python writer can continue the same file
    j3 = PyJournal(p)
    j3.append(b"py")
    j3.close()
    assert read_journal(p)[-1] == b"py"


def test_recovery_with_native_backend(tmp_path):
    import pytest

    try:
        from gigapaxos_tpu.wal.native_journal import NativeJournal  # noqa: F401
    except Exception:
        pytest.skip("native toolchain unavailable")
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 16
    apps = [KVApp() for _ in range(3)]
    wal = PaxosLogger(str(tmp_path), native=True)
    m = PaxosManager(cfg, 3, apps, wal=wal)
    m.create_paxos_instance("svc", [0, 1, 2])
    m.propose("svc", b"PUT k v")
    m.run_ticks(3)
    m.wal.close()
    apps2 = [KVApp() for _ in range(3)]
    m2 = recover(cfg, 3, apps2, str(tmp_path), native=True)
    assert apps2[0].db == apps[0].db
    m2.wal.close()


def test_bulk_create_replays_as_batched_creates(tmp_path, monkeypatch):
    """A journaled bulk create is one OP_CREATE per name; replayed name by
    name it is one full-state rewrite per name (a populate of 1M groups
    could not be recovered in any useful time).  Replay folds each run of
    consecutive creates into one batched create and reaches the same rows,
    the same device state and the same app state — in both replay arms."""
    from gigapaxos_tpu.paxos import state as st

    cfg, apps, m = mk(tmp_path)
    bulk = [f"bulk{i}" for i in range(20)]
    assert m.create_paxos_instances(bulk, [0, 1, 2]) == 20
    m.create_paxos_instance("solo", [0, 1])  # another member set: own run
    for n in ("bulk3", "bulk17", "solo"):
        m.propose(n, b"PUT k v")
    m.run_ticks(4)
    assert m.create_paxos_instances(["late0", "late1"], [0, 1, 2]) == 2
    m.propose("late1", b"PUT k late")
    m.run_ticks(4)
    live_rows = dict(m.rows.items())
    live_state = [np.array(a) for a in m.state]
    live_db = [dict(a.db) for a in apps]
    m.wal.close()  # crash

    calls = []
    orig = st.create_groups
    monkeypatch.setattr(st, "create_groups", lambda s, rows, *a, **k: (
        calls.append(len(rows)), orig(s, rows, *a, **k))[1])
    for mode in ("batched", "reference"):
        calls.clear()
        apps2 = [KVApp() for _ in range(3)]
        m2 = recover(cfg, 3, apps2, str(tmp_path), native=False,
                     replay_mode=mode)
        assert calls == [20, 1, 2], (mode, calls)  # runs, not names
        assert dict(m2.rows.items()) == live_rows
        for got, want in zip(m2.state, live_state):
            assert np.array_equal(np.array(got), want)
        assert [dict(a.db) for a in apps2] == live_db
        m2.wal.close()
