"""Batched (columnar) WAL replay vs. the record-at-a-time reference arm
(ISSUE 19): bit-identity of recovered state, apps, host bookkeeping and
re-logged journal bytes across all dispatch modes, the mixed register
plane and the lease plane; torn-tail/scribble verdict parity of the
bounded-memory (meta_only) scanner; overflow fallback correctness."""

import copy
import shutil

import numpy as np
import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.paxos.manager import PaxosManager  # noqa: F401 (mk)
from gigapaxos_tpu.paxos.state import PaxosState
from gigapaxos_tpu.wal import logger as wal_logger
from gigapaxos_tpu.wal.journal import (PyJournal, iter_scan_records,
                                       scan_journal)
from gigapaxos_tpu.wal.logger import PaxosLogger, recover

R = 3

MODES = {
    "full_eager": dict(compact=False, pipe=False),
    "full_pipe": dict(compact=False, pipe=True),
    "compact_eager": dict(compact=True, pipe=False),
    "compact_pipe": dict(compact=True, pipe=True),
}


def mk(path, compact=False, pipe=False, register=0, leases=False,
       exec_budget=0):
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 32
    cfg.paxos.compact_outbox = compact
    cfg.paxos.pipeline_ticks = pipe
    cfg.paxos.register_groups = register
    if exec_budget:
        cfg.paxos.exec_budget = exec_budget
    if leases:
        cfg.paxos.read_leases = True
        cfg.paxos.lease_ticks = 16
    apps = [KVApp() for _ in range(R)]
    wal = PaxosLogger(str(path), native=False)
    return cfg, apps, PaxosManager(cfg, R, apps, wal=wal)


def drive(m, mixed=False, rounds=6, per_round=3):
    """A workload that exercises every record kind the replay arms see:
    creates, multi-tick proposal traffic (several batch windows), a
    pause/unpause admin barrier mid-journal, and a stop."""
    for g in range(4):
        m.create_paxos_instance(f"kv{g}", [0, 1, 2])
    if mixed:
        m.create_paxos_instance("reg0", [0, 1, 2], register=True)
        m.create_paxos_instance("reg1", [0, 1, 2], register=True)
    for i in range(rounds):
        for g in range(4):
            for j in range(per_round):
                m.propose(f"kv{g}", f"PUT k{i}.{j} v{g}.{i}.{j}".encode())
        if mixed:
            m.propose("reg0", f"PUT rk v{i}".encode())
            m.propose("reg1", f"PUT rk2 w{i}".encode())
        m.run_ticks(2)
    # admin barrier mid-journal: pause a quiescent group, then the next
    # propose transparently unpauses it (OP_PAUSE + OP_UNPAUSE records
    # splitting the OP_TICK stream)
    m.run_ticks(2)
    m._sweep_outstanding()
    m._do_pause(["kv2"])
    m.wal.log_pause(["kv2"])
    m.propose("kv2", b"PUT back alive")
    m.run_ticks(2)
    m.propose_stop("kv3")
    m.run_ticks(3)


def assert_identical(ma, mb):
    for f in PaxosState._fields:
        assert np.array_equal(np.asarray(getattr(ma.state, f)),
                              np.asarray(getattr(mb.state, f))), \
            f"log-plane state field {f} differs"
    if ma.rstate is not None:
        for f in PaxosState._fields:
            assert np.array_equal(np.asarray(getattr(ma.rstate, f)),
                                  np.asarray(getattr(mb.rstate, f))), \
                f"register-plane state field {f} differs"
    if ma._lease is not None:
        from gigapaxos_tpu.ops.tick import LeaseState

        for f in LeaseState._fields:
            assert np.array_equal(np.asarray(getattr(ma._lease, f)),
                                  np.asarray(getattr(mb._lease, f))), \
                f"lease field {f} differs"
            if ma._rlease is not None:
                assert np.array_equal(np.asarray(getattr(ma._rlease, f)),
                                      np.asarray(getattr(mb._rlease, f))), \
                    f"register lease field {f} differs"
        assert np.array_equal(ma._lease_np, mb._lease_np)
        assert ma._lease_clock == mb._lease_clock
    assert ma.tick_num == mb.tick_num
    assert ma._next_rid == mb._next_rid
    assert np.array_equal(ma._host_exec, mb._host_exec)
    for r in range(R):
        assert ma.apps[r].db == mb.apps[r].db, f"replica {r} app diverged"
    assert dict(ma.rows.items()) == dict(mb.rows.items())
    assert ma._stopped_rows == mb._stopped_rows
    assert set(ma.outstanding) == set(mb.outstanding)
    qa = {k: list(v) for k, v in ma._queues.items() if v}
    qb = {k: list(v) for k, v in mb._queues.items() if v}
    assert qa == qb


def journal_bytes(path):
    import glob
    import os

    out = []
    for p in sorted(glob.glob(os.path.join(str(path), "journal.*.log"))):
        with open(p, "rb") as f:
            out.append(f.read())
    return out


def recover_both(tmp_path, cfg, crash_dir, **kw):
    """Recover the crashed dir through both arms (batched arm on a copy)
    and return the two managers."""
    b = tmp_path / "copy"
    shutil.copytree(crash_dir, b)
    m_ref = recover(cfg, R, [KVApp() for _ in range(R)], str(crash_dir),
                    native=False, replay_mode="reference", **kw)
    m_bat = recover(cfg, R, [KVApp() for _ in range(R)], str(b),
                    native=False, replay_mode="batched", **kw)
    return m_ref, m_bat, b


def post_traffic(m):
    for i in range(3):
        m.propose("kv0", f"PUT post{i} p{i}".encode())
        m.propose("kv1", f"PUT post{i} q{i}".encode())
    m.run_ticks(3)


@pytest.mark.parametrize("mode", [
    m if m in ("compact_eager", "full_pipe")
    else pytest.param(m, marks=pytest.mark.slow)
    for m in sorted(MODES)
])
def test_batched_replay_bit_identity(tmp_path, mode):
    a = tmp_path / "a"
    a.mkdir()
    cfg, apps, m = mk(a, **MODES[mode])
    drive(m)
    m.wal.close()  # crash

    m_ref, m_bat, b = recover_both(tmp_path, cfg, a)
    assert_identical(m_ref, m_bat)
    # identical post-recovery traffic must re-log identical journal bytes
    post_traffic(m_ref)
    post_traffic(m_bat)
    assert_identical(m_ref, m_bat)
    m_ref.wal.close()
    m_bat.wal.close()
    assert journal_bytes(a) == journal_bytes(b)


@pytest.mark.parametrize("mode", ["full_pipe", "compact_pipe"])
def test_replay_does_not_depend_on_the_side_a_tick_took(tmp_path, mode):
    """Eight writes a round to each name, against P = 4: every other tick's
    inbox leaves as many behind as it placed and holds its outbox, the ticks
    between complete their own.  The journal records what was placed, so both
    replay arms rebuild the plane as it stood, tables included."""
    a = tmp_path / "a"
    a.mkdir()
    cfg, apps, m = mk(a, **MODES[mode])
    sides = []
    tick = m.tick
    m.tick = lambda: (tick(), sides.append(m._pending_out is not None))[0]
    drive(m, per_round=8)
    assert 8 >= 2 * m.P and sum(a != b for a, b in zip(sides, sides[1:])) >= 10
    m.drain_pipeline()
    tables = [copy.deepcopy(app.db) for app in apps]
    m.wal.close()  # crash

    m_ref, m_bat, b = recover_both(tmp_path, cfg, a)
    assert_identical(m_ref, m_bat)
    assert [app.db for app in m_bat.apps] == tables
    m_ref.wal.close()
    m_bat.wal.close()


def test_batched_replay_mixed_register_plane(tmp_path):
    a = tmp_path / "a"
    a.mkdir()
    cfg, apps, m = mk(a, compact=True, register=8)
    drive(m, mixed=True)
    m.wal.close()

    m_ref, m_bat, b = recover_both(tmp_path, cfg, a)
    assert_identical(m_ref, m_bat)
    for mm in (m_ref, m_bat):
        mm.propose("reg0", b"PUT rk post")
        post_traffic(mm)
    assert_identical(m_ref, m_bat)
    m_ref.wal.close()
    m_bat.wal.close()
    assert journal_bytes(a) == journal_bytes(b)


@pytest.mark.parametrize("register", [
    0, pytest.param(8, marks=pytest.mark.slow)
])
def test_batched_replay_lease_plane(tmp_path, register):
    a = tmp_path / "a"
    a.mkdir()
    cfg, apps, m = mk(a, compact=True, register=register, leases=True)
    drive(m, mixed=bool(register), rounds=4)
    m.wal.close()

    m_ref, m_bat, b = recover_both(tmp_path, cfg, a)
    assert_identical(m_ref, m_bat)
    post_traffic(m_ref)
    post_traffic(m_bat)
    assert_identical(m_ref, m_bat)
    m_ref.wal.close()
    m_bat.wal.close()
    assert journal_bytes(a) == journal_bytes(b)


def test_batched_overflow_falls_back_to_reference(tmp_path, monkeypatch):
    """A tick whose true execution count exceeds the replay scatter
    budget must be detected from the compact header and re-run through
    the exact record-at-a-time body — bit-identity holds even when every
    window overflows."""
    monkeypatch.setattr(wal_logger, "_REPLAY_SCAT_MIN", 1)
    calls = []
    orig = wal_logger._BatchedReplay._reference_tick
    monkeypatch.setattr(
        wal_logger._BatchedReplay, "_reference_tick",
        lambda self, slab, t: (calls.append(t), orig(self, slab, t))[1])

    a = tmp_path / "a"
    a.mkdir()
    # full mode with a tiny exec budget: state evolution is unbudgeted
    # (budget=0 on the tick), but the replay scatter budget inherits the
    # tiny _exec_budget, so windows overflow
    cfg, apps, m = mk(a, compact=False, exec_budget=4)
    drive(m, rounds=4, per_round=4)  # 16 execs/tick >> budget 4
    m.wal.close()

    m_ref, m_bat, b = recover_both(tmp_path, cfg, a)
    assert calls, "expected overflow fallback through _reference_tick"
    assert_identical(m_ref, m_bat)
    m_ref.wal.close()
    m_bat.wal.close()


@pytest.mark.parametrize("register", [0, 8])
def test_sparse_window_replay_bit_identity(tmp_path, monkeypatch,
                                           register):
    """Sparse window replay (gather journal-touched rows → scan at width
    A → scatter back) must be bit-identical to the reference arm.  Forced
    on via GPTPU_REPLAY_SPARSE so the small test plane takes the sparse
    path it would normally skip; the dispatcher counter proves it
    engaged."""
    monkeypatch.setenv("GPTPU_REPLAY_SPARSE", "1")
    a = tmp_path / "a"
    a.mkdir()
    cfg, apps, m = mk(a, compact=True, register=register)
    drive(m, mixed=bool(register))
    m.wal.close()

    m_ref, m_bat, b = recover_both(tmp_path, cfg, a)
    assert m_bat._replay_sparse_windows > 0, "sparse path never engaged"
    assert m_bat._replay_overflows == 0
    assert_identical(m_ref, m_bat)
    post_traffic(m_ref)
    post_traffic(m_bat)
    assert_identical(m_ref, m_bat)
    m_ref.wal.close()
    m_bat.wal.close()
    assert journal_bytes(a) == journal_bytes(b)


def test_sparse_auto_threshold(tmp_path):
    """In auto mode a dense little plane (active rows a large fraction of
    G) must NOT take the sparse path — the crossover heuristic keeps it
    on the dense scan."""
    a = tmp_path / "a"
    a.mkdir()
    cfg, apps, m = mk(a, compact=True)  # G=32, 4 active rows → 8 padded
    drive(m)
    m.wal.close()
    b = tmp_path / "copy"
    shutil.copytree(a, b)
    m_bat = recover(cfg, R, [KVApp() for _ in range(R)], str(b),
                    native=False, replay_mode="batched")
    # 8 padded rows * factor 4 == G: the heuristic rejects sparse here
    assert m_bat._replay_sparse_windows == 0
    assert m_bat._replay_windows > 0
    m_bat.wal.close()


@pytest.mark.slow
def test_batched_window_tail_sizes(tmp_path):
    """Batch sizes that do not divide the tick count exercise the <K tail
    path; K larger than the journal exercises pure-tail replay."""
    a = tmp_path / "a"
    a.mkdir()
    cfg, apps, m = mk(a, compact=True)
    drive(m, rounds=5)
    m.wal.close()

    for K in (3, 1000):
        b = tmp_path / f"copy{K}"
        shutil.copytree(a, b)
        apps_b = [KVApp() for _ in range(R)]
        import os

        os.environ["GPTPU_REPLAY_BATCH"] = str(K)
        try:
            m_bat = recover(cfg, R, apps_b, str(b), native=False,
                            replay_mode="batched")
        finally:
            del os.environ["GPTPU_REPLAY_BATCH"]
        m_ref = recover(cfg, R, [KVApp() for _ in range(R)], str(a),
                        native=False, replay_mode="reference")
        assert_identical(m_ref, m_bat)
        m_ref.wal.close()
        m_bat.wal.close()


# ---------------------------------------------------------------- scanner


def _mk_journal(path, n=8, sync_every=3):
    j = PyJournal(str(path))
    for i in range(n):
        j.append(f"record-{i:04d}".encode() * 4)
        if (i + 1) % sync_every == 0:
            j.sync()
    j.close()


def _assert_scan_parity(path):
    full = scan_journal(str(path))
    meta = scan_journal(str(path), meta_only=True)
    assert meta.kind == full.kind
    assert meta.version == full.version
    assert meta.good_len == full.good_len
    assert meta.bad_offset == full.bad_offset
    assert meta.resync_offset == full.resync_offset
    assert meta.last_seq == full.last_seq
    assert meta.n_synced == full.n_synced
    assert meta.n_records == full.n_records == len(full.records)
    assert meta.n_suffix == full.n_suffix == len(full.suffix)
    assert meta.records == [] and meta.suffix == []
    assert list(iter_scan_records(str(path), meta)) == full.records
    return full


def test_meta_scan_clean_parity(tmp_path):
    p = tmp_path / "j.log"
    _mk_journal(p)
    full = _assert_scan_parity(p)
    assert full.kind == "clean" and full.n_records == 8


def test_meta_scan_torn_tail_parity(tmp_path):
    p = tmp_path / "j.log"
    _mk_journal(p)
    with open(p, "ab") as f:
        f.write(b"\x40\x00\x00\x00\xde\xad\xbe")  # half a frame
    full = _assert_scan_parity(p)
    assert full.kind == "torn_tail" and full.n_records == 8


def test_meta_scan_scribble_parity(tmp_path):
    p = tmp_path / "j.log"
    _mk_journal(p, n=10, sync_every=2)
    # flip a byte inside an early (fsynced, barrier-covered) frame
    with open(p, "r+b") as f:
        f.seek(30)
        c = f.read(1)
        f.seek(30)
        f.write(bytes([c[0] ^ 0xFF]))
    full = _assert_scan_parity(p)
    assert full.kind == "scribble"
    assert full.n_suffix > 0  # intact frames resynced after the damage
