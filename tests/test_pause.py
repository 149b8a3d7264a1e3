"""Pause/spill (deactivation) tests.

Mirrors the reference's memory-scaling machinery (§3.5 of the survey:
``Deactivator`` PaxosManager.java:2951, ``pause`` :2284-2365, ``unpause``
:2370-2412, ``HotRestoreInfo`` paxosutil/HotRestoreInfo.java:31-69): cold
groups spill ~9 scalars per replica to host RAM, their device rows are
recycled, and any touch transparently restores them — which is what lets a
node hold far more groups than device rows.
"""

import numpy as np
import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.paxos.manager import PaxosManager


def mk(G=8, deactivation=0):
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = G
    cfg.paxos.deactivation_ticks = deactivation
    return PaxosManager(cfg, 3, [KVApp() for _ in range(3)])


def run_until(mgr, pred, max_ticks=200):
    for _ in range(max_ticks):
        mgr.tick()
        if pred():
            return True
    return pred()


def test_pause_and_transparent_unpause():
    mgr = mk()
    mgr.create_paxos_instance("a", [0, 1, 2])
    got = {}
    mgr.propose("a", b"PUT k v", lambda r, v: got.update({"r": v}))
    assert run_until(mgr, lambda: "r" in got)
    before = mgr.exec_watermarks("a").copy()
    assert mgr.pause_idle(limit=8) == 1
    assert mgr.paused_count() == 1 and mgr.rows.row("a") is None
    # reads work while paused (served from the spill)
    assert mgr.group_members("a") == [0, 1, 2]
    np.testing.assert_array_equal(mgr.exec_watermarks("a"), before)
    # touching the name unpauses it and consensus continues where it left off
    got2 = {}
    mgr.propose("a", b"GET k", lambda r, v: got2.update({"r": v}))
    assert run_until(mgr, lambda: "r" in got2)
    assert got2["r"] == b"v"
    assert mgr.paused_count() == 0
    np.testing.assert_array_equal(mgr.exec_watermarks("a"), before + 1)


def test_busy_group_not_pausable():
    mgr = mk()
    mgr.create_paxos_instance("busy", [0, 1, 2])
    mgr.propose("busy", b"PUT a 1", None)  # queued, not yet committed
    assert mgr.pause_idle(limit=8) == 0


def test_stopped_flag_survives_pause():
    mgr = mk()
    mgr.create_paxos_instance("s", [0, 1, 2])
    done = {}
    mgr.propose_stop("s", callback=lambda r, v: done.update({"r": v}))
    assert run_until(mgr, lambda: "r" in done)
    assert mgr.is_stopped("s")
    assert mgr.pause_idle(limit=8) == 1
    assert mgr.is_stopped("s")  # visible while spilled
    assert mgr.propose("s", b"PUT x 1", None) is None  # still fenced


def test_more_groups_than_rows():
    """The point of the machinery: G=8 device rows hosting 24 groups, with
    eviction keeping the working set resident."""
    mgr = mk(G=8)
    N = 24
    got = {}
    for i in range(N):
        assert mgr.create_paxos_instance(f"g{i}", [0, 1, 2])
        mgr.propose(f"g{i}", f"PUT k {i}".encode(),
                    lambda r, v, i=i: got.update({i: v}))
        assert run_until(mgr, lambda i=i: i in got, max_ticks=60)
    assert len(got) == N and all(v == b"OK" for v in got.values())
    assert mgr.paused_count() == N - len(mgr.rows)
    assert mgr.paused_count() >= N - 8
    # every group still readable: unpause on demand, state intact
    got2 = {}
    for i in range(N):
        mgr.propose(f"g{i}", b"GET k", lambda r, v, i=i: got2.update({i: v}))
        assert run_until(mgr, lambda i=i: i in got2, max_ticks=60)
        assert got2[i] == str(i).encode(), f"g{i}"


def test_periodic_deactivator_in_tick():
    mgr = mk(deactivation=10)
    mgr.create_paxos_instance("cold", [0, 1, 2])
    got = {}
    mgr.propose("cold", b"PUT k v", lambda r, v: got.update({"r": v}))
    assert run_until(mgr, lambda: "r" in got)
    # run past the idle threshold and the 256-tick deactivator period
    mgr.run_ticks(300)
    assert mgr.paused_count() == 1


def test_pause_wal_replay(tmp_path):
    """Row allocation must stay in lockstep across recovery when pause and
    unpause reshuffled rows mid-journal."""
    from gigapaxos_tpu.wal import PaxosLogger, recover

    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 4
    d = str(tmp_path / "pwal")
    mgr = PaxosManager(cfg, 3, [KVApp() for _ in range(3)],
                       wal=PaxosLogger(d))
    got = {}
    for i in range(6):  # 6 groups > 4 rows: forces eviction mid-journal
        mgr.create_paxos_instance(f"g{i}", [0, 1, 2])
        mgr.propose(f"g{i}", f"PUT k {i}".encode(),
                    lambda r, v, i=i: got.update({i: v}))
        assert run_until(mgr, lambda i=i: i in got, max_ticks=60)
    mgr.wal.close()

    m2 = recover(cfg, 3, [KVApp() for _ in range(3)], d)
    for i in range(6):
        got2 = {}
        m2.propose(f"g{i}", b"GET k", lambda r, v: got2.update({"r": v}))
        assert run_until(m2, lambda: "r" in got2, max_ticks=60)
        assert got2["r"] == str(i).encode(), f"g{i}"
    m2.wal.close()


def test_snapshot_while_paused_recovers(tmp_path):
    """A checkpoint taken while groups are spilled must carry the spill
    store and their app state (losing them once the journal is GC'd would
    be unrecoverable)."""
    from gigapaxos_tpu.wal import PaxosLogger, recover

    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    cfg.paxos.deactivation_ticks = 0
    d = str(tmp_path / "psnap")
    mgr = PaxosManager(cfg, 3, [KVApp() for _ in range(3)],
                       wal=PaxosLogger(d))
    got = {}
    mgr.create_paxos_instance("cold", [0, 1, 2])
    mgr.propose("cold", b"PUT k frozen", lambda r, v: got.update({"r": v}))
    assert run_until(mgr, lambda: "r" in got)
    assert mgr.pause_idle(limit=8) == 1
    mgr.wal.checkpoint()  # snapshot with the group spilled; journal rolled+GC'd
    mgr.wal.close()

    m2 = recover(cfg, 3, [KVApp() for _ in range(3)], d)
    assert m2.paused_count() == 1
    got2 = {}
    m2.propose("cold", b"GET k", lambda r, v: got2.update({"r": v}))
    assert run_until(m2, lambda: "r" in got2)
    assert got2["r"] == b"frozen"
    m2.wal.close()


def test_remove_with_inflight_frees_row_counter():
    """Removing a group with placed-but-unexecuted requests must not wedge
    the recycled row's outstanding counter (which would make it forever
    unpausable)."""
    mgr = mk(G=4)
    mgr.create_paxos_instance("x", [0, 1, 2])
    fails = {}
    mgr.propose("x", b"PUT a 1", lambda r, v: fails.update({"cb": (r, v)}))
    mgr.tick()  # place it so it leaves the queue
    row = mgr.rows.row("x")
    mgr.remove_paxos_instance("x")
    mgr.tick()
    assert mgr._row_outstanding[row] == 0
    assert not mgr.outstanding
    # the recycled row is pausable again
    mgr.create_paxos_instance("y", [0, 1, 2])
    got = {}
    mgr.propose("y", b"PUT b 2", lambda r, v: got.update({"r": v}))
    assert run_until(mgr, lambda: "r" in got)
    assert mgr.pause_idle(limit=8) == 1


def test_pause_idle_answers_nobody_is_idle_without_looking_at_the_device():
    """ISSUE 36: while no resident name has been idle for
    ``deactivation_ticks``, ``pause_idle`` says 0 from the activity column:
    no pipeline drain, no [R, G] pull, no sort of the rows (a 0.5-1 s tick at
    1M groups, every 256 ticks); once one has, it goes the whole way."""
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    cfg.paxos.deactivation_ticks = 6
    mgr = PaxosManager(cfg, 3, [KVApp() for _ in range(3)])
    looked = []
    whole = mgr._pause_eligible
    mgr._pause_eligible = lambda **kw: looked.append(mgr.tick_num) or whole(**kw)
    assert mgr.pause_idle() == 0 and not looked      # no resident name at all
    for name in ("a", "b"):
        mgr.create_paxos_instance(name, [0, 1, 2])
    got = {}
    mgr.propose("a", b"PUT k v", lambda r, v: got.update({"a": v}))
    assert run_until(mgr, lambda: "a" in got)
    assert mgr.pause_idle() == 0 and not looked      # both younger than 6
    while mgr.tick_num < 5:
        mgr.tick()
    assert mgr.pause_idle() == 0 and not looked
    mgr.run_ticks(4)
    mgr.propose("a", b"PUT k w", lambda r, v: got.update({"a2": v}))
    assert run_until(mgr, lambda: "a2" in got)
    # "b" has now been idle for 6 ticks, "a" has not: the whole way, once
    assert mgr.pause_idle() == 1 and len(looked) == 1
    assert mgr.rows.row("b") is None and mgr.rows.row("a") is not None
    assert mgr.pause_idle() == 0 and len(looked) == 1  # only "a" left, young
