"""Pipelined ticks (SURVEY §2.2 item 3): the host executes tick N-1's
decision stream while the device computes tick N and the WAL drains.

Covers the hazards the one-tick pipeline introduces:
* responses arrive one tick later but are still exactly-once and durable;
* a checkpoint drains the pipeline first, so snapshot metadata (app state,
  dedup, queues) covers every tick inside the snapshot's device state —
  crash + recover across a mid-stream checkpoint must reproduce the KV
  contents;
* the driver's stop path drains the trailing pending outbox.
"""

import os
import tempfile
import threading

import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.paxos.driver import TickDriver
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.wal.logger import PaxosLogger, recover


def make_manager(tmp, pipeline=True, checkpoint_every=None):
    cfg = GigapaxosTpuConfig()
    cfg.paxos.pipeline_ticks = pipeline
    wal = PaxosLogger(
        os.path.join(tmp, "wal"),
        checkpoint_every_ticks=checkpoint_every or 1024,
    )
    apps = [KVApp() for _ in range(3)]
    m = PaxosManager(cfg, 3, apps, wal=wal)
    m.create_paxos_instance("svc", [0, 1, 2])
    return m, wal, apps


def test_pipelined_commits_once_and_in_order():
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, apps = make_manager(tmp)
        got = {}
        rids = [
            m.propose("svc", f"PUT k{i} v{i}".encode(),
                      lambda rid, r: got.__setitem__(rid, r))
            for i in range(30)
        ]
        for _ in range(60):
            m.tick()
        m.drain_pipeline()
        assert all(got.get(rid) == b"OK" for rid in rids)
        assert m.stats["executions"] == 30 * 3  # exactly once per replica
        for i in range(30):
            assert apps[0].execute("svc", f"GET k{i}".encode(), 10_000 + i) \
                == f"v{i}".encode()
        wal.close()


def test_checkpoint_drains_then_recovers_consistently():
    with tempfile.TemporaryDirectory() as tmp:
        # checkpoint every 8 ticks: several snapshots land mid-pipeline
        m, wal, _ = make_manager(tmp, checkpoint_every=8)
        got = {}
        for i in range(40):
            m.propose("svc", f"PUT k{i} v{i}".encode(),
                      lambda rid, r: got.__setitem__(rid, r))
            m.tick()
        for _ in range(20):
            m.tick()
        m.drain_pipeline()
        assert len(got) == 40
        wal.close()
        apps2 = [KVApp() for _ in range(3)]
        m2 = recover(m.cfg, 3, apps2, os.path.join(tmp, "wal"))
        for i in range(40):
            assert apps2[1].execute("svc", f"GET k{i}".encode(), 50_000 + i) \
                == f"v{i}".encode(), i
        assert m2._pending_out is None  # recovery is synchronous


def test_driver_stop_drains_pending():
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp)
        d = TickDriver(m, idle_sleep_s=0.01).start()
        d.wait_ready(120)
        ev = threading.Event()
        got = []
        m.propose("svc", b"PUT a 1", lambda rid, r: (got.append(r), ev.set()))
        assert ev.wait(60), "pipelined response never arrived"
        assert got == [b"OK"]
        d.stop()
        assert m._pending_out is None
        wal.close()


def test_idle_pipelined_plane_is_not_pending():
    """Every pipelined tick leaves an outbox behind for the next one to
    complete.  It counts as pending work only while somebody waits on it:
    counted always, it kept an idle plane "busy" for good and its driver
    never backed off."""
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp)
        m.run_ticks(3)
        assert m._pending_out is not None
        assert m.pending_count() == 0
        got = []
        m.propose("svc", b"PUT a 1", lambda rid, r: got.append(r))
        seen = []
        for _ in range(8):
            if got:
                break
            seen.append(m.pending_count())
            m.tick()
        assert got == [b"OK"] and seen and all(n > 0 for n in seen), seen
        m.tick()
        assert m.pending_count() == 0
        wal.close()


@pytest.mark.parametrize("pipeline", [True, False])
def test_inbox_copies_are_handed_in_turn_and_outlive_their_tick(pipeline):
    """``_build_inbox`` hands the tick one of two resident copies of the
    staging arrays, not a fresh one: the copy a tick got stays as it was
    through the next build (its program may still be reading it), never
    aliases the staging arrays, and comes back two builds later."""
    import numpy as np

    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp, pipeline=pipeline)
        got = []
        m.propose("svc", b"PUT a 1", lambda rid, r: got.append(r))
        a = m._build_inbox()
        a_req = np.array(a.req)
        assert a_req.any() and not np.shares_memory(a.req, m._in_req)
        m.propose("svc", b"PUT b 2", lambda rid, r: got.append(r))
        b = m._build_inbox()
        assert not np.shares_memory(a.req, b.req)
        assert not np.shares_memory(a.stop, b.stop)
        assert (np.asarray(a.req) == a_req).all()  # untouched by the build
        c = m._build_inbox()
        assert np.shares_memory(c.req, a.req)
        assert np.shares_memory(c.stop, a.stop)
        assert (np.asarray(c.req) == m._in_req).all()
        wal.close()
    # and the served path on them answers as before
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, apps = make_manager(tmp, pipeline=pipeline)
        got = {}
        rids = []
        for i in range(12):
            rids.append(m.propose("svc", f"PUT k{i} v{i}".encode(),
                                  lambda rid, r: got.__setitem__(rid, r)))
            m.tick()
        m.run_ticks(6)
        m.drain_pipeline()
        assert all(got.get(rid) == b"OK" for rid in rids)
        assert m.stats["executions"] == 12 * 3
        wal.close()


class _SlowIdlePlane:
    """What TickDriver needs of a manager, with a tick that costs
    ``tick_s`` whatever it carries (a plane of a million groups)."""

    def __init__(self, tick_s):
        self.tick_s = tick_s
        self.work = 0
        self.ticks = []  # (start instant, work taken)
        self.cfg = None

    def tick(self):
        import time

        taken, self.work = self.work, 0
        self.ticks.append((time.monotonic(), taken))
        time.sleep(self.tick_s)

    def pending_count(self):
        return self.work


@pytest.mark.parametrize("tick_s, idle_sleep_s", [(0.03, 0.002),
                                                  (0.002, 0.02)])
def test_idle_driver_backs_off_to_its_duty_and_wakes_for_work(tick_s,
                                                              idle_sleep_s):
    """An idle plane's probe ticks take at most ``IDLE_DUTY`` of its time
    (and come no oftener than ``idle_sleep_s``); pending work starts a
    tick within the polling period, not after the back-off."""
    import time

    from gigapaxos_tpu.paxos import driver as drv

    plane = _SlowIdlePlane(tick_s)
    d = TickDriver(plane, idle_sleep_s=idle_sleep_s, drain_ticks=1).start()
    try:
        assert d.wait_ready(10)
        time.sleep(0.2)  # past the drain
        n0, t0 = len(plane.ticks), time.monotonic()
        time.sleep(1.0)
        n = len(plane.ticks) - n0
        period = max(idle_sleep_s + tick_s, tick_s / drv.IDLE_DUTY)
        assert 1 <= n <= (time.monotonic() - t0) / period + 1, (n, period)
        plane.work = 7
        asked = time.monotonic()
        deadline = asked + 5
        while plane.work and time.monotonic() < deadline:
            time.sleep(0.001)
        started, taken = plane.ticks[-1]
        assert taken == 7
        # within a probe in flight plus a few polls (a loaded test host)
        assert started - asked < tick_s + 10 * idle_sleep_s + 0.05, \
            started - asked
    finally:
        d.stop()


def test_sync_due_tick_still_returns_outbox():
    """A tick whose top-of-tick laggard sync drains the pipeline must hand
    the drained outbox to the caller, not swallow it: callers polling
    tick() (auto_sync_laggards consumers, the capacity probe) would
    otherwise silently miss one tick's lag/decided signals on exactly the
    ticks where repair happens.  Full-outbox mode, pipelined."""
    cfg = GigapaxosTpuConfig()
    cfg.paxos.pipeline_ticks = True
    apps = [KVApp() for _ in range(3)]
    m = PaxosManager(cfg, 3, apps)
    m.create_paxos_instance("svc", [0, 1, 2])
    for i in range(4):
        m.propose("svc", f"PUT a{i} {i}".encode())
    m.run_ticks(4)
    # replica 2 falls more than a window behind, then revives: the next
    # completion queues a sync, and the tick after that runs it
    m.set_alive(2, False)
    for i in range(30):
        m.propose("svc", f"PUT k{i} {i}".encode())
    m.run_ticks(12)
    m.set_alive(2, True)
    outs = [m.tick() for _ in range(8)]
    assert m.stats["checkpoint_transfers"] >= 1
    # pipeline was primed before the loop: every tick must return an
    # outbox — including the sync-due ones that drained mid-tick
    assert all(o is not None for o in outs), [o is None for o in outs]
    assert apps[2].db["svc"] == apps[0].db["svc"]


def test_modeb_pipelined_trio_commits():
    from gigapaxos_tpu.modeb import ModeBNode
    from gigapaxos_tpu.net.messenger import Messenger, NodeMap

    ids = ["B0", "B1", "B2"]
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 32
    cfg.paxos.pipeline_ticks = True
    nodemap = NodeMap()
    msgs = {}
    for nid in ids:
        mm = Messenger(nid, ("127.0.0.1", 0), nodemap)
        nodemap.add(nid, "127.0.0.1", mm.port)
        msgs[nid] = mm
    nodes = {nid: ModeBNode(cfg, ids, nid, KVApp(), msgs[nid]) for nid in ids}
    drivers = {}
    try:
        for nid, nd in nodes.items():
            d = TickDriver(nd, idle_sleep_s=0.02)
            nd.on_work = d.kick
            drivers[nid] = d.start()
        for nd in nodes.values():
            for g in range(4):
                nd.create_group(f"g{g}", [0, 1, 2])
        for d in drivers.values():
            d.wait_ready(300)
        done = threading.Semaphore(0)
        resp = {}

        def cb(rid, r):
            resp[rid] = r
            done.release()

        N = 24
        for i in range(N):
            nodes[ids[i % 3]].propose(f"g{i % 4}",
                                      f"PUT k{i} v{i}".encode(), cb)
        for _ in range(N):
            assert done.acquire(timeout=90), f"{len(resp)}/{N} committed"
        assert all(r == b"OK" for r in resp.values())
    finally:
        for d in drivers.values():
            d.stop()
        for nd in nodes.values():
            nd.close()
