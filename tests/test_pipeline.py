"""Pipelined ticks (SURVEY §2.2 item 3): under ``pipeline_ticks`` a tick MAY
HOLD its outbox for the next call, so that the host executes tick N-1's
decision stream while the device computes tick N and the WAL drains.  It
holds when its inbox left work behind that only another tick can place (a
bulk leftover; requests queued behind P placed for their name, where they are
at least as many as the requests the tick did place: a hot name's two behind
a tick full of other names' requests is no reason to make those wait);
otherwise it completes its outbox in the call that dispatched it and a
reply does not wait a period for nothing.  The rule reads the inbox and
nothing else, so every test here takes the side its traffic puts it on.

Covers the rule, and the hazards the one-tick pipeline introduces:
* on the held side responses arrive one tick later but are still
  exactly-once and durable;
* a checkpoint drains the pipeline first, so snapshot metadata (app state,
  dedup, queues) covers every tick inside the snapshot's device state —
  crash + recover across a mid-stream checkpoint must reproduce the KV
  contents, whichever side the ticks took;
* changing sides loses no outbox, answers nothing twice and runs a tick's
  periodic work (sweep, deactivation) once;
* the driver's stop path drains the trailing pending outbox.
"""

import os
import tempfile
import threading

import numpy as np
import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.obs.metrics import registry
from gigapaxos_tpu.paxos.driver import TickDriver
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.wal.logger import PaxosLogger, recover


def completions(m):
    """``tick_completions_total`` of the manager's plane, by mode."""
    snap = registry().snapshot()
    plane = m._pc.plane
    return {mode: snap[f"tick_completions_total{{mode={mode},plane={plane}}}"]
            for mode in ("same_call", "held")}


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


def burst(m, n, got, tag="b"):
    """``n`` writes to one name from one entry replica: more than P of them
    is a backlog, and the ticks that cannot place them all hold."""
    return [m.propose("svc", f"PUT {tag}{len(got)}-{i} v".encode(),
                      lambda rid, r: got.__setitem__(rid, r), entry=0)
            for i in range(n)]


def record_completions(m):
    """Every outbox ``_complete_tick`` hands back from now on, in order,
    with the ``done_at`` it was completed under."""
    completed, numbers = [], []
    real = m._complete_tick

    def recording(*a):
        numbers.append(a[-1])
        completed.append(real(*a))
        return completed[-1]

    m._complete_tick = recording
    return completed, numbers


def test_lone_proposal_is_answered_by_the_call_that_dispatched_it():
    """Nothing left behind: one ``tick()`` places, decides, executes,
    journals and answers, as with the option off; nothing is held for the
    next call."""
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp)
        m.run_ticks(2)
        before = completions(m)
        got = []
        m.propose("svc", b"PUT a 1", lambda rid, r: got.append(r))
        out = m.tick()
        assert got == [b"OK"] and out is not None
        assert m._pending_out is None and m.pending_count() == 0
        after = completions(m)
        assert after["same_call"] == before["same_call"] + 1
        assert after["held"] == before["held"]
        wal.close()


def test_pipelined_commits_once_and_in_order():
    """More than P proposals to one name from one entry replica: every
    tick whose inbox left as many of them behind as it placed holds, and the
    rest complete in their own call; answered once each, in order."""
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, apps = make_manager(tmp)
        before = completions(m)
        got = {}
        rids = [
            m.propose("svc", f"PUT k{i} v{i}".encode(),
                      lambda rid, r: got.__setitem__(rid, r), entry=0)
            for i in range(30)
        ]
        assert 30 > m.P
        for _ in range(60):
            m.tick()
        m.drain_pipeline()
        assert all(got.get(rid) == b"OK" for rid in rids)
        assert list(got) == rids  # answered in the order proposed
        assert m.stats["executions"] == 30 * 3  # exactly once per replica
        for i in range(30):
            assert apps[0].execute("svc", f"GET k{i}".encode(), 10_000 + i) \
                == f"v{i}".encode()
        after = completions(m)
        # 30 requests at P a tick: the inboxes of at least 6 ticks left P
        # or more behind (more where the window refused intake); the one
        # that left 2 behind its 4 did not hold
        held = after["held"] - before["held"]
        assert (30 - m.P) // m.P <= held < 60
        assert after["same_call"] - before["same_call"] == 60 - held
        wal.close()


def test_bulk_wave_that_leaves_a_leftover_is_held():
    """A bulk wave with more than one request per (entry, row) leaves
    ``_bulk_leftover`` behind: those ticks hold, every request executes
    once on each replica and per-key order holds."""
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 64
    cfg.paxos.compact_outbox = True
    cfg.paxos.pipeline_ticks = True
    apps = [KVApp() for _ in range(3)]
    m = PaxosManager(cfg, 3, apps)
    for i in range(4):
        assert m.create_paxos_instance(f"g{i}", [0, 1, 2])
    m.run_ticks(2)
    before = completions(m)
    n = 96  # 24 a row from one entry replica, P at most a tick
    rows = [m.rows.row(f"g{i % 4}") for i in range(n)]
    order = []
    rids = m.propose_bulk(
        rows, [f"PUT k v{i}".encode() for i in range(n)],
        callbacks=[lambda rid, r: order.append(rid)] * n,
        entries=0)
    assert (rids > 0).all()
    m.tick()
    assert m._bulk_leftover.size and m._pending_out is not None
    for _ in range(60):
        m.tick()
    m.drain_pipeline()
    assert m.bulk_stats()["done"] == n and m.bulk_stats()["queued"] == 0
    assert m.stats["executions"] == 3 * n
    assert sorted(order) == sorted(int(r) for r in rids)  # once each
    for g in range(4):  # a row's requests are answered in arrival order
        mine = [int(r) for i, r in enumerate(rids) if i % 4 == g]
        assert [r for r in order if r in set(mine)] == mine
        assert all(a.db[f"g{g}"]["k"] == f"v{n - 4 + g}" for a in apps)
    after = completions(m)
    assert after["held"] - before["held"] >= n // 4 // m.P - 1
    assert after["same_call"] > before["same_call"]  # and back again


def test_changing_sides_hands_every_outbox_to_the_caller_once():
    """Backlog, then none, then backlog: held -> same call completes two
    outboxes in one call and returns the newer, same call -> held
    completes none and returns the oldest that an earlier call kept.
    Every completed outbox is returned by exactly one ``tick()``, and the
    kept ones are the manager's queue, oldest first."""
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp)
        completed, _ = record_completions(m)
        got = {}
        returned = []
        sides = []

        def ticks(n):
            for _ in range(n):
                out = m.tick()
                if out is not None:
                    returned.append(out)
                sides.append(m._pending_out is not None)

        rids = burst(m, 10, got)
        ticks(8)
        ticks(3)  # nothing waiting: same call
        rids += burst(m, 10, got)
        ticks(8)
        rids += burst(m, 1, got)  # no backlog: completed by its own call
        ticks(1)
        assert all(got.get(r) == b"OK" for r in rids) and len(got) == 21
        # held, same call, held again, same call again
        changes = sum(a != b for a, b in zip(sides, sides[1:]))
        assert sides[0] and not sides[-1] and changes == 3, sides
        ids = [id(o) for o in returned]
        assert len(set(ids)) == len(ids), "an outbox was returned twice"
        kept = [o for o in completed if id(o) not in set(ids)]
        assert [id(o) for o in kept] == [id(o) for o in m._unreturned]
        assert len(kept) == 1  # what the second change of sides left
        assert len(completed) == m.tick_num  # nothing pending at the end
        # it goes out with the next call that completes none
        burst(m, 10, got)
        ticks(1)
        assert sides[-1] and returned[-1] is kept[0]
        assert not m._unreturned
        m.drain_pipeline()
        wal.close()


def test_an_outbox_drained_earlier_survives_a_checkpoint_drain():
    """``drain_pipeline()`` completes a held outbox X between calls; the
    next tick has no backlog, returns its own outbox and keeps X; the tick
    after that holds with a checkpoint due, whose drain completes a third.
    The call that completed none itself hands out X, the oldest, and the
    checkpoint's outbox goes out next: nothing is overwritten."""
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp, checkpoint_every=6)
        completed, _ = record_completions(m)
        got = {}
        burst(m, 3 * m.P, got)
        assert m.tick() is None and m._pending_out is not None
        m.drain_pipeline()
        x = completed[-1]
        assert list(m._unreturned) == [x]
        m._queues[m.rows.row("svc")].clear()  # the rest: not this test's
        own = m.tick()
        assert own is completed[-1] and own is not x
        assert list(m._unreturned) == [x]
        while not wal.checkpoint_due():
            assert m.tick() is completed[-1]
        # checkpoint_due() stays true until the tick that writes it
        burst(m, 3 * m.P, got, tag="c")
        n = len(completed)
        out = m.tick()  # holds; the due checkpoint drains it at once
        assert m._pending_out is None and len(completed) == n + 1
        assert out is x
        assert list(m._unreturned) == [completed[-1]]
        wal.close()


@pytest.mark.parametrize("burst_at", [250, 252, 253, 254, 255, 256])
def test_periodic_work_runs_once_a_tick_number_across_side_changes(burst_at):
    """The sweep (every 64th tick) and the deactivation pass (every 256th)
    go by the completed tick's own number, not by the clock of the call
    that completes it: a held -> same-call call completes two ticks, a
    same-call -> held call none, and with the changes of side laid across
    tick 256 each due number still runs its work exactly once."""
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp)
        m.cfg.paxos.deactivation_ticks = 10**9  # the pass runs, pauses none
        _, numbers = record_completions(m)
        swept, paused = [], []
        real_sweep, real_pause = m._sweep_outstanding, m.pause_idle
        m._sweep_outstanding = lambda fr: (swept.append(m.tick_num),
                                           real_sweep(fr))[1]
        m.pause_idle = lambda *a: (paused.append(m.tick_num),
                                   real_pause(*a))[1]
        got = {}
        sides = []
        rids = []
        for t in range(270):
            if t == burst_at:
                rids += burst(m, 3 * m.P, got)
            m.tick()
            sides.append(m._pending_out is not None)
        m.drain_pipeline()
        assert all(got.get(r) == b"OK" for r in rids)
        held = [t for t, h in enumerate(sides) if h]
        assert held and held[0] == burst_at and held[-1] < 269
        assert numbers == list(range(1, 271))  # each tick completed once
        assert len(swept) == 270 // 64 and len(paused) == 1
        wal.close()


@pytest.mark.parametrize("side", ["held", "same_call"])
def test_checkpoint_drains_then_recovers_consistently(side):
    with tempfile.TemporaryDirectory() as tmp:
        # checkpoint every 8 ticks: on the held side (a standing backlog:
        # two P a tick from one entry replica) several snapshots land
        # mid-pipeline; in the same-call mode nothing is pending when one
        # is due
        m, wal, _ = make_manager(tmp, checkpoint_every=8)
        per_tick = 2 * m.P if side == "held" else 1
        before = completions(m)
        got = {}
        n = 0
        for _ in range(40):
            for _ in range(per_tick):
                m.propose("svc", f"PUT k{n} v{n}".encode(),
                          lambda rid, r: got.__setitem__(rid, r), entry=0)
                n += 1
            m.tick()
        for _ in range(2 * n // m.P + 20):
            m.tick()
        m.drain_pipeline()
        assert len(got) == n
        assert completions(m)[side] - before[side] >= 40
        wal.close()
        apps2 = [KVApp() for _ in range(3)]
        m2 = recover(m.cfg, 3, apps2, os.path.join(tmp, "wal"))
        for i in range(n):
            assert apps2[1].execute("svc", f"GET k{i}".encode(), 50_000 + i) \
                == f"v{i}".encode(), i
        assert m2._pending_out is None  # recovery is synchronous


def test_a_few_left_behind_a_full_tick_do_not_make_it_wait():
    """ISSUE 36 (the YCSB cell's hot name): P + 2 requests to one name in a
    tick that carries two requests each to four other names.  The inbox
    leaves two behind and says so (``inbox_deferred_requests``), but it
    placed twelve, and those are answered by the call that dispatched them;
    the two go with the next tick.  Two behind a tick that placed only the
    hot name's four: the same, two are fewer than four."""
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp)
        for k in range(4):
            m.create_paxos_instance(f"cool{k}", [0, 1, 2])
        m.run_ticks(2)
        got = {}
        answer = lambda rid, r: got.__setitem__(rid, r)  # noqa: E731
        for others in (2, 0):
            before, n0 = completions(m), len(got)
            hot = [m.propose("svc", f"PUT h{i} v".encode(), answer, entry=0)
                   for i in range(m.P + 2)]
            for k in range(4):
                for i in range(others):
                    m.propose(f"cool{k}", f"PUT c{i} v".encode(), answer)
            m.tick()
            assert m._pending_out is None
            assert len(got) - n0 == m.P + 4 * others
            assert [r for r in hot if r in got] == hot[:m.P]
            m.tick()
            assert len(got) - n0 == m.P + 2 + 4 * others
            after = completions(m)
            assert after["held"] == before["held"]
            assert after["same_call"] == before["same_call"] + 2
        assert set(got.values()) == {b"OK"}
        wal.close()


def test_a_rows_queue_goes_with_its_last_request():
    """ISSUE 36 (what slowed a serving plane with uptime): the inbox build
    visits the rows that have something queued.  A row whose queue a tick
    emptied is forgotten until its next request; one with more than P queued
    stays, and a request for a forgotten row is queued and answered as the
    first was."""
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp)
        names = ["svc"] + [f"n{k}" for k in range(6)]
        for name in names[1:]:
            m.create_paxos_instance(name, [0, 1, 2])
        got = {}
        answer = lambda rid, r: got.__setitem__(rid, r)  # noqa: E731
        for round_ in range(3):
            for name in names:
                m.propose(name, f"PUT k{round_} v".encode(), answer)
            burst(m, 2 * m.P, got, tag=f"r{round_}")
            m.tick()
            # only the row with more than P queued is still there
            assert list(m._queues) == [m.rows.row("svc")]
            assert len(m._queues[m.rows.row("svc")]) == m.P + 1
            m.run_ticks(3)
            m.drain_pipeline()
            assert not m._queues and m.pending_count() == 0
        assert len(got) == 3 * (len(names) + 2 * m.P)
        assert set(got.values()) == {b"OK"}
        wal.close()


@pytest.mark.parametrize("n", [1, 40])
def test_driver_stop_drains_pending(n):
    """One request (its tick completes itself) and a backlog (held ticks):
    the driver answers all of it and stops with nothing pending."""
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp)
        d = TickDriver(m, idle_sleep_s=0.01).start()
        d.wait_ready(120)
        before = completions(m)
        ev = threading.Event()
        got = {}
        with m.lock:  # the whole burst reaches one inbox build
            rids = [m.propose(
                "svc", f"PUT a{i} 1".encode(),
                lambda rid, r: (got.__setitem__(rid, r),
                                len(got) == n and ev.set()), entry=0)
                for i in range(n)]
        assert ev.wait(60), "pipelined response never arrived"
        assert [got[r] for r in rids] == [b"OK"] * n
        d.stop()
        assert m._pending_out is None
        assert (completions(m)["held"] > before["held"]) == (n > m.P)
        wal.close()


def test_pipelined_plane_is_pending_only_while_somebody_waits():
    """An idle pipelined plane holds nothing and counts as no pending work
    (its driver backs off); a backlog is pending work, on the held ticks
    too, until the tick that answers the last of it."""
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, _ = make_manager(tmp)
        m.run_ticks(3)
        assert m._pending_out is None
        assert m.pending_count() == 0
        got = {}
        rids = burst(m, 3 * m.P, got)
        seen = []
        for _ in range(12):
            if len(got) == len(rids):
                break
            seen.append((m.pending_count(), m._pending_out is not None))
            m.tick()
        assert len(got) == len(rids) and all(n > 0 for n, _ in seen), seen
        assert any(held for _, held in seen), seen
        assert m._pending_out is None and m.pending_count() == 0
        wal.close()


@pytest.mark.parametrize("pipeline", [True, False])
def test_inbox_copies_are_handed_in_turn_and_outlive_their_tick(
        pipeline, monkeypatch):
    """A dense ``_build_inbox`` (one that placed in bulk, or more than the
    short list holds: forced here, tests/test_short_inbox.py has the
    choice) hands the tick one of two resident copies of the staging
    arrays, not a fresh one: the copy a tick got stays as it was through
    the next build (a held tick's program may still be reading it), never
    aliases the staging arrays, and comes back two builds later."""
    from gigapaxos_tpu.paxos import manager as manager_mod

    monkeypatch.setattr(manager_mod, "_SHORT_INBOX", -1)
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
    # and the served path on them answers as before, held ticks included
    with tempfile.TemporaryDirectory() as tmp:
        m, wal, apps = make_manager(tmp, pipeline=pipeline)
        got = {}
        rids = []
        for i in range(12):
            for j in range(1 + i % 3 * m.P):
                rids.append(m.propose(
                    "svc", f"PUT k{i}-{j} v".encode(),
                    lambda rid, r: got.__setitem__(rid, r), entry=0))
            m.tick()
        m.run_ticks(30)
        m.drain_pipeline()
        assert all(got.get(rid) == b"OK" for rid in rids)
        assert list(got) == rids
        assert m.stats["executions"] == len(rids) * 3
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


@pytest.mark.parametrize("backlog", [False, True])
def test_sync_due_tick_still_returns_outbox(backlog):
    """A tick whose top-of-tick laggard sync drains the pipeline must hand
    the drained outbox to the caller, not swallow it: callers polling
    tick() (auto_sync_laggards consumers, the capacity probe) would
    otherwise silently miss one tick's lag/decided signals on exactly the
    ticks where repair happens.  Full-outbox mode, pipelined: with the
    repair's ticks completing their own outbox, and with a backlog that
    holds them."""
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
    m.drain_pipeline()
    while m._unreturned:
        m._unreturned.popleft()
    completed, _ = record_completions(m)
    if backlog:
        for i in range(5 * m.P):
            m.propose("svc", f"PUT l{i} {i}".encode(), entry=0)
    outs = [m.tick() for _ in range(8)]
    assert m.stats["checkpoint_transfers"] >= 1
    handed = [id(o) for o in outs if o is not None]
    if backlog:
        assert completions(m)["held"] >= 4
        # a call that held and had nothing to complete returns None, and
        # what the sync's drain completed waits for such a call: every
        # outbox is handed out once or still queued for it, none is lost
        m.drain_pipeline()
        assert len(set(handed)) == len(handed)
        assert (sorted(handed + [id(o) for o in m._unreturned])
                == sorted(id(o) for o in completed))
    else:
        # every tick returns its own outbox, the sync-due ones too
        assert handed == [id(o) for o in completed] and len(handed) == 8
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
