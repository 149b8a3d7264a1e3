"""Overload plane unit tests (ISSUE 14): deadline propagation, classed
admission control, retry budgets, and breakers — the "finish or refuse
fast" invariant checked mechanism by mechanism.

Integration (real sockets / full stack) lives in ``test_overload_bench.py``;
this file keeps each mechanism's contract pinned at the unit level so a
regression names the exact broken piece.
"""

import time

import pytest

from gigapaxos_tpu import overload
from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp, NoopApp
from gigapaxos_tpu.obs.metrics import registry


def _counter_total(name: str, **want) -> int:
    """Sum a registry counter family, filtered by label subset."""
    total = 0
    for m in registry().find(name):
        labels = dict(m.labels)
        if all(labels.get(k) == v for k, v in want.items()):
            total += int(m.value)
    return total


# ------------------------------------------------------------- primitives
def test_deadline_helpers():
    now = 1_700_000_000.0
    dl = overload.deadline_at(2.0, now=now)
    assert dl == int((now + 2.0) * 1000)
    assert not overload.expired(dl, now=now + 1.0)
    assert overload.expired(dl, now=now + 3.0)
    # no deadline / wire garbage never expires (old-peer compatibility)
    for junk in (None, 0, -5, "soon", 2.5):
        assert not overload.expired(junk)
    assert overload.remaining_s(None) is None
    assert overload.remaining_s(dl, now=now) == pytest.approx(2.0)


def test_count_expired_rejects_unknown_stage():
    with pytest.raises(ValueError):
        overload.count_expired("not_a_stage")


def test_token_bucket_is_a_retry_budget():
    tb = overload.TokenBucket(fraction=0.25, initial=2.0, cap=50.0)
    # a herd funding the bucket with 28 fresh requests banks 7 tokens on
    # top of the 2-token cold-start seed: at most 9 retries total, not 28
    for _ in range(28):
        tb.deposit()
    grants = sum(1 for _ in range(28) if tb.take())
    assert grants == 9
    assert not tb.take()  # dry: every further retry is refused
    assert tb.denied >= 19


def test_token_bucket_caps_banked_good_weather():
    tb = overload.TokenBucket(fraction=1.0, initial=0.0, cap=3.0)
    for _ in range(100):
        tb.deposit()
    assert tb.tokens == 3.0


def test_circuit_breaker_trips_and_recovers():
    t = [0.0]
    br = overload.CircuitBreaker(threshold=3, cooloff_s=1.0,
                                 clock=lambda: t[0])
    assert br.allow()
    for _ in range(3):
        br.record(False)
    assert not br.allow() and br.state == "open"
    t[0] = 1.5  # cooloff elapsed: half-open, probes allowed
    assert br.allow() and br.state == "half-open"
    br.record(False)  # failed probe re-trips with a DOUBLED cooloff
    assert not br.allow()
    t[0] = 2.9
    assert not br.allow()  # 1.5 + 2.0 > 2.9: still open
    t[0] = 4.0
    assert br.allow()
    br.record(True)  # successful probe closes and resets the backoff
    assert br.state == "closed"
    br.record(False)
    assert br.allow()  # one failure after recovery does not re-trip


def test_intake_governor_hysteresis():
    gov = overload.IntakeGovernor(hi=10, lo=4, node="t")
    assert gov.admit(overload.CLS_CLIENT)
    assert gov.update(10) is True  # crossed hi: shedding
    assert not gov.admit(overload.CLS_CLIENT)
    assert gov.admit(overload.CLS_CONTROL)  # control NEVER governed
    assert gov.update(6) is True   # inside the hysteresis band: still on
    assert gov.update(3) is False  # below lo: admitting again
    assert gov.admit(overload.CLS_CLIENT)
    assert gov.transitions == 2


def test_intake_governor_lo_defaults_to_half_hi():
    gov = overload.IntakeGovernor(hi=100, lo=0)
    assert gov.lo == 50
    gov = overload.IntakeGovernor(hi=100, lo=300)  # nonsense lo: clamped
    assert gov.lo == 50


# -------------------------------------------------- Mode A manager intake
def _manager(intake_hi=4096, n=3):
    from gigapaxos_tpu.paxos.manager import PaxosManager

    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    cfg.overload.intake_hi = intake_hi
    m = PaxosManager(cfg, n, [NoopApp() for _ in range(n)])
    m.create_paxos_instance("svc", list(range(n)))
    return m


def test_modea_intake_drops_expired_exactly_once():
    m = _manager()
    before = _counter_total("overload_expired_drops_total", stage="intake")
    got = []
    rid = m.propose("svc", b"dead", lambda r, resp: got.append((r, resp)),
                    deadline=1)  # 1 ms after the epoch: long expired
    assert rid is not None  # admission happened before the intake check
    m.run_ticks(3)
    assert got == [(overload.RID_EXPIRED, None)]
    assert m.stats["expired_drops"] == 1
    after = _counter_total("overload_expired_drops_total", stage="intake")
    assert after - before == 1  # counted ONCE, by the detecting stage


def test_modea_governor_sheds_client_not_control():
    m = _manager(intake_hi=4)
    got = []
    for i in range(6):  # back the intake up past the watermark
        m.propose("svc", f"p{i}".encode())
    m.tick()  # governor feeds on tick: backlog >= hi -> shedding
    assert m.overload.shedding
    rid = m.propose("svc", b"flooded", lambda r, resp: got.append(r),
                    cls=overload.CLS_CLIENT)
    assert rid is None
    m.run_ticks(1)
    assert got == [overload.RID_BUSY]  # explicit NACK, never a silent drop
    assert m.stats["shed_requests"] == 1
    # control class (epoch stops, RC plane) rides through the same overload
    assert m.propose("svc", b"control-op") is not None
    # drain: backlog falls below lo, admission resumes (hysteresis clears)
    m.run_ticks(30)
    assert not m.overload.shedding
    ok = []
    assert m.propose("svc", b"fresh", lambda r, resp: ok.append(r),
                     cls=overload.CLS_CLIENT) is not None
    m.run_ticks(10)
    assert ok and ok[0] > 0


def test_modea_governor_knows_a_batch_is_done_when_its_answers_leave():
    """A caller that sends its next batch the moment the last was answered
    (the benchmark's preload sends its waves so) meets a governor fed
    before the answers were released: it shed while the batch was its
    backlog, and admits the next one without waiting for a tick to start."""
    import numpy as np

    m = _manager(intake_hi=4)
    names = ["svc"] + [f"g{i}" for i in range(5)]
    for name in names[1:]:
        m.create_paxos_instance(name, [0, 1, 2])
    rows = np.array([m.rows.row(n) for n in names], np.int64)
    answered = []

    def sink(offsets, responses):
        answered.extend(responses)

    for wave in (b"w1", b"w2", b"w3"):
        before = m.overload.transitions
        rids = m.propose_bulk(rows, wave, batch_sink=sink,
                              cls=overload.CLS_CLIENT)
        assert (rids > 0).all(), (wave, rids, m.overload.backlog)
        del answered[:]
        for _ in range(10):
            m.tick()
            if len(answered) == len(rows):
                break
        assert len(answered) == len(rows)
        # six at once is past the watermark: it shed, and it has stopped
        assert m.overload.transitions == before + 2
        assert not m.overload.shedding


# ---------------------------------------------------- Mode B node intake
def test_modeb_flood_nacks_then_resumes():
    from gigapaxos_tpu.modeb import ModeBNode
    from gigapaxos_tpu.testing.simnet import SimNet

    ids = ["N0", "N1", "N2"]
    net = SimNet(seed=7)
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    cfg.overload.intake_hi = 8
    cfg.overload.intake_lo = 2
    nodes = {n: ModeBNode(cfg, ids, n, KVApp(), net.messenger(n))
             for n in ids}
    for nd in nodes.values():
        nd.create_group("svc", [0, 1, 2])
    outcomes = {"ok": 0, "busy": 0, "other": 0}

    def cb(rid, resp):
        if rid == overload.RID_BUSY:
            outcomes["busy"] += 1
        elif resp is not None:
            outcomes["ok"] += 1
        else:
            outcomes["other"] += 1

    def spin(k):
        for _ in range(k):
            for nd in nodes.values():
                nd.tick()
            net.pump()

    for i in range(40):  # flood one entry node with client-class writes
        nodes["N0"].propose("svc", f"PUT k{i % 5} v{i}".encode(), cb,
                            cls=overload.CLS_CLIENT)
        if i % 4 == 3:
            spin(1)
    spin(40)
    assert outcomes["busy"] > 0, outcomes  # the flood got explicit NACKs
    assert outcomes["ok"] > 0, outcomes    # admitted work still finished
    assert not nodes["N0"].overload.shedding  # drained below lo: resumed
    done = []
    nodes["N0"].propose("svc", b"PUT post flood", lambda r, p: done.append(r),
                        cls=overload.CLS_CLIENT)
    spin(20)
    assert done and done[0] > 0  # watermark cleared -> client work resumes
    # liveness traffic was never governed at this node
    assert _counter_total("overload_admission_shed_total",
                          cls="control") == 0


# -------------------------------------------------- transport class budget
def test_transport_sheds_client_class_only():
    from gigapaxos_tpu.net.transport import Transport

    inbox = []
    t = Transport("A", ("127.0.0.1", 0), lambda s, k, p: inbox.append(p),
                  resolve=lambda d: None,  # peer unresolvable: queues fill
                  send_queue_cap=8, client_queue_frac=0.5,
                  coalesce_frames=1)
    try:
        before = _counter_total("transport_backpressure_drop_class_total",
                                node="A")
        # one call = one atomic enqueue burst: client cap is 4, the writer
        # can hold at most 1 frame, so >= 25 of 30 frames must shed
        t.send_bytes_many("B", [b"c%d" % i for i in range(30)],
                          cls=overload.CLS_CLIENT)
        client_drops = t.stats.get("backpressure_drop:B:client", 0)
        assert client_drops >= 25
        # the control budget is untouched by the client flood
        t.send_bytes_many("B", [b"fd%d" % i for i in range(6)],
                          cls=overload.CLS_CONTROL)
        assert t.stats.get("backpressure_drop:B:control", 0) == 0
        after = _counter_total("transport_backpressure_drop_class_total",
                               node="A")
        assert after - before == client_drops  # mirrored into the registry
    finally:
        t.close()


def test_transport_drains_control_before_queued_client_backlog():
    import threading

    from gigapaxos_tpu.net.transport import Transport

    order = []
    got = threading.Event()
    rx = Transport("B", ("127.0.0.1", 0),
                   lambda s, k, p: (order.append(bytes(p)),
                                    got.set() if len(order) >= 10 else None),
                   resolve=lambda d: None)
    addr = {}
    tx = Transport("A", ("127.0.0.1", 0), lambda s, k, p: None,
                   resolve=lambda d: addr.get(d),
                   send_queue_cap=64, coalesce_frames=1)
    try:
        # peer unresolvable: a client backlog piles up behind the writer
        for i in range(12):
            tx.send_bytes("B", b"client%d" % i, cls=overload.CLS_CLIENT)
        tx.send_bytes("B", b"CONTROL", cls=overload.CLS_CONTROL)
        time.sleep(0.15)  # let the writer park holding one client frame
        addr["B"] = ("127.0.0.1", rx.port)  # link comes up
        assert got.wait(10)
        idx = order.index(b"CONTROL")
        # the writer may already hold one client frame in hand, but every
        # QUEUED client frame drains after the control frame
        assert idx <= 1, order[:4]
    finally:
        tx.close()
        rx.close()


# --------------------------------------------------------- client damping
def _stub_client(**kw):
    """A client whose wire is a black hole: sends are counted, never
    answered — the shape of a dead active."""
    cfg = GigapaxosTpuConfig()
    cfg.nodes.reconfigurators["RC0"] = ("127.0.0.1", 1)
    cfg.nodes.actives["AR0"] = ("127.0.0.1", 2)
    from gigapaxos_tpu.client import ReconfigurableAppClient

    c = ReconfigurableAppClient(cfg.nodes, **kw)
    sent = []
    c.request_actives = lambda name, force=False: ["AR0"]
    c.m.send = lambda dest, p, **k: sent.append(dest)
    return c, sent


def test_retry_budget_bounds_a_timeout_herd():
    # 6 fresh requests against a dead active fund 0.25*6 = 1.5 retry
    # tokens on top of a 1-token seed: total sends <= 6 fresh + 2 retries,
    # where unbudgeted full-tries retrying would send 6 * tries = 24
    c, sent = _stub_client(retry_fraction=0.25)
    c.retry_budget = overload.TokenBucket(fraction=0.25, initial=1.0)
    try:
        for _ in range(6):
            with pytest.raises(TimeoutError):
                c.request("svc", b"x", timeout=0.5, tries=4)
        assert len(sent) <= 8, len(sent)
        assert len(sent) < 6 * 4
        assert c.retry_budget.denied >= 4
        # satellite (b): the sustained-timeout workload reaped every
        # per-rid map entry — nothing grows without bound
        assert not c._sent_at and not c._callbacks
        assert not c._cb_deadline and not c._trace_ids
    finally:
        c.close()


def test_breaker_screens_dead_target_but_fails_open():
    c, _sent = _stub_client()
    try:
        br = c._breaker("AR1")
        for _ in range(5):
            br.record(False)  # NACK storm trips AR1's breaker
        for _ in range(20):
            assert c._pick_active(["AR0", "AR1"]) == "AR0"
        # every breaker open: fail open so SOME target carries the probe
        br0 = c._breaker("AR0")
        for _ in range(5):
            br0.record(False)
        assert c._pick_active(["AR0", "AR1"]) in ("AR0", "AR1")
    finally:
        c.close()


def test_async_send_stamps_wire_deadline():
    c, _sent = _stub_client(default_deadline_s=3.0)
    sent_pkts = []
    c.m.send = lambda dest, p, **k: sent_pkts.append(p)
    try:
        c.send_request("svc", b"x", lambda p: None)
        dl = sent_pkts[-1]["deadline"]
        assert isinstance(dl, int)
        assert 0 < overload.remaining_s(dl) <= 3.0
        # <= 0 disables stamping (explicit opt-out keeps old-peer shape)
        c.default_deadline_s = 0.0
        c.send_request("svc", b"x", lambda p: None)
        assert sent_pkts[-1]["deadline"] == 0
    finally:
        c.close()
