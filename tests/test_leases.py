"""Lease plane tests (ISSUE 17): linearizable local reads.

Mode A: grant/renew/expiry are ``[G]`` columns folded inside the fused
tick; the holder serves reads locally iff its lease mirror validates and
the group is quiescent (executed frontier == accepted frontier); a new
coordinator waits out the prior holder's lease (+ skew margin) before
admitting writes.  Mode B keeps a pragmatic tick-denominated host twin
whose renewals are anchored at majority-contact time.

Covered here: grant/renew/local-read across every dispatch mode, the
register plane as a lease target, consensus fallback, the write fence on
failover, WAL recovery with leases on, the skew guard, config gates, the
``read_leases`` off bit-identity guarantee, and a multi-seed chaos soak
(crash/partition/fast-reelection flaps + bounded clock skew) with a
linearizability checker over a monotone register plus the per-slot S1
safety ledger.
"""

import os

import numpy as np
import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.modeb import ModeBNode
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.testing.chaos import SafetyLedger
from gigapaxos_tpu.testing.simnet import SimNet
from gigapaxos_tpu.wal.logger import PaxosLogger, recover


def mk_cfg(G=8, G_reg=0, compact=False, pipeline=False, leases=True,
           horizon=16, margin=4, window=None):
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = G
    cfg.paxos.register_groups = G_reg
    cfg.paxos.compact_outbox = compact
    cfg.paxos.pipeline_ticks = pipeline
    cfg.paxos.read_leases = leases
    cfg.paxos.lease_ticks = horizon
    cfg.paxos.lease_margin_ticks = margin
    if window is not None:
        cfg.paxos.window = window
    return cfg


def pump(m, n):
    for _ in range(n):
        m.tick()
    m.drain_pipeline()


# ------------------------------------------------------------ mode A basics

@pytest.mark.parametrize("compact,pipeline,g_reg",
                         [(False, False, 0), (False, True, 0),
                          (True, False, 4), (True, True, 4)])
def test_lease_grant_renew_and_local_read(compact, pipeline, g_reg):
    """The stable-coordinator path in every dispatch mode: a lease is
    granted to the winning coordinator, renewed each tick, and a read is
    answered locally (rid 0, synchronous callback) with the latest
    committed value."""
    m = PaxosManager(mk_cfg(compact=compact, pipeline=pipeline, G_reg=g_reg),
                     3, [KVApp() for _ in range(3)])
    m.create_paxos_instance("svc", [0, 1, 2])
    for i in range(5):
        m.propose("svc", f"PUT k v{i}".encode())
        m.tick()
    pump(m, 10)
    info = m.lease_info("svc")
    assert info is not None
    assert info["holder"] == 0 and info["epoch"] >= 1
    assert info["until"] > info["clock"]  # renewal keeps it ahead
    got = {}
    rid = m.read("svc", b"GET k",
                 lambda r, resp: got.update(rid=r, resp=resp))
    assert rid == 0 and got["rid"] == 0 and got["resp"] == b"v4"
    assert m.stats["local_reads"] >= 1


def test_register_group_lease_read():
    """Register groups (PR 16) are first-class lease targets: the W=1
    plane grants/renews through the same fold and serves local reads."""
    m = PaxosManager(mk_cfg(G_reg=4, compact=True), 3,
                     [KVApp() for _ in range(3)])
    m.create_paxos_instance("reg", [0, 1, 2], register=True)
    for i in range(6):
        m.propose("reg", f"PUT k r{i}".encode())
        m.tick()
    pump(m, 10)
    info = m.lease_info("reg")
    assert info is not None and info["holder"] == 0
    got = {}
    rid = m.read("reg", b"GET k", lambda r, resp: got.update(resp=resp))
    assert rid == 0 and got["resp"] == b"r5"


def test_read_falls_back_without_lease():
    """``read_leases`` off: the read API still works, but every read is a
    consensus round (CLS_READ propose through the ordered stream)."""
    m = PaxosManager(mk_cfg(leases=False), 3, [KVApp() for _ in range(3)])
    m.create_paxos_instance("svc", [0, 1, 2])
    for i in range(3):
        m.propose("svc", f"PUT k v{i}".encode())
        m.tick()
    pump(m, 8)
    assert m.lease_info("svc") is None
    got = {}
    rid = m.read("svc", b"GET k", lambda r, resp: got.update(resp=resp))
    assert rid != 0 and rid is not None
    pump(m, 8)
    assert got["resp"] == b"v2"
    assert m.stats["local_reads"] == 0


def test_skew_guard_blocks_local_reads():
    """The host-side validity check subtracts the configured skew
    allowance; a mirror clock assumed further ahead than the lease end
    must refuse local serving and fall back."""
    m = PaxosManager(mk_cfg(horizon=8, margin=2), 3,
                     [KVApp() for _ in range(3)])
    m.create_paxos_instance("svc", [0, 1, 2])
    m.propose("svc", b"PUT k v")
    pump(m, 6)
    assert m.read("svc", b"GET k") == 0  # sanity: local read works
    m._lease_skew_ticks = -100  # host clock effectively past any until
    got = {}
    rid = m.read("svc", b"GET k", lambda r, resp: got.update(resp=resp))
    assert rid != 0
    pump(m, 8)
    assert got["resp"] == b"v"


def test_write_fence_delays_failover_writes():
    """After the holder dies, the new coordinator may not ack writes
    until the prior lease (+ margin) has run out — and local reads at the
    dead holder are refused immediately."""
    horizon, margin = 12, 4
    m = PaxosManager(mk_cfg(horizon=horizon, margin=margin), 3,
                     [KVApp() for _ in range(3)])
    m.create_paxos_instance("svc", [0, 1, 2])
    m.propose("svc", b"PUT k old")
    pump(m, 5)
    assert m.lease_info("svc")["holder"] == 0
    m.set_alive(0, False)
    got = {}
    rid = m.read("svc", b"GET k", lambda r, resp: got.update(resp=resp))
    assert rid != 0  # dead holder: no local serving
    acks = []
    m.propose("svc", b"PUT k new", lambda r, resp: acks.append(resp))
    waited = 0
    for _ in range(4 * (horizon + margin)):
        m.tick()
        m.drain_pipeline()
        if acks:
            break
        waited += 1
    assert acks == [b"OK"]
    # the write really waited out the fence (several ticks, not one)
    assert waited >= margin, waited
    info = m.lease_info("svc")
    assert info["holder"] == 1 and info["epoch"] >= 2
    # and the new holder serves reads locally again
    got2 = {}
    assert m.read("svc", b"GET k",
                  lambda r, resp: got2.update(resp=resp)) == 0
    assert got2["resp"] == b"new"


def test_lease_cleared_on_remove_and_recreate():
    """Row lifecycle: removing a group drops its lease columns; a
    recreated group re-elects and re-grants from scratch (no stale
    holder resurrection through the row recycler)."""
    m = PaxosManager(mk_cfg(), 3, [KVApp() for _ in range(3)])
    m.create_paxos_instance("svc", [0, 1, 2])
    m.propose("svc", b"PUT k a")
    pump(m, 6)
    assert m.lease_info("svc")["holder"] == 0
    m.remove_paxos_instance("svc")
    assert m.lease_info("svc") is None
    m.create_paxos_instance("svc2", [0, 1, 2])
    m.propose("svc2", b"PUT k b")
    pump(m, 6)
    got = {}
    assert m.read("svc2", b"GET k",
                  lambda r, resp: got.update(resp=resp)) == 0
    assert got["resp"] == b"b"


def test_wal_recover_with_leases(tmp_path):
    """Crash + recover with leases on: the snapshot carries the lease
    plane, replayed ticks re-drive the fold, and the recovered manager
    keeps serving local reads."""
    cfg = mk_cfg(compact=True, pipeline=True)
    d = os.path.join(str(tmp_path), "wal")
    wal = PaxosLogger(d, checkpoint_every_ticks=10)
    apps = [KVApp() for _ in range(3)]
    m = PaxosManager(cfg, 3, apps, wal=wal)
    m.create_paxos_instance("svc", [0, 1, 2])
    for i in range(25):
        m.propose("svc", f"PUT k v{i}".encode())
        m.tick()
    pump(m, 10)
    want = m.exec_watermarks("svc").copy()
    info = m.lease_info("svc")
    assert info["holder"] == 0
    wal.close()
    apps2 = [KVApp() for _ in range(3)]
    m2 = recover(cfg, 3, apps2, d)
    assert np.array_equal(m2.exec_watermarks("svc"), want)
    info2 = m2.lease_info("svc")
    assert info2 is not None and info2["holder"] == 0
    assert info2["clock"] == info["clock"]
    pump(m2, 3)  # renewals continue post-recovery
    got = {}
    assert m2.read("svc", b"GET k",
                   lambda r, resp: got.update(resp=resp)) == 0
    assert got["resp"] == b"v24"


def test_leases_off_bit_identity(tmp_path):
    """The flag-off guarantee, and its stronger cousin: with a stable
    coordinator the lease fold never perturbs consensus — the log-plane
    state arrays and journal bytes are identical with leases on or off."""
    results = []
    for leases, sub in ((False, "off"), (True, "on")):
        cfg = mk_cfg(leases=leases, compact=True)
        d = os.path.join(str(tmp_path), sub)
        wal = PaxosLogger(d, checkpoint_every_ticks=1000)
        m = PaxosManager(cfg, 3, [KVApp() for _ in range(3)], wal=wal)
        m.create_paxos_instance("svc", [0, 1, 2])
        for i in range(12):
            m.propose("svc", f"PUT k{i} v{i}".encode())
            m.tick()
        pump(m, 8)
        wal.close()
        state = {f: np.asarray(getattr(m.state, f)) for f in m.state._fields}
        jpaths = sorted(p for p in os.listdir(d) if p.startswith("journal."))
        blobs = [open(os.path.join(d, p), "rb").read() for p in jpaths]
        results.append((state, jpaths, blobs))
    (st_a, jp_a, bl_a), (st_b, jp_b, bl_b) = results
    for f in st_a:
        assert np.array_equal(st_a[f], st_b[f]), f
    assert jp_a == jp_b
    assert bl_a == bl_b


def test_lease_config_gates():
    cfg = GigapaxosTpuConfig()
    cfg.paxos.read_leases = True
    cfg.paxos.lease_ticks = 0
    with pytest.raises(ValueError):
        cfg.paxos.__post_init__()
    cfg2 = GigapaxosTpuConfig()
    cfg2.paxos.lease_margin_ticks = -1
    with pytest.raises(ValueError):
        cfg2.paxos.__post_init__()


# --------------------------------------------------------- mode A chaos soak

@pytest.mark.parametrize("seed,pipeline", [(11, False), (12, False),
                                           (13, False), (11, True),
                                           (12, True), (13, True)])
def test_lease_soak_mode_a_linearizable(seed, pipeline):
    """Randomized holder crash/revive with skew injection on the shared
    device plane: every read that returns must be linearizable against
    the closed-loop monotone writer (floor = acked at invocation, ceiling
    = issued at response).  Pipelined: nobody drains between ticks, and
    every thirteenth tick another name takes more writes at one entry
    replica than three ticks place, so the lease packs ride held and
    same-call completions in turn and across the changes of side."""
    horizon, margin = 12, 4
    m = PaxosManager(mk_cfg(horizon=horizon, margin=margin, compact=True,
                            pipeline=pipeline),
                     3, [KVApp() for _ in range(3)])
    m.create_paxos_instance("svc", [0, 1, 2])
    m.create_paxos_instance("busy", [0, 1, 2])
    sides = []  # per tick: was its outbox held for the next call
    rng = np.random.default_rng(seed)
    state = {"acked": 0, "issued": 0, "outstanding": None}
    failures = []

    def write():
        val = state["issued"] + 1
        state["issued"] = val
        state["outstanding"] = val

        def cb(r, resp):
            if resp == b"OK":
                state["acked"] = max(state["acked"], val)
                if state["outstanding"] == val:
                    state["outstanding"] = None
        m.propose("svc", f"PUT k {val}".encode(), cb)

    def read(t):
        floor = state["acked"]

        def cb(r, resp, _floor=floor, _t=t):
            hi = state["issued"]
            if resp is None:
                return
            v = 0 if resp == b"NF" else int(resp)
            if not (_floor <= v <= hi):
                failures.append((_t, v, _floor, hi))
        m.read("svc", b"GET k", cb)

    down = None  # (replica, revive_tick)
    for t in range(320):
        if down is None and t > 20 and rng.random() < 0.02:
            victim = int(m.lease_info("svc")["holder"]) \
                if m.lease_info("svc") else 0
            if victim >= 0:
                m.set_alive(victim, False)
                down = (victim, t + int(rng.integers(
                    horizon + margin + 5, 3 * horizon)))
        if down is not None and t >= down[1]:
            m.set_alive(down[0], True)
            down = None
        if t % 40 == 7:  # bounded host-side skew assumption
            m._lease_skew_ticks = int(rng.integers(0, margin + 1))
        if state["outstanding"] is None and t % 3 == 0:
            write()
        if t % 2 == 0:
            read(t)
        if pipeline and t % 13 == 6:
            for i in range(3 * m.P):
                m.propose("busy", f"PUT b{i} x".encode(), entry=0)
        m.tick()
        sides.append(m._pending_out is not None)
        if not pipeline:
            m.drain_pipeline()
    if down is not None:
        m.set_alive(down[0], True)
    pump(m, 60)
    assert not failures, failures[:5]
    assert state["acked"] > 20
    assert m.stats["local_reads"] > 0
    if pipeline:
        assert sum(a != b for a, b in zip(sides, sides[1:])) >= 30, sides


# --------------------------------------------------------- mode B chaos soak

IDS = ["N0", "N1", "N2"]


def _build_modeb(seed, horizon, margin):
    net = SimNet(seed=seed)
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    cfg.paxos.window = 8
    cfg.paxos.fast_reelection = True
    cfg.paxos.read_leases = True
    cfg.paxos.lease_ticks = horizon
    cfg.paxos.lease_margin_ticks = margin
    apps = {n: KVApp() for n in IDS}
    nodes = {n: ModeBNode(cfg, IDS, n, apps[n], net.messenger(n),
                          anti_entropy_every=8) for n in IDS}
    for nd in nodes.values():
        nd.create_group("svc", [0, 1, 2])
    return net, nodes, apps


def test_modeb_local_read_and_takeover_fence():
    """Per-process twin: the winning coordinator serves local reads once
    its (bootstrap-fenced) lease settles; non-coordinators always fall
    back to a consensus round; a partition takeover write-fences."""
    horizon, margin = 8, 2
    net, nodes, apps = _build_modeb(3, horizon, margin)

    def spin(k, only=None):
        for _ in range(k):
            for nid, nd in nodes.items():
                if only is None or nid in only:
                    nd.tick()
            net.pump()

    done = []
    nodes["N0"].propose("svc", b"PUT k v1", lambda r, x: done.append(x))
    spin(60)
    assert done == [b"OK"]
    got = {}
    rid = nodes["N0"].read("svc", b"GET k",
                           lambda r, resp: got.update(resp=resp))
    assert rid == 0 and got["resp"] == b"v1"
    assert nodes["N0"].stats["local_reads"] >= 1
    # a non-coordinator never serves locally
    got2 = {}
    rid2 = nodes["N1"].read("svc", b"GET k",
                            lambda r, resp: got2.update(resp=resp))
    assert rid2 != 0
    spin(20)
    assert got2["resp"] == b"v1"
    # partition the holder away; the successor's writes wait out the fence
    net.partition({"N0"}, {"N1", "N2"})
    for nid in ("N1", "N2"):
        nodes[nid].set_alive(0, False)
    done2 = []
    nodes["N1"].propose("svc", b"PUT k v2", lambda r, x: done2.append(x))
    waited = 0
    for _ in range(8 * (horizon + margin)):
        spin(1, only=("N1", "N2"))
        if done2:
            break
        waited += 1
    assert done2 == [b"OK"]
    assert waited >= margin, waited  # fence delayed the takeover write
    # the isolated ex-holder's lease has lapsed: no local serving
    assert nodes["N0"].read("svc", b"GET k") != 0


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_lease_chaos_soak_modeb(seed):
    """The ISSUE 17 lease-safety soak: partition flaps with fast
    re-election, failure-detector driven takeovers, and bounded tick-skew
    stalls (<= margin per lease window).  Reads — including at isolated
    stale holders — must stay linearizable against the closed-loop
    monotone writer, and the cluster-wide per-slot S1 ledger must stay
    clean."""
    horizon, margin = 24, 6
    net, nodes, apps = _build_modeb(seed, horizon, margin)
    ledger = SafetyLedger()
    for nid, nd in nodes.items():
        ledger.attach(nid, nd)
    rng = np.random.default_rng(seed)
    T = 650
    # precomputed, non-overlapping isolation windows
    events = []
    t = 80
    while t < T - 120:
        victim = IDS[int(rng.integers(0, 3))]
        dur = int(rng.integers(horizon // 2, 2 * (horizon + margin)))
        events.append((t, t + dur, victim))
        t += dur + int(rng.integers(30, 70))

    def isolated(nid, tick):
        return any(s <= tick < e for (s, e, v) in events if v == nid)

    state = {"acked": 0, "issued": 0, "outstanding": None}
    failures = []

    def write(at):
        val = state["issued"] + 1
        state["issued"] = val
        state["outstanding"] = val

        def cb(r, resp):
            if resp == b"OK":
                state["acked"] = max(state["acked"], val)
                if state["outstanding"] == val:
                    state["outstanding"] = None
        nodes[at].propose("svc", f"PUT k {val}".encode(), cb)

    def read(at, tick):
        floor = state["acked"]

        def cb(r, resp, _floor=floor, _t=tick, _n=at):
            hi = state["issued"]
            if resp is None:
                return
            v = 0 if resp == b"NF" else int(resp)
            if not (_floor <= v <= hi):
                failures.append((_n, _t, v, _floor, hi))
        nodes[at].read("svc", b"GET k", cb)

    stalls = {n: 0 for n in IDS}
    for t in range(T):
        for (s, e, v) in events:
            if t == s:
                net.partition({v}, set(n for n in IDS if n != v))
            if t == s + 4 and t < e:  # failure-detector lag
                r = IDS.index(v)
                for nid, nd in nodes.items():
                    if nid != v:
                        nd.set_alive(r, False)
            if t == e:
                net.heal()
                for nd in nodes.values():
                    for r in range(3):
                        nd.set_alive(r, True)
        # bounded clock-skew injection: at most one stall per node per
        # >horizon window, each <= margin ticks (the lease assumption)
        if t % 60 == 17:
            stalls[IDS[int(rng.integers(0, 3))]] = int(
                rng.integers(1, margin + 1))
        # closed-loop writer at a node with no isolation in sight
        if state["outstanding"] is None and t % 3 == 0:
            cands = [n for n in IDS
                     if not any(v == n and s <= t + 50 and e > t
                                for (s, e, v) in events)]
            if cands:
                write(cands[int(rng.integers(0, len(cands)))])
        # reads everywhere, isolated stale holders very much included
        if t % 2 == 0:
            read(IDS[int(rng.integers(0, 3))], t)
        for nid, nd in nodes.items():
            if stalls[nid] > 0:
                stalls[nid] -= 1
                continue
            nd.tick()
        net.pump()
    net.heal()
    for nd in nodes.values():
        for r in range(3):
            nd.set_alive(r, True)
    for _ in range(90):
        for nd in nodes.values():
            nd.tick()
        net.pump()
    ledger.assert_safe()
    assert not failures, failures[:5]
    assert state["acked"] > 20, state
    assert sum(nd.stats["local_reads"] for nd in nodes.values()) > 0
