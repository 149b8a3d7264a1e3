"""Unit tests for the reconfiguration core: records, hashing, coordinator SPI,
demand profiles."""

import numpy as np

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.reconfiguration.consistent_hashing import ConsistentHashRing
from gigapaxos_tpu.reconfiguration.coordinator import PaxosReplicaCoordinator
from gigapaxos_tpu.reconfiguration.demand import (
    DemandProfile,
    RateBasedMigrationPolicy,
)
from gigapaxos_tpu.reconfiguration.records import RCState, ReconfigurationRecord


# ------------------------------------------------------------------- records
def test_record_lifecycle_ready_stop_ready():
    r = ReconfigurationRecord("svc", actives=["a", "b", "c"])
    assert r.can_reconfigure()
    assert r.set_intent(["b", "c", "d"])
    assert r.state == RCState.WAIT_ACK_STOP
    assert not r.set_intent(["x"])  # no intent on top of intent
    assert not r.set_delete_intent()  # no delete mid-reconfiguration
    assert r.set_complete()
    assert r.state == RCState.READY and r.epoch == 1
    assert r.actives == ["b", "c", "d"] and r.new_actives == []


def test_record_delete_flow_and_aging():
    r = ReconfigurationRecord("svc", actives=["a"])
    assert r.set_delete_intent(now=100.0)
    assert r.state == RCState.WAIT_DELETE
    assert not r.set_intent(["b"])  # dead name cannot reconfigure
    assert not r.delete_aged(60.0, now=120.0)
    assert r.delete_aged(60.0, now=161.0)


def test_record_roundtrip():
    r = ReconfigurationRecord("svc", epoch=3, actives=["a", "b"])
    r.set_intent(["b", "c"])
    d = r.to_dict()
    r2 = ReconfigurationRecord.from_dict(d)
    assert r2.to_dict() == d
    assert r2.state == RCState.WAIT_ACK_STOP and r2.epoch == 3


# ------------------------------------------------------------------- hashing
def test_consistent_hashing_deterministic_and_balanced():
    nodes = [f"rc{i}" for i in range(5)]
    ring = ConsistentHashRing(nodes)
    ring2 = ConsistentHashRing(list(reversed(nodes)))
    names = [f"name{i}" for i in range(500)]
    counts = {n: 0 for n in nodes}
    for nm in names:
        grp = ring.replicated_servers(nm, 3)
        assert grp == ring2.replicated_servers(nm, 3)  # order-independent
        assert len(set(grp)) == 3
        counts[grp[0]] += 1
    # every node is primary for a reasonable share (perfect = 100)
    assert min(counts.values()) > 30, counts


def test_consistent_hashing_minimal_disruption_on_node_add():
    nodes = [f"rc{i}" for i in range(5)]
    ring_a = ConsistentHashRing(nodes)
    ring_b = ConsistentHashRing(nodes + ["rc5"])
    names = [f"n{i}" for i in range(300)]
    moved = sum(
        1 for nm in names if ring_a.primary(nm) != ring_b.primary(nm)
    )
    # ~1/6 of primaries should move; far less than a full reshuffle
    assert moved < len(names) * 0.4, moved


def test_consistent_hashing_k_capped():
    ring = ConsistentHashRing(["a", "b"])
    assert sorted(ring.replicated_servers("x", 5)) == ["a", "b"]
    assert ConsistentHashRing([]).replicated_servers("x", 3) == []


# ---------------------------------------------------------------- coordinator
def make_coord(R=3):
    cfg = GigapaxosTpuConfig()
    mgr = PaxosManager(cfg, R, [KVApp() for _ in range(R)])
    nodes = [f"AR{i}" for i in range(R)]
    return PaxosReplicaCoordinator(mgr, nodes), mgr, nodes


def test_coordinator_create_request_epoch_bump_and_final_state():
    coord, mgr, nodes = make_coord()
    assert coord.create_replica_group("svc", 0, b"", nodes)
    assert coord.current_epoch("svc") == 0
    assert sorted(coord.get_replica_group("svc")) == nodes

    got = []
    rid = coord.coordinate_request(
        "svc", 0, b"PUT k v0", lambda r, resp: got.append(resp)
    )
    assert rid is not None
    mgr.run_ticks(4)
    assert got == [b"OK"]

    # wrong epoch is refused outright
    assert coord.coordinate_request("svc", 1, b"PUT k bad") is None

    # stop epoch 0, fetch final state, start epoch 1 from it on fewer nodes
    done = []
    assert coord.stop_replica_group("svc", 0, lambda ok: done.append(ok))
    mgr.run_ticks(4)
    assert done == [True]
    fs = coord.get_final_state("svc", 0)
    assert fs is not None and b"v0" in fs

    assert coord.create_replica_group("svc", 1, fs, nodes[:2])
    assert coord.current_epoch("svc") == 1
    got2 = []
    coord.coordinate_request("svc", 1, b"GET k", lambda r, resp: got2.append(resp))
    mgr.run_ticks(4)
    assert got2 == [b"v0"]  # state carried across the epoch change

    # requests to the stopped old epoch are refused
    assert coord.coordinate_request("svc", 0, b"GET k") is None

    # GC the old epoch
    assert coord.drop_final_state("svc", 0)
    assert coord.get_final_state("svc", 0) is None


def test_get_final_state_serves_from_undrained_pipeline():
    """Pipelined manager on the held side (another name's backlog keeps
    every tick's outbox for the next call): the tick that decides the epoch
    stop leaves the stop (and the epoch's final writes) in the pending
    outbox until the NEXT tick completes it.  get_final_state must drain
    that pipeline under the manager lock and serve the complete final state
    immediately — not answer from the host's one-tick-stale view (None
    here; worse, a checkpoint missing the final writes once watermarks and
    host state skew).  Regression for the drain added to
    reconfiguration/coordinator.py:get_final_state."""
    import pytest as _pytest

    cfg = GigapaxosTpuConfig()
    cfg.paxos.pipeline_ticks = True
    mgr = PaxosManager(cfg, 3, [KVApp() for _ in range(3)])
    nodes = [f"AR{i}" for i in range(3)]
    coord = PaxosReplicaCoordinator(mgr, nodes)
    assert coord.create_replica_group("svc", 0, b"", nodes)
    assert mgr.create_paxos_instance("busy", [0, 1, 2])
    got = []
    coord.coordinate_request("svc", 0, b"PUT k v0",
                             lambda r, resp: got.append(resp))
    mgr.run_ticks(4)
    mgr.drain_pipeline()
    assert got == [b"OK"]

    # more than P a tick for one name at one entry replica, for longer
    # than the stop takes: every tick below holds its outbox
    for i in range(12 * mgr.P):
        mgr.propose("busy", f"PUT b{i} x".encode(), entry=0)

    # final write is device-decided (one tick), but its completion —
    # execution + host bookkeeping — still sits in the pipeline when the
    # stop goes in; a stop in the SAME inbox would win the slot race and
    # fail the write instead
    v1r = []
    coord.coordinate_request("svc", 0, b"PUT k2 v1",
                             lambda r, resp: v1r.append(resp))
    mgr.tick()
    done = []
    assert coord.stop_replica_group("svc", 0, lambda ok: done.append(ok))
    pname = "svc#0"
    for _ in range(8):
        mgr.tick()
        assert mgr._pending_out is not None  # the backlog holds it
        if not mgr.is_stopped(pname):
            # the decisive window: whatever this tick decided (eventually
            # the stop) is still in the pending outbox.  Once the stop is
            # device-decided, get_final_state must serve from HERE.
            fs = coord.get_final_state("svc", 0)
            if fs is not None:
                break
    else:
        _pytest.fail("get_final_state never served while the stop sat in "
                     "the undrained pipeline")
    assert b"v1" in fs and b"v0" in fs
    assert mgr.is_stopped(pname)  # the drain, not a later tick, completed it
    assert v1r == [b"OK"]
    assert done == [True]


def test_final_state_never_served_empty_during_drop():
    """get_final_state racing drop_final_state must return the real final
    state or None — never found-with-EMPTY-bytes.  A drop that frees the
    app table before the row (or without excluding donors) lets a donor
    answer found=True/state=b'' and the fetching newcomer births the new
    epoch empty+UNTAINTED — silent divergence (the null-checkpoint
    disambiguation hazard, PaxosManager.java:383-390).  Same invariant
    holds for the Mode B coordinator (modeb/coordinator.py)."""
    import threading as _t
    import time as _time

    coord, mgr, nodes = make_coord()
    coord.create_replica_group("svc", 0, b"", nodes)
    got = []
    coord.coordinate_request("svc", 0, b"PUT k v0",
                             lambda r, resp: got.append(resp))
    mgr.run_ticks(4)
    assert got == [b"OK"]
    done = []
    coord.stop_replica_group("svc", 0, lambda ok: done.append(ok))
    mgr.run_ticks(4)
    assert done == [True]
    real = coord.get_final_state("svc", 0)
    assert real and b"v0" in real

    # widen the drop's app-free window so an unserialized reader would
    # reliably land inside it
    slow_restores = []
    for app in mgr.apps:
        orig = app.restore

        def slow(name, state, _o=orig):
            _time.sleep(0.05)
            _o(name, state)
        slow_restores.append((app, orig))
        app.restore = slow

    seen = []
    stop_flag = []

    def reader():
        while not stop_flag:
            seen.append(coord.get_final_state("svc", 0))
            _time.sleep(0.001)

    th = _t.Thread(target=reader, daemon=True)
    th.start()
    try:
        _time.sleep(0.02)
        assert coord.drop_final_state("svc", 0)
        _time.sleep(0.05)
    finally:
        stop_flag.append(True)
        th.join(timeout=10)
        for app, orig in slow_restores:
            app.restore = orig
    assert all(s is None or (s and b"v0" in s) for s in seen), \
        [s for s in seen if not (s is None or (s and b"v0" in s))]
    assert coord.get_final_state("svc", 0) is None


def test_drop_final_state_clears_paused_stopped_epoch():
    """A stopped previous-epoch group that got PAUSED (spilled) under row
    pressure must still be fully removed by drop_final_state: leaving the
    _paused record behind would keep is_stopped/exec_watermarks answering
    from it while the app table below was freed — a donor serving
    found=True with EMPTY state (the paused variant of the drop race)."""
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    cfg.paxos.deactivation_ticks = 0  # everything quiescent is pausable
    mgr = PaxosManager(cfg, 3, [KVApp() for _ in range(3)])
    nodes = [f"AR{i}" for i in range(3)]
    coord = PaxosReplicaCoordinator(mgr, nodes)
    assert coord.create_replica_group("svc", 0, b"", nodes)
    got = []
    coord.coordinate_request("svc", 0, b"PUT k v0",
                             lambda r, resp: got.append(resp))
    mgr.run_ticks(4)
    assert got == [b"OK"]
    done = []
    coord.stop_replica_group("svc", 0, lambda ok: done.append(ok))
    mgr.run_ticks(4)
    assert done == [True]
    assert mgr.pause_idle(limit=8) >= 1
    assert mgr.rows.row("svc#0") is None and mgr.paused_count() >= 1
    # the donor still serves the REAL final state from the spill
    fs = coord.get_final_state("svc", 0)
    assert fs is not None and b"v0" in fs
    # GC: the paused record must go with the drop
    assert coord.drop_final_state("svc", 0)
    assert mgr.paused_count() == 0
    assert coord.get_final_state("svc", 0) is None
    assert not mgr.is_stopped("svc#0")


def test_coordinator_final_state_not_available_before_stop():
    coord, mgr, nodes = make_coord()
    coord.create_replica_group("svc", 0, b"", nodes)
    assert coord.get_final_state("svc", 0) is None


def test_coordinator_delete_group():
    coord, mgr, nodes = make_coord()
    coord.create_replica_group("svc", 0, b"", nodes)
    assert coord.delete_replica_group("svc", 0)
    assert coord.get_replica_group("svc") is None
    assert coord.coordinate_request("svc", 0, b"x") is None


# -------------------------------------------------------------------- demand
def test_demand_profile_report_cycle():
    p = DemandProfile("svc", min_requests_before_report=3)
    for i in range(2):
        p.register_request("c1", now=float(i))
    assert not p.should_report()
    p.register_request("c2", now=2.0)
    assert p.should_report()
    stats = p.get_stats()
    assert stats["nreqs"] == 3 and stats["ntotal"] == 3
    assert stats["by_sender"] == {"c1": 2, "c2": 1}
    assert stats["rate"] > 0
    assert not p.should_report()  # reporting reset the delta


def test_demand_aggregation_and_default_no_migration():
    agg = DemandProfile("svc")
    agg.combine({"nreqs": 5, "rate": 10.0, "by_sender": {"c": 5}})
    agg.combine({"nreqs": 7, "rate": 20.0, "by_sender": {"c": 7}})
    assert agg.num_total == 12 and agg.by_sender == {"c": 12}
    assert agg.reconfigure(["a"], ["a", "b"]) is None


def test_rate_based_migration_policy_rotates():
    pol = RateBasedMigrationPolicy("svc", migrate_after=5, min_requests_between=1)
    alln = ["n0", "n1", "n2", "n3", "n4"]
    pol.combine({"nreqs": 4, "rate": 1.0, "by_sender": {}})
    assert pol.reconfigure(["n0", "n1", "n2"], alln) is None  # under threshold
    pol.combine({"nreqs": 4, "rate": 1.0, "by_sender": {}})
    target = pol.reconfigure(["n0", "n1", "n2"], alln)
    assert target == ["n1", "n2", "n3"]
    pol.just_reconfigured()
    assert pol.reconfigure(target, alln) is None  # rate limited until new load
