"""End-to-end control-plane test: a whole deployment in one process.

The analog of ``TESTReconfigurationClient`` driven by
``TESTReconfigurationMain.startLocalServers``
(reconfiguration/testing/TESTReconfigurationMain.java:86 +
TESTReconfigurationClient.java:676-1002): real sockets on loopback, real
reconfigurators with their paxos-replicated DB, real active replicas over
the dense device data plane — create/request/reconfigure/delete, state
carried across epochs.
"""

import pytest

from gigapaxos_tpu.client import ClientError, ReconfigurableAppClient
from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.node import InProcessCluster
from gigapaxos_tpu.reconfiguration.demand import RateBasedMigrationPolicy


def make_cfg(n_active=5, n_rc=3):
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 64
    cfg.paxos.window = 8
    for i in range(n_active):
        cfg.nodes.actives[f"AR{i}"] = ("127.0.0.1", 0)
    for i in range(n_rc):
        cfg.nodes.reconfigurators[f"RC{i}"] = ("127.0.0.1", 0)
    return cfg


@pytest.fixture(scope="module")
def cluster():
    cl = InProcessCluster(
        make_cfg(),
        KVApp,
        demand_profile_factory=lambda name: RateBasedMigrationPolicy(
            name, migrate_after=25
        ),
    )
    yield cl
    cl.close()


@pytest.fixture(scope="module")
def client(cluster):
    c = ReconfigurableAppClient(cluster.cfg.nodes)
    yield c
    c.close()


def test_create_and_request(cluster, client):
    resp = client.create("svc0")
    assert resp["ok"], resp
    actives = client.request_actives("svc0")
    assert len(actives) == 3
    assert set(actives) <= set(cluster.cfg.nodes.active_ids())
    assert client.request("svc0", b"PUT k v1") == b"OK"
    assert client.request("svc0", b"GET k") == b"v1"


def test_duplicate_create_fails(cluster, client):
    assert client.create("svc0")["ok"] is False


def test_unknown_name(cluster, client):
    with pytest.raises(ClientError):
        client.request_actives("nope", force=True)


def test_many_names_spread(cluster, client):
    seen = set()
    for i in range(6):
        name = f"spread{i}"
        assert client.create(name)["ok"]
        seen.update(client.request_actives(name))
        assert client.request(name, b"PUT a 1") == b"OK"
    # consistent hashing should use more than one 3-subset of 5 actives
    assert len(seen) > 3


def test_client_reconfigure_preserves_state(cluster, client):
    assert client.create("mig")["ok"]
    assert client.request("mig", b"PUT city amherst") == b"OK"
    old = set(client.request_actives("mig"))
    pool = set(cluster.cfg.nodes.active_ids())
    new = sorted((pool - old) | set(sorted(old)[:1]))[:3]
    assert set(new) != old
    resp = client.reconfigure("mig", new)
    assert resp["ok"], resp
    got = set(client.request_actives("mig", force=True))
    assert got == set(new)
    # state survived the epoch change via final-state transfer
    assert client.request("mig", b"GET city") == b"amherst"
    assert client.request("mig", b"PUT t 2") == b"OK"
    # record advanced to epoch 1 on every RC replica of the name's group
    rc = cluster.reconfigurators[cluster.rdb.primary_of("mig")]
    rec = rc.db.get("mig")
    assert rec.epoch == 1 and rec.state.value == "READY"


def test_delete(cluster, client):
    assert client.create("gone")["ok"]
    assert client.request("gone", b"PUT x 1") == b"OK"
    resp = client.delete("gone")
    assert resp["ok"], resp
    with pytest.raises(ClientError):
        client.request_actives("gone", force=True)
    # re-creating the same name starts fresh at epoch 0
    assert client.create("gone")["ok"]
    assert client.request("gone", b"GET x") == b"NF"


def _request_across_epochs(client, name: str, payload: bytes,
                           deadline: float) -> bytes:
    """``client.request``, asked again while the name is between epochs: the
    client's own chase is four tries, which a migration on a box shared
    with five other test workers can outlast (``TimeoutError: stopped``).
    The payloads here are idempotent, as that method's docstring asks."""
    import time

    while True:
        try:
            return client.request(name, payload)
        except TimeoutError:
            if time.monotonic() >= deadline:
                raise


def test_demand_driven_migration(cluster, client):
    """RateBasedMigrationPolicy(migrate_after=25): enough requests must
    trigger a primary-RC-driven migration without any client involvement."""
    import time

    assert client.create("hot")["ok"]
    before = set(client.request_actives("hot"))
    # every wait below returns as soon as what it waits for holds; the
    # deadline is sized for six xdist workers on one box, not an idle one
    # (the migration itself is seconds of wall-clock protocol timers)
    deadline = time.monotonic() + 120
    for i in range(40):
        # the migration starts inside this loop (at the 25th request)
        _request_across_epochs(client, "hot", f"PUT k{i} {i}".encode(),
                               deadline)
    after = before
    while time.monotonic() < deadline:
        after = set(client.request_actives("hot", force=True))
        if after != before:
            break
        _request_across_epochs(client, "hot", b"GET k0", deadline)
        time.sleep(0.25)
    assert after != before, "demand-driven migration never happened"
    # data survived
    assert _request_across_epochs(client, "hot", b"GET k1", deadline) == b"1"


def test_batched_creates(cluster, client):
    """One RC commit per create batch per RC group
    (BatchedCreateServiceName.java; TESTReconfigurationClient.java:676-1002
    exercises batched creates the same way)."""
    names = [f"batch{i}" for i in range(8)]
    resp = client.create_batch(names)
    assert resp["ok"], resp
    assert set(resp["results"]) == set(names)
    for n in names[:3]:
        assert client.request(n, b"PUT x 1") == b"OK"
        assert len(client.request_actives(n)) == 3
    # duplicate batch -> per-name exists errors, nothing re-created
    dup = client.create_batch(names[:2])
    assert not dup["ok"]
    assert all(r.get("error") == "exists" for r in dup["results"].values())


def test_anycast_request(cluster, client):
    """Anycast: the client never resolves the name's replica set — any
    active accepts the request and a non-hosting one forwards it to a
    hosting replica, which answers the client directly
    (sendRequestAnycast, ReconfigurableAppClientAsync.java:1357)."""
    assert client.create("anyc")["ok"]
    assert client.request("anyc", b"PUT k val") == b"OK"
    # 5 actives, 3 replicas: repeated anycasts hit non-members too, so the
    # forward path is exercised with high probability
    for _ in range(6):
        assert client.request_anycast("anyc", b"GET k") == b"val"


def test_echo_rtt(cluster, client):
    a = client.request_actives("svc0")[0]
    rtt = client.echo(a)
    assert 0 <= rtt < 5


def test_final_state_gc_starvation_heals_by_peer_repair(monkeypatch):
    """Round-5 root cause of the migrate/recreate stalls: the complete
    commits at a MAJORITY of AckStarts and WaitAckDropEpoch then GCs the
    previous epoch, so a slow member's final-state fetch can find no donor
    forever.  The fix: after a fruitless round past the give-up floor, the
    member births the epoch EMPTY + TAINTED (refusing to serve or donate)
    and the data plane's checkpoint transfer repairs it from a caught-up
    member of the NEW epoch."""
    import socket
    import time

    from gigapaxos_tpu.client import ReconfigurableAppClient
    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.reconfiguration import active_replica as arm
    from gigapaxos_tpu.reconfiguration import packets as pkt
    from gigapaxos_tpu.server import ModeBServer

    monkeypatch.setattr(arm.WaitEpochFinalState, "give_up_floor_s", 0.5)

    def fp():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 32
    cfg.fd.ping_interval_s = 0.1
    cfg.fd.timeout_s = 1.0
    for i in range(4):
        cfg.nodes.actives[f"AR{i}"] = ("127.0.0.1", fp())
    cfg.nodes.reconfigurators["RC0"] = ("127.0.0.1", fp())
    srv = {nid: ModeBServer(nid, cfg, start_fd=True)
           for nid in list(cfg.nodes.actives) + ["RC0"]}
    client = None
    try:
        for s in srv.values():
            assert s.wait_ready(300)
        client = ReconfigurableAppClient(cfg.nodes)
        assert client.create("svc", timeout=60)["ok"]
        assert client.request("svc", b"PUT city amherst", timeout=30) == b"OK"
        old = set(client.request_actives("svc"))
        newcomer = sorted(set(cfg.nodes.active_ids()) - old)[0]
        new = sorted(sorted(old)[:2] + [newcomer])

        # emulate the drop-GC race: every previous active reports the
        # final state GONE (as if WaitAckDropEpoch already ran — a plain
        # found=False without gone means "not stopped yet" and the asker
        # correctly keeps polling instead of giving up)
        def deny(ar):
            def h(sender, p):
                reply = pkt.epoch_final_state(p["name"], p["epoch"], None)
                reply["gone"] = True
                ar.m.send(p["requester"], reply)
            return h

        for nid in old:
            ar = srv[nid].active_replica
            ar.m.register(pkt.REQUEST_EPOCH_FINAL_STATE, deny(ar))
        assert client.reconfigure("svc", new, timeout=120)["ok"]

        deadline = time.monotonic() + 120
        val = None
        while time.monotonic() < deadline:
            try:
                val = client.request("svc", b"GET city", timeout=10)
                if val == b"amherst":
                    break
            except (TimeoutError, Exception):
                pass
            time.sleep(0.5)
        assert val == b"amherst", val

        # the starved member repaired from a NEW-epoch peer: taint gone,
        # real state present
        nc = srv[newcomer]
        deadline = time.monotonic() + 120
        repaired = False
        while time.monotonic() < deadline and not repaired:
            row = nc.node.rows.row("svc#1")
            repaired = (
                row is not None and row not in nc.node._tainted_rows
                and nc.app.db.get("svc#1", {}).get("city") == "amherst"
            )
            time.sleep(0.5)
        assert repaired, (dict(nc.app.db), sorted(nc.node._tainted_rows))
    finally:
        if client is not None:
            client.close()
        for s in srv.values():
            s.close()


def test_recreate_survives_stale_drop_of_old_incarnation():
    """Reincarnation safety (round-5 root cause of the delete/recreate
    stalls): a recreated name continues at tombstone+1, so the OLD
    incarnation's still-in-flight DropEpoch — delivered arbitrarily late —
    addresses a different data-plane group and can never destroy the new
    incarnation."""
    import socket
    import time

    from gigapaxos_tpu.client import ReconfigurableAppClient
    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.reconfiguration import packets as pkt
    from gigapaxos_tpu.server import ModeBServer

    def fp():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 32
    cfg.fd.ping_interval_s = 0.1
    cfg.fd.timeout_s = 1.0
    for i in range(3):
        cfg.nodes.actives[f"AR{i}"] = ("127.0.0.1", fp())
    cfg.nodes.reconfigurators["RC0"] = ("127.0.0.1", fp())
    srv = {nid: ModeBServer(nid, cfg, start_fd=True)
           for nid in list(cfg.nodes.actives) + ["RC0"]}
    client = None
    try:
        for s in srv.values():
            assert s.wait_ready(300)
        client = ReconfigurableAppClient(cfg.nodes)
        assert client.create("re", timeout=60)["ok"]
        assert client.request("re", b"PUT x 1", timeout=30) == b"OK"

        # hold back DROP_EPOCH delivery on every AR: the delete's GC stays
        # "in flight" past the recreate (the late-drop race, made certain)
        held = []

        def holder(ar):
            orig = ar._on_drop_epoch

            def h(sender, p):
                held.append((orig, sender, p))
            return h

        for i in range(3):
            ar = srv[f"AR{i}"].active_replica
            ar.m.register(pkt.DROP_EPOCH, holder(ar))

        # the drop task wants ALL acks but ages out (~8s,
        # WaitAckDropEpoch.max_restarts) and completes the delete anyway —
        # exactly the window where a recreate races the still-held drops
        assert client.delete("re", timeout=60)["ok"]
        assert client.create("re", timeout=60)["ok"]  # reincarnation
        assert client.request("re", b"PUT y 2", timeout=30) == b"OK"
        # every AR hosts the NEW incarnation at epoch tombstone+1 (> 0)
        for i in range(3):
            co = srv[f"AR{i}"].coordinator
            ep = co.current_epoch("re")
            assert ep is not None and ep >= 1, (i, ep)

        # now deliver the stale drops of the old incarnation
        for orig, sender, p in held:
            orig(sender, p)
        time.sleep(1.0)
        # the new incarnation survived: same epoch, data intact, still serving
        for i in range(3):
            co = srv[f"AR{i}"].coordinator
            assert co.current_epoch("re") is not None, i
        assert client.request("re", b"GET y", timeout=30) == b"2"
        assert client.request("re", b"GET x", timeout=30) == b"NF"  # new life
    finally:
        if client is not None:
            client.close()
        for s in srv.values():
            s.close()


@pytest.mark.parametrize("seed", [2, 6])
def test_random_control_plane_churn(seed):
    """Randomized control-plane churn through the real deployment: random
    create / write / migrate / delete / recreate across names, asserting
    read-your-writes across every epoch change, duplicate-create rejection,
    deleted-name fencing, and full model agreement at the end (the
    randomized twin of the ordered TESTReconfigurationClient methods,
    reconfiguration/testing/TESTReconfigurationClient.java:676-1002)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    cfg = make_cfg()
    cfg.paxos.max_groups = 96
    cluster = InProcessCluster(cfg, KVApp)
    client = ReconfigurableAppClient(cfg.nodes)
    ar = cfg.nodes.active_ids()
    model = {}  # name -> expected KV dict (None = deleted)
    try:
        for step in range(40):
            op = rng.choice(["create", "write", "migrate", "delete"],
                            p=[0.2, 0.4, 0.25, 0.15])
            name = f"churn{int(rng.integers(0, 6))}"
            if op == "create":
                resp = client.create(name, timeout=120)
                if model.get(name) is None:
                    assert resp["ok"], (step, name, resp)
                    model[name] = {}
                else:
                    # a timed-out-then-retried create maps 'exists' to
                    # ok=True (created_by_earlier_attempt) — only a CLEAN
                    # ok on a live name is a duplicate-create bug
                    assert (not resp["ok"]
                            or resp.get("note") == "created_by_earlier_attempt"),                         (step, name, resp)
            elif model.get(name) is None:
                continue
            elif op == "write":
                k, v = f"k{int(rng.integers(0, 4))}", f"v{step}"
                assert client.request(name, f"PUT {k} {v}".encode(),
                                      timeout=90) == b"OK"
                model[name][k] = v
            elif op == "migrate":
                base = int(rng.integers(0, len(ar)))
                new = [ar[(base + j) % len(ar)] for j in range(3)]
                assert client.reconfigure(name, new, timeout=120)["ok"]
                for k, v in model[name].items():  # read-your-writes
                    assert client.request(name, f"GET {k}".encode(),
                                          timeout=90) == v.encode()
            elif op == "delete":
                resp = client.delete(name, timeout=120)
                model[name] = None
                # a slow first attempt can succeed while its retry answers
                # not-ok against the WAIT_DELETE record — the authoritative
                # outcome is the fence, asserted either way below
                with pytest.raises((ClientError, TimeoutError)):
                    client.request(name, b"GET k0", timeout=8)
        for name, st in model.items():
            if st is None:
                continue
            for k, v in st.items():
                assert client.request(name, f"GET {k}".encode(),
                                      timeout=90) == v.encode()
    finally:
        client.close()
        cluster.close()
