"""The short inbox (ISSUE 37) against the dense one of the same tick.

A tick that placed at most ``manager._SHORT_INBOX`` requests and none in
bulk hands the device a list of its placements, and ``ops.tick
.scatter_inbox`` makes the [R, P, G] ``req`` / ``stop`` there; any other
tick hands over the dense arrays, copied and uploaded, as every tick did.
The tick's programs cannot tell the two apart.  Held here: a manager that
chooses serves, journals, answers and holds in its state exactly what one
forced to dense does (the seam: ``_SHORT_INBOX = -1``, a list nothing
fits) on one device, the (log, register) pair, the group axis sharded over
four devices and the device app; the choice falls where the count says at
K - 1, K and K + 1; a bulk placement takes its tick to dense; an idle
plane's inbox is one resident all-zero pair; a short list in flight under
a held tick outlives the next build; and after both ways were dispatched
once, going back and forth between them traces, lowers and compiles
nothing.
"""

import os

import jax
import numpy as np
import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.device_kv import OP_PUT
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.obs.metrics import registry
from gigapaxos_tpu.ops import tick as tk
from gigapaxos_tpu.paxos import manager as manager_mod
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.wal.logger import PaxosLogger
from test_outbox_head import assert_same_outbox
from test_replay_batched import journal_bytes

R = 3


def builds(plane: str) -> dict:
    snap = registry().snapshot()
    return {path: snap[f"inbox_builds_total{{path={path},plane={plane}}}"]
            for path in ("short", "dense")}


def upload_bytes(plane: str) -> dict:
    return registry().snapshot()[f"inbox_upload_bytes{{plane={plane}}}"]


def state_arrays(m) -> list:
    return [np.asarray(a) for s in (m.state, m.rstate, m.kv)
            if s is not None for a in jax.tree.leaves(s)]


def manager(tmp, plane: str, *, max_groups=64, register=0, mesh=0,
            device_app=False, pipeline=False, wal=True):
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = max_groups
    cfg.paxos.compact_outbox = True
    cfg.paxos.register_groups = register
    cfg.paxos.mesh_devices = mesh
    cfg.paxos.device_app = device_app
    cfg.paxos.pipeline_ticks = pipeline
    apps = [None if device_app else KVApp() for _ in range(R)]
    log = (PaxosLogger(os.path.join(str(tmp), plane), native=False)
           if wal else None)
    return PaxosManager(cfg, R, apps, wal=log, spill_ns=plane), apps, log


def served(tmp, plane: str, dense_only: bool, monkeypatch, *, ticks=40,
           **build):
    """One seeded run through a journaling manager: bursts from all three
    entry replicas, a bulk placement in three of the ticks, a replica that
    is an entry of queued requests dead for a while (they are re-homed),
    three ticks without a quorum (placed intake is rejected, so requeued),
    a stop, and sixteen ticks with nothing to place.  Everything a caller
    or a restart could see of it, as plain data."""
    with monkeypatch.context() as mp:
        if dense_only:  # the parent's inbox: no tick's list fits
            mp.setattr(manager_mod, "_SHORT_INBOX", -1)
        m, apps, wal = manager(tmp, plane, **build)
        names = [f"g{i}" for i in range(5)]
        for name in names:
            m.create_paxos_instance(name, [0, 1, 2])
        regs = [f"r{i}" for i in range(2 if build.get("register") else 0)]
        for name in regs:
            m.create_paxos_instance(name, [0, 1, 2], register=True)
        rows = np.array([m.rows.row(n) for n in names], np.int64)
        replies, bulk_replies, outs, requeued, paths = {}, {}, [], 0, []
        bulked = []  # ticks whose inbox holds a bulk placement
        real = m._process_compact

        def spy(co, placed=None, *a, **kw):
            nonlocal requeued
            requeued += sum(
                not tk.taken_bit(co, entry, row, p)
                for row, take in (placed or []) for _, entry, p in take)
            return real(co, placed, *a, **kw)

        m._process_compact = spy
        rng = np.random.default_rng(37)
        for t in range(ticks):
            if t < 24 and t % 2 == 0:
                for name in names + regs:
                    for i in range(int(rng.integers(1, 7))):
                        m.propose(name, f"PUT k{t}.{i} v{t}".encode(),
                                  lambda rid, r: replies.__setitem__(rid, r),
                                  entry=int(rng.integers(R)))
            if t in (4, 5, 16):  # a bulk placement in the tick: dense
                m.propose_bulk(
                    rows, [f"PUT b{t} {i}".encode() for i in range(5)],
                    callbacks=[lambda rid, r: bulk_replies.__setitem__(rid, r)
                               ] * 5)
            if t in (6, 18):  # the entry of queued requests dies
                m.set_alive(2, t == 18)
            if t in (10, 13):  # no quorum: windows fill, intake is rejected
                m.set_alive(1, t == 13)
            if t == 22:
                m.propose_stop("g4")
            before = builds(plane)
            co = m.tick()
            paths.append(next(p for p, n in builds(plane).items()
                              if n > before[p]))
            if m._bulk_placed is not None:
                bulked.append(t)
            outs.append(co._replace(taken_bits=tk.taken_dense(co, m.G_total),
                                    taken_shift=0))
        m.drain_pipeline()
        wal.close()
        return dict(replies=replies, bulk_replies=bulk_replies, outs=outs,
                    requeued=requeued, paths=paths, bulked=bulked,
                    journal=journal_bytes(os.path.join(str(tmp), plane)),
                    dbs=[a.db for a in apps], stats=dict(m.stats),
                    state=state_arrays(m), upload=upload_bytes(plane),
                    G=m.G_total, dense_bytes=5 * R * m.P * m.G_total)


def assert_same_run(short: dict, dense: dict) -> None:
    assert len(short["outs"]) == len(dense["outs"])
    for t, (a, b) in enumerate(zip(short["outs"], dense["outs"])):
        assert_same_outbox(a, b, short["G"], f"tick {t}")
    for key in ("replies", "bulk_replies", "requeued", "journal", "dbs",
                "stats"):
        assert short[key] == dense[key], key
    assert len(short["state"]) == len(dense["state"])
    for a, b in zip(short["state"], dense["state"]):
        assert a.dtype == b.dtype and np.array_equal(a, b)


BUILDS = {
    "one-device": dict(),
    "register-pair": dict(register=16),
    "mesh-of-4": dict(mesh=4, max_groups=512),
}


@pytest.mark.parametrize("build", BUILDS)
def test_a_manager_on_short_lists_serves_what_one_on_dense_inboxes_serves(
        tmp_path, monkeypatch, build):
    if build == "mesh-of-4" and len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    kw = BUILDS[build]
    tag = build.replace("-", "_")
    short = served(tmp_path, f"t_short_{tag}", False, monkeypatch, **kw)
    dense = served(tmp_path, f"t_dense_{tag}", True, monkeypatch, **kw)
    assert dense["paths"] == ["dense"] * 40
    # the first build is dense whatever it placed; then the ticks that
    # placed in bulk: the three proposed into and those they left work for
    assert short["bulked"] == dense["bulked"]
    assert {4, 5, 16} <= set(short["bulked"]) and len(short["bulked"]) < 8
    want = ["dense" if t == 0 or t in short["bulked"] else "short"
            for t in range(40)]
    assert short["paths"] == want
    # the traffic met what the list has to carry
    assert short["requeued"] > 0 and short["stats"]["executions"] > 100
    assert sum(int(o.e_stop.sum()) for o in short["outs"]) == R  # the stop
    assert len(short["replies"]) > 50 and len(short["bulk_replies"]) == 15
    assert short["journal"]
    assert_same_run(short, dense)
    # what was handed to the dispatch: the dense pair, a list, or nothing
    K = manager_mod._SHORT_INBOX
    up, n_dense = short["upload"], want.count("dense")
    # ticks that placed nothing took the resident pair (the register rows,
    # one decision a tick, are still draining at the run's end)
    idle = up["buckets"].get("0", 0)
    assert up["count"] == 40 and (idle >= 4 or "register" in kw)
    assert up["sum"] == (n_dense * short["dense_bytes"]
                         + (40 - n_dense - idle) * 5 * 4 * K)
    assert dense["upload"]["sum"] == 40 * short["dense_bytes"]


def test_the_device_app_takes_a_short_inbox_as_it_takes_a_dense_one(
        tmp_path, monkeypatch):
    """``fused_compact`` is the program here.  Its requests arrive in bulk
    (dense); its idle ticks and a stop, a scalar placement, are short."""
    def run(plane, dense_only):
        with monkeypatch.context() as mp:
            if dense_only:
                mp.setattr(manager_mod, "_SHORT_INBOX", -1)
            m, _apps, wal = manager(tmp_path, plane, device_app=True,
                                    max_groups=32)
            for i in range(8):
                m.create_paxos_instance(f"d{i}", [0, 1, 2])
            rows = np.array([m.rows.row(f"d{i}") for i in range(8)])
            got, outs = {}, []
            for t in range(16):
                if t in (1, 2, 6):
                    m.propose_bulk_kv(
                        rows, [OP_PUT] * 8, [7] * 8, [t * 100 + i for i in
                                                      range(8)],
                        callbacks=[lambda rid, r: got.__setitem__(rid, r)] * 8)
                if t == 9:
                    m.propose_stop("d7", callback=lambda rid, r:
                                   got.__setitem__(rid, r))
                outs.append(m.tick())
            m.drain_pipeline()
            wal.close()
            return dict(got=got, outs=outs, paths=builds(plane),
                        state=state_arrays(m), stats=dict(m.stats),
                        journal=journal_bytes(os.path.join(str(tmp_path),
                                                           plane)))

    short, dense = run("t_short_devapp", False), run("t_dense_devapp", True)
    assert dense["paths"] == {"short": 0, "dense": 16}
    assert short["paths"]["short"] >= 10 and short["paths"]["dense"] >= 4
    assert len(short["got"]) == 25
    assert sum(int(o.e_stop.sum()) for o in short["outs"]) == R
    for t, (a, b) in enumerate(zip(short["outs"], dense["outs"])):
        assert_same_outbox(a, b, 32, f"tick {t}")
    for key in ("got", "stats", "journal"):
        assert short[key] == dense[key], key
    for a, b in zip(short["state"], dense["state"]):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("over", [-1, 0, 1])
def test_the_count_decides_at_the_lists_length(tmp_path, monkeypatch, over):
    """A tick that places K - 1 or K requests hands over the list, one that
    places K + 1 the dense arrays, and all three are served as a dense-only
    manager serves them."""
    K = 16

    def run(plane, dense_only):
        with monkeypatch.context() as mp:
            mp.setattr(manager_mod, "_SHORT_INBOX", -1 if dense_only else K)
            m, apps, wal = manager(tmp_path, plane)
            names = [f"n{i}" for i in range(K + 1)]
            for name in names:
                m.create_paxos_instance(name, [0, 1, 2])
            m.tick()  # the first build: dense whatever it placed
            got = {}
            for i, name in enumerate(names[:K + over]):
                m.propose(name, f"PUT k v{i}".encode(),
                          lambda rid, r: got.__setitem__(rid, r), entry=i % R)
            before = builds(plane)
            inbox = m._build_inbox()
            took = {p: n - before[p] for p, n in builds(plane).items()}
            req, stop = np.asarray(inbox.req), np.asarray(inbox.stop)
            assert int((req != 0).sum()) == K + over and not stop.any()
            assert np.array_equal(req, m._in_req)
            # the build placed them; a tick of its own inbox decides them
            fn, args = m.tick_program(inbox)
            res = fn(*args)
            (m.state, *_), packs = res
            wal.close()
            return dict(took=took, req=req, isnp=isinstance(inbox.req,
                                                            np.ndarray),
                        pack=np.asarray(packs.out.head),
                        state=state_arrays(m))

    short, dense = run(f"t_k_short_{over}", False), run(f"t_k_dense_{over}",
                                                       True)
    assert dense["took"] == {"short": 0, "dense": 1} and dense["isnp"]
    assert short["took"] == ({"short": 0, "dense": 1} if over > 0
                             else {"short": 1, "dense": 0})
    assert short["isnp"] == (over > 0)
    assert np.array_equal(short["req"], dense["req"])
    assert np.array_equal(short["pack"], dense["pack"])
    for a, b in zip(short["state"], dense["state"]):
        assert np.array_equal(a, b)


def test_an_idle_planes_inbox_is_one_resident_pair_of_zeros(tmp_path):
    m, _apps, _ = manager(tmp_path, "t_idle_inbox", wal=False)
    m.create_paxos_instance("svc", [0, 1, 2])
    m.tick()
    assert m._zero_inbox is None  # the first build was dense
    a = m._build_inbox()
    b = m._build_inbox()
    assert a.req is b.req and a.stop is b.stop and a.req is m._zero_inbox[0]
    assert a.alive is not b.alive and isinstance(a.alive, np.ndarray)
    assert a.req.shape == (R, m.P, m.G_total) and a.req.dtype == np.int32
    assert a.stop.dtype == np.bool_
    assert not np.asarray(a.req).any() and not np.asarray(a.stop).any()
    m.run_ticks(5)  # ticks take it, and leave it as it was
    assert m._build_inbox().req is a.req and not np.asarray(a.req).any()
    up = upload_bytes("t_idle_inbox")
    assert builds("t_idle_inbox") == {"short": 8, "dense": 1}
    # the first, dense; the list of nothing that made the pair; then 0
    assert up["sum"] == (5 * R * m.P * m.G_total
                         + 5 * 4 * manager_mod._SHORT_INBOX)
    assert up["buckets"]["0"] == 7


def test_a_short_list_in_flight_outlives_the_next_build(tmp_path,
                                                       monkeypatch):
    """The held side of ``pipeline_ticks``: a backlog on one name keeps
    every tick's outbox for the next call, so tick N's program may still be
    reading its inbox while N + 1's is built.  Each short inbox is made
    from a fresh list and is an array of its own; the run is served as a
    dense-only manager serves it."""
    def run(plane, dense_only):
        with monkeypatch.context() as mp:
            if dense_only:
                mp.setattr(manager_mod, "_SHORT_INBOX", -1)
            m, apps, wal = manager(tmp_path, plane, pipeline=True)
            m.create_paxos_instance("svc", [0, 1, 2])
            m.create_paxos_instance("other", [0, 1, 2])
            m.tick()
            got, order, held, inboxes = {}, [], [], []
            real = m._build_inbox

            def keeping():
                inboxes.append(real())
                # what the tick before was handed, still as it was handed
                for ib, req in inboxes[-2:-1]:
                    assert np.array_equal(np.asarray(ib.req), req)
                inboxes[-1] = (inboxes[-1], np.array(inboxes[-1].req))
                return inboxes[-1][0]

            m._build_inbox = keeping
            for i in range(3 * m.P):  # one name, one entry: a backlog
                m.propose("svc", f"PUT k{i} v".encode(),
                          lambda rid, r: (got.__setitem__(rid, r),
                                          order.append(rid)), entry=0)
            for t in range(8):
                if t == 3:
                    m.propose("other", b"PUT x y",
                              lambda rid, r: got.__setitem__(rid, r))
                m.tick()
                held.append(m._pending_out is not None)
            m.drain_pipeline()
            wal.close()
            return dict(got=got, order=order, held=held,
                        reqs=[req for _, req in inboxes],
                        fresh=len({id(ib.req) for ib, _ in inboxes[:3]}),
                        paths=builds(plane), dbs=[a.db for a in apps],
                        stats=dict(m.stats), state=state_arrays(m),
                        journal=journal_bytes(os.path.join(str(tmp_path),
                                                           plane)))

    short, dense = run("t_held_short", False), run("t_held_dense", True)
    assert short["held"][:2] == [True, True] and short["held"] == dense["held"]
    assert short["paths"] == {"short": 8, "dense": 1} and short["fresh"] == 3
    assert len(short["got"]) == 3 * 4 + 1 and short["order"] == sorted(
        short["order"])
    for a, b in zip(short["reqs"], dense["reqs"]):
        assert np.array_equal(a, b)
    for key in ("got", "order", "dbs", "stats", "journal"):
        assert short[key] == dense[key], key
    for a, b in zip(short["state"], dense["state"]):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("build", ["one-device", "mesh-of-4"])
def test_going_between_dense_and_short_compiles_nothing(tmp_path, build):
    """The tick's programs take a short inbox's device arrays as they take
    a dense one's numpy arrays: after each way was dispatched once (and an
    empty one), dense -> short -> dense -> short traces, lowers and
    compiles nothing, and looks nothing up in the persistent cache."""
    if build == "mesh-of-4" and len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    plane = "t_no_compile_" + build[:4]
    m, _apps, _ = manager(tmp_path, plane, wal=False, **BUILDS[build])
    for i in range(4):
        m.create_paxos_instance(f"g{i}", [0, 1, 2])
    rows = np.array([m.rows.row(f"g{i}") for i in range(4)], np.int64)
    got = []

    def dense_tick():
        m.propose_bulk(rows, b"PUT b 1",
                       callbacks=[lambda rid, r: got.append(r)] * 4)
        m.tick()

    def short_tick():
        for i in range(4):
            m.propose(f"g{i}", b"PUT s 2", lambda rid, r: got.append(r))
        m.tick()

    def compiles():
        snap = registry().snapshot()
        return ([snap[f"jit_compile_seconds{{stage={s}}}"]["count"]
                 for s in ("trace", "lower", "backend")],
                [snap[f"compile_cache_lookups_total{{result={r}}}"]
                 for r in ("hit", "miss")])

    m.tick()  # the first build
    dense_tick(), short_tick(), m.run_ticks(3)  # the warm-up, idle ticks too
    before, paths0 = compiles(), builds(plane)
    dense_tick(), short_tick(), dense_tick(), short_tick()
    assert compiles() == before
    paths = builds(plane)
    assert {p: paths[p] - paths0[p] for p in paths} == {"short": 2, "dense": 2}
    m.run_ticks(4)
    assert got.count(b"OK") == 6 * 4
