"""The head of the compact outbox (ISSUE 34) against the flat buffer of the
same tick.

The served tick returns, beside the flat compact buffer, a short head of it
(``ops.tick.CompactLayout``: the acceptance bits packed ``32 // P`` groups
to a word, the first ``head_exec`` entries of the exec columns, the laggard
columns whole), and the host pulls the head alone unless its header says
the tick decided more than it holds.  Held here: the host outbox built from
the head equals the one built from the flat buffer field by field, for
every P and for widths that are no multiple of ``32 // P``; the boundary
``n_exec = head_exec`` falls on the side the header says; and a manager
that pulls heads journals, answers and executes exactly what one forced to
the flat pull does: one device, the (log, register) pair, the group axis
sharded over four devices, and with the placement plane's host fold.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.obs.metrics import registry
from gigapaxos_tpu.ops import tick as tk
from gigapaxos_tpu.paxos import manager as manager_mod
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.wal.logger import PaxosLogger
from test_replay_batched import journal_bytes

R, W = 3, 4

# ------------------------------------------------ the two unpacks, one tick


def random_outbox(seed: int, G: int, P: int, hits: int,
                  laggards: int) -> tk.TickOutbox:
    """A tick's outbox with ``hits`` executions (requests, noops and stops
    among them), ``laggards`` laggard pairs and random acceptance bits."""
    rng = np.random.default_rng([seed, G, P])
    cnt = np.zeros(R * G, np.int32)
    cnt[rng.choice(R * G, hits, replace=False)] = 1
    lag = rng.integers(0, W, (R, G)).astype(np.int32)
    lag.reshape(-1)[rng.choice(R * G, laggards, replace=False)] = W + 2

    def i32(hi, shape):
        return rng.integers(0, hi, shape).astype(np.int32)

    return tk.TickOutbox(**{k: jnp.asarray(v) for k, v in dict(
        exec_req=i32(1 << 30, (R, W, G)) * (rng.random((R, W, G)) < 0.8),
        exec_stop=rng.random((R, W, G)) < 0.2,
        exec_base=i32(1 << 20, (R, G)),
        exec_count=cnt.reshape(R, G),
        intake_taken=rng.random((R, P, G)) < 0.4,
        coord_id=i32(R, (G,)),
        decided_now=i32(3, (G,)),
        lag=lag,
        donor=i32(R, (R, G)) - 1,
        donor_exec=i32(1 << 20, (R, G)),
        donor_status=i32(5, (R, G)),
    ).items()})


def assert_same_outbox(head: tk.CompactHostOutbox, flat: tk.CompactHostOutbox,
                       G: int, what="") -> None:
    """Field by field; the acceptance words through their readers."""
    for f in tk.CompactHostOutbox._fields:
        if f in ("taken_bits", "taken_shift"):
            continue
        a, b = getattr(head, f), getattr(flat, f)
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape, (what, f)
            assert np.array_equal(a, b), (what, f)
        else:
            assert a == b, (what, f, a, b)
    assert np.array_equal(tk.taken_dense(head, G), tk.taken_dense(flat, G)), (
        what, "taken_bits")


# per = 32 // P groups to a word: 32, 8, 4 and 1; widths that are and are
# not a multiple of it, narrower than a word's groups and wider
GEOMETRIES = [(1, 70), (1, 64), (4, 8), (4, 13), (4, 64), (4, 5), (8, 30),
              (8, 5), (31, 17)]


@pytest.mark.parametrize("P,G", GEOMETRIES,
                         ids=[f"P{p}-G{g}" for p, g in GEOMETRIES])
def test_the_head_unpacks_to_the_flat_buffers_outbox(P, G):
    E, Lb = 3 * G + 5, 6
    fn = jax.jit(lambda out: tk._compact_outbox_impl(out, E, Lb))
    L = tk.CompactLayout(R, G, E, Lb, P)
    assert L.per == 32 // P and L.Gw == -(-G // L.per)
    assert L.head_exec == E  # far under the served K: the head holds all
    for seed, (hits, laggards) in enumerate(
            [(0, 0), (1, 1), (G, 3), (2 * G, Lb),
             (3 * G, min(Lb + 4, R * G))]):
        out = random_outbox(seed, G, P, hits, laggards)
        pack = fn(out)
        assert pack.head.shape == (L.total_head,)
        assert pack.flat.shape == (L.total_plain,)
        flat = tk.unpack_compact(pack.flat, R, G, E, Lb)
        head = tk.unpack_head(pack.head, R, G, P, E, Lb)
        assert flat.n_exec == hits and flat.lag_n == laggards
        assert_same_outbox(head, flat, G, (P, G, hits))
        # every placed position reads the same bit, scalar and vectorized
        taken = np.asarray(out.intake_taken)
        e, p, g = np.meshgrid(np.arange(R), np.arange(P), np.arange(G),
                              indexing="ij")
        for co in (head, flat):
            assert np.array_equal(tk.taken_bit(co, e, g, p), taken)
        for e, p, g in [(0, 0, 0), (R - 1, P - 1, G - 1), (1, P // 2, G // 2)]:
            assert tk.taken_bit(head, e, g, p) == int(taken[e, p, g])


@pytest.mark.parametrize("over", [-1, 0, 1])
def test_the_header_says_whether_the_head_holds_the_tick(monkeypatch, over):
    """``n_exec`` at ``head_exec`` - 1, at it and past it: head, head, and
    None (the caller pulls the flat buffer), on either branch of the
    compaction."""
    P, G, E, Lb, Kh = 4, 2048, 300, 8, 24
    monkeypatch.setattr(tk, "_SPARSE_BLOCKS", Kh)
    L = tk.CompactLayout(R, G, E, Lb, P)
    assert L.head_exec == Kh < E
    assert tk.compact_blocks(R * W * G, E) == Kh  # the sparse branch exists
    fn = jax.jit(lambda out: tk._compact_outbox_impl(out, E, Lb))
    out = random_outbox(over + 1, G, P, Kh + over, 2)
    pack = fn(out)
    flat = tk.unpack_compact(pack.flat, R, G, E, Lb)
    head = tk.unpack_head(pack.head, R, G, P, E, Lb)
    assert flat.n_exec == Kh + over
    if over > 0:
        assert head is None
        assert int(np.asarray(pack.head)[0]) == Kh + over
    else:
        assert_same_outbox(head, flat, G, over)
    with pytest.raises(ValueError):  # a head of another geometry
        tk.unpack_head(np.asarray(pack.head)[:-1], R, G, P, E, Lb)


def test_a_merge_of_two_heads_is_the_merge_of_their_flat_buffers():
    """The (log, register) pair: composite rows do not divide into either
    plane's packed words, so the merge expands them."""
    P, g_log, g_reg, E, Lb = 4, 13, 6, 64, 4
    fn = jax.jit(lambda out: tk._compact_outbox_impl(out, E, Lb))
    packs = [fn(random_outbox(3, g, P, g, 2)) for g in (g_log, g_reg)]
    flats = [tk.unpack_compact(pk.flat, R, g, E, Lb)
             for pk, g in zip(packs, (g_log, g_reg))]
    heads = [tk.unpack_head(pk.head, R, g, P, E, Lb)
             for pk, g in zip(packs, (g_log, g_reg))]
    want = tk.merge_compact_outbox(*flats, g_log)
    got = tk.merge_compact_outbox(*heads, g_log, g_reg)
    assert got.taken_bits.shape == (R, g_log + g_reg) and not got.taken_shift
    assert_same_outbox(got, want, g_log + g_reg)
    with pytest.raises(ValueError):
        tk.merge_compact_outbox(*heads, g_log)


# -------------------------------------- a manager on heads, one on the flat
def _pulls(plane: str) -> dict:
    snap = registry().snapshot()
    return {pull: snap[f"outbox_pulls_total{{plane={plane},pull={pull}}}"]
            for pull in ("head", "full")}


def served(tmp, plane: str, flat_only: bool, monkeypatch, *, max_groups=64,
           register=0, mesh=0, placement=False, exec_budget=0, ticks=40):
    """One seeded run through a journaling manager: bursts from all three
    entry replicas, a replica that falls more than W behind and comes back
    (a laggard, its repair), three ticks without a quorum (the windows
    fill and placed intake is rejected, so requeued), and a stop.  Everything a caller or a restart
    could see of it, as plain data."""
    with monkeypatch.context() as mp:
        if flat_only:  # the parent's pull: every head is refused unread
            mp.setattr(manager_mod, "unpack_head", lambda *a: None)
        cfg = GigapaxosTpuConfig()
        cfg.paxos.max_groups = max_groups
        cfg.paxos.compact_outbox = True
        cfg.paxos.register_groups = register
        cfg.paxos.mesh_devices = mesh
        cfg.paxos.exec_budget = exec_budget
        if placement:
            cfg.placement.enabled = True
            cfg.paxos.read_leases = True  # keeps the demand fold on the host
        apps = [KVApp() for _ in range(R)]
        wal = PaxosLogger(os.path.join(str(tmp), plane), native=False)
        m = PaxosManager(cfg, R, apps, wal=wal, spill_ns=plane)
        names = [f"g{i}" for i in range(5)]
        for name in names:
            m.create_paxos_instance(name, [0, 1, 2])
        regs = [f"r{i}" for i in range(2 if register else 0)]
        for name in regs:
            m.create_paxos_instance(name, [0, 1, 2], register=True)
        replies, outs, requeued = {}, [], 0
        real = m._process_compact

        def spy(co, placed=None, *a, **kw):
            nonlocal requeued
            requeued += sum(
                not tk.taken_bit(co, entry, row, p)
                for row, take in (placed or []) for _, entry, p in take)
            return real(co, placed, *a, **kw)

        m._process_compact = spy
        rng = np.random.default_rng(34)
        for t in range(ticks):
            if t < 24 and t % 2 == 0:
                for name in names + regs:
                    for i in range(int(rng.integers(1, 7))):
                        m.propose(name, f"PUT k{t}.{i} v{t}".encode(),
                                  lambda rid, r: replies.__setitem__(rid, r),
                                  entry=int(rng.integers(R)))
            if t in (6, 18):  # long enough to fall W behind: a laggard
                m.set_alive(2, t == 18)
            if t in (10, 13):  # no quorum: windows fill, intake is rejected
                m.set_alive(1, t == 13)
            if t == 30:
                m.propose_stop("g4")
            co = m.tick()
            outs.append(co._replace(taken_bits=tk.taken_dense(co, m.G_total),
                                    taken_shift=0))
        m.drain_pipeline()
        wal.close()
        demand = (None if m._placement is None
                  else np.asarray(m._placement.demand_snapshot()))
        return dict(replies=replies, outs=outs, requeued=requeued,
                    journal=journal_bytes(os.path.join(str(tmp), plane)),
                    dbs=[a.db for a in apps], stats=dict(m.stats),
                    pulls=_pulls(plane), lagged=sum(o.lag_n for o in outs),
                    demand=demand, G=m.G_total)


BUILDS = {
    "one-device": dict(),
    "register-pair": dict(register=16),
    "mesh-of-4": dict(mesh=4, max_groups=512),
    "placement-host-fold": dict(placement=True),
}


@pytest.mark.parametrize("build", BUILDS)
def test_a_manager_on_heads_serves_what_one_on_the_flat_buffer_serves(
        tmp_path, monkeypatch, build):
    if build == "mesh-of-4" and len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    kw = BUILDS[build]
    tag = build.replace("-", "_")
    head = served(tmp_path, f"t_head_{tag}", False, monkeypatch, **kw)
    flat = served(tmp_path, f"t_flat_{tag}", True, monkeypatch, **kw)
    planes = 2 if "register" in kw else 1
    assert head["pulls"] == {"head": 40 * planes, "full": 0}
    assert flat["pulls"] == {"head": 0, "full": 40 * planes}
    # the traffic met what the head has to carry
    assert head["requeued"] > 0 and head["lagged"] > 0
    assert sum(int(o.e_stop.sum()) for o in head["outs"]) == R  # the stop
    assert head["stats"]["executions"] > 100
    assert len(head["outs"]) == len(flat["outs"]) == 40
    for t, (a, b) in enumerate(zip(head["outs"], flat["outs"])):
        assert_same_outbox(a, b, head["G"], f"tick {t}")
    for key in ("replies", "requeued", "journal", "dbs", "stats"):
        assert head[key] == flat[key], key
    assert len(head["replies"]) > 50 and head["journal"]
    if kw.get("placement"):
        assert head["demand"].sum() > 0
        assert np.array_equal(head["demand"], flat["demand"])


@pytest.mark.parametrize("over", [-1, 0, 1])
def test_a_tick_past_the_head_is_pulled_whole_and_served_the_same(
        tmp_path, monkeypatch, over):
    """Through the manager, with the module's K lowered so that the run's
    widest tick decides ``head_exec`` - 1, exactly it, and one more: only
    the last pulls the flat buffer, and only for the ticks that need it."""
    probe = served(tmp_path, f"t_probe_{over}", True, monkeypatch,
                   exec_budget=4001)
    widest = max(o.n_exec for o in probe["outs"])
    assert widest > 12
    monkeypatch.setattr(tk, "_SPARSE_BLOCKS", widest - over)
    # an exec budget no other test uses: the program is traced under this K
    budget = 4010 + over
    head = served(tmp_path, f"t_edge_head_{over}", False, monkeypatch,
                  exec_budget=budget)
    flat = served(tmp_path, f"t_edge_flat_{over}", True, monkeypatch,
                  exec_budget=budget)
    past = sum(o.n_exec > widest - over for o in flat["outs"])
    assert (past > 0) == (over > 0)
    assert head["pulls"] == {"head": 40 - past, "full": past}
    for t, (a, b) in enumerate(zip(head["outs"], flat["outs"])):
        assert_same_outbox(a, b, head["G"], f"tick {t}")
    for key in ("replies", "requeued", "journal", "dbs", "stats"):
        assert head[key] == flat[key], key
