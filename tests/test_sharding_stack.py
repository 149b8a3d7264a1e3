"""Full PaxosManager stack on the sharded data plane (shard_map tick).

``tests/test_sharding.py`` proves the bare tick is bit-identical under
GSPMD; these tests prove the WHOLE framework is bit-identical when the
manager runs its data plane as the shard_map program
(``parallel/shard_tick.py``, ``cfg.paxos.mesh_devices``): bulk/queued
admission, compact AND full outbox, WAL journaling, pipelined ticks,
replica death, laggard checkpoint repair — same scripted workload on the
8-device virtual CPU mesh vs one device, every state field and every app
table compared exactly.

Plus the kernel policy: on a TPU backend the Pallas ring gather traces in a
single-device program and INSIDE the shard_map body (where each shard sees
a concrete local block) whatever the visible device count, and only the
global-view GSPMD formulation refuses it.
"""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.paxos import state as st
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.wal.logger import PaxosLogger

W = 4
N_GROUPS = 8


def run_stack(tmpdir, R, mesh_devices=0, replica_shards=1, compact=True):
    """Scripted deterministic workload through a real manager; returns
    (state-as-numpy, per-replica app tables, responses, stats)."""
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 256
    cfg.paxos.window = W
    cfg.paxos.compact_outbox = compact
    cfg.paxos.pipeline_ticks = True
    cfg.paxos.deactivation_ticks = 0
    cfg.paxos.mesh_devices = mesh_devices
    cfg.paxos.mesh_replica_shards = replica_shards
    wal = PaxosLogger(os.path.join(tmpdir, "wal"), sync_every_ticks=2,
                      checkpoint_every_ticks=16)
    apps = [KVApp() for _ in range(R)]
    m = PaxosManager(cfg, R, apps, wal=wal)
    assert (m.mesh is not None) == bool(mesh_devices)
    members = list(range(R))
    for g in range(N_GROUPS):
        assert m.create_paxos_instance(f"svc{g}", members)

    resp = {}

    def cb(rid, r):
        resp[rid] = r

    # phase 1: normal replicated traffic across every group
    for i in range(5):
        for g in range(N_GROUPS):
            m.propose(f"svc{g}", f"PUT k{i} v{g}.{i}".encode(), cb)
        m.tick()
    # phase 2: last replica dies; push > W decisions so it falls off the
    # ring (gap-sync territory, not ordinary catch-up)
    m.set_alive(R - 1, False)
    for i in range(2 * W + 4):
        m.propose("svc0", f"PUT q{i} w{i}".encode(), cb)
        m.tick()
    # phase 3: revive -> in-tick auto laggard repair (checkpoint transfer)
    m.set_alive(R - 1, True)
    for _ in range(8):
        m.tick()
    m.drain_pipeline()

    state = jax.tree.map(np.asarray, m.state)
    dbs = [{k: dict(v) for k, v in a.db.items()} for a in apps]
    stats = dict(m.stats)
    wal.close()
    return state, dbs, resp, stats


def assert_same_run(ref, got):
    rs, rdb, rresp, rstats = ref
    gs, gdb, gresp, gstats = got
    for f in rs._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(rs, f)), np.asarray(getattr(gs, f)), err_msg=f
        )
    assert rdb == gdb
    assert rresp == gresp
    for k in ("decisions", "executions", "checkpoint_transfers"):
        assert rstats[k] == gstats[k], (k, rstats[k], gstats[k])


def test_stack_mesh_compact_bit_identical(tmp_path):
    """(2 replica, 4 groups) mesh, compact outbox: both mesh axes active —
    the replica all_gather/slice-back AND the groups-local pallas-eligible
    blocks — through the full WAL+pipeline+repair stack."""
    assert len(jax.devices()) == 8
    R = 4  # divisible by 2 replica shards
    ref = run_stack(str(tmp_path / "ref"), R)
    got = run_stack(str(tmp_path / "mesh"), R,
                    mesh_devices=8, replica_shards=2)
    assert ref[3]["checkpoint_transfers"] >= 1  # repair actually exercised
    assert_same_run(ref, got)


def test_stack_mesh_full_outbox_bit_identical(tmp_path):
    """(1, 8) pure groups-parallel mesh, FULL outbox mode: exercises the
    host-side per-field outbox assembly (shard_tick.fetch_host_outbox)
    through the pipelined _pending_out path."""
    R = 3
    ref = run_stack(str(tmp_path / "ref"), R, compact=False)
    got = run_stack(str(tmp_path / "mesh"), R, compact=False,
                    mesh_devices=8, replica_shards=1)
    assert ref[3]["checkpoint_transfers"] >= 1
    assert_same_run(ref, got)


def test_mesh_recovery_replays_through_the_shard_map_tick(tmp_path):
    """A mesh manager's state is partitioned over its devices, so its WAL
    replays through the same shard_map program the live run dispatched —
    and lands on the state a single-device recovery of the same journal
    reaches."""
    from gigapaxos_tpu.wal.logger import recover

    R = 3
    live = run_stack(str(tmp_path / "mesh"), R, mesh_devices=8)
    wal_dir = os.path.join(str(tmp_path / "mesh"), "wal")

    def cfg_for(mesh_devices):
        cfg = GigapaxosTpuConfig()
        cfg.paxos.max_groups = 256
        cfg.paxos.window = W
        cfg.paxos.compact_outbox = True
        cfg.paxos.pipeline_ticks = True
        cfg.paxos.deactivation_ticks = 0
        cfg.paxos.mesh_devices = mesh_devices
        return cfg

    got = {}
    for tag, mesh_devices in (("mesh", 8), ("one", 0)):
        copy = str(tmp_path / f"replay_{tag}")
        shutil.copytree(wal_dir, copy)
        apps = [KVApp() for _ in range(R)]
        m = recover(cfg_for(mesh_devices), R, apps, copy)
        assert (m.mesh is not None) == bool(mesh_devices)
        got[tag] = (jax.tree.map(np.asarray, m.state),
                    [{k: dict(v) for k, v in a.db.items()} for a in apps])
        m.wal.close()
    for f in got["one"][0]._fields:
        np.testing.assert_array_equal(
            getattr(got["one"][0], f), getattr(got["mesh"][0], f), err_msg=f)
        np.testing.assert_array_equal(
            getattr(live[0], f), getattr(got["mesh"][0], f), err_msg=f)
    assert got["one"][1] == got["mesh"][1] == live[1]


# ------------------------------------------------------- pallas-in-shard_map
def _build_state(R, G, W_):
    s = st.init_state(R, G, W_)
    return st.create_groups(
        s, np.arange(G, dtype=np.int32), np.ones((G, R), bool)
    )


def _load_inbox(R, G, P=2, seed=0):
    from gigapaxos_tpu.ops.tick import TickInbox

    rng = np.random.default_rng(seed)
    req = np.zeros((R, P, G), np.int32)
    for g in range(G):
        for p in range(int(rng.integers(0, P + 1))):
            req[rng.integers(0, R), p, g] = int(rng.integers(1, 1 << 20))
    return TickInbox(jnp.asarray(req), jnp.zeros((R, P, G), jnp.bool_),
                     jnp.ones((R,), jnp.bool_))


def test_pallas_gather_executes_inside_shard_map(monkeypatch):
    """With a (pretend) multi-device TPU backend the heuristic refuses the
    pallas kernels in global-view programs — but inside the shard_map body
    each shard is a concrete local block, so they trace and run there
    (interpret mode on CPU), and the results stay bit-identical."""
    import gigapaxos_tpu.ops.pallas_gather as pg
    from gigapaxos_tpu.ops.tick import paxos_tick_impl
    from gigapaxos_tpu.parallel import mesh as pmesh, shard_tick as stk

    R, G = 3, 256  # 2 group shards -> local G=128, pallas-shape eligible

    # reference on the portable XLA path, before any patching
    ref_tick = jax.jit(paxos_tick_impl)
    s = _build_state(R, G, W)
    ref_outs = []
    for t in range(3):
        s, out = ref_tick(s, _load_inbox(R, G, seed=t))
        ref_outs.append(jax.tree.map(np.asarray, out))
    ref_state = jax.tree.map(np.asarray, s)

    calls = {"gather": 0, "match": 0}
    orig_gather, orig_match = pg.gather_planes_pallas, pg.match_planes_pallas

    def counting_gather(arr, idx, **kw):
        calls["gather"] += 1
        return orig_gather(arr, idx, **kw)

    def counting_match(vals, keys, idx, **kw):
        calls["match"] += 1
        return orig_match(vals, keys, idx, **kw)

    monkeypatch.setattr(pg, "gather_planes_pallas", counting_gather)
    monkeypatch.setattr(pg, "match_planes_pallas", counting_match)
    # pretend: TPU backend (kernels default to interpret so they actually
    # execute on this CPU host, which shows 8 devices)
    monkeypatch.setattr(pg, "_on_tpu", lambda: True)
    monkeypatch.setenv("GPTPU_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("GPTPU_PALLAS", raising=False)
    monkeypatch.delenv("GPTPU_NO_PALLAS", raising=False)
    assert len(jax.devices()) > 1

    # global-view trace: GSPMD would replicate the kernels' operands ->
    # that one formulation keeps the select chain
    mesh = pmesh.make_mesh(jax.devices()[:2], replica_shards=1)
    pmesh.sharded_tick(mesh).lower(_build_state(R, G, W),
                                   _load_inbox(R, G, seed=0))
    assert calls["gather"] == 0 and calls["match"] == 0

    # single-device program on a host that SHOWS several devices: where the
    # program runs decides, not how many devices are visible
    def one_device_tick(state, inbox):
        return paxos_tick_impl(state, inbox)

    jax.jit(one_device_tick).lower(_build_state(R, G, W),
                                   _load_inbox(R, G, seed=0))
    assert calls["gather"] > 0 and calls["match"] > 0
    calls["gather"] = calls["match"] = 0

    # shard_map trace: shard-local -> the pallas kernels are in the program
    tick = stk.make_shardmap_tick(mesh)
    s = pmesh.shard_state(_build_state(R, G, W), mesh)
    sm_outs = []
    for t in range(3):
        s, out = tick(s, pmesh.shard_inbox(_load_inbox(R, G, seed=t), mesh))
        sm_outs.append(jax.tree.map(np.asarray, out))
    assert calls["gather"] > 0, "pallas gather never traced inside shard_map"
    sm_state = jax.tree.map(np.asarray, s)

    for f in ref_state._fields:
        np.testing.assert_array_equal(
            getattr(ref_state, f), getattr(sm_state, f), err_msg=f
        )
    for a, b in zip(ref_outs, sm_outs):
        for f in a._fields:
            np.testing.assert_array_equal(
                getattr(a, f), getattr(b, f), err_msg=f
            )
