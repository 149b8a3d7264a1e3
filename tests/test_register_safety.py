"""Randomized crash/recover safety for REGISTER groups (ISSUE 16).

Extends the PR-10 safety harness to the register plane.  A register group
has no slot ring — every decision overwrites version v with v+1 — so the
per-slot S1 ledger generalizes to per-(group, version): replica 0 is kept
continuously alive and its execution order IS the version order (W=1
executes strictly in watermark order with no gaps), every other replica's
executed sequence must embed into it order-consistently (same rid at the
same version wherever both executed), and no replica executes a version's
rid twice.  Gaps are legal — a revived replica heals by checkpoint
transfer ("ship the register"), never by replaying overwritten versions.

Storage faults ride the same Mode A journal as log groups: a torn tail on
the newest journal is tolerated across mixed planes (OP_REG records replay
fine after repair), a scribble inside the fsynced body fail-stops with
``WalQuarantinedError``.  Acked durability: every response RELEASED to a
client must survive full crash + recovery, register and log alike.
"""

import os

import numpy as np
import pytest

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.testing import faultdisk
from gigapaxos_tpu.wal.journal import scan_journal
from gigapaxos_tpu.wal.logger import (PaxosLogger, WalQuarantinedError,
                                      recover)

LOG_GROUPS = ["g0", "g1"]
REG_GROUPS = ["rg0", "rg1"]


class LedgerKVApp(KVApp):
    """KVApp that journals its execution order per group — the raw
    material for the per-(group, version) agreement check."""

    def __init__(self):
        super().__init__()
        self.ledger = {}  # name -> [rid] in execution order

    def execute(self, name, request, request_id):
        self.ledger.setdefault(name, []).append(request_id)
        return super().execute(name, request, request_id)


def _embeds_in_order(sub, full):
    """True when ``sub`` is an ordered subsequence of ``full``."""
    it = iter(full)
    return all(any(x == y for y in it) for x in sub)


def mk_cfg(compact):
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 4
    cfg.paxos.register_groups = 4
    cfg.paxos.pipeline_ticks = True
    cfg.paxos.compact_outbox = compact
    return cfg


def _mixed_manager(cfg, d, apps, ckpt=16):
    wal = PaxosLogger(d, checkpoint_every_ticks=ckpt)
    m = PaxosManager(cfg, 3, apps, wal=wal)
    for g in LOG_GROUPS:
        m.create_paxos_instance(g, [0, 1, 2])
    for g in REG_GROUPS:
        m.create_paxos_instance(g, [0, 1, 2], register=True)
    return m


# six seeds, both dispatch modes — the acceptance bar is zero violations
# (a pipelined tick holds its outbox only when its inbox left work behind,
# which this trickle does about once a run; ``bursts`` adds, every eleventh
# tick, more writes to one group at one entry replica than three ticks
# place, log and register groups in turn, so those runs change sides both
# ways a dozen times under the same crashes and checkpoints)
@pytest.mark.parametrize("seed,compact,bursts", [
    (3, False, False), (11, True, False), (29, False, False),
    (57, True, False), (101, False, False), (211, True, False),
    (3, True, True), (57, False, True), (211, True, True)])
def test_register_random_crash_recover(tmp_path, seed, compact, bursts):
    rng = np.random.default_rng(seed)
    sides = []  # per tick: was its outbox held for the next call
    cfg = mk_cfg(compact)
    d = os.path.join(str(tmp_path), "wal")
    apps = [LedgerKVApp() for _ in range(3)]
    m = _mixed_manager(cfg, d, apps)
    groups = LOG_GROUPS + REG_GROUPS

    committed = {}  # rid -> (group, key, value) for responses RELEASED

    def mk_cb(rid, g, k, v):
        def cb(_rid, resp):
            if resp == b"OK":
                committed[rid] = (g, k, v)
        return cb

    sent = 0
    for t in range(100):
        # random crash/recover of replicas 1 and 2 only (at most one down):
        # replica 0 stays alive the whole run, so its execution order is
        # the ground-truth version order for every register group
        for r in (1, 2):
            if rng.random() < 0.1:
                if m.alive[r]:
                    if int((~m.alive).sum()) < 1:
                        m.set_alive(r, False)
                else:
                    m.set_alive(r, True)
        # untracked background churn (callback-less staging)
        for _ in range(int(rng.integers(0, 4))):
            g = groups[int(rng.integers(0, len(groups)))]
            m.propose(g, f"PUT bg{int(rng.integers(0, 6))} x".encode(),
                      None, False, None)
        # one tracked request per tick under a UNIQUE key
        g = groups[int(rng.integers(0, len(groups)))]
        sent += 1
        k, v = f"t{sent}", f"tv{t}"
        m.propose(g, f"PUT {k} {v}".encode(), mk_cb(sent, g, k, v))
        if bursts and t % 11 == 5:
            for i in range(3 * m.P):
                m.propose(groups[t % len(groups)],
                          f"PUT burst{i} x".encode(), None, False, 0)
        m.tick()
        sides.append(m._pending_out is not None)
    for r in range(3):
        m.set_alive(r, True)
    for _ in range(60):
        m.tick()
    m.drain_pipeline()
    assert m.stats["executions"] > 0
    if bursts:
        assert sum(a != b for a, b in zip(sides, sides[1:])) >= 12, sides
    acked_groups = {gkv[0] for gkv in committed.values()}
    assert acked_groups & set(REG_GROUPS), "no register decision ever acked"
    m.wal.close()

    # ---- per-(group, version) ledger: S1 + S3 generalized to registers
    for g in REG_GROUPS:
        truth = apps[0].ledger.get(g, [])
        assert len(truth) == len(set(truth)), f"{g}: replica 0 dup execute"
        for r in (1, 2):
            seq = apps[r].ledger.get(g, [])
            assert len(seq) == len(set(seq)), f"{g}: replica {r} dup execute"
            assert _embeds_in_order(seq, truth), (
                f"{g}: replica {r} executed versions disagree with the "
                f"ground-truth order: {seq} vs {truth}")

    # ---- 0 lost acked decisions: full crash, recover, audit every release
    apps2 = [KVApp() for _ in range(3)]
    recover(cfg, 3, apps2, d)
    for rid, (g, k, v) in committed.items():
        got = apps2[0].execute(g, f"GET {k}".encode(), 10_000_000 + rid)
        assert got == v.encode(), (rid, g, k, v, got)


def _run_mixed_workload(cfg, d, ticks=30):
    apps = [KVApp() for _ in range(3)]
    m = _mixed_manager(cfg, d, apps, ckpt=10_000)  # journal-only recovery
    committed = {}

    def mk_cb(g, k, v):
        def cb(_rid, resp):
            if resp == b"OK":
                committed[(g, k)] = v
        return cb

    for i in range(ticks):
        for g in LOG_GROUPS + REG_GROUPS:
            k, v = f"k{i}", f"v{i}"
            m.propose(g, f"PUT {k} {v}".encode(), mk_cb(g, k, v))
        m.tick()
    for _ in range(20):
        m.tick()
    m.drain_pipeline()
    m.wal.close()
    return committed


def test_torn_tail_tolerated_across_mixed_planes(tmp_path):
    """A classic torn tail (garbage suffix from a power cut mid-append) on
    the newest journal is tolerated: replay walks the clean prefix —
    OP_CREATE(register), OP_REG, and OP_TICK records alike — and every
    acked decision on BOTH planes survives."""
    cfg = mk_cfg(compact=True)
    d = os.path.join(str(tmp_path), "wal")
    committed = _run_mixed_workload(cfg, d)
    assert committed

    p = faultdisk.newest_journal(d)
    with open(p, "ab") as f:
        f.write(b"\x07garbage-partial-frame")
    assert scan_journal(p).kind == "torn_tail"

    apps2 = [KVApp() for _ in range(3)]
    m2 = recover(cfg, 3, apps2, d)
    for (g, k), v in committed.items():
        got = apps2[0].execute(g, f"GET {k}".encode(), 20_000_000)
        assert got == v.encode(), (g, k, v, got)
    # the recovered register plane keeps deciding
    n0 = m2.stats["decisions"]
    m2.propose("rg0", b"PUT after x")
    for _ in range(10):
        m2.tick()
    m2.drain_pipeline()
    assert m2.stats["decisions"] >= n0 + 1


def test_truncated_tail_still_recovers_registers(tmp_path):
    """Tearing real bytes off the journal end (partial final frame) is
    still a torn tail, not a quarantine: recovery repairs and the register
    groups come back functional."""
    cfg = mk_cfg(compact=False)
    d = os.path.join(str(tmp_path), "wal")
    _run_mixed_workload(cfg, d, ticks=20)
    p = faultdisk.newest_journal(d)
    faultdisk.tear_tail(p, 13)
    assert scan_journal(p).kind in ("torn_tail", "clean")
    m2 = recover(cfg, 3, [KVApp() for _ in range(3)], d)
    assert all(g in m2.rows for g in REG_GROUPS + LOG_GROUPS)


def test_scribble_mid_journal_fail_stops(tmp_path):
    """A bit flip inside the fsynced body of a mixed-plane journal is
    corrupt acked data: recovery must quarantine, never skip-and-diverge —
    register groups get the same fail-stop contract as log groups."""
    cfg = mk_cfg(compact=True)
    d = os.path.join(str(tmp_path), "wal")
    _run_mixed_workload(cfg, d, ticks=20)
    p = faultdisk.newest_journal(d)
    faultdisk.flip_byte(p, offset=8 + 4)  # first frame's CRC: fsynced body
    assert scan_journal(p).kind == "scribble"
    with pytest.raises(WalQuarantinedError):
        recover(cfg, 3, [KVApp() for _ in range(3)], d)
