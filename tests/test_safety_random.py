"""Randomized safety/liveness property tests (the sanitizer analog).

The reference relies on Java assertions run with ``-ea`` (e.g. the
non-conflicting-accept assert, PaxosAcceptor.java:306-308, and slot invariant
:387-391).  Here we drive the whole dense data plane through random request
arrivals and random crash/recover schedules and check the global Paxos
invariants from the outside:

  S1 (agreement): for every group and slot, every replica that executes that
     slot executes the same request id.
  S2 (prefix order): each replica's executed sequence is a prefix of the
     longest executed sequence for that group.
  S3 (no dup slots): no replica executes a slot twice.
  L1 (liveness): with a majority continuously alive, submitted requests
     eventually execute.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from gigapaxos_tpu.ops.tick import TickInbox, paxos_tick
from gigapaxos_tpu.paxos import state as st


def run_random(seed, R=3, G=8, W=8, P=2, ticks=60, crash_prob=0.15,
               majority_guard=True):
    rng = np.random.default_rng(seed)
    s = st.init_state(R, G, W)
    s = st.create_groups(s, np.arange(G, dtype=np.int32), np.ones((G, R), bool))

    executed = [[dict() for _ in range(G)] for _ in range(R)]  # slot -> req
    submitted = [set() for _ in range(G)]
    pending = [[] for _ in range(G)]
    next_rid = 1
    alive = np.ones(R, bool)

    for t in range(ticks):
        # random crash/recover, optionally keeping a majority alive
        for r in range(R):
            if rng.random() < crash_prob:
                alive[r] = not alive[r]
        if majority_guard and alive.sum() < R // 2 + 1:
            alive[:] = True

        req = np.zeros((R, P, G), np.int32)
        stp = np.zeros((R, P, G), bool)
        for g in range(G):
            # retry pending (rejected intake) first, then maybe a new request
            if rng.random() < 0.5:
                pending[g].append(next_rid)
                submitted[g].add(next_rid)
                next_rid += 1
            live = [r for r in range(R) if alive[r]]
            for p, rid in enumerate(pending[g][: P]):
                r = rng.choice(live) if live else 0
                req[r, p % P, g] = rid
        ib = TickInbox(jnp.asarray(req), jnp.asarray(stp), jnp.asarray(alive.copy()))
        s, out = paxos_tick(s, ib)

        taken = np.array(out.intake_taken)
        for g in range(G):
            kept = []
            for p, rid in enumerate(pending[g][: P]):
                placed = False
                for r in range(R):
                    if req[r, p % P, g] == rid and taken[r, p % P, g]:
                        placed = True
                if not placed:
                    kept.append(rid)
            pending[g] = kept + pending[g][P:]

        er = np.array(out.exec_req)
        eb = np.array(out.exec_base)
        ec = np.array(out.exec_count)
        for r in range(R):
            for g in range(G):
                for j in range(int(ec[r, g])):
                    slot = int(eb[r, g]) + j
                    rid = int(er[r, j, g])
                    assert slot not in executed[r][g], (
                        f"S3 violated: r{r} g{g} slot {slot} twice"
                    )
                    executed[r][g][slot] = rid

    # S1/S2: per-slot agreement and prefix consistency
    for g in range(G):
        merged = {}
        for r in range(R):
            for slot, rid in executed[r][g].items():
                if slot in merged:
                    assert merged[slot] == rid, (
                        f"S1 violated: g{g} slot {slot}: {merged[slot]} vs {rid}"
                    )
                merged[slot] = rid
            if executed[r][g]:
                slots = sorted(executed[r][g])
                assert slots == list(range(slots[0] + len(slots)))[slots[0]:], (
                    f"S2 violated: r{r} g{g} has gaps: {slots}"
                )
                assert slots[0] == 0
    return s, executed, submitted, pending


def test_random_crash_recover_safety():
    for seed in range(6):
        run_random(seed)


def test_liveness_all_alive():
    s, executed, submitted, pending = run_random(
        seed=99, crash_prob=0.0, ticks=40
    )
    for g, subs in enumerate(submitted):
        done = set(executed[0][g].values())
        missing = subs - done - set(pending[g])
        assert not missing, f"L1 violated: g{g} lost {missing}"
        assert len(done) >= len(subs) - 2  # at most the last couple in flight


def test_noop_decisions_allowed():
    """Failover may commit noop fillers; executed req id 0 means 'skip' and
    must never collide with a real request id."""
    for seed in (3, 7):
        _, executed, _, _ = run_random(seed, crash_prob=0.3, ticks=50)
        # merged histories stay consistent even with noops present
        # (assertions inside run_random cover S1-S3)


@pytest.mark.parametrize("seed,compact,bursts", [
    (7, False, False), (13, False, False), (32, False, False),
    (128, False, False), (7, True, False), (128, True, False),
    (7, False, True), (13, True, True), (32, False, True),
    (128, True, True)])
def test_manager_random_crash_recover_pipelined(tmp_path, seed, compact,
                                                bursts):
    """Manager-level randomized safety with PIPELINED ticks + WAL: random
    request arrivals, random replica crash/recover (majority kept alive),
    periodic checkpoints (which drain the pipeline), then a full process
    crash + recovery — every response ever released must be durable and
    exactly-once, and the recovered KV state must agree with a sequential
    replay of the committed responses.

    Each non-default seed caught a distinct silent-loss bug in the
    round-5 soaks: 7 = sync watermark/blob pipeline skew (donor device
    watermark paired with host app state one tick behind), 13 = payload
    swept while a dead member could still ring-replay its slot on
    revival, 32 = the sweep rotation bound off-by-one at slot == base-W,
    128 = the sweep judging "everyone passed" from DEVICE exec, which
    includes the in-flight pipelined tick — dropping the payload of the
    very delivery that advanced it (the _host_exec watermark fix).

    A pipelined tick holds its outbox only when its inbox left work behind
    (ISSUE 31) and as much of it as it placed (ISSUE 36), which this trickle
    of arrivals does once or twice a run; ``bursts`` adds, every eleventh
    tick, more writes to one name at one entry replica than five ticks
    place, so the run changes sides a dozen times, in both directions, under
    the same crashes and checkpoints."""
    import os

    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.models.replicable import KVApp
    from gigapaxos_tpu.paxos.manager import PaxosManager
    from gigapaxos_tpu.wal.logger import PaxosLogger, recover

    rng = np.random.default_rng(seed)
    cfg = GigapaxosTpuConfig()
    cfg.paxos.pipeline_ticks = True
    if compact:  # the compact-outbox twin of every repair path
        cfg.paxos.compact_outbox = True
    wal = PaxosLogger(os.path.join(str(tmp_path), "wal"),
                      checkpoint_every_ticks=16)
    apps = [KVApp() for _ in range(3)]
    m = PaxosManager(cfg, 3, apps, wal=wal)
    for g in range(4):
        m.create_paxos_instance(f"g{g}", [0, 1, 2])

    committed = {}  # rid -> (group, key, value) for responses RELEASED
    sent = 0
    sides = []  # per tick: was its outbox held for the next call

    def mk_cb(rid, g, k, v):
        def cb(_rid, resp):
            if resp == b"OK":
                committed[rid] = (g, k, v)
        return cb

    for t in range(120):
        # random crash/recover keeping a majority
        for r in range(3):
            if rng.random() < 0.1:
                down = int((~m.alive).sum())
                if m.alive[r] and down < 1:
                    m.set_alive(r, False)
                elif not m.alive[r]:
                    m.set_alive(r, True)
        # untracked background writes (exercise callback-less staging)
        for _ in range(rng.integers(0, 4)):
            g = int(rng.integers(0, 4))
            m.propose(f"g{g}", f"PUT bg{rng.integers(0, 6)} x".encode(),
                      None, False, None)
        # one tracked request per tick, under a UNIQUE key so the recovery
        # check can demand exactly this value
        g = int(rng.integers(0, 4))
        sent += 1
        k, v = f"t{sent}", f"tv{t}"
        m.propose(f"g{g}", f"PUT {k} {v}".encode(), mk_cb(sent, g, k, v))
        if bursts and t % 11 == 5:
            for i in range(5 * m.P):
                m.propose(f"g{t % 4}", f"PUT burst{i} x".encode(),
                          None, False, 0)
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
        assert 20 <= sum(sides) <= 100, sides
    wal.close()

    # crash everything; recover and check every released response is present
    apps2 = [KVApp() for _ in range(3)]
    recover(cfg, 3, apps2, os.path.join(str(tmp_path), "wal"))
    for rid, (g, k, v) in committed.items():
        got = apps2[0].execute(f"g{g}", f"GET {k}".encode(), 10_000_000 + rid)
        assert got == v.encode(), (rid, g, k, v, got)
