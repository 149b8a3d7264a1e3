"""Register-mode (RMWPaxos, ISSUE 16) memory + throughput artifact.

The tentpole claim: collapsing the ``[G, W]`` slot ring to a W=1 in-place
register cuts per-group HBM by ~W x, so the same memory holds W x more
groups.  This bench measures it four ways and writes
``benchmarks/results_register_pr16.json``:

* ``bytes_per_group`` — committed bytes per group for a log-mode W=8
  plane vs a register plane, from the actual dense arrays (gate: >= 4x);
* ``max_dense_groups`` — how many groups fit a fixed memory budget in
  each mode (pure arithmetic on the measured bytes/group);
* ``dense_mixed_alloc`` — >= 4M mixed-mode groups allocated as dense
  arrays on CPU, created, and driven through one mixed tick;
* ``dec_per_s_1m_mixed`` — sustained decisions/s through the mixed
  kernel at 1M groups (log + register planes in one vmapped pass);
* ``journal_bytes_per_decision`` and ``snapshot_bytes_per_group`` — the
  WAL side: compact OP_REG journaling and the smaller register plane in
  checkpoints.

Run: ``python benchmarks/register_bench.py [--json PATH] [--quick]``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

if os.environ.get("GPTPU_BENCH_PLATFORM"):
    jax.config.update("jax_platforms", os.environ["GPTPU_BENCH_PLATFORM"])

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

R = 3
LOG_W = 8  # the production slot-ring depth the register mode replaces


def state_nbytes(s) -> int:
    return int(sum(np.asarray(getattr(s, f)).nbytes for f in s._fields))


def bench_bytes_per_group(G: int = 4096) -> dict:
    """Committed bytes/group from the dense arrays themselves."""
    from gigapaxos_tpu.paxos import state as st

    log8 = st.init_state(R, G, LOG_W)
    reg = st.init_state(R, G, 1)
    bl, br = state_nbytes(log8) / G, state_nbytes(reg) / G
    return {
        "log_w8_bytes": round(bl, 1),
        "register_bytes": round(br, 1),
        "reduction_x": round(bl / br, 2),
        "gate_pass": bool(bl / br >= 4.0),
    }


def bench_max_dense_groups(bpg: dict, budget_gb: float = 8.0) -> dict:
    """Groups per memory budget — arithmetic on the measured bytes/group
    (the capacity statement: same memory, ~W x more register groups)."""
    budget = budget_gb * (1 << 30)
    return {
        "budget_gb": budget_gb,
        "log_w8_groups": int(budget // bpg["log_w8_bytes"]),
        "register_groups": int(budget // bpg["register_bytes"]),
    }


def _mixed_planes(g_log: int, g_reg: int):
    from gigapaxos_tpu.paxos import state as st

    s = st.init_state(R, g_log, LOG_W)
    s = st.create_groups(s, np.arange(g_log, dtype=np.int32),
                         np.ones((g_log, R), bool))
    r = st.init_state(R, g_reg, 1)
    r = st.create_groups(r, np.arange(g_reg, dtype=np.int32),
                         np.ones((g_reg, R), bool))
    return s, r


def _gen_inbox_fn(g_total: int, p: int = 1):
    from gigapaxos_tpu.ops.tick import TickInbox

    def gen(rid_base):
        g = jnp.arange(g_total, dtype=jnp.int32)
        rids = rid_base + g
        req = jnp.zeros((R, p, g_total), jnp.int32).at[:, 0, :].set(
            jnp.where(g[None, :] % R == jnp.arange(R)[:, None],
                      rids[None, :], 0))
        return TickInbox(req, jnp.zeros((R, p, g_total), jnp.bool_),
                         jnp.ones((R,), jnp.bool_))

    return jax.jit(gen)


def _mixed_tick(s, r, inbox):
    """One full-outbox tick of both planes through the served entry."""
    from gigapaxos_tpu.ops.tick import (TickParams, TickPlanes,
                                        paxos_tick_planes)

    (s, r, *_), packs = paxos_tick_planes(TickPlanes(s, r), inbox,
                                          TickParams())
    return s, r, packs.out, packs.rout


def bench_dense_mixed_alloc(g_log: int, g_reg: int) -> dict:
    """>= 4M mixed-mode groups as dense arrays on CPU: allocate, create,
    one mixed tick — the committed-bytes statement of the tentpole."""
    t0 = time.perf_counter()
    s, r = _mixed_planes(g_log, g_reg)
    alloc_s = time.perf_counter() - t0
    total = state_nbytes(s) + state_nbytes(r)
    gen = _gen_inbox_fn(g_log + g_reg)
    t0 = time.perf_counter()
    s, r, pk_l, pk_r = _mixed_tick(s, r, gen(jnp.int32(1)))
    jax.block_until_ready(pk_r)
    tick_s = time.perf_counter() - t0
    out = {
        "groups_total": g_log + g_reg,
        "log_groups": g_log,
        "register_groups": g_reg,
        "committed_bytes": total,
        "bytes_per_group": round(total / (g_log + g_reg), 1),
        "alloc_create_s": round(alloc_s, 2),
        "first_mixed_tick_s": round(tick_s, 2),
    }
    del s, r, pk_l, pk_r
    return out


def bench_dec_per_s_mixed(g_log: int, g_reg: int, ticks: int = 10) -> dict:
    """Sustained mixed-kernel decisions/s: both planes stepped in one
    donated jit per tick, decisions counted from replica-0 exec deltas."""
    s, r = _mixed_planes(g_log, g_reg)
    gen = _gen_inbox_fn(g_log + g_reg)

    def exec_sum(s, r):
        return int(jnp.sum(s.exec_slot[0])) + int(jnp.sum(r.exec_slot[0]))

    g_total = g_log + g_reg
    for i in range(3):  # compile + fill the self-proposal pipeline
        s, r, pk_l, pk_r = _mixed_tick(s, r, gen(jnp.int32(1 + i * g_total)))
    jax.block_until_ready(pk_r)
    base = exec_sum(s, r)
    t0 = time.perf_counter()
    for i in range(ticks):
        s, r, pk_l, pk_r = _mixed_tick(
            s, r, gen(jnp.int32(1 + (3 + i) * g_total)))
    jax.block_until_ready(pk_r)
    dt = time.perf_counter() - t0
    decs = exec_sum(s, r) - base
    return {
        "groups_total": g_total,
        "log_groups": g_log,
        "register_groups": g_reg,
        "ticks": ticks,
        "decisions": decs,
        "decisions_per_s": round(decs / dt, 1),
        "ms_per_tick": round(1e3 * dt / ticks, 2),
    }


def _journal_arm(register: bool, n: int, groups: int = 64) -> dict:
    """Journal + snapshot cost of one plane: ``groups`` groups of one
    mode, ``n`` tracked decisions each of a unique 64 B body."""
    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.models.replicable import NoopApp
    from gigapaxos_tpu.paxos.manager import PaxosManager
    from gigapaxos_tpu.wal.logger import PaxosLogger

    cfg = GigapaxosTpuConfig()
    cfg.paxos.compact_outbox = True
    if register:
        cfg.paxos.max_groups = 1  # floor: the log plane still exists
        cfg.paxos.register_groups = groups
    else:
        cfg.paxos.max_groups = groups
    d = tempfile.mkdtemp(prefix="gptpu_regbench_")
    try:
        wal = PaxosLogger(os.path.join(d, "wal"), sync_every_ticks=8,
                          checkpoint_every_ticks=10**9)
        m = PaxosManager(cfg, R, [NoopApp() for _ in range(R)], wal=wal)
        for g in range(groups):
            m.create_paxos_instance(f"g{g}", [0, 1, 2], register=register)
        m.tick()

        def jbytes():
            return sum(os.path.getsize(p) for p in
                       glob.glob(os.path.join(d, "wal", "journal.*.log")))

        base = jbytes()
        e0 = sum(int(m.exec_watermarks(f"g{g}")[0]) for g in range(groups))
        rng = np.random.default_rng(1)
        for i in range(n):
            for g in range(groups):
                m.propose(f"g{g}", rng.bytes(64))
            m.tick()
        for _ in range(20):
            m.tick()
        m.drain_pipeline()
        decs = sum(int(m.exec_watermarks(f"g{g}")[0])
                   for g in range(groups)) - e0
        grew = jbytes() - base
        wal.checkpoint()
        snap = max(glob.glob(os.path.join(d, "wal", "snapshot.*.bin")),
                   key=os.path.getmtime)
        snap_bytes = os.path.getsize(snap)
        wal.close()
        return {
            "decisions": decs,
            "journal_bytes_per_decision": round(grew / max(decs, 1), 1),
            "snapshot_bytes_per_group": round(snap_bytes / groups, 1),
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_wal_cost(n: int = 120) -> dict:
    log = _journal_arm(register=False, n=n)
    reg = _journal_arm(register=True, n=n)
    return {
        "log": log,
        "register": reg,
        "journal_ratio_log_over_register": round(
            log["journal_bytes_per_decision"]
            / max(reg["journal_bytes_per_decision"], 1e-9), 2),
        "snapshot_ratio_log_over_register": round(
            log["snapshot_bytes_per_group"]
            / max(reg["snapshot_bytes_per_group"], 1e-9), 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the artifact to this path")
    ap.add_argument("--groups", type=int, default=1 << 20,
                    help="total groups for the mixed dec/s run")
    ap.add_argument("--big-groups", type=int, default=1 << 22,
                    help="total groups for the dense-alloc demonstration")
    ap.add_argument("--log-frac", type=float, default=0.125,
                    help="fraction of groups on the log plane")
    ap.add_argument("--ticks", type=int, default=10)
    ap.add_argument("--quick", action="store_true",
                    help="small shapes for smoke testing")
    args = ap.parse_args()
    if args.quick:
        args.groups, args.big_groups, args.ticks = 1 << 12, 1 << 13, 3

    def split(total):
        g_log = max(1, int(total * args.log_frac))
        return g_log, total - g_log

    bpg = bench_bytes_per_group()
    result = {
        "metric": "register_vs_log_bytes_per_group_reduction",
        "value": bpg["reduction_x"],
        "unit": f"x smaller than W={LOG_W} log plane (gate >= 4x)",
        "platform": jax.devices()[0].platform,
        "bytes_per_group": bpg,
        "max_dense_groups": bench_max_dense_groups(bpg),
        "dense_mixed_alloc": bench_dense_mixed_alloc(*split(args.big_groups)),
        "dec_per_s_1m_mixed": bench_dec_per_s_mixed(*split(args.groups),
                                                    ticks=args.ticks),
        "wal_cost": bench_wal_cost(n=24 if args.quick else 120),
        "gate_pass": bpg["gate_pass"],
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1)
        result["written"] = args.json
    print(json.dumps(result))


if __name__ == "__main__":
    main()