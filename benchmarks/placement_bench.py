"""Placement-plane benchmark: Zipf-skewed load on an 8-shard virtual mesh,
rebalancer ON vs OFF.

What it models
--------------
Names are created in popularity order, so the row allocator packs the hot
names into the first shards' row ranges — the pathological-but-natural
placement the demand-driven rebalancer exists to fix.  Offered load is
Zipf-distributed over the names; each mesh shard models one machine of the
deployment with a bounded per-tick intake frame (``--edge-budget``, the
analog of a node's transport frame/NIC): requests for a name are admitted
through the frame of the shard the name currently lives in, and queue when
that frame is full.  Under skew the hot shard's frame saturates while the
cold shards' frames idle; after migration the same offered load spreads
over more frames and aggregate admitted (= decided) throughput rises.

That per-shard edge budget is a DRIVER-SIDE model: the single-process
virtual mesh has no real per-node NIC, so without it shard imbalance is
invisible to throughput (the dense device tick processes all rows every
tick regardless).  The shard-load ratio, by contrast, is measured from the
real placement counters (EWMA demand folded on device through the compact
dispatch).

Usage: python benchmarks/placement_bench.py [--rebalance] [--ticks N] ...
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=256)
    ap.add_argument("--names", type=int, default=96)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--ticks", type=int, default=160)
    ap.add_argument("--warmup", type=int, default=8)
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--edge-budget", type=int, default=48,
                    help="per-shard per-tick admission frame (see docstring)")
    ap.add_argument("--offered", type=int, default=300,
                    help="offered requests per tick across all names")
    ap.add_argument("--zipf", type=float, default=1.05)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--rebalance", action="store_true")
    ap.add_argument("--rebalance-every", type=int, default=8)
    args = ap.parse_args()

    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.models.replicable import KVApp
    from gigapaxos_tpu.paxos.manager import PaxosManager
    from gigapaxos_tpu.placement import GroupMigrator, ShardRebalancer
    from gigapaxos_tpu.reconfiguration.coordinator import (
        PaxosReplicaCoordinator,
    )

    R = args.replicas
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = args.groups
    cfg.paxos.window = args.window
    cfg.paxos.compact_outbox = True
    cfg.paxos.pipeline_ticks = True
    cfg.paxos.deactivation_ticks = 0
    cfg.paxos.mesh_devices = 8
    cfg.paxos.mesh_replica_shards = 1
    cfg.placement.enabled = True
    cfg.placement.sample_every_ticks = args.rebalance_every
    cfg.placement.min_interval_ticks = 2 * args.rebalance_every

    m = PaxosManager(cfg, R, [KVApp() for _ in range(R)])
    nodes = [f"AR{i}" for i in range(R)]
    coord = PaxosReplicaCoordinator(m, nodes)
    names = [f"svc{i:03d}" for i in range(args.names)]
    for n in names:  # popularity order -> hot names pack the first shards
        assert coord.create_replica_group(n, 0, b"", nodes)

    gs, per = m.shard_geometry()
    mig = GroupMigrator(coord, counters=m._placement)
    reb = ShardRebalancer(
        m.G, gs, skew_threshold=1.5, hysteresis=1.1,
        min_interval_ticks=cfg.placement.min_interval_ticks,
        max_moves_per_plan=4,
    )

    rng = np.random.default_rng(args.seed)
    w = 1.0 / np.arange(1, args.names + 1) ** args.zipf
    probs = w / w.sum()

    queues = [0] * args.names  # pending offered requests per name

    def shard_of(i):
        n = names[i]
        return m.rows.row(f"{n}#{coord.current_epoch(n)}") // per

    def admit_tick():
        """Offered load arrives; each shard's frame admits up to budget."""
        for i, k in enumerate(rng.multinomial(args.offered, probs)):
            queues[i] += int(k)
        frame = [args.edge_budget] * gs
        admitted = 0
        # round-robin over names within each shard's frame
        by_shard = [[] for _ in range(gs)]
        for i in range(args.names):
            if queues[i]:
                by_shard[shard_of(i)].append(i)
        for k in range(gs):
            idx = by_shard[k]
            while frame[k] > 0 and idx:
                nxt = []
                for i in idx:
                    if frame[k] == 0:
                        break
                    take = min(queues[i], max(frame[k] // len(idx), 1),
                               frame[k])
                    for _ in range(take):
                        coord.coordinate_request(
                            names[i], coord.current_epoch(names[i]),
                            b"PUT x 1")
                    queues[i] -= take
                    frame[k] -= take
                    admitted += take
                    if queues[i]:
                        nxt.append(i)
                idx = nxt
        return admitted

    for _ in range(args.warmup):
        admit_tick()
        m.tick()
    m.drain_pipeline()
    base_decided = int(m.stats["decisions"])
    base_ticks = m.tick_num

    t0 = time.perf_counter()
    moved_total, plans = 0, 0
    for t in range(args.ticks):
        admit_tick()
        m.tick()
        if args.rebalance and t % args.rebalance_every == 0:
            demand = m.demand_snapshot()
            plan = reb.propose(m.tick_num, demand,
                               free_rows_in_shard=m.free_rows_in_shard)
            if plan:
                plans += 1
                n = mig.execute_plan(plan, pump=m.tick)
                reb.record_executed(n)
                moved_total += n
    m.drain_pipeline()
    dt = time.perf_counter() - t0

    decided = int(m.stats["decisions"]) - base_decided
    ticks_run = m.tick_num - base_ticks
    # measured from the real device-folded EWMA counters
    m.demand_snapshot()
    loads = m._placement.shard_loads()
    ratio = float(loads.max()) / max(float(loads.min()), 1.0)
    out = {
        "metric": (
            f"placement_stack_{args.groups}_groups_{args.names}_names_"
            f"mesh8x1r_zipf{args.zipf}_cpu"
        ),
        "rebalance": bool(args.rebalance),
        "groups": args.groups, "names": args.names, "replicas": R,
        "ticks": ticks_run, "edge_budget": args.edge_budget,
        "offered_per_tick": args.offered,
        "decisions": decided,
        "decisions_per_s": round(decided / dt, 1),
        "decisions_per_tick": round(decided / max(ticks_run, 1), 2),
        "ms_per_tick": round(1e3 * dt / max(ticks_run, 1), 3),
        "backlog_end": int(sum(queues)),
        "shard_loads_ewma": [round(float(x), 1) for x in loads],
        "shard_load_max_min_ratio": round(ratio, 2),
        "groups_moved": moved_total, "plans": plans,
        "migration_stats": mig.stats.snapshot(),
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
