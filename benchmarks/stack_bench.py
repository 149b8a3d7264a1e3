"""End-to-end framework throughput: decisions/sec through the REAL
PaxosManager stack (inbox build -> device tick -> WAL -> compacted outbox ->
vectorized execution -> completion accounting) at 100k-1M groups.

This is the measurement the kernel-only ``bench.py`` deliberately excludes:
every decision here flows through request admission (``propose_bulk``),
journaling, the compacted device->host transfer, app execution
(``DenseCounterApp``), and client-visible completion — the full hot-path
inventory of SURVEY §3.2.  Methodology mirrors the reference capacity probe
(``gigapaxos/testing/TESTPaxosConfig.java:190-229``): sustained open-loop
load with admission control, steady-state window measured.

Usage:  python benchmarks/stack_bench.py [--groups N] [--ticks T] [--wal]
        [--platform cpu] [--profile] [--mesh N] [--mesh-kernel]
Prints one JSON line per run.

``--mesh N`` runs the full manager stack sharded over an N-device
(replica, groups) mesh (``paxos.mesh_devices``; shard_map tick).
``--mesh-kernel`` instead runs the kernel-level A/B at the same sizes:
the GSPMD global-view tick (``parallel/mesh.sharded_tick`` — pallas
disabled, the partitioner owns the layout) vs the shard_map tick
(``parallel/shard_tick``) on the same mesh, quantifying the GSPMD
penalty the shard_map formulation recovers.

Commit latency: every measured tick samples ``--lat-samples`` requests
spread across the group space with real completion callbacks; p50/p99 of
entry->callback (WAL-durable release included) lands in ``detail``.
"""

from __future__ import annotations

import argparse
import json
import os
import struct
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=1 << 17)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--ticks", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=8)
    ap.add_argument("--wal", action="store_true", help="journal every tick")
    ap.add_argument("--device", action="store_true",
                    help="device-app mode: decisions execute ON DEVICE "
                         "(propose_bulk_kv; no host app work at all)")
    ap.add_argument("--wal-dir", default="/tmp/gptpu_stack_wal")
    ap.add_argument("--sync-every", type=int, default=8)
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu)")
    ap.add_argument("--baseline", choices=["unreplicated", "lazy"],
                    default=None,
                    help="measurement baseline (PaxosManager.java:1751-1799)"
                         ": 'unreplicated' executes at the entry replica "
                         "with no coordination at all; 'lazy' responds at "
                         "the entry and propagates through consensus in "
                         "the background")
    ap.add_argument("--profile", action="store_true",
                    help="report per-stage host timings")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard the data plane over N devices "
                         "(-1 = all visible); 0 = single-device")
    ap.add_argument("--mesh-replica-shards", type=int, default=1)
    ap.add_argument("--mesh-kernel", action="store_true",
                    help="kernel-level GSPMD-vs-shard_map tick A/B on the "
                         "--mesh mesh (no manager stack)")
    ap.add_argument("--lat-samples", type=int, default=64,
                    help="commit-latency samples per measured tick "
                         "(0 disables)")
    ap.add_argument("--health", action="store_true",
                    help="fold the group-health plane into the tick "
                         "(paxos.group_health; ISSUE 18 A/B arm)")
    ap.add_argument("--health-topk", type=int, default=8)
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    if args.mesh_kernel:
        mesh_kernel_compare(args)
        return

    import numpy as np

    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.models.dense_apps import DenseCounterApp
    from gigapaxos_tpu.paxos.manager import PaxosManager

    G, R = args.groups, args.replicas
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = G
    cfg.paxos.window = args.window
    cfg.paxos.proposals_per_tick = 2
    cfg.paxos.compact_outbox = True
    cfg.paxos.pipeline_ticks = True
    cfg.paxos.exec_budget = R * G + 4096  # steady-state demand + headroom
    cfg.paxos.bulk_capacity = 8 * G
    cfg.paxos.sync_every_ticks = args.sync_every
    cfg.paxos.deactivation_ticks = 0  # no pause scans mid-measurement
    if args.device:
        cfg.paxos.device_app = True
    if args.health:
        cfg.paxos.group_health = True
        cfg.paxos.health_topk = args.health_topk
    if args.baseline == "unreplicated":
        cfg.paxos.emulate_unreplicated = True
    elif args.baseline == "lazy":
        cfg.paxos.lazy_propagation = True
    if args.mesh:
        cfg.paxos.mesh_devices = args.mesh
        cfg.paxos.mesh_replica_shards = args.mesh_replica_shards

    apps = ([None] * R if args.device
            else [DenseCounterApp(G) for _ in range(R)])
    wal = None
    if args.wal:
        import shutil

        from gigapaxos_tpu.wal.logger import PaxosLogger

        shutil.rmtree(args.wal_dir, ignore_errors=True)
        wal = PaxosLogger(args.wal_dir, sync_every_ticks=args.sync_every,
                          checkpoint_every_ticks=1 << 30)
    m = PaxosManager(cfg, R, apps, wal=wal)
    if not args.device:
        for a in apps:
            a.row_of = m.rows.row

    # bulk-create all groups through the real admin path (batched
    # createPaxosInstance: one device call + one WAL group-commit)
    t0 = time.perf_counter()
    names = [f"g{i}" for i in range(G)]
    made = m.create_paxos_instances(names, list(range(R)))
    assert made == G, f"bulk create made {made} of {G}"
    create_s = time.perf_counter() - t0
    rows = np.array([m.rows.row(n) for n in names], np.int32)

    # pre-generated request waves (TESTPaxosClient pre-generates too); the
    # payloads are distinct 8-byte deltas so nothing is amortized unfairly
    n_waves = 4
    if args.device:
        from gigapaxos_tpu.models.device_kv import OP_PUT

        kv_waves = [
            (np.full(G, OP_PUT, np.int32),
             (np.arange(G) % (cfg.paxos.kv_slots - 1) + 1).astype(np.int32),
             np.arange(w, w + G, dtype=np.int32))
            for w in range(n_waves)
        ]
    else:
        waves = []
        for w in range(n_waves):
            pa = np.empty(G, object)
            pa[:] = [struct.pack("<q", (w * G + i) % 97) for i in range(G)]
            waves.append(pa)

    stages = {"propose": 0.0, "tick": 0.0}

    # commit-latency sampling: K requests per measured tick get a real
    # completion callback; entry->callback spans admission, the device
    # tick(s), host execution and the WAL-durable release — the latency a
    # client actually sees.  Sample indices spread over the whole group
    # space so every group shard is represented in mesh mode.
    lat: list = []
    intake_rows: list = []
    samp_idx = None
    cb_arr = None
    if args.lat_samples > 0:
        samp_idx = np.linspace(
            0, G - 1, min(args.lat_samples, G), dtype=np.intp
        )
        cb_arr = np.empty(G, object)

    def one_tick(i, sample=False):
        t = time.perf_counter()
        # admission control: only offer what the store window can take
        if m.bulk_stats()["queued"] < G:
            cbs = None
            if sample and samp_idx is not None:
                t_entry = time.perf_counter()

                def cb(rid, resp, _t=t_entry):
                    lat.append(time.perf_counter() - _t)

                cb_arr[samp_idx] = cb
                cbs = cb_arr
            if args.device:
                ops, keys, vals = kv_waves[i % n_waves]
                m.propose_bulk_kv(rows, ops, keys, vals, callbacks=cbs)
            else:
                m.propose_bulk(rows, list(waves[i % n_waves]),
                               callbacks=cbs)
            if sample and args.mesh and m.bulk is not None:
                intake_rows.append(m.bulk.live_by_row(m.G))
        t2 = time.perf_counter()
        m.tick()
        t3 = time.perf_counter()
        stages["propose"] += t2 - t
        stages["tick"] += t3 - t2

    for i in range(args.warmup):
        one_tick(i)
    m.drain_pipeline()
    base_dec = m.stats["decisions"]
    base_done = m.bulk_stats()["done"]
    for k in stages:
        stages[k] = 0.0
    t0 = time.perf_counter()
    for i in range(args.ticks):
        one_tick(args.warmup + i, sample=True)
    m.drain_pipeline()
    dt = time.perf_counter() - t0
    decisions = m.stats["decisions"] - base_dec
    done = m.bulk_stats()["done"] - base_done

    backend = jax.devices()[0].platform
    mesh_tag = ""
    if args.mesh:
        n_mesh = len(jax.devices()) if args.mesh < 0 else args.mesh
        mesh_tag = f"_mesh{n_mesh}x{args.mesh_replica_shards}r"
    result = {
        "metric": f"stack_decisions_per_sec_{G}_groups_{R}_replicas"
                  + ("_device_kv" if args.device else "")
                  + (f"_{args.baseline}" if args.baseline else "")
                  + ("_wal" if args.wal else "")
                  + mesh_tag
                  + (f"_{backend}" if backend != "tpu" else ""),
        "value": round(decisions / dt, 1),
        "unit": "decisions/s",
        "vs_baseline": round(decisions / dt / 100_000.0, 2),
        "detail": {
            "ticks_per_s": round(args.ticks / dt, 4),
            "completions_per_s": round(done / dt, 1),
            # unreplicated executes at the entry replica ONLY (no
            # coordination); every other mode executes on all R replicas
            "executions_per_s": round(
                decisions * (1 if args.baseline == "unreplicated" else R)
                / dt, 1),
            "groups": G,
            "replicas": R,
            "create_s": round(create_s, 2),
            "wal": bool(args.wal),
            # every run self-describes its consensus shape: slot-ring depth
            # and how many groups ran on each plane (ISSUE 16 — numbers
            # from mixed-mode runs were uninterpretable without these)
            "window": args.window,
            "mode_mix": {"log": G,
                         "register": int(cfg.paxos.register_groups)},
        },
    }
    if lat:
        ls = np.asarray(lat) * 1e3
        result["detail"]["commit_latency_ms"] = {
            "p50": round(float(np.percentile(ls, 50)), 3),
            "p99": round(float(np.percentile(ls, 99)), 3),
            "n": int(ls.size),
        }
    if args.mesh and intake_rows:
        # intake balance across the groups axis (bulkstore.live_by_row):
        # live requests binned per group shard at each measured tick's
        # admission point (post-propose, pre-tick) — a skewed split means
        # one shard absorbs most of the decision work while others idle
        gs = m.mesh.shape["groups"]
        per_row = np.sum(intake_rows, axis=0)
        result["detail"]["live_per_group_shard"] = [
            int(x) for x in per_row.reshape(gs, -1).sum(axis=1)
        ]
    if args.profile:
        result["detail"]["stage_s_per_tick"] = {
            k: round(v / args.ticks, 4) for k, v in stages.items()
        }
    print(json.dumps(result))
    if wal is not None:
        wal.close()


def mesh_kernel_compare(args) -> None:
    """Tick-kernel A/B on one mesh: GSPMD global-view vs shard_map.

    Same state, same on-device load generator, same mesh; the only variable
    is who partitions the program.  Open-loop like bench.py: dispatch the
    measured ticks back-to-back, block once on the accumulated decision
    counts.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gigapaxos_tpu.ops.tick import TickInbox
    from gigapaxos_tpu.parallel import mesh as pm
    from gigapaxos_tpu.parallel import shard_tick as stk
    from gigapaxos_tpu.paxos import state as st

    R, G, W, P = args.replicas, args.groups, args.window, 2
    devs = jax.devices()
    n = len(devs) if args.mesh < 0 else (args.mesh or len(devs))
    mesh = pm.make_mesh(devs[:n], replica_shards=args.mesh_replica_shards)
    stk.validate_mesh_for(mesh, R, G)

    def gen_inbox(rid_base):
        g = jnp.arange(G, dtype=jnp.int32)
        rids = rid_base + g
        req = jnp.zeros((R, P, G), jnp.int32).at[:, 0, :].set(
            jnp.where(g[None, :] % R == jnp.arange(R)[:, None],
                      rids[None, :], 0)
        )
        return TickInbox(req, jnp.zeros((R, P, G), jnp.bool_),
                         jnp.ones((R,), jnp.bool_))

    gen = jax.jit(gen_inbox, out_shardings=pm.inbox_shardings(mesh))

    def fresh_state():
        state = st.init_state(R, G, W)
        state = st.create_groups(
            state, np.arange(G, dtype=np.int32), np.ones((G, R), bool)
        )
        return pm.shard_state(state, mesh)

    def run_variant(tick_fn):
        state = fresh_state()
        state, out = tick_fn(state, gen(jnp.int32(1)))  # compile + warm
        jax.block_until_ready(out.decided_now)
        accs = []
        t0 = time.perf_counter()
        for i in range(args.ticks):
            state, out = tick_fn(state, gen(jnp.int32(1 + (i + 1) * G)))
            accs.append(jnp.sum(out.decided_now))
        total = sum(int(a) for a in accs)  # blocks on the queued ticks
        dt = time.perf_counter() - t0
        del state
        return round(total / dt, 1), total

    gspmd_dps, gspmd_n = run_variant(pm.sharded_tick(mesh))
    smap_dps, smap_n = run_variant(stk.make_shardmap_tick(mesh))

    backend = jax.devices()[0].platform
    print(json.dumps({
        "metric": f"mesh_kernel_tick_{G}_groups_{R}_replicas"
                  f"_mesh{n}x{args.mesh_replica_shards}r"
                  + (f"_{backend}" if backend != "tpu" else ""),
        "value": smap_dps,
        "unit": "decisions/s",
        "vs_baseline": round(smap_dps / 100_000.0, 2),
        "detail": {
            "gspmd_decisions_per_s": gspmd_dps,
            "shard_map_decisions_per_s": smap_dps,
            "recovered_ratio": round(smap_dps / gspmd_dps, 3)
            if gspmd_dps else None,
            "decisions": {"gspmd": gspmd_n, "shard_map": smap_n},
            "groups": G,
            "window": W,
            "mode_mix": {"log": G, "register": 0},  # mesh path is log-only
            "ticks": args.ticks,
            "mesh": {"devices": n,
                     "replica_shards": args.mesh_replica_shards},
        },
    }))


if __name__ == "__main__":
    main()
