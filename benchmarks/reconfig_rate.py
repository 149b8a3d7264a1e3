"""Sustained reconfiguration rate through the full control plane.

The reference's ``TESTReconfigurationClient`` measures how fast names can
be migrated end-to-end (``testReconfigureRate``-style ordered tests,
``reconfiguration/testing/TESTReconfigurationClient.java:676-1002``): each
reconfiguration is a full epoch change — RC paxos commit of the intent,
StopEpoch at the old actives (a consensus stop), final-state transfer,
StartEpoch + acks, record READY — so the rate measures the whole epoch
pipeline, not a metadata flip.

Drives an in-process deployment (5 ARs + 3 RCs over real loopback
sockets, the ``TESTReconfigurationMain.startLocalServers`` shape) with K
names round-robining across active subsets, k names in flight at a time.

Usage: python benchmarks/reconfig_rate.py [--names N] [--rounds R]
       [--inflight K]
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--names", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=6,
                    help="migrations per name")
    ap.add_argument("--inflight", type=int, default=4)
    ap.add_argument("--actives", type=int, default=5)
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    from gigapaxos_tpu.client import ReconfigurableAppClient
    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.models.replicable import KVApp
    from gigapaxos_tpu.node import InProcessCluster

    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 4 * args.names + 16
    for i in range(args.actives):
        cfg.nodes.actives[f"AR{i}"] = ("127.0.0.1", 0)
    for i in range(3):
        cfg.nodes.reconfigurators[f"RC{i}"] = ("127.0.0.1", 0)

    cluster = InProcessCluster(cfg, KVApp)
    client = ReconfigurableAppClient(cfg.nodes)
    ar = [f"AR{i}" for i in range(args.actives)]
    names = [f"rr{i}" for i in range(args.names)]
    try:
        for n in names:
            assert client.create(n)["ok"]
            assert client.request(n, b"PUT v 1") == b"OK"

        t0 = time.monotonic()
        ok_count = [0]
        fail = []
        sem = threading.Semaphore(args.inflight)
        lock = threading.Lock()

        def worker(idx: int, name: str) -> None:
            # rounds are SERIAL per name (overlapping reconfigurations of
            # one name are rejected as busy by the RC); the semaphore bounds
            # how many distinct names migrate concurrently.  Deterministic
            # rotation through 3-subsets of the active set (no hash(): that
            # is randomized per process and would vary the migration
            # pattern run to run).
            for r in range(args.rounds):
                base = (idx + r) % len(ar)
                new = [ar[(base + j) % len(ar)] for j in range(3)]
                with sem:
                    try:
                        resp = client.reconfigure(name, new, timeout=120)
                        with lock:
                            if resp.get("ok"):
                                ok_count[0] += 1
                            else:
                                fail.append((name, r, resp))
                    except Exception as e:  # noqa: BLE001 - record, continue
                        with lock:
                            fail.append((name, r, str(e)))

        threads = [
            threading.Thread(target=worker, args=(i, n))
            for i, n in enumerate(names)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        dt = time.monotonic() - t0

        # every name still serves its state after all the epoch churn
        survivors = sum(
            1 for n in names if client.request(n, b"GET v", timeout=60) == b"1"
        )
        print(json.dumps({
            "metric": "reconfigurations_per_sec_e2e",
            "value": round(ok_count[0] / dt, 2),
            "unit": "reconfigurations/s",
            "vs_baseline": 0.0,
            "detail": {
                "completed": ok_count[0],
                "attempted": args.names * args.rounds,
                "failed": len(fail),
                "elapsed_s": round(dt, 2),
                "inflight": args.inflight,
                "names": args.names,
                "state_survivors": survivors,
            },
        }))
        if fail[:3]:
            print("failures:", fail[:3], file=sys.stderr)
    finally:
        client.close()
        cluster.close()


if __name__ == "__main__":
    main()
