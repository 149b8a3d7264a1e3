"""The deployment under test, built from a configuration file.

Copies of ``chip_smoke.py``'s ``make_config``, ``build_cluster``,
``populate``, ``replica_tables``, ``kernels_in`` and ``tick_program`` (PR 21
ran them on the chip), taken from a data file instead of arguments, so that
later PRs may change the program and the smoke but not the yardstick.
"""

from __future__ import annotations

import os

import numpy as np

#: rows named ``<prefix><i>#0``: epoch 0 of service name ``<prefix><i>``
NAME_PREFIX = "bg"


class DeploymentError(RuntimeError):
    pass


def make_config(config: dict):
    """``GigapaxosTpuConfig`` for the README Mode A deployment the file
    describes: every key under ``paxos`` is set on ``cfg.paxos`` (an unknown
    key is an error, not a silent no-op), all else is the shipped default."""
    from gigapaxos_tpu.config import GigapaxosTpuConfig

    cfg = GigapaxosTpuConfig()
    for i in range(int(config["nodes"]["actives"])):
        cfg.nodes.actives[f"AR{i}"] = ("127.0.0.1", 0)
    for i in range(int(config["nodes"]["reconfigurators"])):
        cfg.nodes.reconfigurators[f"RC{i}"] = ("127.0.0.1", 0)
    for key, value in config["paxos"].items():
        if not hasattr(cfg.paxos, key):
            raise DeploymentError(f"cfg.paxos has no setting {key!r}")
        setattr(cfg.paxos, key, value)
    if cfg.native_journal != config["native_journal"]:
        raise DeploymentError(
            f"the shipped default native_journal={cfg.native_journal} is not "
            f"the configuration's {config['native_journal']}")
    return cfg


def build_cluster(config: dict, cfg, run_dir: str,
                  ready_timeout_s: float = 1100.0):
    """The Mode A cluster on fresh WAL directories under ``run_dir``."""
    from gigapaxos_tpu.models import replicable
    from gigapaxos_tpu.node import InProcessCluster
    from gigapaxos_tpu.wal.native_journal import NativeJournal

    cluster = InProcessCluster(
        cfg, getattr(replicable, config["app"]),
        wal_dir=os.path.join(run_dir, "wal_ar"),
        rc_wal_dir=os.path.join(run_dir, "wal_rc"),
        ready_timeout_s=ready_timeout_s,
    )
    if config["native_journal"]:
        for plane, m in (("data", cluster.manager), ("rc", cluster.rc_manager)):
            if not isinstance(m.wal.journal, NativeJournal):
                cluster.close()
                raise DeploymentError(
                    f"the {plane} plane runs {type(m.wal.journal).__name__}, "
                    f"not the native journal the configuration states")
    return cluster


def populate(cluster, n: int) -> list:
    """``n`` groups resident on all replicas through the journaled admin
    path, as epoch-0 rows of service names the actives then answer for.
    Returns the service names (what a client addresses)."""
    m = cluster.manager
    members = list(range(m.R))
    names = [f"{NAME_PREFIX}{i}" for i in range(n)]
    made = m.create_paxos_instances([f"{s}#0" for s in names], members)
    if made != n:
        raise DeploymentError(f"bulk create made {made} of {n} groups")
    adopted = cluster.coordinator.adopt_live_epochs()
    if adopted < n:
        raise DeploymentError(f"the actives adopted {adopted} of {n} names")
    return names


def warm_sweep_buckets(m, up_to: int) -> int:
    """Compile, in set-up, every row-count bucket the payload sweep of plane
    ``m`` can meet in a window.

    Every 64 ticks ``PaxosManager._frontier_gather`` gathers the rows that
    hold outstanding records through ``ops.tick.frontier_rows``, padded to a
    power of two from 16 up: one program per bucket, compiled on first use on
    the tick thread under the manager's lock.  The warm-up's sweep meets one
    bucket; a window's sweep that meets its neighbour stalls the plane for
    the compile (0.26 s from the persistent cache at 1M groups, 1 traced run
    in 3: my chip runs, PR 29), inside the window.  This makes the same calls
    on the plane's own frontier, so the program finds each bucket in its jit
    cache.  Returns the number of buckets."""
    import jax
    from gigapaxos_tpu.ops import tick as tk

    with m.lock:  # the tick donates the state it is given
        fr = tk.sweep_frontier(m.state.exec_slot, m.state.member,
                               m.alive.copy())
        outs, k = [], 16
        while k <= up_to:
            outs.append(tk.frontier_rows(*fr, np.zeros(k, np.int32)))
            k *= 2
        jax.block_until_ready(outs)
    return len(outs)


def replica_tables(cluster, service: str) -> list:
    """The app state every replica holds for one service name."""
    return [dict(app.db.get(f"{service}#0", {}))
            for app in cluster.manager.apps]


def memory_peak_bytes(n_chips: int) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax

    peaks = []
    for d in jax.local_devices()[:n_chips]:
        ms = d.memory_stats() or {}
        peaks.append(int(ms.get("peak_bytes_in_use", 0)))
    return max(peaks)


# ----------------------------------------------------- the program that ran
def tick_program(m):
    """The jitted tick a compact-outbox manager without lease, health,
    register plane, device app or mesh dispatches, with arguments shaped like
    the state it holds (the branch of ``chip_smoke.tick_program`` the
    benchmark's deployments take)."""
    from gigapaxos_tpu.ops import tick as tk

    if (not m._use_compact or m._health is not None or m._device_app
            or m.mesh is not None or m._lease is not None
            or m.rstate is not None):
        raise DeploymentError("not the plain compact tick: kernels_in() "
                              "would count another program")
    inbox = tk.TickInbox(
        np.zeros((m.R, m.P, m.G_total), np.int32),
        np.zeros((m.R, m.P, m.G_total), bool), np.ones(m.R, bool))
    return tk.paxos_tick_compact, (m.state, inbox, -1, m._exec_budget,
                                   m._lag_budget)


def kernels_in(fn, *args) -> dict:
    """What a jitted program carries of the two Pallas kernels: calls traced
    (and how many interpreted), and Mosaic custom calls in the lowered text."""
    from gigapaxos_tpu.ops.pallas_gather import GATHER_KERNEL, MATCH_KERNEL

    traced = fn.trace(*args)
    jaxpr = str(traced.jaxpr)
    text = traced.lower().as_text()
    return {
        "pallas_calls": jaxpr.count("pallas_call["),
        "interpreted": jaxpr.count("interpret=True"),
        "mosaic_gather": text.count(f'kernel_name = "{GATHER_KERNEL}"'),
        "mosaic_match": text.count(f'kernel_name = "{MATCH_KERNEL}"'),
        "gather_name": GATHER_KERNEL,
        "match_name": MATCH_KERNEL,
    }
