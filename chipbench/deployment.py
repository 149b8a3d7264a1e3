"""The deployment under test, built from a configuration file.

Copies of ``chip_smoke.py``'s ``make_config``, ``build_cluster``,
``populate``, ``replica_tables``, ``kernels_in`` and ``tick_program`` (PR 21
ran them on the chip), taken from a data file instead of arguments, so that
later PRs may change the program and the smoke but not the yardstick.
"""

from __future__ import annotations

import collections.abc
import os
import threading

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


class Loaded(collections.abc.Mapping):
    """service name -> {key: value} of the records ``preload`` wrote: what a
    reference is given as ``initial``.  Held as one array of bytes, not a
    dict of a million dicts that the collector would walk inside the
    window."""

    def __init__(self, key: str, values: np.ndarray):
        self._key, self._values = key, values

    def __getitem__(self, name: str) -> dict:
        number = name[len(NAME_PREFIX):]
        if not (name.startswith(NAME_PREFIX) and number.isdecimal()
                and int(number) < len(self._values)):
            raise KeyError(name)
        return {self._key: self._values[int(number)].decode()}

    def __iter__(self):
        return (f"{NAME_PREFIX}{i}" for i in range(len(self._values)))

    def __len__(self) -> int:
        return len(self._values)


#: the sequence numbers of the loaded records' values start here: past the
#: window's (from 0) and the warm-up's (from 10**9)
PRELOAD_SEQ0 = 2 * 10 ** 9


def record_values(seed: int, n: int, width: int) -> np.ndarray:
    """``n`` record values as an ``S<width>`` array, made as the generators
    make theirs, with sequence numbers from ``PRELOAD_SEQ0``."""
    from .generators.open_poisson import value_array

    return value_array(np.random.default_rng([int(seed), 3]), n, width,
                       PRELOAD_SEQ0)


def _bulk_wave(cluster, rows: np.ndarray, payloads: list,
               timeout_s: float) -> list:
    """One ``propose_bulk`` of one request a row (as ``chip_smoke.py``'s
    ``_wave``); the answers, in no order, once every one has come."""
    answers: list = []
    done = threading.Event()

    def sink(offsets, responses):
        answers.extend(responses)
        if len(answers) >= len(payloads):
            done.set()

    rids = cluster.manager.propose_bulk(rows, payloads, batch_sink=sink)
    if not (rids > 0).all():
        raise DeploymentError(f"preload: {int((rids <= 0).sum())} of "
                              f"{len(payloads)} records not admitted")
    cluster.driver.kick()
    if not done.wait(timeout_s):
        raise DeploymentError(f"preload: {len(answers)} of {len(payloads)} "
                              f"records answered within {timeout_s:.0f}s")
    return answers


#: names a ``propose_bulk`` of the preload: a wave of 262,144 completes in
#: 1.6-1.7 s at 1M groups on one chip (my scratch runs, PR 31)
PRELOAD_WAVE = 262144


def preload(cluster, names: list, params: dict, seed: int,
            timeout_s: float = 600.0, wave: int = PRELOAD_WAVE) -> Loaded:
    """Load one seeded record into every name before the warm-up, through
    consensus and the journal: ``PaxosManager.propose_bulk`` in waves of one
    ``PUT <key> <value>`` a name, each wave waited for and every answer held
    to ``OK``.  Nothing is written into ``app.db`` behind the protocol's
    back: what a replica holds after a restart is what the journal says.
    ``params`` is a traffic file's ``preload``: ``key`` and ``value_bytes``.
    ``wave`` is not a traffic file's to set: a test makes it small so that
    the several waves a 1M load takes run at a rehearsal's size."""
    m = cluster.manager
    key = params["key"]
    values = record_values(seed, len(names), int(params["value_bytes"]))
    rows = np.fromiter((m.rows.row(f"{s}#0") for s in names), np.int64,
                       len(names))
    put = f"PUT {key} ".encode()
    for lo in range(0, len(names), wave):
        answers = _bulk_wave(
            cluster, rows[lo:lo + wave],
            [put + v for v in values[lo:lo + wave].tolist()], timeout_s)
        wrong = sum(a != b"OK" for a in answers)
        if wrong:
            raise DeploymentError(f"preload: {wrong} of {len(answers)} "
                                  f"records not answered OK")
    return Loaded(key, values)


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
