"""chip_smoke.py: the served path, once, on the chip, at the north-star width.

The quickest proof that the system still starts on a TPU.  One process:

1. report the device, the versions, the compile cache and the journal;
2. build the README's Mode A deployment (``node.InProcessCluster``, 3 actives
   + 3 reconfigurators on loopback, ``KVApp``, WAL on) at
   ``max_groups = 1 << 20``, W=4, the shipped defaults otherwise;
3. bulk-create ``(1 << 20) - 64`` groups through the journaled admin path;
4. serve: a ``ReconfigurableAppClient`` over real sockets creates names and
   issues PUTs and GETs; every acknowledged write is read back through the
   client and from all three replicas' app state, against a plain ``dict``
   reference fed the same operations; then one wave of 65,536 requests, one
   per group, admitted in a single tick (``manager.propose_bulk``);
5. prove the device path ran: the dispatched tick, lowered for the state the
   manager holds, carries the Mosaic custom calls of both Pallas kernels;
6. one tick each of the other programs a manager can dispatch at the same G
   (mixed log+register, lease, health; the device KV app is reported only);
7. restart on the same WAL directories (the replay scans) and serve the
   acknowledged values again.

It exits non-zero when any step fails or JAX's default backend is not a
TPU; there is no CPU mode.  ``tests/test_chip_smoke.py`` rehearses the same
stage functions on the CPU at 4,096 groups with the kernels interpreted.
Wall times are printed per stage as information, not as a benchmark.

    python chip_smoke.py                   # the check the driver runs
    python chip_smoke.py --mesh-devices 4  # four-chip host: group axis sharded
"""

from __future__ import annotations

import argparse
import contextlib
import faulthandler
import hashlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_PATH = os.path.join(HERE, "chiprun_out", "chip_smoke.log")

FULL_GROUPS = 1 << 20
#: rows left free for the names the client creates (and their epochs)
SPARE_ROWS = 64
FULL_WAVE = 1 << 16
#: the contract allows 1200 s; past this the process dumps every thread's
#: stack and exits non-zero instead of being killed without a word
DEADLINE_S = 1150


# ------------------------------------------------------------------ logging
class Log:
    """Lines to stdout and to ``chiprun_out/chip_smoke.log`` (the chip tool
    shows only the end of stdout), plus the per-stage wall times."""

    def __init__(self, path=None):
        self.t0 = time.monotonic()
        self.times: dict = {}
        self._f = None
        if path is not None:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            self._f = open(path, "w")

    def __call__(self, msg: str) -> None:
        line = f"[{time.monotonic() - self.t0:7.1f}s] {msg}"
        print(line, flush=True)
        if self._f is not None:
            self._f.write(line + "\n")
            self._f.flush()

    @contextlib.contextmanager
    def stage(self, name: str):
        t, verdict = time.monotonic(), "FAILED"
        self(f"--- {name}")
        try:
            yield
            verdict = "ok"
        finally:
            dt = time.monotonic() - t
            self.times[name] = round(dt, 2)
            self(f"--- {name}: {verdict} in {dt:.1f}s")

    def close(self) -> None:
        if self._f is not None:
            self._f.close()


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------- reference
class RefKV:
    """The plain reference: per-name dicts with the KV app's request
    semantics (``PUT k v`` -> OK, ``GET k`` -> value or NF, ``DEL k`` -> OK
    or NF), written here independently of ``models/replicable.KVApp``."""

    def __init__(self):
        self.tables: dict = {}

    def apply(self, name: str, request: bytes) -> bytes:
        op, _, rest = request.decode().partition(" ")
        table = self.tables.setdefault(name, {})
        if op == "PUT":
            key, _, value = rest.partition(" ")
            table[key] = value
            return b"OK"
        if op == "GET":
            return table[rest].encode() if rest in table else b"NF"
        if op == "DEL":
            return b"OK" if table.pop(rest, None) is not None else b"NF"
        raise ValueError(f"reference does not know {request!r}")


def cache_since(snap) -> str:
    """Persistent-compile-cache hits and misses since ``snap`` (a
    ``compiles.cache_lookups()`` pair), from the program's own counters."""
    from gigapaxos_tpu.obs import compiles

    hits, misses = compiles.cache_lookups()
    return f"{hits - snap[0]} from the cache, {misses - snap[1]} compiled"


# ------------------------------------------------------------------- stages
def report_environment(log: Log, cache_dir: str) -> dict:
    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # not installed (CPU rehearsal images)
        libtpu = "not installed"
    log(f"platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']} default_backend={jax.default_backend()}")
    log(f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"python={sys.version.split()[0]}")
    log(f"compile cache: {cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'default under the checkout'}; "
        f"{len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        f"entries at start)")
    return device


def make_config(groups: int, mesh_devices: int = 0):
    """The README Quick-start deployment at ``groups`` rows, with the two
    settings config.py calls required at 100k-1M groups; everything else is
    the shipped default (asserted, so a changed default shows here)."""
    from gigapaxos_tpu.config import GigapaxosTpuConfig

    cfg = GigapaxosTpuConfig()
    for i in range(3):
        cfg.nodes.actives[f"AR{i}"] = ("127.0.0.1", 0)
        cfg.nodes.reconfigurators[f"RC{i}"] = ("127.0.0.1", 0)
    cfg.paxos.max_groups = groups
    cfg.paxos.compact_outbox = True
    cfg.paxos.pipeline_ticks = True
    if mesh_devices:
        cfg.paxos.mesh_devices = mesh_devices
        cfg.paxos.mesh_replica_shards = 1
    p = cfg.paxos
    check((p.window, p.proposals_per_tick, p.sync_every_ticks) == (4, 4, 1)
          and p.deactivation_ticks == 10_000 and cfg.native_journal,
          "shipped defaults changed under the smoke")
    return cfg


def build_cluster(log: Log, cfg, run_dir: str, ready_timeout_s: float):
    """Stage 2 (and the restart leg): the Mode A cluster on its WAL
    directories; recovers when they already hold a journal."""
    from gigapaxos_tpu.models.replicable import KVApp
    from gigapaxos_tpu.node import InProcessCluster
    from gigapaxos_tpu.wal.native_journal import NativeJournal

    cluster = InProcessCluster(
        cfg, KVApp,
        wal_dir=os.path.join(run_dir, "wal_ar"),
        rc_wal_dir=os.path.join(run_dir, "wal_rc"),
        ready_timeout_s=ready_timeout_s,
    )
    for plane, m, drv in (("data", cluster.manager, cluster.driver),
                          ("rc", cluster.rc_manager, cluster.rc_driver)):
        journal = type(m.wal.journal).__name__
        log(f"{plane} plane: R={m.R} G={m.G} W={m.W} P={m.P} "
            f"first tick (compile) {drv.first_tick_s:.1f}s, journal "
            f"backend {journal}")
        check(isinstance(m.wal.journal, NativeJournal),
              f"cfg.native_journal is true but the {plane} plane runs "
              f"{journal} (the loader logged why)")
    return cluster


def state_bytes(*states) -> int:
    import jax

    return sum(a.nbytes for s in states if s is not None
               for a in jax.tree.leaves(s))


def report_memory(log: Log, what: str, shape_bytes: int) -> None:
    import jax

    for d in jax.devices():
        ms = d.memory_stats()
        if not ms:
            log(f"{what}: device {d.id} reports no memory_stats; "
                f"{shape_bytes:,} B counted from state shapes")
            continue
        log(f"{what}: device {d.id} bytes_in_use={ms['bytes_in_use']:,} "
            f"peak={ms.get('peak_bytes_in_use', 0):,} | state shapes count "
            f"{shape_bytes:,} B")


def populate(log: Log, cluster, n: int) -> list:
    """Stage 3: ``n`` named groups on all three replicas, one journaled
    batch (the admin path ``benchmarks/stack_bench.py`` uses)."""
    m = cluster.manager
    names = [f"bg{i}" for i in range(n)]
    made = m.create_paxos_instances(names, [0, 1, 2])
    check(made == n, f"bulk create made {made} of {n}")
    shape = state_bytes(m.state, cluster.rc_manager.state)
    log(f"populated {made:,} groups; {len(m.rows):,} rows resident; both "
        f"planes' state by shape: {shape:,} B "
        f"({state_bytes(m.state) / m.G:.0f} B/group/plane)")
    report_memory(log, "after populate", shape)
    return names


def tick_period(log: Log, cluster, what: str, window_s: float = 3.0) -> None:
    """The tick period both planes show over a short window."""
    planes = (("data", cluster.manager), ("rc", cluster.rc_manager))
    t0, n0 = time.monotonic(), [m.tick_num for _, m in planes]
    time.sleep(window_s)
    dt = time.monotonic() - t0
    for (plane, m), before in zip(planes, n0):
        dn = m.tick_num - before
        log(f"tick period, {what}, {plane} plane: "
            + (f"{1e3 * dt / dn:.0f} ms ({dn} ticks in {dt:.1f}s, tick "
               f"{m.tick_num})" if dn else f"no tick in {dt:.1f}s"))


def replica_tables(cluster, pname: str) -> list:
    return [dict(app.db.get(pname, {})) for app in cluster.manager.apps]


def serve(log: Log, cluster, cfg, ref: RefKV, seed: int,
          rpc_timeout_s: float) -> list:
    """Stage 4a: client -> ActiveReplica -> PaxosManager -> tick -> WAL ->
    reply over real sockets.  Returns the client's names."""
    from gigapaxos_tpu.client import ReconfigurableAppClient

    rng = np.random.default_rng(seed)
    names = [f"smoke{seed}-{i}" for i in range(3)]
    client = ReconfigurableAppClient(cfg.nodes)
    replies = []
    sides0 = sides(cluster.manager)
    try:
        for name in names:
            t = time.monotonic()
            resp = client.create(name, timeout=rpc_timeout_s)
            check(resp.get("ok"), f"create {name}: {resp}")
            log(f"rpc create {name}: {time.monotonic() - t:.2f}s")
        for name in names:
            v = [f"v{int(rng.integers(1 << 30))}" for _ in range(3)]
            ops = [f"PUT k0 {v[0]}", f"PUT k1 {v[1]}", "GET k0",
                   f"PUT k0 {v[2]}", "GET k0", "GET nokey", "DEL k1",
                   "GET k1"]
            for op in ops:
                t = time.monotonic()
                got = client.request(name, op.encode(),
                                     timeout=rpc_timeout_s, tries=2)
                want = ref.apply(f"{name}#0", op.encode())
                check(got == want, f"{name}: {op!r} answered {got!r}, the "
                      f"reference says {want!r}")
                replies.append(got)
                log(f"rpc {name} {op.split()[0]}: "
                    f"{time.monotonic() - t:.2f}s")
        read_back(log, cluster, client, ref, names, rpc_timeout_s)
    finally:
        client.close()
    took = {key: int(v - sides0[key])
            for key, v in sides(cluster.manager).items()}
    log(f"served one request at a time; ticks since by where their outbox "
        f"was completed, outbox buffers by pull, inboxes by path and exec "
        f"lists by the compaction's branch: {took}")
    # a request or two a tick is a list: no tick copies and uploads [R, P, G]
    check(took["short"] > 0 and took["dense"] == 0, f"the trickle's inboxes "
          f"were not all handed over as short lists: {took}")
    # and a few executions a tick take the narrow end of the ladder: no
    # tick's compaction paid for the widest sparse width or the whole plane
    tiers = exec_tiers(cluster.manager)
    if tiers:
        wide = [f"exec:sparse{tiers[-1]}", "exec:dense"]
        check(all(took[k] == 0 for k in wide) and sum(
            took[f"exec:sparse{k}"] for k in tiers[:-1]) > 0,
            f"the trickle's ticks did not all compact at the narrow widths "
            f"{tiers[:-1]}: {took}")
    return names


def read_back(log: Log, cluster, client, ref: RefKV, names,
              rpc_timeout_s: float) -> None:
    """Every acknowledged PUT: (a) GET through the client, (b) all three
    replicas' app state, both against the reference."""
    for name in names:
        table = ref.tables[f"{name}#0"]
        for key, value in table.items():
            got = client.request(name, f"GET {key}".encode(),
                                 timeout=rpc_timeout_s, tries=2)
            check(got == value.encode(),
                  f"{name}: GET {key} -> {got!r}, acknowledged {value!r}")
        for r, got in enumerate(replica_tables(cluster, f"{name}#0")):
            check(got == table, f"{name}: replica {r} holds {got}, the "
                  f"reference {table}")
    log(f"read back {sum(len(ref.tables[f'{n}#0']) for n in names)} "
        f"acknowledged values by GET and from 3 replicas: equal to the "
        f"reference")


def sides(m) -> dict:
    """The data plane's dispatched ticks by where their outbox was completed
    (``pipeline_ticks`` is on: a tick may hold it for the next call), its
    outbox buffers by what was pulled (the head, or the flat buffer whole
    where a tick decided more than the head holds), its inboxes by how
    they reached the device (a short list of placements, or dense), and its
    exec lists by the branch the compaction took (``exec:sparse<K>`` for
    each width of ``m``'s ladder, ``exec:dense``)."""
    from gigapaxos_tpu.obs.metrics import registry

    snap = registry().snapshot()  # the cluster's data plane is "ar"
    took = {key: snap.get(series % key, 0) for series, keys in (
        ("tick_completions_total{mode=%s,plane=ar}", ("same_call", "held")),
        ("outbox_pulls_total{plane=ar,pull=%s}", ("head", "full")),
        ("inbox_builds_total{path=%s,plane=ar}", ("short", "dense")))
        for key in keys}
    for path in [f"sparse{k}" for k in exec_tiers(m)] + ["dense"]:
        took[f"exec:{path}"] = snap.get(
            f"compact_path_ticks_total{{list=exec,path={path},plane=ar}}", 0)
    return took


def exec_tiers(m) -> tuple:
    """The widths of the data plane's exec-list compaction, narrowest
    first; empty where the plane is too narrow for any (the rehearsal's)."""
    from gigapaxos_tpu.ops.tick import compact_tiers

    return compact_tiers(m.R * m.W * m.G, m._exec_budget)


def _wave(log: Log, cluster, what: str, rows, payloads,
          timeout_s: float) -> list:
    """One ``propose_bulk`` of one request per row; returns the responses
    in row order once every one completed."""
    m = cluster.manager
    n = len(rows)
    got: list = [None] * n
    batches: list = []
    done = threading.Event()

    def sink(offsets, responses):
        for o, resp in zip(offsets, responses):
            got[int(o)] = resp
        batches.append((len(offsets), m.tick_num))
        if sum(b[0] for b in batches) >= n:
            done.set()

    tick0, t0, sides0 = m.tick_num, time.monotonic(), sides(m)
    rids = m.propose_bulk(rows, payloads, batch_sink=sink)
    check((rids > 0).all(), f"{what}: {int((rids <= 0).sum())} of {n} "
          f"requests not admitted")
    cluster.driver.kick()
    check(done.wait(timeout_s), f"{what}: {sum(b[0] for b in batches)} of "
          f"{n} completed within {timeout_s:.0f}s")
    took = {mode: int(v - sides0[mode]) for mode, v in sides(m).items()}
    log(f"{what}: {n:,} requests admitted at tick {tick0}, completed in "
        f"{time.monotonic() - t0:.2f}s by tick {m.tick_num}, in "
        f"{len(batches)} completion batch(es) (size, tick) {batches[:4]}; "
        f"ticks since by where their outbox was completed, outbox buffers "
        f"by pull, inboxes by path and exec lists by the compaction's "
        f"branch: {took}")
    # a bulk placement is not a list: its tick hands over the dense inbox
    check(took["dense"] >= 1, f"{what}: {n:,} requests placed in bulk and "
          f"no inbox handed over dense (path=dense): {took}")
    # one tick executes the wave on every replica: past the head, that
    # tick's outbox is the one pull of the whole flat buffer
    if m.R * n > m._compact_layout.head_exec:
        check(took["full"] >= 1, f"{what}: {m.R * n:,} executions in one "
              f"tick and no outbox pulled whole (pull=full): {took}")
    # past the widest sparse width the compaction is the dense code
    if m.R * n > max(exec_tiers(m), default=0):
        check(took["exec:dense"] >= 1, f"{what}: {m.R * n:,} executions in "
              f"one tick and no exec list compacted dense: {took}")
    # completions fire once per entry replica; all from one tick's pass =
    # decided and executed by one tick, hence admitted by one
    ticks = {t for _, t in batches}
    check(len(ticks) == 1, f"{what}: the wave completed over ticks "
          f"{sorted(ticks)}, not one")
    return got


def wide_wave(log: Log, cluster, ref: RefKV, bg_names, n_wave: int,
              seed: int, timeout_s: float) -> list:
    """Stage 4b: one request to each of ``n_wave`` distinct background
    groups in a single tick, executed on all three replicas; then the same
    groups read back in a second wave.  Returns the wave's group names."""
    m = cluster.manager
    rng = np.random.default_rng(seed + 1)
    pick = rng.choice(len(bg_names), size=n_wave, replace=False)
    names = [bg_names[i] for i in pick]
    rows = np.array([m.rows.row(n) for n in names], np.int64)
    check(len(set(rows.tolist())) == n_wave, "wave rows are not distinct")

    puts = [f"PUT w {seed}-{i}".encode() for i in pick]
    want = [ref.apply(n, p) for n, p in zip(names, puts)]
    got = _wave(log, cluster, "wide wave PUT", rows, puts, timeout_s)
    check(got == want, "wide wave PUT: responses differ from the reference")
    # the entry replica answers; every member executes in the same pass, so
    # all three replicas hold the write once the wave's batch has fired
    for r, app in enumerate(m.apps):
        bad = [n for n in names if app.db.get(n) != ref.tables[n]]
        check(not bad, f"wide wave: replica {r} differs from the reference "
              f"on {len(bad)} groups, e.g. {bad[:3]}")
    gets = [b"GET w"] * n_wave
    want = [ref.apply(n, p) for n, p in zip(names, gets)]
    got = _wave(log, cluster, "wide wave GET", rows, gets, timeout_s)
    check(got == want, "wide wave GET: responses differ from the reference")
    log(f"wide wave: {n_wave:,} groups executed on 3 replicas, responses "
        f"and app state equal to the reference")
    return names


def kernels_in(fn, *args) -> dict:
    """What a jitted program carries of the two Pallas kernels: calls traced
    (and whether interpreted), and Mosaic custom calls in the lowered text."""
    from gigapaxos_tpu.ops.pallas_gather import GATHER_KERNEL, MATCH_KERNEL

    traced = fn.trace(*args)
    jaxpr = str(traced.jaxpr)
    text = traced.lower().as_text()
    return {
        "pallas_calls": jaxpr.count("pallas_call["),
        "interpreted": jaxpr.count("interpret=True"),
        "mosaic_gather": text.count(f'kernel_name = "{GATHER_KERNEL}"'),
        "mosaic_match": text.count(f'kernel_name = "{MATCH_KERNEL}"'),
        "tpu_custom_calls": text.count("@tpu_custom_call"),
    }


def kernel_builds() -> dict:
    """``pallas_kernel_builds_total`` by kernel and tile lanes: the tiles
    the process's kernels took (one increment a distinct build)."""
    from gigapaxos_tpu.obs.metrics import registry

    fam = "pallas_kernel_builds_total{"
    return {k[len(fam):-1]: v for k, v in registry().snapshot().items()
            if k.startswith(fam)}


def tick_program(m):
    """The jitted tick ``PaxosManager.tick`` dispatches for this manager,
    with arguments shaped like the state it holds: the manager says which
    (``PaxosManager.tick_program``).  A mesh tick is two dispatches behind
    one callable; the kernels are in the first, the shard_map tick."""
    from gigapaxos_tpu.ops import tick as tk

    inbox = tk.TickInbox(
        np.zeros((m.R, m.P, m.G_total), np.int32),
        np.zeros((m.R, m.P, m.G_total), bool), np.ones(m.R, bool))
    check(m._use_compact, "the smoke builds compact-outbox managers only")
    if m.mesh is not None:
        from gigapaxos_tpu.parallel.shard_tick import make_shardmap_tick

        return make_shardmap_tick(m.mesh, -1, m._exec_budget), (m.state, inbox)
    return m.tick_program(inbox)


def prove_device_path(log: Log, m, what: str, on_chip: bool) -> dict:
    """Stage 5: the kernels are in the program that ran.  On the chip they
    must be Mosaic custom calls, not interpreted and not the select chain;
    in the CPU rehearsal the same calls must be traced, interpreted."""
    fn, args = tick_program(m)
    if m.mesh is None:
        check(fn._cache_size() > 0, f"{what}: the manager never dispatched "
              f"{getattr(fn, '__name__', fn)} - tick_program() is stale")
    k = kernels_in(fn, *args)
    log(f"{what}: {k}; kernel builds by tile lanes so far: "
        f"{kernel_builds()}")
    check(k["pallas_calls"] > 0, f"{what}: no pallas_call traced - the "
          f"program runs the XLA select chain")
    if on_chip:
        check(k["interpreted"] == 0, f"{what}: kernels are interpreted")
        check(k["mosaic_gather"] > 0 and k["mosaic_match"] > 0,
              f"{what}: lowered text lacks the Mosaic custom calls")
        check(k["mosaic_gather"] + k["mosaic_match"] == k["pallas_calls"]
              == k["tpu_custom_calls"],
              f"{what}: traced and lowered kernel counts disagree")
    else:
        check(k["interpreted"] == k["pallas_calls"],
              f"{what}: rehearsal kernels are not all interpreted")
    return k


def check_mesh_spread(log: Log, m, n_devices: int) -> None:
    """Four chips: the state is spread over the devices, not on the first."""
    import jax

    ex = m.state.exec_slot
    shards = ex.addressable_shards
    devs = {s.device.id for s in shards}
    log(f"mesh: exec_slot sharding {ex.sharding}; {len(shards)} shards of "
        f"shape {shards[0].data.shape} on devices {sorted(devs)}")
    check(len(devs) == n_devices and all(
        s.data.shape == (m.R, m.G // n_devices) for s in shards),
        f"state is not spread over {n_devices} devices")
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in
            jax.devices()[:n_devices]]
    share = state_bytes(m.state) // n_devices
    log(f"mesh: bytes_in_use per device {used}; one plane's state is "
        f"{share:,} B per device by shape")
    if all(u is not None for u in used):
        check(min(used) >= 0.9 * share,
              f"a device holds less than its share of the state: {used}")


# ---- the other programs a manager can dispatch, one tick each at the same G
def _variant_config(groups: int, **paxos):
    cfg = make_config(groups)
    cfg.nodes.actives.clear()
    cfg.nodes.reconfigurators.clear()
    for k, v in paxos.items():
        setattr(cfg.paxos, k, v)
    return cfg


def _drive(m, proposals, max_ticks: int = 12) -> dict:
    """Propose ``(name, payload)`` pairs, tick until all answered."""
    got: dict = {}
    for i, (name, payload) in enumerate(proposals):
        m.propose(name, payload, lambda rid, resp, i=i: got.setdefault(i, resp))
    for _ in range(max_ticks):
        m.tick()
        if len(got) == len(proposals):
            break
    m.drain_pipeline()
    return got


def run_host_variant(what: str, groups: int, on_chip: bool, log: Log,
                     **paxos) -> str:
    """A standalone manager of one variant: create a few groups (half of
    them register-mode where there is a register plane), one PUT and one GET
    each, checked against the reference on all three replicas."""
    from gigapaxos_tpu.models.replicable import KVApp
    from gigapaxos_tpu.paxos.manager import PaxosManager

    t0 = time.monotonic()
    m = PaxosManager(_variant_config(groups, **paxos), 3,
                     [KVApp() for _ in range(3)])
    ref = RefKV()
    names = [f"{what}{i}" for i in range(4)]
    for i, name in enumerate(names):
        check(m.create_paxos_instance(
            name, [0, 1, 2], register=bool(m.G_reg) and i % 2 == 1),
            f"{what}: create {name}")
    t1 = time.monotonic()
    puts = [(n, f"PUT k {what}-{i}".encode()) for i, n in enumerate(names)]
    got = _drive(m, puts)
    first = time.monotonic() - t1
    want = {i: ref.apply(n, p) for i, (n, p) in enumerate(puts)}
    check(got == want, f"{what}: PUT answers {got}, reference {want}")
    gets = [(n, b"GET k") for n in names]
    got = _drive(m, gets)
    want = {i: ref.apply(n, p) for i, (n, p) in enumerate(gets)}
    check(got == want, f"{what}: GET answers {got}, reference {want}")
    for r, app in enumerate(m.apps):
        for n in names:
            check(app.db.get(n) == ref.tables[n],
                  f"{what}: replica {r} differs on {n}")
    extra = ""
    if m._lease is not None:
        # a lease-local read: rid 0 and a synchronous callback
        reads: list = []
        rid = m.read(names[0], b"GET k", lambda rid, resp: reads.append(resp))
        if rid != 0:
            m.run_ticks(4)
            m.drain_pipeline()
        check(reads == [ref.apply(names[0], b"GET k")],
              f"{what}: lease read answered {reads}")
        extra = (f"; read served {'under the lease' if rid == 0 else 'by consensus'}"
                 f", lease {m.lease_info(names[0])}")
    if m._health is not None:
        snap = m.health_snapshot()
        check(snap is not None and snap["allocated"] == len(names),
              f"{what}: health snapshot {snap}")
        extra = f"; health: allocated={snap['allocated']} wedged={snap['wedged']}"
    k = prove_device_path(log, m, f"{what} tick", on_chip)
    return (f"compiled and ran at G={m.G}"
            + (f"+{m.G_reg} register rows" if m.G_reg else "")
            + f": build {t1 - t0:.1f}s, first ticks (compile) {first:.1f}s, "
            f"{k['pallas_calls']} kernel calls{extra}")


def run_device_kv(groups: int, on_chip: bool, log: Log) -> str:
    """``models/device_kv.fused_compact``: reported, not gated."""
    import struct

    from gigapaxos_tpu.models.device_kv import OP_GET, OP_PUT
    from gigapaxos_tpu.paxos.manager import PaxosManager

    t0 = time.monotonic()
    m = PaxosManager(_variant_config(groups, device_app=True), 3, [None] * 3)
    for i in range(4):
        check(m.create_paxos_instance(f"dkv{i}", [0, 1, 2]), "dkv create")
    rows = np.array([m.rows.row(f"dkv{i}") for i in range(4)])
    got: dict = {}
    for tag, op, vals in (("put", OP_PUT, [100 + i for i in range(4)]),
                          ("get", OP_GET, [0] * 4)):
        m.propose_bulk_kv(rows, [op] * 4, [7] * 4, vals, callbacks=[
            (lambda rid, resp, k=(tag, i): got.setdefault(k, resp))
            for i in range(4)])
        for _ in range(12):
            m.tick()
            if sum(k[0] == tag for k in got) == 4:
                break
        m.drain_pipeline()
    want = {(tag, i): struct.pack("<i", 100 + i)
            for tag in ("put", "get") for i in range(4)}
    check(got == want, f"device_kv answered {got}")
    k = prove_device_path(log, m, "device_kv tick", on_chip)
    return (f"compiled and ran at G={m.G} in {time.monotonic() - t0:.1f}s, "
            f"{k['pallas_calls']} kernel calls")


def other_programs(log: Log, groups: int, on_chip: bool) -> None:
    """Stage 6.  The four managers compile side by side (XLA releases the
    interpreter lock); their ticks then share the one chip."""
    gated = {
        "mixed": dict(register_groups=groups),
        "lease": dict(read_leases=True),
        "health": dict(group_health=True),
    }
    with ThreadPoolExecutor(max_workers=4) as pool:
        futs = {what: pool.submit(run_host_variant, what, groups, on_chip,
                                  log, **kw) for what, kw in gated.items()}
        futs["device_kv"] = pool.submit(run_device_kv, groups, on_chip, log)
        failed = []
        for what, fut in futs.items():
            try:
                log(f"program {what}: {fut.result()}")
            except Exception as e:
                log(f"program {what}: FAILED {type(e).__name__}: {e}")
                if what in gated:
                    failed.append(what)
                else:
                    log("program device_kv is reported, not gated "
                        "(ROADMAP A4)")
    check(not failed, f"programs that must pass failed: {failed}")


def restart(log: Log, cfg, run_dir: str, ref: RefKV, client_names,
            wave_names, ready_timeout_s: float, rpc_timeout_s: float):
    """Stage 7: a second cluster on the same WAL directories (the recover
    branch, i.e. the replay scans), serving the acknowledged values."""
    from gigapaxos_tpu.client import ReconfigurableAppClient

    cluster = build_cluster(log, cfg, run_dir, ready_timeout_s)
    try:
        m = cluster.manager
        log(f"replayed: data plane tick {m.tick_num}, "
            f"{getattr(m, '_replay_windows', 0)} scan windows "
            f"({getattr(m, '_replay_sparse_windows', 0)} sparse, "
            f"{getattr(m, '_replay_overflows', 0)} overflowed to the "
            f"record-at-a-time body); {len(m.rows):,} rows resident")
        check(getattr(m, "_replay_windows", 0) > 0 or m.mesh is not None,
              "restart did not run a replay scan")
        client = ReconfigurableAppClient(cfg.nodes)
        try:
            read_back(log, cluster, client, ref, client_names, rpc_timeout_s)
        finally:
            client.close()
        for r, app in enumerate(m.apps):
            bad = [n for n in wave_names if app.db.get(n) != ref.tables[n]]
            check(not bad, f"after restart replica {r} differs from the "
                  f"reference on {len(bad)} wave groups, e.g. {bad[:3]}")
        log(f"after restart: {len(wave_names):,} wave groups equal to the "
            f"reference on 3 replicas")
    except BaseException:
        cluster.close()
        raise
    return cluster


def digest(ref: RefKV) -> str:
    """One hash over everything acknowledged, to compare two runs by eye
    (one chip against four)."""
    blob = json.dumps(ref.tables, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


# ---------------------------------------------------------------------- run
def run(groups: int = FULL_GROUPS, wave: int = FULL_WAVE, seed: int = 0,
        mesh_devices: int = 0, on_chip: bool = True, log_path=LOG_PATH,
        ready_timeout_s: float = 600.0, rpc_timeout_s: float = 240.0,
        backend_init_s=None) -> dict:
    """Every stage in order; raises on the first failure.  Returns the
    device as JAX reports it.  ``on_chip=False`` is the CPU rehearsal: the
    same stages, the kernels interpreted (the caller sets GPTPU_PALLAS=1 and
    GPTPU_PALLAS_INTERPRET=1), and no Mosaic text to find."""
    from gigapaxos_tpu import compile_cache
    from gigapaxos_tpu.obs import compiles

    log = Log(log_path)
    if backend_init_s is not None:
        log.times["backend init"] = round(backend_init_s, 2)
    run_dir = tempfile.mkdtemp(prefix="chip_smoke_")  # WALs: too big to keep
    cluster = None
    try:
        cache_dir = compile_cache.configure()
        with log.stage("environment"):
            device = report_environment(log, cache_dir)
        compiles.install()  # before the first program: it counts from here
        ref = RefKV()
        with log.stage("build cluster"):
            cluster = build_cluster(log, make_config(groups, mesh_devices),
                                    run_dir, ready_timeout_s)
            log(f"tick programs: {cache_since((0, 0))}")
            if mesh_devices:
                check_mesh_spread(log, cluster.manager, mesh_devices)
        with log.stage("populate"):
            bg_names = populate(log, cluster, groups - SPARE_ROWS)
        tick_period(log, cluster, "populated and unloaded")
        with log.stage("serve"):
            client_names = serve(log, cluster, cluster.cfg, ref, seed,
                                 rpc_timeout_s)
        with log.stage("wide wave"):
            wave_names = wide_wave(log, cluster, ref, bg_names, wave, seed,
                                   rpc_timeout_s)
        with log.stage("device path"):
            prove_device_path(log, cluster.manager, "served tick", on_chip)
            report_memory(log, "after serving", state_bytes(
                cluster.manager.state, cluster.rc_manager.state))
        with log.stage("shutdown"):
            drained = cluster.shutdown(drain_timeout_s=120.0)
            cluster = None
            check(drained, "shutdown did not drain within 120s")
        with log.stage("other programs"):
            snap = compiles.cache_lookups()
            other_programs(log, groups, on_chip)
            log(f"other programs: {cache_since(snap)}")
        with log.stage("restart"):
            snap = compiles.cache_lookups()
            cluster = restart(log, make_config(groups, mesh_devices),
                              run_dir, ref, client_names, wave_names,
                              ready_timeout_s, rpc_timeout_s)
            log(f"restart leg programs: {cache_since(snap)}")
            check(cluster.shutdown(drain_timeout_s=120.0),
                  "second shutdown did not drain")
            cluster = None
        log(f"acknowledged state digest {digest(ref)} (seed {seed}, "
            f"{groups} rows, wave {wave})")
        log("stage wall times, information, not a benchmark: "
            + json.dumps(log.times))
        return device
    finally:
        if cluster is not None:
            cluster.close()
        shutil.rmtree(run_dir, ignore_errors=True)
        log.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh-devices", type=int, default=0,
                    help="shard the group axis over this many chips "
                         "(cfg.paxos.mesh_devices, one replica shard)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the keys and values written")
    args = ap.parse_args(argv)

    for var in ("GPTPU_PALLAS_INTERPRET", "GPTPU_NO_PALLAS"):
        if os.environ.get(var):
            print(f"chip_smoke: {var} is set; refusing to run, the point is "
                  f"the kernels on the chip", file=sys.stderr)
            return 2
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    import jax

    t0 = time.monotonic()
    backend = jax.default_backend()  # initialises the backend
    if backend != "tpu":
        print(f"chip_smoke: JAX's default backend is {backend!r}, not a "
              f"TPU; there is no CPU mode (tests/test_chip_smoke.py "
              f"rehearses the stages)", file=sys.stderr)
        return 2
    device = run(mesh_devices=args.mesh_devices, seed=args.seed,
                 backend_init_s=time.monotonic() - t0)
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
