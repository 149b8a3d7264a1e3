"""Where a request's time goes and what stalls a tick, from inside the
program (ISSUE 26): the ``request_stage_seconds`` histograms of the Mode A
manager, the JAX compile listener, the named scopes of the tick programs and
the phase clock's trace annotations.

The default registry is process-wide and other test files share the process,
so every assertion here is on the difference of two snapshots, on a plane
label of the test's own where a manager is built directly.
"""

import re
import threading

import jax
import jax.numpy as jnp
import pytest

from gigapaxos_tpu.client import ReconfigurableAppClient
from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.node import InProcessCluster
from gigapaxos_tpu.obs import compiles
from gigapaxos_tpu.obs.metrics import Registry, registry
from gigapaxos_tpu.obs.phase import (DRIVER_PHASES, PHASE_RUNS, TICK_SCOPES,
                                     PhaseClock, annotation_name)
from gigapaxos_tpu.ops import tick as tk
from gigapaxos_tpu.paxos import state as st
from gigapaxos_tpu.paxos.manager import PaxosManager


def _hist(snap: dict, family: str, **labels) -> tuple:
    """(count, sum) over the family's series that carry ``labels``."""
    count, total = 0, 0.0
    for key, val in snap.items():
        name, _, rest = key.partition("{")
        have = dict(kv.split("=", 1) for kv in rest.rstrip("}").split(",")
                    if "=" in kv)
        if name == family and all(have.get(k) == v
                                  for k, v in labels.items()):
            count, total = count + val["count"], total + val["sum"]
    return count, total


def _delta(snap0: dict, snap1: dict, family: str, **labels) -> tuple:
    a, b = _hist(snap0, family, **labels), _hist(snap1, family, **labels)
    return b[0] - a[0], b[1] - a[1]


# ------------------------------------------------------------ request stages
@pytest.fixture(scope="module")
def cluster():
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 16
    for i in range(3):
        cfg.nodes.actives[f"AR{i}"] = ("127.0.0.1", 0)
        cfg.nodes.reconfigurators[f"RC{i}"] = ("127.0.0.1", 0)
    cl = InProcessCluster(cfg, KVApp)
    client = ReconfigurableAppClient(cfg.nodes)
    try:
        assert client.create("staged", timeout=120)["ok"]
        yield cl, client
    finally:
        client.close()
        cl.close()


def test_every_acknowledged_put_adds_one_queue_and_one_commit_sample(cluster):
    _, client = cluster
    reg = registry()
    n = 6
    snap0 = reg.snapshot()
    for i in range(n):
        assert client.request("staged", f"PUT k{i} v{i}".encode(),
                              timeout=60) == b"OK"
    snap1 = reg.snapshot()
    queue = _delta(snap0, snap1, "request_stage_seconds",
                   plane="ar", stage="queue")
    commit = _delta(snap0, snap1, "request_stage_seconds",
                    plane="ar", stage="commit")
    assert queue[0] == n and commit[0] == n
    assert queue[1] >= 0 and commit[1] > 0
    # the control plane carried none of them
    assert _delta(snap0, snap1, "request_stage_seconds",
                  plane="rc", stage="commit")[0] == 0


def test_queue_plus_commit_lies_inside_the_ars_commit_latency(cluster):
    """The AR times arrival -> release; staged -> placed -> response held
    lies inside it, request by request, so also in the sums."""
    _, client = cluster
    reg = registry()
    snap0 = reg.snapshot()
    for i in range(4):
        assert client.request("staged", f"PUT q{i} w".encode(),
                              timeout=60) == b"OK"
    snap1 = reg.snapshot()
    ar = _delta(snap0, snap1, "commit_latency_seconds")  # over its nodes
    queue = _delta(snap0, snap1, "request_stage_seconds",
                   plane="ar", stage="queue")
    commit = _delta(snap0, snap1, "request_stage_seconds",
                    plane="ar", stage="commit")
    assert ar[0] == queue[0] == commit[0] == 4
    # each histogram's sum is rounded to a microsecond in the snapshot
    assert queue[1] + commit[1] <= ar[1] + 3e-6


def _tick_until(m: PaxosManager, done: threading.Event, limit: int = 64):
    for _ in range(limit):
        m.tick()
        if done.is_set():
            break
    m.drain_pipeline()


def test_a_refused_or_expired_request_adds_no_sample():
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    plane = "t_stage_refused"
    m = PaxosManager(cfg, 3, [KVApp() for _ in range(3)], spill_ns=plane)
    m.create_paxos_instance("a", [0, 1, 2])
    reg = registry()

    def stages(snap0, snap1):
        return [_delta(snap0, snap1, "request_stage_seconds", plane=plane,
                       stage=s)[0] for s in ("queue", "commit")]

    # an unknown name is refused at propose; a request whose deadline has
    # passed is dropped at intake; one staged for a group that is removed
    # before the tick drains it fails: none of them is a sample
    snap0 = reg.snapshot()
    assert m.propose("nobody", b"PUT k v") is None
    expired, failed = threading.Event(), threading.Event()
    seen = []
    m.propose("a", b"PUT k v", lambda rid, r: (seen.append((rid, r)),
                                               expired.set()), deadline=1)
    _tick_until(m, expired)
    m.create_paxos_instance("gone", [0, 1, 2])
    m.propose("gone", b"PUT k v", lambda rid, r: (seen.append((rid, r)),
                                                  failed.set()))
    m.remove_paxos_instance("gone")
    _tick_until(m, failed)
    assert expired.is_set() and failed.is_set()
    assert all(r is None for _, r in seen)
    assert stages(snap0, reg.snapshot()) == [0, 0]
    # ... and the acknowledged one beside them is exactly one of each
    done = threading.Event()
    m.propose("a", b"PUT k v", lambda rid, r: done.set())
    _tick_until(m, done)
    assert done.is_set()
    assert stages(snap0, reg.snapshot()) == [1, 1]


# ------------------------------------------------------------------ compiles
def test_the_compile_listener_counts_a_fresh_jit():
    compiles.install()
    reg = registry()
    snap0 = reg.snapshot()

    @jax.jit
    def never_compiled_before(x):
        return x * 26 + 2026

    never_compiled_before(jnp.arange(7)).block_until_ready()
    snap1 = reg.snapshot()
    for stage in ("trace", "lower", "backend"):
        count, seconds = _delta(snap0, snap1, "jit_compile_seconds",
                                stage=stage)
        assert count >= 1 and seconds > 0, stage
    # the same shape again compiles nothing
    never_compiled_before(jnp.arange(7)).block_until_ready()
    assert _delta(snap1, reg.snapshot(), "jit_compile_seconds")[0] == 0


def test_the_compile_listener_is_installed_once_however_many_managers():
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    for i in range(2):
        PaxosManager(cfg, 3, [KVApp() for _ in range(3)],
                     spill_ns=f"t_listener_{i}")
    compiles.install()
    reg = registry()
    hits0, misses0 = compiles.cache_lookups()
    # a listener of the test's own counts the same events: the program's
    # must have seen each once, where a second installation would see it twice
    seen = []

    def witness(event, duration_secs, **_kw):
        if event in compiles.STAGE_EVENTS:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(witness)
    try:
        snap0 = reg.snapshot()

        @jax.jit
        def compiled_once(x):
            return x - 26

        compiled_once(jnp.arange(5)).block_until_ready()
        snap1 = reg.snapshot()
    finally:
        jax.monitoring.unregister_event_duration_listener(witness)
    assert seen
    assert _delta(snap0, snap1, "jit_compile_seconds")[0] == len(seen)
    hits, misses = compiles.cache_lookups()
    assert hits >= hits0 and misses >= misses0


# -------------------------------------------------------------- named scopes
@pytest.fixture(scope="module")
def lowered():
    """Every program that opens a scope, lowered on the CPU with its
    locations: scope name -> the text its ops' metadata must show it in."""
    R, W, P, G, Lb = 3, 4, 4, 128, 16
    E = 2 * G

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def S(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt)

    state = shaped(jax.eval_shape(lambda: st.init_state(R, G, W)))
    lease = shaped(jax.eval_shape(lambda: tk.init_lease(G, 8)))
    health = shaped(jax.eval_shape(lambda: tk.init_health(G)))
    inbox = tk.TickInbox(S((R, P, G)), S((R, P, G), jnp.bool_),
                         S((R,), jnp.bool_))

    def text(fn, *args):
        return fn.lower(*args).as_text(debug_info=True)

    params = tk.TickParams(exec_budget=E, lag_budget=Lb, compact=True,
                           lease_horizon=64)

    def served(**planes):
        return text(tk.paxos_tick_planes, tk.TickPlanes(state, **planes),
                    inbox, params)

    compact = served()
    texts = {scope: compact for scope in TICK_SCOPES}
    texts["lease_fold"] = served(lease=lease)
    texts["health_fold"] = served(health=health)
    texts["sweep_frontier"] = text(tk.sweep_frontier, S((R, G)),
                                   S((R, G), jnp.bool_), S((R,), jnp.bool_))
    texts["frontier_rows"] = text(tk.frontier_rows, S((G,)), S((G,)),
                                  S((G,), jnp.bool_), S((16,)))
    return texts


@pytest.mark.parametrize("scope", TICK_SCOPES)
def test_each_tick_scope_is_in_the_lowered_programs_op_metadata(lowered,
                                                                scope):
    text = lowered[scope]
    # an op's location is "jit(<program>)/<scope>/<primitive>"
    assert f')/{scope}/' in text, scope


#: the scopes of the two programs a sharded plane's tick dispatches
#: (parallel/shard_tick.py): the tick body's, and the compaction's
MESH_SCOPES = {"mesh_paxos_tick": TICK_SCOPES[:TICK_SCOPES.index("lease_fold")],
               "mesh_compact_outbox": ("compact_outbox",)}


@pytest.fixture(scope="module")
def mesh_compiled():
    """The two mesh programs compiled for four virtual CPU devices: program
    -> optimized HLO text, whose ``op_name`` is what a device trace shows."""
    from gigapaxos_tpu.parallel import mesh as pmesh, shard_tick as stk

    R, W, P, G, Lb = 3, 4, 4, 512, 16
    mesh = pmesh.make_mesh(jax.devices()[:4], replica_shards=1)
    state = st.init_state(R, G, W, shardings=pmesh.state_shardings(mesh))
    inbox = tk.make_inbox(R, G, P)
    tick = stk.make_shardmap_tick(mesh, -1, 2 * G)
    out = jax.eval_shape(tick, state, inbox)[1]
    return {
        "mesh_paxos_tick": tick.lower(state, inbox).compile().as_text(),
        "mesh_compact_outbox": stk.make_mesh_compact(2 * G, Lb).lower(
            out).compile().as_text()}


@pytest.mark.parametrize("program,scope", [
    (program, scope) for program, scopes in MESH_SCOPES.items()
    for scope in scopes])
def test_each_mesh_program_has_its_name_and_keeps_the_tick_scopes(
        mesh_compiled, program, scope):
    """The trace readers find the mesh's programs by name
    (``jit_mesh_paxos_tick``, ``jit_mesh_compact_outbox``: not the one-device
    programs' ``jit__paxos_tick*``) and split their ops by the same scopes,
    which survive inside the ``shard_map`` body and under GSPMD."""
    text = mesh_compiled[program]
    assert text.startswith(f"HloModule jit_{program},"), text[:80]
    assert not re.match(r"HloModule jit__?paxos_tick", text)
    inside = "/shard_map" if program == "mesh_paxos_tick" else ""
    assert f'op_name="jit({program}){inside}/{scope}/' in text, scope


# --------------------------------------------------------- trace annotations
class FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: no profiler session,
    which a test under xdist could not own."""

    enabled = True
    log: list = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.log.append(("open", self.name))

    def __exit__(self, *exc):
        self.log.append(("close", self.name))


@pytest.fixture
def fake_annotation():
    FakeAnnotation.enabled, FakeAnnotation.log = True, []
    return FakeAnnotation


def _one_pipelined_tick(pc: PhaseClock) -> None:
    first, second = PHASE_RUNS["modea"]
    pc.begin()
    for phase in first:
        pc.mark(phase)
    pc.touch()
    for phase in second:
        pc.mark(phase)
    pc.end()


def test_phase_clock_opens_and_closes_one_annotation_per_marked_phase(
        fake_annotation):
    pc = PhaseClock("modea", plane="t", reg=Registry(),
                    annotation=fake_annotation)
    _one_pipelined_tick(pc)
    _one_pipelined_tick(pc)
    want = []
    for _ in range(2):
        for phase in DRIVER_PHASES["modea"]:
            name = annotation_name("modea", "t", phase)
            assert name == f"gptpu/modea/t/{phase}"
            want += [("open", name), ("close", name)]
    assert fake_annotation.log == want


def test_phase_clock_emits_nothing_while_no_profile_is_taken(fake_annotation):
    fake_annotation.enabled = False
    reg = Registry()
    pc = PhaseClock("modea", plane="t", reg=reg, annotation=fake_annotation)
    _one_pipelined_tick(pc)
    assert fake_annotation.log == []
    # the histograms are fed all the same
    assert all(h.count == 1 for h in reg.find("tick_phase_seconds"))
    # a profile that starts in the middle of a phase opens at the next one,
    # and one that stops leaves nothing open
    pc.begin()
    fake_annotation.enabled = True
    pc.mark("repair")
    fake_annotation.enabled = False
    pc.mark("intake")
    pc.mark("dispatch")
    assert fake_annotation.log == [("open", "gptpu/modea/t/intake"),
                                   ("close", "gptpu/modea/t/intake")]


def test_a_driver_without_declared_runs_emits_no_annotation(fake_annotation):
    pc = PhaseClock("modeb", plane="t", reg=Registry(),
                    annotation=fake_annotation)
    pc.begin()
    for phase in DRIVER_PHASES["modeb"]:
        pc.mark(phase)
    pc.touch()
    pc.end()
    assert fake_annotation.log == []


# ------------------------------------------- which compaction branch ran
def _counter(snap: dict, family: str, **labels) -> float:
    want = {f"{k}={v}" for k, v in labels.items()}
    return sum(val for key, val in snap.items()
               if key.partition("{")[0] == family
               and want <= set(key.partition("{")[2].rstrip("}").split(",")))


def _tier_named(tiers: tuple, count: int) -> str:
    """The narrowest width of ``tiers`` that holds ``count``, as the
    counter's ``path`` names it; ``dense`` past the last."""
    return next((f"sparse{k}" for k in tiers if count <= k), "dense")


@pytest.mark.parametrize("mesh_devices", [0, 4])
@pytest.mark.parametrize("k_blocks", [None, 2, (3, 6)],
                         ids=["None", "2", "3-6"])
def test_compact_path_counter_rises_once_per_list_per_tick(monkeypatch,
                                                           k_blocks,
                                                           mesh_devices):
    """``compact_path_ticks_total`` mirrors, from the header alone, the rule
    the device branched on: with the served ladder a test-sized plane is
    dense from its shape; with K lowered to 2 a tick is ``sparse2`` exactly
    while ``n_exec <= 2``; with the ladder lowered to (3, 6) it names the
    narrowest width that holds the tick (0 and 3 executions: ``sparse3``, 6:
    ``sparse6``, 12: ``dense``).  The same through the manager with the
    group axis sharded over four devices, where the compaction is the mesh
    tick's second dispatch: every branch, and the answers of the one-device
    run."""
    if k_blocks is not None:
        *tiers, top = k_blocks if isinstance(k_blocks, tuple) else (k_blocks,)
        monkeypatch.setattr(tk, "_SPARSE_BLOCKS", top)
        monkeypatch.setattr(tk, "_SPARSE_TIERS", tuple(tiers))
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 512 if mesh_devices else 128
    cfg.paxos.compact_outbox = True
    cfg.paxos.pipeline_ticks = False
    cfg.paxos.mesh_devices = mesh_devices
    ladder = ("-".join(map(str, k_blocks)) if isinstance(k_blocks, tuple)
              else k_blocks)
    plane = f"t_compact_path_{ladder}_{mesh_devices}"
    apps = [KVApp() for _ in range(3)]
    m = PaxosManager(cfg, 3, apps, spill_ns=plane)
    assert (m.mesh is not None) == bool(mesh_devices)
    names = [f"g{i}" for i in range(4)]
    for name in names:
        m.create_paxos_instance(name, [0, 1, 2])
    lists = (("exec", m.R * m.W * m.G, m._exec_budget),
             ("lag", m.R * m.G, m._lag_budget))
    paths = {lst: [f"sparse{k}" for k in tk.compact_tiers(n, cap)] + ["dense"]
             for lst, n, cap in lists}
    want = {(lst, path): 0 for lst in paths for path in paths[lst]}
    reg = registry()
    snap0 = reg.snapshot()
    ticks = 8
    # requests proposed before a tick, one per name: each is executed on
    # the three replicas in that tick (0, 3, 6 and 12 executions)
    proposed = {1: 4, 3: 1, 5: 2}
    for t in range(ticks):
        for name in names[:proposed.get(t, 0)]:
            m.propose(name, b"PUT k v", lambda *a: None)
        out = m.tick()
        for lst, n, cap in lists:
            count = out.n_exec if lst == "exec" else out.lag_n
            want[lst, _tier_named(tk.compact_tiers(n, cap), count)] += 1
    snap1 = reg.snapshot()
    got = {key: _counter(snap1, "compact_path_ticks_total", plane=plane,
                         list=key[0], path=key[1])
           - _counter(snap0, "compact_path_ticks_total", plane=plane,
                      list=key[0], path=key[1]) for key in want}
    assert got == want
    assert sum(v for (lst, _), v in got.items() if lst == "exec") == ticks
    assert sum(v for (lst, _), v in got.items() if lst == "lag") == ticks
    if k_blocks is None:
        assert paths["exec"] == paths["lag"] == ["dense"]
    else:  # every branch of the exec list was met
        assert all(got["exec", path] > 0 for path in paths["exec"]), got
        assert len(paths["exec"]) == (3 if isinstance(k_blocks, tuple)
                                      else 2)
    # whichever branch compacted them, the four requests were executed
    assert [a.db for a in apps] == [{name: {"k": "v"} for name in names}] * 3
    programs = {prog: _counter(snap1, "mesh_dispatches_total", plane=plane,
                               program=prog) for prog in ("tick", "compact")}
    assert programs == dict.fromkeys(programs, ticks if mesh_devices else 0)


#: every width of the served ladder at 1M groups, per list, and its bounds
_SERVED_BOUNDARIES = [
    (lst, n, cap, k, d)
    for lst, n, cap in (("exec", 3 * 4 * (1 << 20), 2 << 20),
                        ("lag", 3 * (1 << 20), 1024))
    for k in tk.compact_tiers(n, cap) for d in (-1, 0, 1)]


@pytest.mark.parametrize("lst,n,cap,k,d", _SERVED_BOUNDARIES, ids=[
    f"{lst}-{k}{d:+d}" for lst, _, _, k, d in _SERVED_BOUNDARIES])
def test_compact_path_names_the_tier_at_every_boundary(lst, n, cap, k, d):
    """The host's mirror of the device's switch at the benchmark's widths:
    one under and at a width name it, one over names the next (or dense)."""
    tiers = tk.compact_tiers(n, cap)
    assert tiers == ((128, 1024, 8192) if lst == "exec" else (128, 1024))
    up = tiers.index(k) + (d > 0)
    assert tk.compact_path(n, cap, k + d) == (
        f"sparse{tiers[up]}" if up < len(tiers) else "dense")
    assert tk.compact_path(n, cap, k + d) == _tier_named(tiers, k + d)
