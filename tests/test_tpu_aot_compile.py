"""Every tick program a manager can dispatch, compiled for a TPU v5e — here.

The sandbox has no chip, but libtpu is installed, and it will compile for a
described topology without one (``jax.experimental.topologies``).  So a
program Mosaic or XLA:TPU refuses — a kernel shape, a layout, a shard_map
body — fails this test on the CPU box instead of costing chip time to find.
What it cannot show is that the programs run and agree with the reference:
that is ``chip_smoke.py``'s job, on the chip.

Runs in a fresh process: loading libtpu is kept out of the test process, and
no jit cache traced for the CPU can stand in.  Skipped where libtpu cannot
describe a topology (an image without it).
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r'''
import sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print("NO-TOPOLOGY", type(e).__name__, e)
    sys.exit(0)

from gigapaxos_tpu.ops import tick as tk
from gigapaxos_tpu.parallel import mesh as pmesh, shard_tick as stk
from gigapaxos_tpu.paxos import state as st

R, W, P, G, Lb = 3, 4, 4, 4096, 1024
E = 2 * G
one = SingleDeviceSharding(topo.devices[0])


def shaped(tree, sharding=None):
    if sharding is None:
        sharding = jax.tree.map(lambda _: one, tree)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, sharding)


def S(shape, dt=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dt, sharding=one)


def inbox(g):
    return tk.TickInbox(S((R, P, g)), S((R, P, g), jnp.bool_),
                        S((R,), jnp.bool_))


state = shaped(jax.eval_shape(lambda: st.init_state(R, G, W)))
rstate = shaped(jax.eval_shape(lambda: st.init_state(R, G, 1)))
lease = shaped(jax.eval_shape(lambda: tk.init_lease(G, 8)))
health = shaped(jax.eval_shape(lambda: tk.init_health(G)))
narrow = shaped(jax.eval_shape(lambda: st.init_state(R, 128, W)))
K, M = 8, 8
xs = {"e": S((K, M)), "p": S((K, M)), "g": S((K, M)), "rid": S((K, M)),
      "stop": S((K, M), jnp.bool_), "alive": S((K, R), jnp.bool_)}
mesh = pmesh.make_mesh(topo.devices, replica_shards=1)
m_state = shaped(jax.eval_shape(lambda: st.init_state(R, G, W)),
                 pmesh.state_shardings(mesh))
m_inbox = shaped(inbox(G), pmesh.inbox_shardings(mesh))
m_outbox = shaped(
    jax.eval_shape(lambda: tk.paxos_tick_impl(
        st.init_state(R, G, W), tk.make_inbox(R, G, P))[1]),
    tk.TickOutbox(**{f: jax.sharding.NamedSharding(mesh, spec)
                     for f, spec in stk._OUTBOX_SPECS.items()}))

params = tk.TickParams(exec_budget=E, lag_budget=Lb, compact=True,
                       lease_horizon=64)


def served(g, **planes):
    return (tk.TickPlanes(state, **planes), inbox(g), params)


head = tk.CompactLayout(R, G, E, Lb, P).total_head
programs = [
    # (name, jitted fn, args, Pallas calls it must carry, heads it returns:
    # one per compacted plane beside the flat buffer, none from replay)
    ("log-plane compact tick", tk.paxos_tick_planes, served(G), 26, 1),
    ("the kept name the harness traces", tk.paxos_tick_compact,
     (state, inbox(G), -1, E, Lb), 26, 1),
    ("mixed log+register tick (W=4 and W=1)", tk.paxos_tick_planes,
     served(2 * G, rstate=rstate), 52, 2),
    ("lease tick", tk.paxos_tick_planes, served(G, lease=lease), 26, 1),
    ("health tick", tk.paxos_tick_planes, served(G, health=health), 26, 1),
    ("replay scan, sparse window of 128 lanes", tk.replay_scan_ticks,
     (tk.TickPlanes(narrow), xs, P, params, E), 26, 0),
    ("shard_map tick over four chips", stk.make_shardmap_tick(mesh, -1, E),
     (m_state, m_inbox), 26, 0),
    ("compaction of a sharded outbox over four chips",
     stk.make_mesh_compact(E, Lb), (m_outbox,), 0, 1),
    # the inbox of a tick that placed few requests, from a list of them
    ("short inbox scatter", tk.scatter_inbox, (S((5, 4096)), R, P, G), 0, 0),
    ("short inbox scatter into the four chips' layout",
     stk.make_mesh_scatter_inbox(mesh),
     (jax.ShapeDtypeStruct((5, 4096), jnp.int32,
                           sharding=jax.sharding.NamedSharding(
                               mesh, jax.sharding.PartitionSpec())),
      R, P, G), 0, 0),
]
for name, fn, args, want, heads in programs:
    low = fn.lower(*args)
    got = low.as_text().count("@tpu_custom_call")
    assert got == want, f"{name}: {got} Mosaic custom calls, expected {want}"
    got = [o.shape for o in jax.tree.leaves(low.out_info)].count((head,))
    assert got == heads, f"{name}: {got} outputs of a head's length"
    hlo = low.compile().as_text()
    # the named scopes survive XLA:TPU's fusion: the compaction's fusions
    # still say where they came from, which is what a trace reader splits
    # the device time by (obs/phase.py TICK_SCOPES)
    scopes = ["compact_outbox/scatter"] if heads else []
    if want and ("compact" in name or "health" in name or "lease" in name):
        scopes += ["compact_outbox/scatter", "/prepare/", "/accept/"]
    for scope in scopes:
        assert scope in hlo, f"{name}: no op carries {scope!r}"
    print("COMPILED", name, flush=True)
print("ALL-COMPILED")
'''


def test_tick_programs_compile_for_v5e_without_a_chip():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", GPTPU_PALLAS="1",
               # compile-only: no device to contend for, so another process
               # holding libtpu's lockfile is no reason to fail
               ALLOW_MULTIPLE_LIBTPU_LOAD="1",
               PYTHONPATH=ROOT + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("GPTPU_PALLAS_INTERPRET", None)
    env.pop("GPTPU_NO_PALLAS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                         text=True, capture_output=True, timeout=900)
    if "NO-TOPOLOGY" in out.stdout:
        pytest.skip("libtpu cannot describe a v5e topology here: "
                    + out.stdout.strip()[-300:])
    assert out.returncode == 0 and "ALL-COMPILED" in out.stdout, (
        out.stdout[-2000:] + out.stderr[-4000:])
