"""Device mesh + sharding specs for the consensus data plane.

The reference's two scaling axes (SURVEY §2.2) map to two mesh axes:

* ``groups``  — millions of independent RSMs, embarrassingly parallel
  (the MultiArrayMap instance table, PaxosManager.java:132): pure data
  parallelism, no cross-shard communication;
* ``replica`` — the 3-5-way replication dimension whose quorum traffic
  (ACCEPT fan-out / ACCEPT_REPLY fan-in over NIO,
  nio/NIOTransport.java:65-114) becomes XLA collectives over ICI: every
  reduction over the leading replica axis of the tick turns into a psum /
  all-reduce when that axis is sharded.

We write global-view code and annotate shardings (GSPMD); XLA inserts the
collectives.  ``alive`` stays replicated (tiny, indexed by global node id
inside the tick); the member mask shards like every other ``[R, G]`` array.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.tick import TickInbox
from ..paxos.state import PaxosState

REPLICA_AXIS = "replica"
GROUPS_AXIS = "groups"

# PartitionSpec per state field.  [R, G] -> (replica, groups);
# [R, W, G] -> (replica, None, groups); [G] -> (groups,).
_RG = P(REPLICA_AXIS, GROUPS_AXIS)
_RWG = P(REPLICA_AXIS, None, GROUPS_AXIS)
_STATE_SPECS = dict(
    exec_slot=_RG,
    bal_num=_RG,
    bal_coord=_RG,
    status=_RG,
    acc_bnum=_RWG,
    acc_bcoord=_RWG,
    acc_req=_RWG,
    acc_slot=_RWG,
    acc_stop=_RWG,
    dec_req=_RWG,
    dec_slot=_RWG,
    dec_valid=_RWG,
    dec_stop=_RWG,
    coord_active=_RG,
    coord_preparing=_RG,
    coord_fast=_RG,
    coord_bnum=_RG,
    next_slot=_RG,
    prop_req=_RWG,
    prop_slot=_RWG,
    prop_valid=_RWG,
    prop_stop=_RWG,
    member=_RG,
    n_members=P(GROUPS_AXIS),
    epoch=P(GROUPS_AXIS),
)

_INBOX_SPECS = dict(
    req=_RWG,  # [R, P, G]
    stop=_RWG,
    alive=P(None),  # replicated: indexed by global node id inside the tick
)


def make_mesh(
    devices: Optional[Sequence] = None,
    replica_shards: int = 1,
    groups_shards: Optional[int] = None,
) -> Mesh:
    """Build a (replica, groups) mesh over the given (or all) devices.

    ``replica_shards`` must divide both the device count and the replica-slot
    dimension R of the state it will run.  The remaining devices form the
    groups axis (pure data parallel).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % replica_shards:
        raise ValueError(f"{replica_shards} replica shards over {n} devices")
    if groups_shards is None:
        groups_shards = n // replica_shards
    if replica_shards * groups_shards != n:
        raise ValueError("replica_shards * groups_shards != device count")
    arr = np.array(devices).reshape(replica_shards, groups_shards)
    return Mesh(arr, (REPLICA_AXIS, GROUPS_AXIS))


def state_shardings(mesh: Mesh) -> PaxosState:
    return PaxosState(
        **{f: NamedSharding(mesh, _STATE_SPECS[f]) for f in PaxosState._fields}
    )


def inbox_shardings(mesh: Mesh) -> TickInbox:
    return TickInbox(
        **{f: NamedSharding(mesh, _INBOX_SPECS[f]) for f in TickInbox._fields}
    )


def shard_state(state: PaxosState, mesh: Mesh) -> PaxosState:
    sh = state_shardings(mesh)
    return PaxosState(
        *(jax.device_put(a, s) for a, s in zip(state, sh))
    )


def shard_inbox(inbox: TickInbox, mesh: Mesh) -> TickInbox:
    sh = inbox_shardings(mesh)
    return TickInbox(*(jax.device_put(a, s) for a, s in zip(inbox, sh)))


def sharded_tick(mesh: Mesh):
    """Jit the tick with explicit input/output shardings for `mesh`.

    Under GSPMD the replica-axis reductions in the tick body (promise
    matching, vote tally psum, decision sync) compile to cross-replica
    collectives riding ICI; the groups axis never communicates.
    """
    from ..ops.pallas_gather import global_view_trace
    from ..ops.tick import paxos_tick_impl

    def tick(state, inbox):
        # GSPMD partitions this program: a pallas call here would have its
        # operands replicated across the mesh, so this one formulation
        # keeps the select chain (the shard_map tick runs the kernels)
        with global_view_trace():
            return paxos_tick_impl(state, inbox)

    st_sh = state_shardings(mesh)
    ib_sh = inbox_shardings(mesh)
    return jax.jit(
        tick,
        in_shardings=(st_sh, ib_sh),
        donate_argnums=(0,),
    )
