"""shard_map tick: the per-shard-local formulation of the fused tick.

``parallel/mesh.sharded_tick`` writes global-view code and lets GSPMD
partition it.  That is correct but slow in exactly the way that matters at
the BASELINE design point: inside a GSPMD program the Pallas ring gather has
no sharding rule, so that formulation must disable it
(``pallas_gather.global_view_trace``) and falls back to the W²-broadcast XLA
select chain — a multi-chip deployment built on it would run the
unoptimized path.

This module instead wraps the UNCHANGED tick body in ``jax.shard_map`` over
the (replica, groups) mesh:

* Each shard sees a concrete local ``[R_local(, W), G_local]`` block, so the
  Pallas kernels run per-shard, as in a single-device program.
* Cross-replica exchange is explicit: the body ``all_gather``s the
  replica-led state/inbox fields over the ``replica`` axis (one tiled ICI
  collective per field — the ACCEPT fan-out / ACCEPT_REPLY fan-in), runs the
  tick on the full-R local-G block, and slices its own replica rows back
  out.  Because the math inside the body is the verbatim single-device
  ``paxos_tick_impl`` over gathered operands, results are bit-identical to
  the unsharded tick by construction — the quorum tallies, lexicographic
  ballot maxes, and promise cross-products never get re-associated by a
  partitioner.
* The groups axis never communicates, except the exec-budget global ranking,
  which exchanges a tiny [W, R] count block (see ``group_axis`` in
  ``paxos_tick_impl``).
* With ``replica_shards == 1`` (the v5e-4 deployment shape: 4 chips on the
  groups axis) the gathers degenerate to no-ops and the tick program is pure
  data-parallel with zero collectives in the hot phases.

A tick of a sharded plane is TWO dispatches (three with the placement fold;
the manager counts them in ``mesh_dispatches_total{plane,program}``), and the
jitted functions carry the names a trace finds them by:

``jit_mesh_paxos_tick``
    the ``shard_map`` above.  Its ops keep the tick body's
    ``jax.named_scope`` names (``obs/phase.py`` TICK_SCOPES) behind a
    ``shard_map`` component: ``jit(mesh_paxos_tick)/shard_map/accept/...``.
``jit_mesh_compact_outbox``
    outbox compaction, OUTSIDE the shard_map as a global-view GSPMD program
    over the sharded outbox: the compact prefix-scatter ranks executions
    across ALL groups, and keeping it global means ``CompactLayout`` /
    ``unpack_compact`` and the whole host loop are byte-compatible with the
    single-device path.  It is ``tk._compact_columns``, shared with the
    single-device programs: both its branches partition to the one-device
    buffer (tests/test_compact_sparse.py, on the virtual CPU mesh; through
    the manager in tests/test_obs_request_stages.py).  Like the one-device
    tick it returns a ``tk.CompactPack``: the flat buffer and the short
    head the host pulls in its place while the tick decided no more than
    the head holds (1.7 MB against 46 MB at 1M groups).  The second output
    is in the same jit and leaves the partitioner's assignment alone: both
    outputs equal the one-device program's word for word
    (tests/test_compact_sparse.py, tests/test_outbox_head.py), unlike the
    demand operand below.
``jit_mesh_demand_fold``
    the placement plane's demand EWMA, only with ``cfg.placement.enabled``.

The one-device program's name (``jit__paxos_tick_planes_impl``, the one
served entry ``ops.tick.paxos_tick_planes``; readers match
``^jit__?paxos_tick``) matches none of these, so a metric that reads it
reads nothing on a mesh, and the other way round.

What the two-dispatch structure costs was measured on a v5e-4 host at 1M
groups (PERF.md sections 5 and 6, ``probe-1m-mesh4-open1k``): the tick
program is the cheap half, and the compaction is most of the device time of
a tick, because the partitioner all-gathers its full-width ``[R, W, G]``
operands onto every chip before the rank (ROADMAP A8).  The host paid more
than the device while the ``dispatch`` phase committed a numpy ``[R, P, G]``
inbox to four devices' layout every tick; since PR 37 a tick that placed
few requests hands over a list of them and ``jit__scatter_inbox_impl``
(:func:`make_mesh_scatter_inbox`, a third small dispatch) makes the inbox
in that layout on the devices; the dense commit is left to ticks that
placed in bulk.  The tick's outputs cross the dispatch
boundary as ordinary committed sharded arrays and stay device-resident.

Why two programs and not one: the split dates from a jax release on which
consuming unchecked shard_map outputs downstream in the same jit returned
wrong values.  Under jax 0.9.0 a fused tick + compaction gave the identical
buffer on a 4-device virtual CPU mesh, for one and two replica shards; the
structure stays until ROADMAP C1 folds the entry points, and fusing would
not remove the all-gathers.  The demand fold is still a program of its own
for a fault that jax 0.9.0 has not been shown free of (see
``make_shardmap_tick_compact``).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import tick as tk
from ..ops.pallas_gather import check_lanes
from ..ops.tick import TickInbox, TickOutbox
from ..paxos.state import PaxosState
from .mesh import (GROUPS_AXIS, REPLICA_AXIS, _INBOX_SPECS, _STATE_SPECS,
                   inbox_shardings, state_shardings)

# state fields with a leading replica axis: gathered across replica shards
# on entry to the body, sliced back to local rows on exit.
_REPLICA_LED = tuple(
    f for f, spec in _STATE_SPECS.items()
    if len(spec) and spec[0] == REPLICA_AXIS
)

#: the programs a sharded plane's tick may enqueue, as the manager counts them
#: (``mesh_dispatches_total{program=}``) and as the trace names them:
#: ``jit_mesh_paxos_tick``, ``jit_mesh_compact_outbox``, ``jit_mesh_demand_fold``
MESH_PROGRAMS = ("tick", "compact", "fold")

_RWG = P(REPLICA_AXIS, None, GROUPS_AXIS)
_RG = P(REPLICA_AXIS, GROUPS_AXIS)
_OUTBOX_SPECS = dict(
    exec_req=_RWG,
    exec_stop=_RWG,
    exec_base=_RG,
    exec_count=_RG,
    intake_taken=_RWG,
    # [G] fields are computed from replica-gathered operands, hence
    # deterministically identical on every replica shard: replicated.
    coord_id=P(GROUPS_AXIS),
    decided_now=P(GROUPS_AXIS),
    lag=_RG,
    # laggard-repair control summary: per (laggard replica, group), computed
    # from the replica-gathered exec watermarks inside the body and sliced
    # back to local rows like the other replica-led fields.
    donor=_RG,
    donor_exec=_RG,
    donor_status=_RG,
)


def validate_mesh_for(mesh: Mesh, R: int, G: int) -> None:
    rs = mesh.shape[REPLICA_AXIS]
    gs = mesh.shape[GROUPS_AXIS]
    if R % rs:
        raise ValueError(f"replica dim {R} not divisible by {rs} shards")
    if G % gs:
        raise ValueError(f"group dim {G} not divisible by {gs} shards")
    check_lanes(G // gs, f"paxos.max_groups / {gs} group shards")


def shard_tick_body(mesh: Mesh, own_row: int = -1, exec_budget: int = 0):
    """The shard_map-wrapped tick: (state, inbox) -> (state, TickOutbox).

    Not jitted — compose it (e.g. with pack/compact stages) and jit the
    whole program; see the ``make_shardmap_tick*`` builders below.
    """
    rs = mesh.shape[REPLICA_AXIS]
    gs = mesh.shape[GROUPS_AXIS]
    group_axis = GROUPS_AXIS if gs > 1 else None

    def mesh_paxos_tick(state, inbox):
        if rs > 1:
            def ag(x):
                return jax.lax.all_gather(x, REPLICA_AXIS, axis=0, tiled=True)

            state = state._replace(
                **{f: ag(getattr(state, f)) for f in _REPLICA_LED}
            )
            inbox = inbox._replace(req=ag(inbox.req), stop=ag(inbox.stop))
        new, out = tk.paxos_tick_impl(
            state, inbox, own_row, exec_budget, group_axis=group_axis
        )
        if rs > 1:
            ri = jax.lax.axis_index(REPLICA_AXIS)
            rloc = new.exec_slot.shape[0] // rs

            def sl(x):
                return jax.lax.dynamic_slice_in_dim(x, ri * rloc, rloc, axis=0)

            new = new._replace(**{f: sl(getattr(new, f)) for f in _REPLICA_LED})
            out = out._replace(
                exec_req=sl(out.exec_req),
                exec_stop=sl(out.exec_stop),
                exec_base=sl(out.exec_base),
                exec_count=sl(out.exec_count),
                intake_taken=sl(out.intake_taken),
                lag=sl(out.lag),
                donor=sl(out.donor),
                donor_exec=sl(out.donor_exec),
                donor_status=sl(out.donor_status),
            )
        return new, out

    return jax.shard_map(
        mesh_paxos_tick,
        mesh=mesh,
        in_specs=(PaxosState(**_STATE_SPECS), TickInbox(**_INBOX_SPECS)),
        out_specs=(PaxosState(**_STATE_SPECS), TickOutbox(**_OUTBOX_SPECS)),
        # the body mixes collectives with device-varying slicing (and pallas
        # calls, which have no replication rule); skip the static check.
        check_vma=False,
    )


def make_shardmap_tick(mesh: Mesh, own_row: int = -1, exec_budget: int = 0):
    """Jitted shard_map tick returning the full TickOutbox (test/debug)."""
    body = shard_tick_body(mesh, own_row, exec_budget)
    return jax.jit(
        body,
        in_shardings=(state_shardings(mesh), inbox_shardings(mesh)),
        donate_argnums=(0,),
    )


def fetch_host_outbox(out: TickOutbox) -> "tk.HostOutbox":
    """Assemble the full outbox on the host directly from the sharded fields.

    The mesh full-outbox path skips the on-device ``pack_outbox_impl``
    (historically a GSPMD concatenate over the mixed-sharding outbox fields
    returned wrong values, see module docstring); per-field assembly from
    the committed shards is exact and moves the same bytes.  Full-outbox
    mode is the small-scale/debug path; at scale the compact path is the
    transfer that matters.
    """
    jax.block_until_ready(out)
    return tk.HostOutbox(*(np.asarray(f) for f in out))


def make_mesh_compact(exec_budget: int, lag_budget: int):
    """The jitted global-view compaction of a sharded TickOutbox (donated)
    into its ``tk.CompactPack`` (flat buffer, head): the second dispatch of
    a mesh tick, ``jit_mesh_compact_outbox`` in a trace."""
    def mesh_compact_outbox(out):
        return tk._compact_outbox_impl(out, exec_budget=exec_budget,
                                       lag_budget=lag_budget)

    return jax.jit(mesh_compact_outbox, donate_argnums=(0,))


def make_mesh_scatter_inbox(mesh: Mesh):
    """``tk.scatter_inbox`` with its result laid out as the mesh tick takes
    its inbox (its ``in_shardings``): the list goes to every device, a few
    kilobytes, and the tick finds ``req`` / ``stop`` in place."""
    sh = inbox_shardings(mesh)
    return jax.jit(tk._scatter_inbox_impl, static_argnums=(1, 2, 3),
                   out_shardings=(sh.req, sh.stop))


def make_shardmap_tick_compact(mesh: Mesh, own_row: int, exec_budget: int,
                               lag_budget: int, demand_decay=None):
    """shard_map tick + budgeted on-device compaction (O(budget) transfer).

    The compaction stage runs global-view over the sharded outbox in its own
    dispatch (see module docstring) — its prefix-sum scatter ranks
    executions across ALL groups, and the flat buffer layout
    (``CompactLayout``) stays identical to the single-device path so the
    manager's unpack/WAL/replay code needs no sharded variant.

    ``demand_decay`` (placement plane): per-group ``decided_now`` [G] never
    reaches the host in compact mode — only its sum survives the flat
    buffer — so the demand EWMA fold ``d' = decay*d + decided_now`` must run
    on device, and it must run in THIS dispatch: the compaction donates the
    TickOutbox, so no later dispatch can read ``decided_now``.  With a decay
    set, the returned callable takes and returns the [G] f32 demand array
    (``P(groups)``-sharded, see :func:`init_demand`):
    ``fn(state, inbox, demand) -> (state, pack, new_demand)``.
    """
    tick = make_shardmap_tick(mesh, own_row, exec_budget)
    compact = make_mesh_compact(exec_budget, lag_budget)
    if demand_decay is None:
        def fn(state, inbox):
            state, out = tick(state, inbox)
            return state, compact(out)

        return fn

    decay = float(demand_decay)

    # the fold is a SEPARATE dispatch from the compaction, not fused: adding
    # the P(groups)-sharded demand operand/output to the compact jit changes
    # the partitioner's sharding assignment and the flat buffer comes back
    # with its counts multiplied by the groups-axis size (the same
    # double-reduction failure the module docstring describes for same-jit
    # fusion).  The fold is elementwise over two P(groups) arrays — no
    # reductions for the partitioner to mangle — and it reads
    # ``decided_now`` BEFORE the compact dispatch donates the outbox.
    def mesh_demand_fold(decided_now, demand):
        return decay * demand + decided_now.astype(demand.dtype)

    fold = jax.jit(mesh_demand_fold, donate_argnums=(1,))

    def fn3(state, inbox, demand):
        state, out = tick(state, inbox)
        new_demand = fold(out.decided_now, demand)
        return state, compact(out), new_demand

    return fn3


def init_demand(mesh: Mesh, n_groups: int):
    """Zeroed [G] f32 demand array, groups-sharded to match the fold."""
    from jax.sharding import NamedSharding

    import jax.numpy as jnp

    return jax.device_put(
        jnp.zeros(n_groups, jnp.float32),
        NamedSharding(mesh, P(GROUPS_AXIS)),
    )
