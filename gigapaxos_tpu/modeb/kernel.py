"""Per-node consensus step for Mode B (independent processes per replica).

Mode A runs the whole replica set as one device program (``ops/tick.py``);
Mode B gives every node its own process, disk and device state — the
reference's actual deployment shape (one ``PaxosManager`` per machine,
gigapaxos/PaxosManager.java:104-119, replica traffic over NIO,
nio/NIOTransport.java:65-114).

Design: each node holds the full ``[R, ...]`` state arrays but is
**authoritative only for its own row r**.  Peer rows are *mirrors*, updated
exclusively by replica frames received over the transport (``wire.py``).
The node step reuses the verified fused dataflow (``paxos_tick_impl``) and
then keeps only row r of the result — peer rows stay whatever the last
frames said.

Why this is safe with stale mirrors: the tick runs with ``own_row=r``
(``ops/tick.py``), which confines every state *transition* — candidacy,
promise upgrade, prepare win, intake, accept — to row r.  Peer rows are
pure frame-derived snapshots, and every cross-replica read then consumes
only *monotone facts*:

* a promise in a mirror row means that acceptor really promised that ballot
  at its frame snapshot (promises only rise), so counting a prepare
  majority from mirrors counts real promises, and the carryover window
  rides the same frame snapshot (= "accepteds as of the promise", the
  classic prepare-reply content, PaxosInstanceStateMachine.java:1017);
* a vote (accepted pvalue) in a mirror is a historical fact: once a
  majority ever accepted (slot, ballot, value), that value is chosen —
  tallying stale votes can only *under*-count, never fabricate a quorum;
* a pushing peer coordinator (mirror coord_active + prop ring, shipped
  together in one frame) is a real ACCEPT in flight — the value and ballot
  are the peer's own consistent facts, never locally recomputed;
* decisions are facts by construction.

Without the own-row confinement the fused tick SIMULATES peer promises and
accepts in the same step (that is Mode A's whole point: one device program
IS the replica set), and counting those toward quorums would let an
isolated minority self-elect and commit — split brain.  Regression:
``tests/test_modeb_partition.py`` (isolated node must never commit; two
live coordinators across a partition must not diverge).

Staleness therefore costs latency (a decision needs a frame round-trip to
gather votes), never agreement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..ops.tick import TickInbox, paxos_tick_impl

#: own-row state fields shipped in replica frames ([R, G] / [R, W, G])
FRAME_FIELDS_2D = ("exec_slot", "bal_num", "bal_coord", "status",
                   "coord_active", "coord_preparing", "coord_fast",
                   "coord_bnum", "next_slot")
FRAME_FIELDS_3D = ("acc_bnum", "acc_bcoord", "acc_req", "acc_slot",
                   "acc_stop", "dec_req", "dec_slot", "dec_valid",
                   "dec_stop", "prop_req", "prop_slot", "prop_valid",
                   "prop_stop")


def node_tick_impl(state, inbox: TickInbox, r: int, fast: bool = False):
    """One Mode-B node step: fused dataflow, own-row commit, change mask.

    Returns (state', outbox, changed[G]) where ``changed`` marks groups
    whose own-row frame fields differ from before (the delta-frame mask —
    the batching analog of PaxosPacketBatcher coalescing per-peer traffic,
    gigapaxos/PaxosPacketBatcher.java:28-35).

    ``fast`` enables consecutive-ballot fast re-election (see
    ``paxos_tick_impl``); the ``coord_fast`` bit it maintains travels in
    the frame flags word, so peers' acceptors apply the conflict-refusal
    rule to this node's fast pushes.
    """
    # a node program is single-device by construction (each Mode-B process
    # owns one chip), so on a TPU it runs the Pallas gathers
    new, out = paxos_tick_impl(state, inbox, own_row=r, fast_elect=fast)
    R = state.exec_slot.shape[0]
    row2 = (jnp.arange(R) == r)[:, None]        # [R, 1]
    row3 = row2[:, None, :]                      # [R, 1, 1]

    merged = {}
    changed = jnp.zeros(state.exec_slot.shape[1], jnp.bool_)
    for f in FRAME_FIELDS_2D:
        old_a, new_a = getattr(state, f), getattr(new, f)
        merged[f] = jnp.where(row2, new_a, old_a)
        changed = changed | (new_a[r] != old_a[r])
    for f in FRAME_FIELDS_3D:
        old_a, new_a = getattr(state, f), getattr(new, f)
        merged[f] = jnp.where(row3, new_a, old_a)
        changed = changed | jnp.any(new_a[r] != old_a[r], axis=0)
    # member/n_members/epoch are config state managed by create/free ops,
    # identical on every node — the tick never writes them
    return state._replace(**merged), out, changed


@functools.lru_cache(maxsize=None)
def node_tick(r: int, fast: bool = False):
    """Jitted per-node step (r, fast static; state donated)."""
    return jax.jit(functools.partial(node_tick_impl, r=r, fast=fast),
                   donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def node_tick_packed(r: int, fast: bool = False):
    """Jitted per-node step returning (state', flat_i32) where the flat
    buffer is pack_outbox(outbox) ++ changed — ONE device->host transfer
    per tick instead of one per consumed field (see ops/tick.HostOutbox)."""
    from ..ops.tick import pack_outbox_impl

    def impl(state, inbox):
        new, out, changed = node_tick_impl(state, inbox, r, fast)
        flat = jnp.concatenate(
            [pack_outbox_impl(out), changed.astype(jnp.int32)]
        )
        return new, flat

    return jax.jit(impl, donate_argnums=(0,))


def unpack_node_tick(flat, R: int, P: int, W: int, G: int):
    """Host inverse of :func:`node_tick_packed`'s flat buffer."""
    import numpy as np

    from ..ops.tick import unpack_outbox

    flat = np.asarray(flat)
    out = unpack_outbox(flat[:-G], R, P, W, G)
    return out, flat[-G:].astype(bool)


@functools.lru_cache(maxsize=None)
def node_tick_device(r: int, K: int, fast: bool = False):
    """Jitted per-node step with the device KV app fused behind it (the
    Mode-B twin of models/device_kv.fused_compact): descriptor upload +
    consensus tick + own-row on-device execution in ONE program.

    The node's kv has replica-axis 1 (it executes only its own row).  Rows
    with ANY descriptor miss this tick — or held by the host (``hold``:
    rows whose execution stream is stalled on an unarrived payload) — are
    SUPPRESSED on device: no kv write at all, because applying slot j+1
    while slot j is missing/stalled would break RSM order.  The host
    re-applies a suppressed row's batch in order through the scalar
    fallback (reusing the digest stall machinery).  reg_*: up to K new
    descriptors (rid 0 = empty).

    Returns (state', kv', flat) with flat = pack_outbox ++ changed[G] ++
    resp[W*G] ++ row_skip[G] (own row, window-major).
    """
    from ..models.device_kv import kv_apply, register_requests
    from ..ops.tick import pack_outbox_impl

    def impl(state, kv, inbox, reg_rids, reg_ops, reg_keys, reg_vals, hold):
        kv = register_requests(kv, reg_rids, reg_ops, reg_keys, reg_vals,
                               mix=True)
        new, out, changed = node_tick_impl(state, inbox, r, fast)
        er = out.exec_req[r:r + 1]      # [1, W, G]
        ec = out.exec_count[r:r + 1]
        kv2, resp, miss = kv_apply(kv, er, ec, mix=True)
        row_skip = jnp.any(miss[0], axis=0) | hold  # [G]
        # suppress every kv effect of a skipped row (host replays in order)
        keep = ~row_skip[None, :, None]
        kv2 = kv2._replace(
            key=jnp.where(keep, kv2.key, kv.key),
            val=jnp.where(keep, kv2.val, kv.val),
        )
        flat = jnp.concatenate([
            pack_outbox_impl(out), changed.astype(jnp.int32),
            resp[0].reshape(-1), row_skip.astype(jnp.int32),
        ])
        return new, kv2, flat

    return jax.jit(impl, donate_argnums=(0, 1))


def unpack_node_tick_device(flat, R: int, P: int, W: int, G: int):
    """Host inverse of :func:`node_tick_device`: -> (outbox, changed[G],
    resp[W, G], row_skip[G])."""
    import numpy as np

    from ..ops.tick import unpack_outbox

    flat = np.asarray(flat)
    tail = G + W * G + G
    out = unpack_outbox(flat[:-tail], R, P, W, G)
    changed = flat[-tail:-tail + G].astype(bool)
    resp = flat[-tail + G:-G].reshape(W, G)
    row_skip = flat[-G:].astype(bool)
    return out, changed, resp, row_skip


@functools.lru_cache(maxsize=None)
def frame_extract(r: int, K: int):
    """Jitted own-row gather for frame building: selects ``K`` rows of every
    frame field in one device program and returns one flat i32 buffer
    (layout: scalars [S,K] ++ flags [K] ++ rings [NR,K,W] ++ bits [NB,K,W]).
    The round-2 path sliced ~21 fields individually (one dispatch+transfer
    each) per frame per tick; K is pow2-padded so the jit cache stays
    bounded."""
    from .wire import FLAG_COORD_ACTIVE, FLAG_COORD_FAST, \
        FLAG_COORD_PREPARING, RING_BITS, RINGS, SCALARS

    def impl(state, rows):
        parts = []
        for f in SCALARS:
            parts.append(getattr(state, f)[r, rows])                 # [K]
        flags = (state.coord_active[r, rows].astype(jnp.int32)
                 * FLAG_COORD_ACTIVE
                 + state.coord_preparing[r, rows].astype(jnp.int32)
                 * FLAG_COORD_PREPARING
                 + state.coord_fast[r, rows].astype(jnp.int32)
                 * FLAG_COORD_FAST)
        parts.append(flags)
        for f in RINGS + RING_BITS:
            parts.append(getattr(state, f)[r][:, rows].T)            # [K, W]
        return jnp.concatenate(
            [p.astype(jnp.int32).ravel() for p in parts]
        )

    return jax.jit(impl)


def unpack_frame_extract(flat, n: int, K: int, W: int):
    """Host inverse of :func:`frame_extract`: -> (scalars dict, flags,
    rings dict, ring_bits dict) truncated to the first ``n`` rows."""
    import numpy as np

    from .wire import RING_BITS, RINGS, SCALARS

    flat = np.asarray(flat)
    off = 0
    scalars = {}
    for f in SCALARS:
        scalars[f] = flat[off:off + K][:n]
        off += K
    flags = flat[off:off + K][:n]
    off += K
    rings = {}
    for f in RINGS:
        rings[f] = flat[off:off + K * W].reshape(K, W)[:n]
        off += K * W
    bits = {}
    for f in RING_BITS:
        bits[f] = flat[off:off + K * W].reshape(K, W)[:n].astype(bool)
        off += K * W
    return scalars, flags, rings, bits


def mirror_apply_impl(state, sr, rows, scalars, flags, rings, bits):
    """Apply one decoded replica frame to sender ``sr``'s mirror rows in a
    single fused device step.

    The naive path (one ``.at[].set`` dispatch per field per frame — ~20
    dispatches) dominates host time at high frame rates; fusing them into
    one jitted program is the ingest analog of PaxosPacketBatcher
    coalescing per-peer traffic (gigapaxos/PaxosPacketBatcher.java:28-35).

    rows: i32 [K], padded with G (out-of-bounds -> mode='drop' discards);
    scalars: i32 [S, K] in wire.SCALARS order; flags: i32 [K];
    rings: i32 [NR, K, W] in wire.RINGS order; bits: bool [NB, K, W] in
    wire.RING_BITS order.
    """
    from .wire import (FLAG_COORD_ACTIVE, FLAG_COORD_FAST,
                       FLAG_COORD_PREPARING, RING_BITS, RINGS, SCALARS)

    upd = {}
    for i, f in enumerate(SCALARS):
        upd[f] = getattr(state, f).at[sr, rows].set(scalars[i], mode="drop")
    upd["coord_active"] = state.coord_active.at[sr, rows].set(
        (flags & FLAG_COORD_ACTIVE) > 0, mode="drop"
    )
    upd["coord_preparing"] = state.coord_preparing.at[sr, rows].set(
        (flags & FLAG_COORD_PREPARING) > 0, mode="drop"
    )
    upd["coord_fast"] = state.coord_fast.at[sr, rows].set(
        (flags & FLAG_COORD_FAST) > 0, mode="drop"
    )
    for i, f in enumerate(RINGS):
        upd[f] = getattr(state, f).at[sr, :, rows].set(rings[i], mode="drop")
    for i, f in enumerate(RING_BITS):
        upd[f] = getattr(state, f).at[sr, :, rows].set(bits[i], mode="drop")
    return state._replace(**upd)


mirror_apply = jax.jit(mirror_apply_impl, donate_argnums=(0,))


def ring_downstream(alive, r: int) -> int:
    """Next alive replica clockwise from ``r``: the dissemination-ring hop
    target (HT-Ring Paxos, arxiv 1507.04086).  The tick above orders rids
    only (digest accepts); the payload bytes those rids reference travel
    along the ring this routing defines — one downstream send per node per
    tick regardless of R.  Returns -1 when no OTHER replica is alive (a
    singleton keeps its payloads staged until someone rejoins).  Host-side
    like the unpack inverses: ``alive`` is the manager's numpy liveness
    mirror, never device state."""
    R = len(alive)
    for k in range(1, R):
        i = (r + k) % R
        if alive[i]:
            return i
    return -1
