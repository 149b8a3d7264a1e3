"""Device-resident KV application: execution fused behind the consensus tick.

The reference's workload app (``gigapaxos/testing/TESTPaxosApp.java:60``)
executes inside the JVM next to the acceptor; every decision still crosses
the per-request handler stack.  Host apps here have the same shape — the
decision stream leaves the device and ``Replicable.execute`` runs
interpreted Python per request (``paxos/manager.py``), which caps e2e
throughput orders of magnitude below the raw kernel.

:class:`DeviceKV` moves the app itself into device arrays so the decision
stream NEVER leaves the device:

* app state — a direct-mapped KV cache per (replica, group):
  ``key[R, G, S]`` / ``val[R, G, S]`` int32 (0 = empty slot — key 0 is
  RESERVED as that sentinel, clients use keys >= 1; key k lives at
  slot ``k & (S-1)``, last-writer-wins on collision, deterministic on every
  replica by construction);
* request descriptors — clients register ``rid -> (op, key, val)`` in a
  hashed device table ``[T]`` (op PUT=1/GET=2/DEL=3); the tick's executed
  rids gather their descriptors and a vectorized apply updates the KV
  arrays for every group at once;
* misses (descriptor evicted/never uploaded) surface in a ``miss`` mask so
  the host can repair via its slow path — mirroring the dense design's
  general fast-path/slow-path split (SURVEY §7 hard part f).

``fused_step`` runs ``paxos_tick`` and the KV apply in ONE jitted program —
XLA fuses the gather/scatter chain with the tick's phase-4 extraction, so
"execute" costs one more fused elementwise pass over ``[R, W, G]``, not a
host round-trip per decision.
"""

from __future__ import annotations

import json
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.tick import TickInbox, paxos_tick_impl
from ..types import NO_REQUEST

OP_NONE = 0
OP_PUT = 1
OP_GET = 2
OP_DEL = 3

I32 = jnp.int32


class DeviceKVState(NamedTuple):
    """Dense app state + request-descriptor table (all device arrays)."""

    key: jnp.ndarray   # i32 [R, G, S]   stored key per slot (0 = empty)
    val: jnp.ndarray   # i32 [R, G, S]
    t_rid: jnp.ndarray  # i32 [T] descriptor table: registered rid (0 = none)
    t_op: jnp.ndarray   # i32 [T]
    t_key: jnp.ndarray  # i32 [T]
    t_val: jnp.ndarray  # i32 [T]

    @property
    def slots(self) -> int:
        return self.key.shape[2]

    @property
    def table(self) -> int:
        return self.t_rid.shape[0]


def init_kv(n_replicas: int, n_groups: int, slots: int = 16,
            table: int = 1 << 16) -> DeviceKVState:
    assert slots & (slots - 1) == 0 and table & (table - 1) == 0
    R, G = n_replicas, n_groups
    return DeviceKVState(
        key=jnp.zeros((R, G, slots), I32),
        val=jnp.zeros((R, G, slots), I32),
        t_rid=jnp.zeros((table,), I32),
        t_op=jnp.zeros((table,), I32),
        t_key=jnp.zeros((table,), I32),
        t_val=jnp.zeros((table,), I32),
    )


def _table_idx(rids, table: int, mix: bool):
    """Descriptor-table index for a batch of rids.

    ``mix=False`` (Mode A): plain low-bits mask — manager rids are one
    sequential stream, so any live window of <= table consecutive rids maps
    injectively (the eviction-safety invariant in paxos/manager.py).
    ``mix=True`` (Mode B): rids are origin-tagged ``(origin << 24) | seq``
    and every origin's seq streams advance together, so the plain mask
    would collide ALL origins at equal seqs; a multiplicative (Fibonacci)
    hash spreads them — a rare collision evicts a descriptor, which is a
    miss, which is the (correct) scalar fallback."""
    if not mix:
        return jnp.bitwise_and(rids, table - 1)
    h = (rids * jnp.int32(-1640531527)).astype(jnp.uint32)  # 0x9E3779B9
    return jnp.bitwise_and(h >> jnp.uint32(8), table - 1).astype(I32)


def register_requests(kv: DeviceKVState, rids, ops, keys, vals,
                      mix: bool = False) -> DeviceKVState:
    """Upload request descriptors (host batch -> one scatter).  Clients call
    this before proposing the rids; collisions evict (the evicted request
    will execute as a miss and fall back to the host slow path).

    rid 0 marks an EMPTY upload slot (fixed-size batches pad with zeros) —
    those scatter out of bounds and drop, instead of clobbering whatever
    live descriptor hashes to index 0 on every padded upload."""
    rids = jnp.asarray(rids, I32)
    idx = jnp.where(rids == 0, kv.table, _table_idx(rids, kv.table, mix))
    return kv._replace(
        t_rid=kv.t_rid.at[idx].set(rids, mode="drop"),
        t_op=kv.t_op.at[idx].set(jnp.asarray(ops, I32), mode="drop"),
        t_key=kv.t_key.at[idx].set(jnp.asarray(keys, I32), mode="drop"),
        t_val=kv.t_val.at[idx].set(jnp.asarray(vals, I32), mode="drop"),
    )


def kv_apply(kv: DeviceKVState, exec_req: jnp.ndarray,
             exec_count: jnp.ndarray,
             mix: bool = False) -> Tuple[DeviceKVState, jnp.ndarray,
                                         jnp.ndarray]:
    """Vectorized execution of one tick's decision stream.

    exec_req: i32 [R, W, G] executed rids in window order (0 = none);
    exec_count: i32 [R, G].
    Returns (kv', responses i32 [R, W, G] — PUT echoes the value, GET/DEL
    return the pre-op value (0 = absent) — and miss bool [R, W, G]).

    Window plane j executes slot base+j, so planes apply in order: a
    ``lax.scan`` over the W axis (W is small and static) threads the store
    through the planes — each step is fully vectorized over [R, G], and XLA
    unrolls/fuses the short scan into the surrounding program.  This is the
    TPU idiom for the reference's in-order ``execute`` loop
    (PaxosInstanceStateMachine.java:1755-1842) with read-your-writes inside
    one tick's batch.
    """
    from jax import lax

    R, W, G = exec_req.shape
    S = kv.slots
    ji = jnp.arange(W, dtype=I32)
    valid = (exec_req != NO_REQUEST) & (ji[None, :, None] < exec_count[:, None, :])

    tix = _table_idx(exec_req, kv.table, mix)  # [R, W, G]
    hit = valid & (kv.t_rid[tix] == exec_req)
    op = jnp.where(hit, kv.t_op[tix], OP_NONE)
    k = kv.t_key[tix]
    v = kv.t_val[tix]
    slot = jnp.bitwise_and(k, S - 1)  # [R, W, G]

    rr = jnp.arange(R, dtype=I32)[:, None]
    gg = jnp.arange(G, dtype=I32)[None, :]

    def plane(carry, xs):
        key_s, val_s = carry  # [R, G, S]
        op_j, k_j, v_j, slot_j = xs  # [R, G]
        cur_key = key_s[rr, gg, slot_j]
        cur_val = val_s[rr, gg, slot_j]
        present = cur_key == k_j
        resp = jnp.where(
            op_j == OP_PUT, v_j, jnp.where(present, cur_val, 0)
        )
        # DEL writes only when the key is actually resident: deleting an
        # absent key must not erase a colliding occupant (and must match
        # the scalar fallback's semantics exactly)
        wr = (op_j == OP_PUT) | ((op_j == OP_DEL) & present)
        wslot = jnp.where(wr, slot_j, S)  # S -> drop
        nk = jnp.where(op_j == OP_DEL, 0, k_j)
        nv = jnp.where(op_j == OP_DEL, 0, v_j)
        key_s = key_s.at[rr, gg, wslot].set(nk, mode="drop")
        val_s = val_s.at[rr, gg, wslot].set(nv, mode="drop")
        return (key_s, val_s), resp

    xs = (op.transpose(1, 0, 2), k.transpose(1, 0, 2),
          v.transpose(1, 0, 2), slot.transpose(1, 0, 2))
    (key_s, val_s), resps = lax.scan(plane, (kv.key, kv.val), xs)
    responses = jnp.where(hit, resps.transpose(1, 0, 2), 0)
    kv2 = kv._replace(key=key_s, val=val_s)
    miss = valid & ~hit
    return kv2, responses, miss


def fused_step(state, kv: DeviceKVState, inbox: TickInbox, own_row: int = -1,
               fast_elect: bool = False):
    """One consensus tick + device app execution in a single program."""
    new_state, out = paxos_tick_impl(state, inbox, own_row,
                                     fast_elect=fast_elect)
    kv2, responses, miss = kv_apply(kv, out.exec_req, out.exec_count)
    return new_state, kv2, out, responses, miss


fused_step_jit = jax.jit(fused_step, donate_argnums=(0, 1),
                         static_argnums=(3, 4))


def _fused_compact_impl(state, kv: DeviceKVState, inbox: TickInbox,
                        reg_rids, reg_ops, reg_keys, reg_vals,
                        own_row: int, exec_budget: int, lag_budget: int,
                        fast_elect: bool = False):
    """Descriptor upload + consensus tick + KV apply + outbox compaction in
    ONE device program: the deployment-path twin of :func:`fused_step`.

    The compacted buffer grows one extra array vs the consensus-only
    compaction: per-execution KV responses (e_resp), scattered with the
    same prefix-sum ranks, so entry replicas answer clients without any
    O(R*W*G) transfer.  reg_*: this tick's new request descriptors
    ([K] i32; rid 0 = empty slot — a fixed-size upload keeps the jit
    signature static).
    """
    from ..ops.tick import (_compact_columns, _compact_outbox_impl,
                            _exec_mask, paxos_tick_impl)

    kv = register_requests(kv, reg_rids, reg_ops, reg_keys, reg_vals)
    new_state, out = paxos_tick_impl(state, inbox, own_row, exec_budget,
                                     fast_elect=fast_elect)
    kv2, responses, miss = kv_apply(kv, out.exec_req, out.exec_count)
    # the extras ride the flat buffer, so the device app pulls that whole
    packed = _compact_outbox_impl(out, exec_budget, lag_budget).flat
    # responses ride the exec stream's compaction: same mask, same ranks
    R, _, G = out.exec_req.shape
    with jax.named_scope("compact_outbox"):
        _, extras = _compact_columns(_exec_mask(out).reshape(-1),
                                     [responses, miss], exec_budget)
    flat = jnp.concatenate([packed, extras.reshape(-1)])
    # pack/unpack agreement enforced at trace time against the shared
    # layout descriptor (consumers slice via CompactLayout.kv_extras)
    from ..ops.tick import CompactLayout

    L = CompactLayout(R, G, exec_budget, lag_budget)
    assert flat.shape[0] == L.total_device, (flat.shape, L.total_device)
    assert packed.shape[0] == L.o_resp
    return new_state, kv2, flat


fused_compact = jax.jit(_fused_compact_impl, donate_argnums=(0, 1),
                        static_argnums=(7, 8, 9, 10))


#: descriptor wire format for device-app request payloads: op, key, value
DESC = "<iii"
DESC_LEN = 12


def pack_desc(op: int, key: int, val: int) -> bytes:
    import struct

    return struct.pack(DESC, op, key, val)


class DeviceKVApp:
    """Replicable face of the MANAGER-OWNED device KV state.

    One source of truth: ``owner.kv`` is the live DeviceKVState the fused
    tick evolves (``PaxosManager.kv`` in device-app mode); this wrapper
    gives the control plane (checkpoint transfer, epoch final state,
    recovery seeding) row-granular views of it.  The hot path never calls
    ``execute`` — decisions execute on-device inside ``fused_compact``;
    the scalar ``execute`` below applies one descriptor through the same
    semantics for the rare host fallbacks (control-plane proposes, WAL
    scalar replay).

    ``row_of(name)`` maps service names to group rows (wire it to the
    manager's RowAllocator).
    """

    def __init__(self, owner, replica: int, row_of=None):
        self.owner = owner  # any object with a mutable .kv attribute
        self.replica = replica
        self.row_of = row_of or (lambda name: None)

    def _lock(self):
        """Every access to owner.kv must exclude the fused tick: the tick
        DONATES the kv buffers, so a concurrent read races buffer deletion.
        The owner's lock is reentrant (tick-held paths still work)."""
        import contextlib

        lk = getattr(self.owner, "lock", None)
        return lk if lk is not None else contextlib.nullcontext()

    @property
    def kv(self) -> DeviceKVState:
        return self.owner.kv

    @kv.setter
    def kv(self, v: DeviceKVState) -> None:
        self.owner.kv = v

    def execute(self, name: str, request: bytes, request_id: int) -> bytes:
        """Scalar fallback: apply one 12-byte descriptor to this replica's
        row (same semantics as the vectorized kv_apply plane step)."""
        import struct

        row = self.row_of(name)
        if row is None or len(request) != DESC_LEN:
            return b""
        op, k, v = struct.unpack(DESC, request)
        with self._lock():
            kv = self.kv
            slot = k & (kv.slots - 1)
            cur_k = int(kv.key[self.replica, row, slot])
            cur_v = int(kv.val[self.replica, row, slot])
            present = cur_k == k
            if op == OP_PUT:
                self.kv = kv._replace(
                    key=kv.key.at[self.replica, row, slot].set(k),
                    val=kv.val.at[self.replica, row, slot].set(v),
                )
                resp = v
            elif op == OP_DEL:
                if present:
                    self.kv = kv._replace(
                        key=kv.key.at[self.replica, row, slot].set(0),
                        val=kv.val.at[self.replica, row, slot].set(0),
                    )
                resp = cur_v if present else 0
            else:  # GET / NONE
                resp = cur_v if present else 0
        return struct.pack("<i", resp)

    def checkpoint(self, name: str) -> bytes:
        row = self.row_of(name)
        if row is None:
            return b""
        with self._lock():
            keys = np.asarray(self.kv.key[self.replica, row])
            vals = np.asarray(self.kv.val[self.replica, row])
        live = keys != 0
        return json.dumps({
            "k": keys[live].tolist(), "v": vals[live].tolist(),
        }).encode()

    def restore(self, name: str, state: bytes) -> None:
        row = self.row_of(name)
        if row is None:
            return
        with self._lock():
            S = self.kv.slots
            keys = np.zeros(S, np.int32)
            vals = np.zeros(S, np.int32)
            if state:
                d = json.loads(state.decode())
                for k, v in zip(d["k"], d["v"]):
                    keys[k & (S - 1)] = k
                    vals[k & (S - 1)] = v
            self.kv = self.kv._replace(
                key=self.kv.key.at[self.replica, row].set(jnp.asarray(keys)),
                val=self.kv.val.at[self.replica, row].set(jnp.asarray(vals)),
            )
