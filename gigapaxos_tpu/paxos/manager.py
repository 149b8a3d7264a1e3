"""PaxosManager: the host control loop that owns the device data plane.

The reference's ``PaxosManager`` (gigapaxos/PaxosManager.java:104-119) is the
per-node multiplexer: instance map, request demultiplexing, the propose API,
recovery driver and pause logic.  Here it owns:

* the dense device state (one :class:`PaxosState`) and the jitted tick;
* the name<->row table (RowAllocator = IntegerMap/MultiArrayMap analog,
  paxosutil/IntegerMap.java:40 / utils/MultiArrayMap.java:41);
* the request store: request-id -> payload/callback (the ``outstanding`` map,
  PaxosManager.java:189-259), with execution-side dedup so a request that
  commits in two slots (possible across coordinator turnover, the
  "preempted request" hazard of PaxosManager.java:1298-1352) executes once;
* per-replica-slot app instances (``Replicable``), executed on the host from
  the device's ordered decision stream;
* the per-tick batcher (RequestBatcher analog, gigapaxos/RequestBatcher.java:25):
  queued proposals are packed into the inbox tensor, rejected intake is
  re-queued.

This manager drives the whole replica set of a mesh (Mode A).  In a
multi-host deployment each host runs one manager per node and the replica
axis exchange goes over the transport instead (net/, Mode B).
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..config import GigapaxosTpuConfig
from .. import overload as _overload
from ..models.replicable import Replicable
from ..types import GroupStatus, NO_REQUEST
from ..utils.intmap import RowAllocator
from ..obs import compiles as _compiles
from ..obs.metrics import registry as _obs_registry
from ..obs.phase import phase_clock as _phase_clock
from ..utils.locking import ContendedLock, locked as _locked
from ..utils.reqtrace import tracer as _reqtrace

#: process-wide manager counter for trace namespaces (never reused)
import itertools as _itertools

_MGR_SEQ = _itertools.count()
from . import state as st
from .bulkstore import BulkOverrun, BulkStore
from .paystore import PayloadStore
from ..wal.journal import MAX_RECORD as _WAL_MAX_RECORD
from ..ops.pallas_gather import check_lanes
from ..ops.tick import (LP_ASN, LP_EPOCH, LP_HOLDER, LP_UNTIL, LP_WAIT,
                        CompactHostOutbox, CompactPack, HostOutbox, TickInbox,
                        TickParams, TickPlanes, compact_path, frontier_rows,
                        health_clear_rows, init_health, lease_clear_rows,
                        merge_compact_outbox, merge_health, merge_outbox,
                        one_or_pair, paxos_tick_planes, scatter_inbox,
                        sweep_frontier, taken_bit, taken_dense,
                        unpack_compact, unpack_head, unpack_health,
                        unpack_outbox)


#: The most scalar placements a tick hands the device as a list, for
#: ``ops.tick.scatter_inbox`` to make the [R, P, G] inbox there; a tick that
#: placed more, or anything in bulk, hands over the dense arrays.  Sized from
#: what XLA:TPU charges a scatter, 4.6-4.9 ns per offered update with the
#: padding (PERF.md section 6): 20 us an array, against 60-140 placements a
#: tick at 1,000 req/s.  One length, so one program.
_SHORT_INBOX = 4096

#: journal bytes of one bulk placement beside its body, rounded up: rid,
#: entry, p, row and stop columns (21) and the codec's tag and length (5)
_BULK_JOURNAL_OVERHEAD = 32


@dataclass
class RequestRecord:
    rid: int
    name: str
    row: int
    payload: bytes
    stop: bool
    callback: Optional[Callable[[int, bytes], None]]
    entry: int  # entry replica slot
    slot: int = -1  # filled at first execution
    executed_by: set = field(default_factory=set)
    responded: bool = False
    #: perf_counter() when propose staged it / when _build_inbox first
    #: placed it in a tick's inbox (request_stage_seconds; 0.0 = not yet)
    t_staged: float = 0.0
    t_placed: float = 0.0


def _pad_rows(rows: np.ndarray, oob: int) -> np.ndarray:
    """Pad a row batch to the next power of two with an out-of-range index
    (``oob`` = plane size; jnp ``mode="drop"`` ignores it) so jitted
    point-clears compile once per size class instead of once per batch."""
    n = max(1, 1 << int(len(rows) - 1).bit_length())
    out = np.full(n, oob, np.int32)
    out[:len(rows)] = rows
    return out


class PaxosManager:
    def __init__(
        self,
        cfg: GigapaxosTpuConfig,
        n_replicas: int,
        apps: List[Replicable],
        wal=None,
        spill_ns: str = "default",
    ):
        """``spill_ns`` namespaces this manager's disk spill store — several
        managers (data plane + RC plane) share one cfg, and their DiskMaps
        must never adopt or clear each other's cold files."""
        assert len(apps) == n_replicas
        self.cfg = cfg
        self.R = n_replicas
        self.G = cfg.paxos.max_groups
        self.W = cfg.paxos.window
        self.P = cfg.paxos.proposals_per_tick
        # Register plane (RMWPaxos): a second dense state block at W=1 for
        # in-place consensus registers.  Composite row space: [0, G) log
        # rows, [G, G_total) register rows — the row index IS the mode bit,
        # so every row-keyed host structure below is sized G_total and the
        # two device planes stay separate jit inputs (the tick splits the
        # composite inbox at the static boundary).  G_reg == 0 keeps every
        # structure and code path bit-identical to pre-register builds.
        self.G_reg = cfg.paxos.register_groups
        self.G_total = self.G + self.G_reg
        if not cfg.paxos.mesh_devices:  # a mesh checks its per-shard width
            check_lanes(self.G, "paxos.max_groups")
        check_lanes(self.G_reg, "paxos.register_groups")
        self.state = st.init_state(self.R, self.G, self.W)
        self.rstate = (st.init_state(self.R, self.G_reg, 1)
                       if self.G_reg else None)
        self.rows = RowAllocator(self.G_total, split=self.G)
        self.apps = apps
        self.wal = wal
        self.alive = np.ones(self.R, bool)
        self.tick_num = 0
        self.outstanding: Dict[int, RequestRecord] = {}
        self._next_rid = 1
        # content-addressed payload interning (ordering/dissemination split,
        # Mode A half): N admitted requests sharing one body hold one bytes
        # object, and every digest-keyed consumer (WAL dedup, GBR2 batch
        # frames) sees identity-stable payloads
        self._paystore = PayloadStore()
        self._queues: Dict[int, collections.deque] = collections.defaultdict(
            collections.deque
        )  # row -> rids waiting for intake
        # callbacks held until the WAL record covering their tick is fsynced
        # (log-before-respond, the analog of logAndMessage's log-before-send,
        # AbstractPaxosLogger.java:157-178)
        self._held_callbacks: list = []
        # egress coalescing scopes bracketing each callback flush: hooks
        # return a close-callable; the response edge (ActiveReplica's
        # ClientEgress) uses this to hand the transport per-(client, tick)
        # frame lists instead of frame-at-a-time sends
        self._flush_scope_hooks: list = []
        # per (replica, row) dedup of executed request ids (bounded)
        self._seen: Dict[tuple, collections.OrderedDict] = collections.defaultdict(
            collections.OrderedDict
        )
        self._seen_cap = 8 * self.W
        self.stats = collections.Counter()
        # overload plane (ISSUE 14): watermark-with-hysteresis admission of
        # CLIENT-class work at the node intake.  Control-class proposes
        # (epoch stops, RC plane) are never governed — liveness traffic
        # rides through an overload.  None when disabled.
        self.overload = (
            _overload.IntakeGovernor(cfg.overload.intake_hi,
                                     cfg.overload.intake_lo,
                                     node=spill_ns or "-")
            if cfg.overload.enabled else None
        )
        self._ov_node = spill_ns or "-"
        self._stopped_rows: set[int] = set()
        # ---- pause/spill (deactivation, PaxosManager.java:2284-2412) ----
        # name -> HotRestoreInfo dict (+ "stopped" flag); device row freed.
        # With spill_dir set, cold paused records demand-page to disk
        # (DiskMap analog) so the paused population can exceed host RAM.
        import os as _os

        from ..utils.diskmap import DiskMap

        self._paused = DiskMap(
            _os.path.join(cfg.paxos.spill_dir, spill_ns)
            if cfg.paxos.spill_dir else None,
            cfg.paxos.spill_cache,
        )
        self._last_active = np.zeros(self.G_total, np.int64)
        self._row_outstanding = collections.Counter()
        # Host mirrors of config state (member mask / group size).  The tick
        # never writes these; they change only in create/remove/pause/unpause
        # — so the hot path (propose placement, execution bookkeeping) reads
        # numpy instead of paying a jitted scalar-index dispatch per request
        # (round-2 profile: ~230us per state.n_members[row] lookup).
        self._member_np = np.zeros((self.R, self.G_total), bool)
        self._n_members_np = np.zeros(self.G_total, np.int32)
        # further host mirrors for the vectorized (bulk/compact) path:
        # stopped flags, row->name, member bitmask, member-ordinal table
        self._stopped_np = np.zeros(self.G_total, bool)
        self._row_name_np = np.empty(self.G_total, object)
        self._member_bits = np.zeros(self.G_total, np.int64)
        self._member_ord = None  # lazy [R, G_total] cumulative member ordinal
        #: per-row window / laggard threshold: W for log rows, 1 for
        #: register rows (a register replica one version behind already
        #: needs the register shipped — there is no ring to catch up from)
        self._w_np = np.full(self.G_total, self.W, np.int32)
        self._w_np[self.G:] = 1
        # ---- compacted-outbox / bulk-propose machinery ----
        self._use_compact = bool(cfg.paxos.compact_outbox)
        self._exec_budget = cfg.paxos.exec_budget or max(4096, 2 * self.G_total)
        self._lag_budget = max(64, cfg.paxos.lag_budget)
        from ..ops.tick import CompactLayout

        self._compact_layout = CompactLayout(
            self.R, self.G, self._exec_budget, self._lag_budget, self.P
        )
        self._compact_layout_reg = (CompactLayout(
            self.R, self.G_reg, self._exec_budget, self._lag_budget
        ) if self.G_reg else None)
        bc = cfg.paxos.bulk_capacity or max(1 << 16, 4 * self.G)
        self._bulk_cap = 1 << (bc - 1).bit_length()
        self.bulk: Optional[BulkStore] = None  # lazy (most managers: unused)
        self._bulk_cbs: Dict[int, Callable] = {}  # optional per-rid cbs
        #: columnar completion sinks: one per admitted contiguous rid
        #: block — [rid0, rid0+n) -> sink(offsets, responses) called in
        #: per-tick batches instead of one Python callback per request
        #: (the completion-side twin of propose_bulk's columnar admission).
        #: Kept sorted by rid0; vectorized lookup via searchsorted.
        self._sink_blocks: list = []  # [rid0, n, remaining, sink]
        self._bulk_chunks: list = []  # FIFO of staged rid arrays
        self._bulk_leftover = np.zeros(0, np.int64)  # queued, not yet placed
        self._bulk_placed = None  # (rids, entries, ps, rows) of last tick
        #: the last completed tick's compacted laggard table — the l_*
        #: columns (rep, row, donor, donor exec, donor status, laggard
        #: exec): everything a checkpoint transfer needs, device-selected
        z0 = np.zeros(0, np.int64)
        self._lag_pending = (z0, z0, z0, z0, z0, z0)
        #: (replica, row) transfers noticed during tick completion, run at
        #: the next tick() top after a pipeline drain (watermark/blob skew)
        self._lag_sync_due: list = []
        #: pairs repaired at the previous tick() top: the pipelined outbox
        #: completed during that same drain re-flags them from pre-repair
        #: state, and without this filter the next tick would pay a
        #: pipeline drain just to find every entry already healed
        self._repaired_last: set = set()
        #: device sweep frontier (urows + amin/base/live [rows] gathers,
        #: _frontier_gather) stashed at the dispatch whose completion will
        #: sweep — see _complete_tick
        self._sweep_every = 64
        #: HOST-APPLIED execution watermark [R, G]: how far each replica's
        #: app has actually executed (device exec_slot runs one pipelined
        #: tick ahead of it).  The payload sweep must judge "everyone
        #: passed this slot" against THIS, not device state: a payload
        #: swept in the gap makes the very delivery that advanced the
        #: device watermark skip host-side — a silent lost write
        self._host_exec = np.zeros((self.R, self.G_total), np.int32)
        # ---- device-resident application (models/device_kv.py) ----
        self._device_app = bool(cfg.paxos.device_app)
        self.kv = None
        if self._device_app:
            if not self._use_compact:
                raise ValueError("device_app requires compact_outbox")
            if self.G_reg:
                raise ValueError(
                    "register_groups + device_app is not supported yet: the "
                    "fused KV program has no mixed-plane formulation"
                )
            if cfg.paxos.emulate_unreplicated or cfg.paxos.lazy_propagation:
                raise ValueError(
                    "baseline modes are host-app measurement tools; the "
                    "device app executes on-device only"
                )
            from ..models.device_kv import DeviceKVApp, init_kv

            table = cfg.paxos.kv_table or (
                1 << max(16, (4 * self.G - 1).bit_length())
            )
            # live-descriptor evictions must be impossible: rids are
            # sequential and the admit window caps live spread at
            # bulk_capacity, so a table >= 2x that can only ever evict
            # descriptors of already-freed requests
            table = max(table, 2 * self._bulk_cap)
            self.kv = init_kv(self.R, self.G, cfg.paxos.kv_slots, table)
            # the manager owns the device state; the Replicable faces the
            # control plane sees are row-granular views of it
            self.apps = [DeviceKVApp(self, r, row_of=self.rows.row)
                         for r in range(self.R)]
            apps = self.apps
            self._kv_reg_budget = cfg.paxos.kv_reg_budget or 2 * self.G
            self._kv_chunks: list = []  # staged descriptor uploads
            self._kv_watermark = 0  # highest rid with descriptor on device
            self._kv_uploaded = None  # this tick's upload (journaled)
        # ---- sharded data plane (parallel/shard_tick) ----
        # mesh_devices > 0 (or -1 = all): state lives partitioned over a
        # (replica, groups) device mesh and the tick runs as a shard_map
        # program — pallas gathers stay enabled per-shard, quorum exchange
        # is an explicit replica-axis all_gather.  Bit-identical to the
        # single-device path (tests/test_sharding_stack.py), so everything
        # downstream (WAL, replay, laggard repair, compaction layout) is
        # unchanged.
        self.mesh = None
        self._mesh_tick = None
        self._mesh_tick_compact = None
        #: makes a short inbox on the device(s), laid out as the tick takes it
        self._scatter_inbox = scatter_inbox
        if cfg.paxos.mesh_devices:
            import jax

            from ..parallel import shard_tick as _stk
            from ..parallel.mesh import make_mesh, state_shardings

            if self.G_reg:
                raise ValueError(
                    "register_groups + mesh_devices is not supported yet: "
                    "the shard_map tick has no mixed-plane formulation"
                )
            if self._device_app:
                raise ValueError(
                    "device_app + mesh_devices is not supported yet: the "
                    "fused KV program has no shard_map formulation"
                )
            devs = jax.devices()
            n = len(devs) if cfg.paxos.mesh_devices < 0 else cfg.paxos.mesh_devices
            if n > len(devs):
                raise ValueError(
                    f"mesh_devices={n} but only {len(devs)} devices visible"
                )
            self.mesh = make_mesh(
                devs[:n], replica_shards=cfg.paxos.mesh_replica_shards
            )
            _stk.validate_mesh_for(self.mesh, self.R, self.G)
            if self._use_compact:
                self._mesh_tick_compact = _stk.make_shardmap_tick_compact(
                    self.mesh, -1, self._exec_budget, self._lag_budget,
                    demand_decay=(cfg.placement.ewma_decay
                                  if cfg.placement.enabled else None),
                )
            else:
                self._mesh_tick = _stk.make_shardmap_tick(self.mesh, -1)
            self._scatter_inbox = _stk.make_mesh_scatter_inbox(self.mesh)
            # recreate the state distributed (each device materializes only
            # its shard; no single-device peak)
            self.state = st.init_state(
                self.R, self.G, self.W,
                shardings=state_shardings(self.mesh),
            )
        # ---- placement plane (placement/): advisory demand counters ----
        # Excluded from WAL/snapshot on purpose: a recovered node restarts
        # with cold counters and waits out the rebalancer's min-interval
        # guard; the migrations themselves ARE journaled (OP_CREATE_AT).
        self._placement = None
        self._demand_dev = None
        if cfg.placement.enabled:
            from ..parallel.mesh import GROUPS_AXIS as _GAX
            from ..placement.counters import PlacementCounters

            gs = self.mesh.shape[_GAX] if self.mesh is not None else 1
            self._placement = PlacementCounters(
                self.G, gs,
                decay=cfg.placement.ewma_decay,
                sample_every_ticks=cfg.placement.sample_every_ticks,
            )
            if self._mesh_tick_compact is not None:
                # device fold active: the tick threads this array through
                # the compact dispatch (see make_shardmap_tick_compact)
                from ..parallel import shard_tick as _stk2

                self._demand_dev = _stk2.init_demand(self.mesh, self.G)
            elif self._use_compact and not self._device_app \
                    and not self.G_reg and not cfg.paxos.read_leases \
                    and not cfg.paxos.group_health:
                # one device, compact: the intake-popcount fold runs inside
                # the served tick (``TickPlanes.demand``) instead of as an
                # O(G*P) host popcount per tick in _process_compact.  The
                # tick would fold it beside any other plane; register, lease
                # and health builds keep the host fold only because moving
                # them is a change of behaviour nobody has asked for yet
                # (ROADMAP C1).  Demand covers the LOG plane only: register
                # rows never migrate shards.
                self._demand_dev = jnp.zeros(self.G, jnp.float32)
        # ---- leader-lease plane (ISSUE 17) ----
        # Dense [G]/[G_reg] lease columns folded inside the fused tick:
        # holder/epoch/until live on device (authoritative for the write
        # fence); the host keeps a per-tick [5, G_total] mirror
        # (_lease_np) + its own lockstep clock for the local-read validity
        # check.  None when off: absent from the tick's planes, and the
        # fold from its program.
        self._lease = None
        self._rlease = None
        self._lease_np = None         # [5, G_total] lease_pack mirror
        self._lease_clock = 0         # host lockstep clock (+1/completed tick)
        self._lease_skew_ticks = 0    # test hook: injected holder clock skew
        self._lease_horizon = int(cfg.paxos.lease_ticks)
        self._lease_margin = int(cfg.paxos.lease_margin_ticks)
        if cfg.paxos.read_leases:
            if self._device_app:
                raise ValueError(
                    "read_leases + device_app is not supported yet: the "
                    "fused KV program has no lease formulation"
                )
            if cfg.paxos.mesh_devices:
                raise ValueError(
                    "read_leases + mesh_devices is not supported yet: the "
                    "shard_map tick has no lease formulation"
                )
            from ..ops.tick import init_lease as _init_lease

            self._lease = _init_lease(self.G, self._lease_margin)
            self._rlease = (_init_lease(self.G_reg, self._lease_margin)
                            if self.G_reg else None)
            self._lease_np = np.zeros((5, self.G_total), np.int32)
            self._lease_np[0, :] = -1  # holder column: -1 = none
        # ---- group-health plane (ISSUE 18) ----
        # Dense per-group stall/churn/heat columns folded inside the fused
        # tick; the host consumes only an O(K) health pack per tick (scalar
        # gauges + log2 histograms + top-K anomaly rows).  Observation-only:
        # nothing here feeds back into consensus, and with the flag off the
        # columns are None and the fold is not in the tick's program.
        self._health = None
        self._rhealth = None
        self._health_view = None      # HealthView as of last completed tick
        self._health_clock = 0        # host lockstep clock (+1/completed tick)
        self._health_topk = int(cfg.paxos.health_topk)
        self._health_wedge = int(cfg.paxos.health_wedge_ticks)
        self._health_shift = int(cfg.paxos.health_decay_shift)
        self._wedged_rows: set = set()   # last tick's wedged top-K rows
        self._topk_stuck: tuple = ()     # last tick's stuck top-K rows
        #: optional FlightRecorder set by the serving layer; health-state
        #: transitions (newly wedged/recovered, top-K churn) land in its
        #: ring so a SIGKILL'd cell's dump names its last-known sick groups
        self.flight = None
        if cfg.paxos.group_health:
            if self._device_app:
                raise ValueError(
                    "group_health + device_app is not supported yet: the "
                    "fused KV program has no health formulation"
                )
            if cfg.paxos.mesh_devices:
                raise ValueError(
                    "group_health + mesh_devices is not supported yet: the "
                    "shard_map tick has no health formulation"
                )
            self._health = init_health(self.G)
            self._rhealth = init_health(self.G_reg) if self.G_reg else None
        # first-occurrence scratch (generation-tagged so no per-tick clear)
        self._scr_pos = np.zeros(self.R * self.G_total, np.int64)
        self._scr_gen = np.zeros(self.R * self.G_total, np.int64)
        self._scr2_pos = None  # store-capacity scratch, allocated w/ store
        self._scr2_gen = None
        self._gen = 0
        # preallocated inbox staging buffers; entries placed last tick are
        # zeroed lazily at the next build instead of reallocating R*P*G
        self._in_req = np.zeros((self.R, self.P, self.G_total), np.int32)
        self._in_stp = np.zeros((self.R, self.P, self.G_total), bool)
        #: the two copies of them that dense ticks are handed in turn, made
        #: at the first dense build (see _build_inbox)
        self._in_handed: list = []
        #: the device's all-zero (req, stop): the first short inbox that
        #: placed nothing, handed to every such tick after it
        self._zero_inbox = None
        self._placed: list = []
        #: pipelined mode: what _complete_tick needs of a dispatched tick
        #: whose outbox was held, consumed by the next call (SURVEY §2.2
        #: item 3); None after a tick completed in its own call
        self._pending_out = None
        #: outboxes completed without being returned (by drain_pipeline(),
        #: or ahead of a newer one in the same call), oldest first, for the
        #: next tick() calls that complete none: a caller polling tick()
        #: misses none
        self._unreturned = collections.deque()
        #: whether the last _build_inbox left work behind that only another
        #: tick can place, and as much of it as it placed: what a pipelined
        #: tick holds its outbox for (tick() has the weighing)
        self._backlog = False
        #: lock-free propose staging (drained at each tick; deque append/
        #: popleft are thread-safe) + a tiny rid-assignment lock that never
        #: contends with the tick
        self._staged: collections.deque = collections.deque()
        self._rid_lock = threading.Lock()
        self._draining = False
        #: per-request flow tracing (RequestInstrumenter analog; no-op
        #: unless GPTPU_REQTRACE is set — see utils/reqtrace.py).  Each
        #: manager has its own rid namespace (all start at rid 1), drawn
        #: from a monotonic counter (id() would be reused after GC).
        self.reqtrace = _reqtrace(f"pxm:{next(_MGR_SEQ)}")
        #: always-on tick phase clock (obs/phase.py): host timestamps only —
        #: "dispatch" is enqueue cost, the device wait lands in "tally" at
        #: the unpack sync point, so no device synchronization is added.
        #: Exact device time is the profiler trace's to give.
        self._pc = _phase_clock("modea", plane=spill_ns)
        #: where a request's time goes on this plane, observed once per
        #: acknowledged scalar request when its response is handed to the
        #: held callbacks: staged -> first placed in a tick's inbox (queue),
        #: then -> that hand-over (commit).  Refused, failed and expired
        #: requests observe nothing; the bulk path carries no time per
        #: request and is left out.
        self._stage_queue_h, self._stage_commit_h = (
            _obs_registry().histogram(
                "request_stage_seconds",
                help="scalar request: staged->placed (queue), "
                     "placed->response held (commit)",
                plane=spill_ns, stage=stage)
            for stage in ("queue", "commit"))
        #: dispatched ticks by where their outbox was completed: in the call
        #: that dispatched them, or held for the next (pipeline_ticks)
        self._completions_c = {
            mode: _obs_registry().counter(
                "tick_completions_total",
                help="dispatched ticks by the call that completed their "
                     "outbox: the one that dispatched them, or the next",
                plane=spill_ns, mode=mode)
            for mode in ("same_call", "held")}
        #: what a deployment's bodies and skew cost this plane (PR 36), each
        #: a histogram of plain numbers: the requests a tick's inbox left
        #: queued behind P placed for their name; the bytes a tick handed to
        #: the journal; the bytes of each response handed to a scalar
        #: request's callback at its entry replica
        self._deferred_h = _obs_registry().histogram(
            "inbox_deferred_requests", unit="",
            help="requests a tick's inbox left queued because their name "
                 "already had proposals_per_tick placed",
            plane=spill_ns)
        self._wal_bytes_h = _obs_registry().histogram(
            "wal_append_bytes", unit="",
            help="bytes of the records a tick handed to the journal",
            plane=spill_ns)
        self._reply_bytes_h = _obs_registry().histogram(
            "app_reply_bytes", unit="",
            help="bytes execute() returned for a scalar request released "
                 "at its entry replica",
            plane=spill_ns)
        #: the part of "tally" that is blocked until the program's outbox is
        #: ready, before the pull
        self._device_wait_h = _obs_registry().histogram(
            "tick_device_wait_seconds",
            help="a tick's completion blocked until its outbox is ready on "
                 "the device, before the pull",
            plane=spill_ns)
        #: what follows the wait in "tally": the pull and the unpack; and
        #: which buffer a compacted plane's completion pulled: the head, or
        #: the whole flat buffer where the tick decided more than the head
        #: holds (ops/tick.py CompactLayout)
        self._outbox_pull_h = _obs_registry().histogram(
            "tick_outbox_pull_seconds",
            help="a tick's completion pulling its outbox to the host and "
                 "unpacking it, after the wait for the program",
            plane=spill_ns)
        self._outbox_pull_c = {
            pull: _obs_registry().counter(
                "outbox_pulls_total",
                help="compacted outbox buffers pulled, one per completed "
                     "tick and plane: the head, or the flat buffer whole",
                plane=spill_ns, pull=pull)
            for pull in ("head", "full")}
        #: how each tick's inbox reached the device: a list of its
        #: placements (or the resident all-zero inbox), or the dense arrays;
        #: and the bytes of what _build_inbox handed to the dispatch
        self._inbox_builds_c = {
            path: _obs_registry().counter(
                "inbox_builds_total",
                help="tick inboxes by how they were handed to the device: a "
                     "short list of placements scattered there, or dense",
                plane=spill_ns, path=path)
            for path in ("short", "dense")}
        self._inbox_bytes_h = _obs_registry().histogram(
            "inbox_upload_bytes", unit="",
            help="bytes of the host arrays a tick's inbox handed to the "
                 "dispatch",
            plane=spill_ns)
        #: which branch the device's compaction took for each list, one
        #: increment per compaction; mirrored from the header this loop
        #: reads anyway through the rule the device used (compact_path)
        self._compact_path_c = functools.partial(
            _obs_registry().counter, "compact_path_ticks_total",
            help="outbox compactions by list and the branch the device took "
                 "(block-sparse at the width K of sparse<K>, or dense over "
                 "the whole plane)",
            plane=spill_ns)
        #: programs a sharded plane's ticks enqueued (parallel/shard_tick.py):
        #: one tick is two dispatches, three with the placement fold
        self._mesh_dispatch_c = {}
        if self.mesh is not None:
            from ..parallel.shard_tick import MESH_PROGRAMS

            self._mesh_dispatch_c = {
                prog: _obs_registry().counter(
                    "mesh_dispatches_total",
                    help="programs enqueued by the ticks of a plane whose "
                         "state is sharded over a device mesh",
                    plane=spill_ns, program=prog)
                for prog in MESH_PROGRAMS}
        # compiles and cache lookups inside the served path are metrics
        # from the first manager of the process on
        _compiles.install()
        # Control-plane threads (messenger readers, protocol tasks) call the
        # admin/propose API while a tick driver loops on tick(); one reentrant
        # lock serializes them (the reference synchronizes on the instance map
        # the same way, PaxosManager.java:2284-2412).
        # register-plane capacity gauge (tests/test_obs_coverage.py WIRING)
        from ..obs.metrics import registry as _obsreg

        _obsreg().gauge(
            "register_groups",
            help="register-mode (RMW) row capacity of this manager",
        ).set(self.G_reg)
        # lease/read metric families (ISSUE 17; WIRING-gated)
        self._lease_gauge = _obsreg().gauge(
            "lease_holder_groups",
            help="groups with a currently granted read lease",
            node=self._ov_node)
        self._reads_local_c = _obsreg().counter(
            "reads_local_total",
            help="reads answered locally under a valid lease (no consensus "
                 "round)", node=self._ov_node)
        self._reads_fallback_c = _obsreg().counter(
            "reads_fallback_total",
            help="reads that fell back to a consensus round (no/invalid "
                 "lease or non-quiescent group)", node=self._ov_node)
        self._lease_waits_c = _obsreg().counter(
            "lease_waits_total",
            help="per-tick count of groups whose coordinator is write-"
                 "fenced waiting out a prior holder's lease",
            node=self._ov_node)
        # group-health gauge families (ISSUE 18; WIRING-gated).  Scalars
        # only: the histograms and top-K columns travel on the JSON
        # /health route, not the Prometheus scrape.
        self._hg_backlog = _obsreg().gauge(
            "health_backlogged_groups",
            help="groups with pending intake, an unexecuted assignment "
                 "frontier, or an unresolved election (health fold)",
            node=self._ov_node)
        self._hg_wedged = _obsreg().gauge(
            "health_wedged_groups",
            help="backlogged groups with no commit/exec progress for at "
                 "least health_wedge_ticks ticks", node=self._ov_node)
        self._hg_max_stall = _obsreg().gauge(
            "health_max_stall_ticks",
            help="largest per-group stall age (ticks since last progress "
                 "among backlogged groups)", node=self._ov_node)
        self._hg_max_churn = _obsreg().gauge(
            "health_max_churn",
            help="largest per-group coordinator-churn EWMA (handoffs over "
                 "a decaying window)", node=self._ov_node)
        self._hg_lease_wait = _obsreg().gauge(
            "health_lease_wait_groups",
            help="groups write-fenced behind a prior holder's lease this "
                 "tick (0 when leases are off)", node=self._ov_node)
        self.lock = ContendedLock()
        if self.wal is not None:
            self.wal.attach(self)

    # -------------------------------------------------- plane dispatch helpers
    # The composite row space is [0, G) log + [G, G_total) register; these
    # helpers are the ONLY places host code maps a composite row onto one
    # of the two device planes.  All are trivially log-plane passthroughs
    # when G_reg == 0 (rstate is None).

    def is_register_row(self, row: int) -> bool:
        return row >= self.G

    def _plane_state(self, row: int):
        """(plane_state, plane_row) for a composite row."""
        if row >= self.G:
            return self.rstate, row - self.G
        return self.state, row

    def _set_plane_state(self, row: int, new_state) -> None:
        if row >= self.G:
            self.rstate = new_state
        else:
            self.state = new_state

    def _dev_exec_np(self) -> np.ndarray:
        """Composite [R, G_total] device exec watermark (one fetch per
        plane)."""
        ex = np.asarray(self.state.exec_slot)
        if self.rstate is None:
            return ex
        return np.hstack([ex, np.asarray(self.rstate.exec_slot)])

    def _dev_exec_col(self, row: int) -> np.ndarray:
        """Device exec watermark column [R] for one composite row."""
        pst, prow = self._plane_state(row)
        return np.array(pst.exec_slot[:, prow])

    def _set_exec_status(self, r: int, row: int, exec_slot: int,
                         status: int) -> None:
        """Point-write a replica's exec watermark + status on the owning
        plane (checkpoint-transfer apply)."""
        pst, prow = self._plane_state(row)
        self._set_plane_state(row, pst._replace(
            exec_slot=pst.exec_slot.at[r, prow].set(exec_slot),
            status=pst.status.at[r, prow].set(status),
        ))

    # ------------------------------------------------------------ lease plane
    # (ISSUE 17) Host side of the read-lease columns.  The device fold in
    # ops/tick.py owns grant/renew/expiry and the write fence; the host
    # mirrors each tick's [5, G] lease_pack and answers reads against it.

    def _adopt_lease_pack(self, lease_pack) -> None:
        """Consume one tick's lease pack(s) at completion (the device sync
        point, so the pack describes the tick that just finished).  Mixed
        planes hand a (log, register) pair that lands side by side in the
        composite [5, G_total] mirror."""
        if isinstance(lease_pack, tuple):
            lp = np.concatenate([np.asarray(lease_pack[0]),
                                 np.asarray(lease_pack[1])], axis=1)
        else:
            lp = np.asarray(lease_pack)
        self._lease_np = lp
        self._lease_clock += 1  # lockstep with the device fold's clock+1
        self._lease_gauge.set(int((lp[LP_HOLDER] >= 0).sum()))
        waits = int(lp[LP_WAIT].sum())
        if waits:
            self._lease_waits_c.inc(waits)

    def _lease_drop_rows(self, rows) -> None:
        """Reset lease columns for freed rows (remove/pause/migration): a
        recycled row must not inherit the previous occupant's lease.  Row
        batches are padded to the next power of two with an out-of-range
        index (``mode="drop"`` ignores it) so the jitted clear compiles
        once per size class, not once per batch."""
        if self._lease is None or not len(rows):
            return
        if self._pending_out is not None:
            # a pending tick's lease_pack predates this drop; complete it
            # first so adoption cannot resurrect the dropped holder
            self.drain_pipeline()
        rows = np.asarray(rows, np.int32)
        lrows = rows[rows < self.G]
        rrows = rows[rows >= self.G] - np.int32(self.G)
        if len(lrows):
            self._lease = lease_clear_rows(
                self._lease, _pad_rows(lrows, self.G))
        if len(rrows) and self._rlease is not None:
            self._rlease = lease_clear_rows(
                self._rlease, _pad_rows(rrows, self.G_reg))
        if self._lease_np is not None:
            # the mirror may wrap a read-only device buffer zero-copy
            self._lease_np = np.array(self._lease_np)
            self._lease_np[LP_HOLDER, rows] = -1
            self._lease_np[LP_UNTIL, rows] = 0

    @_locked
    def lease_info(self, name: str) -> Optional[dict]:
        """Host view of one group's lease columns as of the last completed
        tick (tests/observability; None when leases are off or the group
        is not resident)."""
        if self._lease_np is None:
            return None
        row = self.rows.row(name)
        if row is None:
            return None
        lp = self._lease_np
        return {
            "holder": int(lp[LP_HOLDER, row]),
            "epoch": int(lp[LP_EPOCH, row]),
            "until": int(lp[LP_UNTIL, row]),
            "asn": int(lp[LP_ASN, row]),
            "clock": self._lease_clock,
        }

    # ----------------------------------------------------------- health plane
    # (ISSUE 18) Host side of the group-health fold.  The device owns the
    # dense stall/churn/heat columns; the host consumes one O(K) pack per
    # completed tick — scalar gauges, log2 histograms, and the top-K
    # stuckest/churniest/hottest rows — so finding the sick needles among
    # a million rows never costs an O(G) transfer.

    def _adopt_health_pack(self, health_pack) -> None:
        """Consume one tick's health pack(s) at completion (the device
        sync point, so the pack describes the tick that just finished).
        Mixed planes hand a (log, register) pair merged with register
        rows re-offset into the composite row space."""
        K = self._health_topk
        if isinstance(health_pack, tuple):
            hv = merge_health(
                unpack_health(np.asarray(health_pack[0]), min(K, self.G)),
                unpack_health(np.asarray(health_pack[1]),
                              min(K, self.G_reg)),
                self.G, K)
        else:
            hv = unpack_health(np.asarray(health_pack), min(K, self.G))
        self._health_view = hv
        self._health_clock += 1  # lockstep with the device fold's clock+1
        self._hg_backlog.set(int(hv.backlog))
        self._hg_wedged.set(int(hv.wedged))
        self._hg_max_stall.set(int(hv.max_stall))
        self._hg_max_churn.set(int(hv.max_churn) / 16.0)  # Q4 -> handoffs
        self._hg_lease_wait.set(int(hv.lease_wait))
        # transition detection -> flight ring: a SIGKILL'd cell's dump
        # should name its last-known sick groups, so newly wedged rows,
        # recoveries, and top-K membership churn are recorded as events
        stall_by_row = {int(r): int(v)
                        for v, r in zip(hv.stuck_val, hv.stuck_row)
                        if int(v) > 0}
        wedged_now = {r for r, v in stall_by_row.items()
                      if v >= self._health_wedge}
        stuck_now = tuple(sorted(stall_by_row))
        if self.flight is not None:
            for r in sorted(wedged_now - self._wedged_rows):
                self.flight.record("group_wedged", {
                    "row": r, "name": self.rows.name(r),
                    "stall_ticks": stall_by_row[r],
                    "tick": self.tick_num})
            for r in sorted(self._wedged_rows - wedged_now):
                self.flight.record("group_recovered", {
                    "row": r, "name": self.rows.name(r),
                    "tick": self.tick_num})
            if stuck_now != self._topk_stuck:
                self.flight.record("health_topk", {
                    "stuck_rows": list(stuck_now), "tick": self.tick_num})
        self._wedged_rows = wedged_now
        self._topk_stuck = stuck_now

    def _health_drop_rows(self, rows) -> None:
        """Reset health columns for freed rows (remove/pause/migration): a
        recycled row must not inherit the previous occupant's stall age or
        churn window.  Same padded-batch clear as _lease_drop_rows."""
        if self._health is None or not len(rows):
            return
        if self._pending_out is not None:
            # a pending tick's health_pack predates this drop; complete it
            # first so adoption cannot resurrect the dropped row
            self.drain_pipeline()
        rows = np.asarray(rows, np.int32)
        lrows = rows[rows < self.G]
        rrows = rows[rows >= self.G] - np.int32(self.G)
        if len(lrows):
            self._health = health_clear_rows(
                self._health, _pad_rows(lrows, self.G))
        if len(rrows) and self._rhealth is not None:
            self._rhealth = health_clear_rows(
                self._rhealth, _pad_rows(rrows, self.G_reg))

    @_locked
    def health_snapshot(self) -> Optional[dict]:
        """JSON-friendly view of the last completed tick's health pack
        (the ``/health`` route body; None when the fold is off or no tick
        has completed).  Top-K rows are resolved back to group names."""
        hv = self._health_view
        if hv is None:
            return None

        def _top(vals, rs, scale=1):
            return [{"row": int(r), "name": self.rows.name(int(r)),
                     "value": int(v) / scale}
                    for v, r in zip(vals, rs) if int(v) > 0]

        return {
            "clock": self._health_clock,
            "allocated": int(hv.alloc),
            "backlogged": int(hv.backlog),
            "wedged": int(hv.wedged),
            "max_stall_ticks": int(hv.max_stall),
            "max_churn": int(hv.max_churn) / 16.0,
            "lease_wait": int(hv.lease_wait),
            "wedge_ticks": self._health_wedge,
            "hist_stall": [int(x) for x in hv.hist_stall],
            "hist_churn": [int(x) for x in hv.hist_churn],
            "top_stuck": _top(hv.stuck_val, hv.stuck_row),
            "top_churny": _top(hv.churn_val, hv.churn_row, scale=16),
            "top_hot": _top(hv.heat_val, hv.heat_row, scale=16),
        }

    @_locked
    def group_info(self, name: str) -> Optional[dict]:
        """Upstream-style single-group drill-down (the dense analog of
        printing one PaxosInstanceStateMachine): ballot, frontiers, member
        liveness, lease columns, register version, pending intake, health
        columns, and a bounded WAL tail — all from row-gathers, no O(G)
        host work.  None when the group is not resident here.

        Accepts either the epoch-qualified paxos name (``svc#3``) or the
        bare service name — the latter resolves to the highest resident
        epoch, the same answer the reconfigurator's live-epoch map gives."""
        row = self.rows.row(name)
        if row is None and "#" not in name:
            prefix, best = name + "#", None
            for pname in self.rows.names():
                base, sep, etxt = pname.rpartition("#")
                if base == name and sep and etxt.isdigit():
                    if best is None or int(etxt) > best:
                        best = int(etxt)
            if best is not None:
                name = prefix + str(best)
                row = self.rows.row(name)
        if row is None:
            return None
        pst, prow = self._plane_state(row)
        register = row >= self.G
        member = np.asarray(pst.member[:, prow])
        bal_n = np.asarray(pst.bal_num[:, prow])
        bal_c = np.asarray(pst.bal_coord[:, prow])
        exec_s = np.asarray(pst.exec_slot[:, prow])
        next_s = np.asarray(pst.next_slot[:, prow])
        status = np.asarray(pst.status[:, prow])
        coord_a = np.asarray(pst.coord_active[:, prow])
        coord_p = np.asarray(pst.coord_preparing[:, prow])
        members = [int(r) for r in np.nonzero(member)[0]]
        replicas = {
            int(r): {
                "alive": bool(self.alive[r]),
                "ballot": [int(bal_n[r]), int(bal_c[r])],
                "exec_slot": int(exec_s[r]),
                "next_slot": int(next_s[r]),
                "status": int(status[r]),
                "coordinator": bool(coord_a[r]),
                "preparing": bool(coord_p[r]),
            }
            for r in members
        }
        info = {
            "name": name,
            "row": int(row),
            "mode": "register" if register else "log",
            "epoch": int(np.asarray(pst.epoch[prow])),
            "members": members,
            "replicas": replicas,
            "stopped": row in self._stopped_rows,
            "pending_intake": len(self._queues.get(row) or ())
            + int(self._row_outstanding[row]),
            "tick": self.tick_num,
        }
        if register and members:
            # register-plane rows carry one in-place value; the executed
            # slot IS its monotone version counter (RMWPaxos)
            info["version"] = max(int(exec_s[r]) for r in members)
        if self._lease_np is not None:
            info["lease"] = self.lease_info(name)
        if self._health is not None:
            h = self._rhealth if register else self._health
            info["health"] = {
                "stall_ticks": int(h.clock) - int(h.last_active[prow]),
                "coordinator": int(h.last_coord[prow]),
                "churn": int(h.churn[prow]) / 16.0,
                "heat": int(h.heat[prow]) / 16.0,
            }
        if self.wal is not None:
            try:
                info["wal_tail"] = self.wal.tail_for_row(row, name)
            except Exception:
                info["wal_tail"] = None
        return info

    def read(
        self,
        name: str,
        payload: bytes = b"",
        callback: Optional[Callable[[int, bytes], None]] = None,
        deadline: Optional[int] = None,
    ) -> Optional[int]:
        """Linearizable read (ISSUE 17).

        Answered LOCALLY — no consensus round, no journal entry — iff the
        last completed tick's lease mirror shows a live holder whose lease
        has not expired (minus any injected skew) AND the group is
        quiescent: the holder's executed frontier equals the accepted
        frontier as of that same tick, so every acked write is already
        applied at the holder.  Otherwise the read falls back to a
        CLS_READ propose through the ordered stream (a classic consensus
        read), which also renews liveness for the next attempt.

        ``payload`` must be side-effect-free under the app's ``execute``
        (the same payload may execute once locally or R times via the
        fallback).  The callback fires ``(rid, response)`` like propose's;
        local reads use rid 0 and fire synchronously.
        """
        if deadline is not None and _overload.expired(deadline):
            _overload.count_expired("intake", self._ov_node)
            if callback is not None:
                callback(_overload.RID_EXPIRED, None)
            return None
        row = self.rows.row(name)  # racy read: benign (propose's argument)
        lp = self._lease_np
        if (lp is not None and row is not None
                and row not in self._stopped_rows):
            holder = int(lp[LP_HOLDER, row])
            if (holder >= 0 and self.alive[holder]
                    and (self._lease_clock - self._lease_skew_ticks)
                    < int(lp[LP_UNTIL, row])
                    and int(self._host_exec[holder, row])
                    == int(lp[LP_ASN, row])):
                resp = self.apps[holder].execute(name, payload, 0)
                self._reads_local_c.inc()
                self.stats["local_reads"] += 1
                if callback is not None:
                    callback(0, resp)
                return 0
        self._reads_fallback_c.inc()
        return self.propose(name, payload, callback, deadline=deadline,
                            cls=_overload.CLS_READ)

    # ------------------------------------------------------------------ admin
    @_locked
    def create_paxos_instance(
        self, name: str, members: List[int], epoch: int = 0,
        register: bool = False,
    ) -> bool:
        """createPaxosInstance analog (PaxosManager.java:611).

        ``register=True`` births the group on the register plane (in-place
        RMW consensus; requires cfg.paxos.register_groups > 0) — the mode
        is permanent for the group's lifetime and journaled with the
        create."""
        if name in self.rows or name in self._paused:
            return False
        if register and not self.G_reg:
            raise ValueError(
                "register-mode create requires paxos.register_groups > 0")
        if register:
            if self.rows.full(hi=True):
                return False
            row = self.rows.alloc(name, hi=True)
        else:
            row = self._alloc_row(name)
        if row is None:
            return False
        mask = np.zeros((1, self.R), bool)
        for m in members:
            mask[0, m] = True
        pst, prow = self._plane_state(row)
        self._set_plane_state(row, st.create_groups(
            pst,
            np.array([prow], np.int32),
            mask,
            np.array([epoch], np.int32),
        ))
        self._set_member_row(row, mask[0], name)
        self._stopped_rows.discard(row)
        self._stopped_np[row] = False
        self._last_active[row] = self.tick_num
        if self.wal is not None:
            self.wal.log_create(name, members, epoch, register=register)
        return True

    @_locked
    def create_paxos_instance_at(
        self, name: str, members: List[int], epoch: int, row: int,
        app_seed: Optional[bytes] = None,
    ) -> bool:
        """Targeted create at a SPECIFIC free row (placement migration:
        the destination row selects the destination mesh shard).

        Unlike :meth:`create_paxos_instance` this never evicts — a full
        destination shard is a planning failure, not an excuse to spill
        someone else's group.  ``app_seed`` (the migrated epoch's final
        checkpoint) is restored into every member's app UNDER THE SAME
        LOCK as the birth and journaled WITH the create (OP_CREATE_AT):
        the plain create path's seed is applied by the caller and never
        journaled, which is fine for empty births but would lose a
        migrated group's state on replay."""
        if name in self.rows or name in self._paused:
            return False
        try:
            self.rows.alloc_at(name, row)
        except KeyError:
            return False  # row occupied / out of range
        mask = np.zeros((1, self.R), bool)
        for m in members:
            mask[0, m] = True
        # the row index encodes the mode: a targeted create at a register
        # row lands on the register plane with no extra record field
        pst, prow = self._plane_state(row)
        self._set_plane_state(row, st.create_groups(
            pst,
            np.array([prow], np.int32),
            mask,
            np.array([epoch], np.int32),
        ))
        self._set_member_row(row, mask[0], name)
        self._stopped_rows.discard(row)
        self._stopped_np[row] = False
        self._last_active[row] = self.tick_num
        if app_seed is not None:
            for s in members:
                self.apps[s].restore(name, app_seed)
        if self.wal is not None:
            self.wal.log_create_at(name, list(members), epoch, row, app_seed)
        return True

    def create_paxos_instances(
        self, names: List[str], members: List[int], epoch: int = 0
    ) -> int:
        """Batched createPaxosInstance: one device call + one WAL
        group-commit for the whole batch (the BatchedCreateServiceName
        shape, gigapaxos/PaxosManager.java:611 + batched creates).  Returns
        how many were created; names already present are skipped and
        capacity overflow spills to the single-create path (which can
        evict cold rows)."""
        if not all(0 <= m < self.R for m in members):
            raise ValueError(f"member slots out of range [0, {self.R}): "
                             f"{members}")
        with self.lock:
            fresh = list(dict.fromkeys(  # order-preserving dedup
                n for n in names
                if n not in self.rows and n not in self._paused
            ))
            take = fresh[:self.rows.free_count()]
            rest = fresh[len(take):]
            if take:
                rows = np.array([self.rows.alloc(n) for n in take], np.int32)
                mask = np.zeros((len(take), self.R), bool)
                mask[:, members] = True
                self.state = st.create_groups(
                    self.state, rows, mask,
                    np.full(len(take), epoch, np.int32),
                )
                # vectorized host-mirror refresh (the batched analog of
                # _set_member_row)
                self._member_np[:, rows] = mask.T
                self._n_members_np[rows] = mask.sum(axis=1)
                bits = int(np.bitwise_or.reduce(
                    (1 << np.array(members, np.int64))
                )) if members else 0
                self._member_bits[rows] = bits
                self._row_name_np[rows] = take
                self._member_ord = None
                self._stopped_np[rows] = False
                self._stopped_rows.difference_update(int(r) for r in rows)
                self._last_active[rows] = self.tick_num
                if self.wal is not None:
                    # one fsync for the whole batch, not one per name
                    self.wal.log_creates(take, list(members), epoch)
            made = len(take)
        for n in rest:  # overflow: single-create path (may evict)
            if self.create_paxos_instance(n, list(members), epoch):
                made += 1
        return made

    def _set_member_row(self, row, mask, name) -> None:
        """Refresh every host mirror of one row's config (mask: [R] bool)."""
        self._member_np[:, row] = mask
        self._n_members_np[row] = mask.sum()
        self._member_bits[row] = int(
            np.bitwise_or.reduce((1 << np.where(mask)[0]).astype(np.int64))
        ) if mask.any() else 0
        self._row_name_np[row] = name
        self._member_ord = None

    def _clear_member_rows(self, rows) -> None:
        self._host_exec[:, rows] = 0  # recycled rows restart at slot 0
        self._member_np[:, rows] = False
        self._n_members_np[rows] = 0
        self._member_bits[rows] = 0
        self._row_name_np[rows] = None
        self._member_ord = None

    @_locked
    def remove_paxos_instance(self, name: str) -> bool:
        """kill/cremation analog (PaxosManager.java:2162-2205)."""
        if name in self._paused:
            del self._paused[name]
            if self.wal is not None:
                self.wal.log_remove(name)
            return True
        row = self.rows.row(name)
        if row is None:
            return False
        # a pipelined pending outbox may still reference this row under its
        # OLD name<->row mapping; complete it before the row is freed (and
        # possibly recycled) so stale placements/decisions cannot resolve
        # against a future occupant
        self.drain_pipeline()
        pst, prow = self._plane_state(row)
        self._set_plane_state(
            row, st.free_groups(pst, np.array([prow], np.int32)))
        self._kv_clear_rows([row])
        self._clear_member_rows([row])
        self._lease_drop_rows([row])
        self._health_drop_rows([row])
        self.rows.free(name)
        self._fail_queued(row)
        self._purge_row_outstanding(row)
        if self.bulk is not None:
            gone = np.nonzero(self.bulk.valid & (self.bulk.row == row))[0]
            if len(gone):
                if self._bulk_cbs or self._sink_blocks:
                    self._bulk_fire(
                        self.bulk.rid[gone[~self.bulk.responded[gone]]]
                    )
                self.stats["failed_requests"] += self.bulk.fail(gone)
        self._stopped_rows.discard(row)
        self._stopped_np[row] = False
        if self.wal is not None:
            self.wal.log_remove(name)
        return True

    @_locked
    def group_members(self, name: str) -> Optional[List[int]]:
        if name in self._paused:
            hri = self._paused[name]
            return [int(r) for r in np.where(hri["member"])[0]]
        row = self.rows.row(name)
        if row is None:
            return None
        return [int(r) for r in np.where(self._member_np[:, row])[0]]

    @_locked
    def is_stopped(self, name: str) -> bool:
        if name in self._paused:
            return bool(self._paused[name].get("stopped"))
        row = self.rows.row(name)
        return row is not None and row in self._stopped_rows

    @_locked
    def exec_watermarks(self, name: str) -> Optional[np.ndarray]:
        """Per-replica-slot execution watermark for the group ([R] int), the
        donor-selection signal for checkpoint transfer: only a replica at
        the group maximum holds the complete (e.g. epoch-final) state."""
        if name in self._paused:
            return np.array(self._paused[name]["exec_slot"])
        row = self.rows.row(name)
        if row is None:
            return None
        return self._dev_exec_col(row)

    # ---------------------------------------------------------- placement
    def shard_geometry(self) -> tuple:
        """(groups_shards, rows_per_shard): mesh shard k owns the
        contiguous row range [k*per, (k+1)*per)."""
        gs = 1
        if self.mesh is not None:
            from ..parallel.mesh import GROUPS_AXIS as _GAX

            gs = self.mesh.shape[_GAX]
        return gs, self.G // gs

    @_locked
    def free_rows_in_shard(self, shard: int) -> int:
        """Free-row capacity of one mesh shard (rebalancer's budget)."""
        gs, _per = self.shard_geometry()
        lo, hi = st.shard_row_range(self.G, gs, shard)
        return sum(1 for r in self.rows._free if lo <= r < hi)

    @_locked
    def blob_bytes_of_row(self, row: int) -> int:
        """Checkpoint-blob size a migration of ``row`` would transfer (the
        rebalancer's move-cost estimator; MigrationStats.bytes_transferred
        records the same quantity after the fact).  0 for free rows.

        Serializes one member's checkpoint, so call it only at plan time
        (the rebalancer probes a handful of near-tie candidates per plan,
        and plans are min-interval paced) — never per tick."""
        name = self.rows.name(int(row))
        if name is None:
            return 0
        for r in range(self.R):
            if self.alive[r] and self._member_np[r, int(row)]:
                blob = self.apps[r].checkpoint(name)
                return len(blob) if blob is not None else 0
        return 0

    def demand_snapshot(self):
        """Host view of the per-group demand EWMA [G] (None when the
        placement plane is disabled).  Device-folded demand is pulled at
        most every ``placement.sample_every_ticks`` ticks."""
        p = self._placement
        if p is None:
            return None
        if self._demand_dev is not None and p.should_sample():
            p.sample_device()  # one device->host pull per sample window
        return p.demand_snapshot()

    # ------------------------------------------------------------ pause/spill
    def _resident_row(self, name: str) -> Optional[int]:
        """Row of ``name``, transparently unpausing a spilled group
        (getInstance -> unpause, PaxosManager.java:2370-2412)."""
        row = self.rows.row(name)
        if row is not None:
            return row
        if name in self._paused:
            return self._unpause(name)
        return None

    def _alloc_row(self, name: str) -> Optional[int]:
        """Row allocation with eviction under pressure: a full table
        force-pauses the coldest quiescent group to make room."""
        if self.rows.full():
            evicted = self._pause_eligible(limit=1, ignore_idle=True)
            if not evicted:
                return None  # every row is hot — table genuinely full
        return self.rows.alloc(name)

    @_locked
    def pause_idle(self, limit: int = 64) -> int:
        """Deactivator analog (PaxosManager.java:2951, period
        PC.DEACTIVATION_PERIOD): spill groups idle for
        ``deactivation_ticks``.  Returns the number paused."""
        # Nobody idle that long is the common answer (every tick a process
        # runs before its ``deactivation_ticks``-th, and any plane whose
        # names are all in use), and one pass over the activity column
        # gives it.  Finding it out below costs a drained pipeline, two
        # [R, G] pulls and a sort of every resident row in Python: a tick of
        # 0.5-1 s at 1M groups, every 256 ticks, inside every measured
        # window (PERF.md section 6, PR 36).
        active = self._last_active[self._n_members_np > 0]
        if (not active.size or self.tick_num - int(active.min())
                < self.cfg.paxos.deactivation_ticks):
            return 0
        return len(self._pause_eligible(limit=limit, ignore_idle=False))

    def _pause_eligible(self, limit: int, ignore_idle: bool) -> List[str]:
        # quiescence is judged against host bookkeeping — admit staged
        # proposals and complete any pipelined pending outbox first so the
        # judgment is current (and no stale placement can target a row this
        # call is about to free)
        self._drain_staged()
        self.drain_pipeline()
        idle_after = 0 if ignore_idle else self.cfg.paxos.deactivation_ticks
        exec_slot = np.array(self.state.exec_slot)
        next_slot = np.array(self.state.next_slot)
        member = self._member_np
        # rows referenced by live/queued bulk requests are not pausable
        # (bulk requests are invisible to _row_outstanding)
        bulk_ref = None
        if self.bulk is not None and (
            self.bulk.n_live or self._bulk_leftover.size or self._bulk_chunks
        ):
            bulk_ref = np.zeros(self.G_total, bool)
            bulk_ref[self.bulk.row[self.bulk.valid]] = True
            parts = ([self._bulk_leftover] if self._bulk_leftover.size
                     else []) + self._bulk_chunks
            if parts:
                q = np.concatenate(parts)
                qi, qlive = self.bulk.lookup(q)
                bulk_ref[self.bulk.row[qi[qlive]]] = True
        # coldest first so eviction keeps the working set hot
        cands = sorted(
            self.rows.items(), key=lambda kv: self._last_active[kv[1]]
        )
        paused: List[str] = []
        for name, row in cands:
            if len(paused) >= limit:
                break
            if row >= self.G:
                # register rows never pause: their whole footprint is the
                # register cell (no ring to reclaim), and hot_restore/HRI
                # extraction are log-plane shaped
                continue
            if self.tick_num - self._last_active[row] < idle_after:
                if not ignore_idle:
                    break  # sorted: everything later is hotter
                continue
            if self._queues.get(row) or self._row_outstanding[row] > 0:
                continue
            if bulk_ref is not None and bulk_ref[row]:
                continue
            ms = np.where(member[:, row])[0]
            if len(ms) == 0:
                continue
            ex = exec_slot[ms, row]
            # quiescent = every member executed everything anyone assigned
            if ex.min() != ex.max() or next_slot[ms, row].max() > ex.min():
                continue
            paused.append(name)
        if paused:
            self._do_pause(paused)
            if self.wal is not None:
                self.wal.log_pause(paused)
        return paused

    def _kv_clear_rows(self, rows) -> None:
        """Scrub device-app KV rows on free: a recycled row must not leak
        the previous occupant's keys to the next group."""
        if self.kv is not None and len(rows):
            r = np.asarray(rows, np.int32)
            self.kv = self.kv._replace(
                key=self.kv.key.at[:, r].set(0),
                val=self.kv.val.at[:, r].set(0),
            )

    def _do_pause(self, names: List[str]) -> None:
        """Spill exactly ``names`` (selection already done — also the WAL
        replay entry point, which must mirror the original run's choice so
        row allocation stays in lockstep)."""
        rows_to_free = []
        for name in names:
            row = self.rows.row(name)
            hri = st.extract_hri(self.state, row)
            hri["stopped"] = row in self._stopped_rows
            if self.kv is not None:
                # device-app state is keyed by ROW — it must ride the
                # spilled record or pause would silently drop it
                hri["dkv_key"] = np.asarray(self.kv.key[:, row])
                hri["dkv_val"] = np.asarray(self.kv.val[:, row])
            self._paused[name] = hri
            rows_to_free.append(row)
        self.state = st.free_groups(self.state, np.array(rows_to_free, np.int32))
        self._kv_clear_rows(rows_to_free)
        self._clear_member_rows(rows_to_free)
        self._lease_drop_rows(rows_to_free)
        self._health_drop_rows(rows_to_free)
        for name in names:
            row = self.rows.free(name)
            self._stopped_rows.discard(row)
            self._stopped_np[row] = False
            self._queues.pop(row, None)
        self.stats["paused"] += len(names)

    def _unpause(self, name: str) -> Optional[int]:
        hri = self._paused.get(name)
        if hri is None:
            return None
        row = self._alloc_row(name)
        if row is None:
            return None
        del self._paused[name]
        # reset the row to a clean slate, then restore the scalar columns
        mask = hri["member"].reshape(1, -1)
        self.state = st.create_groups(
            self.state, np.array([row], np.int32), mask,
            np.array([hri["epoch"]], np.int32),
        )
        self._set_member_row(row, mask[0], name)
        self.state = st.hot_restore(self.state, row, hri)
        # pause spills drained state (host == device), so the restored
        # device watermark is also the host-applied one for this row
        self._host_exec[:, row] = np.asarray(
            self.state.exec_slot[:, row]).astype(np.int32)
        if self.kv is not None and "dkv_key" in hri:
            self.kv = self.kv._replace(
                key=self.kv.key.at[:, row].set(jnp.asarray(hri["dkv_key"])),
                val=self.kv.val.at[:, row].set(jnp.asarray(hri["dkv_val"])),
            )
        if hri.get("stopped"):
            self._stopped_rows.add(row)
            self._stopped_np[row] = True
        self._last_active[row] = self.tick_num
        self.stats["unpaused"] += 1
        if self.wal is not None:
            self.wal.log_unpause(name)
        return row

    def paused_count(self) -> int:
        return len(self._paused)

    # ---------------------------------------------------------------- propose
    def propose(
        self,
        name: str,
        payload: bytes,
        callback: Optional[Callable[[int, bytes], None]] = None,
        stop: bool = False,
        entry: Optional[int] = None,
        deadline: Optional[int] = None,
        cls: int = _overload.CLS_CONTROL,
    ) -> Optional[int]:
        """propose/proposeStop analog (PaxosManager.java:1214-1288).

        ``deadline``: absolute wire deadline (unix ms); a request still
        staged when it passes is dropped at intake with callback
        ``(RID_EXPIRED, None)`` — dead work never reaches the device.
        ``cls``: traffic class; CLS_CLIENT proposes are refused with a
        retriable busy NACK ``(RID_BUSY, None)`` while the intake
        governor sheds, CLS_CONTROL (default) is never governed.

        Returns the request id, or None if the group is unknown (or fenced
        by a stop).  The common case takes NO manager lock: the request is
        staged into a thread-safe deque the next tick drains (the
        RequestBatcher.enqueue decoupling, gigapaxos/RequestBatcher.java:
        25-60) — so a client thread's propose latency is O(1) instead of
        up to a full tick of lock wait.  On the single-core artifact box
        end-to-end throughput is unchanged (within the run-to-run band);
        the decoupling targets multi-core hosts, where client threads no
        longer serialize behind the tick.  The existence/fenced pre-checks
        are racy reads; the authoritative outcome always rides the
        callback (a request staged for a group that is removed or stops
        before the drain fails with response None, as before).
        """
        if self.wal is not None and not self.wal.accepting_writes():
            return self._shed_propose(callback)
        if (cls != _overload.CLS_CONTROL and self.overload is not None
                and not self.overload.admit(cls)):
            return self._shed_busy(callback, cls)
        row = self.rows.row(name)  # racy read: benign (see docstring)
        if row is None:
            if name in self._paused:
                # cold group: unpause needs the lock anyway (rare path)
                return self._propose_locked(name, payload, callback, stop,
                                            entry)
            return None
        if row in self._stopped_rows:
            return self._propose_locked(name, payload, callback, stop, entry)
        with self._rid_lock:
            rid = self._next_rid
            self._next_rid += 1
        self._staged.append((rid, name, payload, callback, stop, entry,
                             deadline, time.perf_counter()))
        if self.reqtrace.enabled:
            self.reqtrace.event(rid, "staged", name=name)
        return rid

    @_locked
    def _shed_propose(self, callback):
        """Storage low-watermark / failed WAL: refuse new writes with the
        retriable failure convention (response None) while reads and the
        already-admitted pipeline keep serving.  The disk-full case clears
        itself once the GC or an operator frees space; the failed case
        fail-stops the node at the next tick anyway."""
        self.wal.note_shed()
        if callback is not None:
            self._held_callbacks.append((callback, -1, None))
        self.stats["shed_requests"] += 1
        self.stats["failed_requests"] += 1
        return None

    @_locked
    def _shed_busy(self, callback, cls: int = _overload.CLS_CLIENT):
        """Intake governor shed (ISSUE 14): the explicit retriable NACK —
        the callback fires with RID_BUSY so the edge answers ``busy``
        (retry the SAME active after backoff) instead of a silent drop or
        a misleading ``not_active``.  ``cls`` labels the shed counter
        (client writes vs lease-era consensus-fallback reads)."""
        if callback is not None:
            self._held_callbacks.append((callback, _overload.RID_BUSY, None))
        self.stats["shed_requests"] += 1
        _overload.count_shed(cls, "intake", self._ov_node)
        return None

    @_locked
    def _propose_locked(self, name, payload, callback, stop, entry):
        """Slow path (cold or fenced groups): the original locked propose."""
        row = self._resident_row(name)
        if row is None:
            return None
        if row in self._stopped_rows:
            # stopped epoch: fail fast so the client can re-resolve actives
            if callback is not None:
                self._held_callbacks.append((callback, -1, None))
            self.stats["failed_requests"] += 1
            return None
        with self._rid_lock:
            rid = self._next_rid
            self._next_rid += 1
        if self.reqtrace.enabled:
            self.reqtrace.event(rid, "staged", name=name, path="slow")
        self._admit(rid, name, row, payload, callback, stop, entry,
                    time.perf_counter())
        return rid

    def _admit(self, rid, name, row, payload, callback, stop, entry,
               t_staged) -> None:
        """Insert one request into the per-row queues (manager lock held)."""
        if isinstance(payload, bytes):
            payload = self._paystore.intern(payload)
        members = np.where(self._member_np[:, row])[0]
        if entry is None or entry not in members:
            # spread entry replicas across the group's members (not the whole
            # replica set — a non-member never executes, so its callback
            # would be orphaned)
            entry = int(members[rid % len(members)]) if len(members) else 0
        rec = RequestRecord(rid, name, row, payload, stop, callback, entry,
                            t_staged=t_staged)
        self.outstanding[rid] = rec
        self._row_outstanding[row] += 1
        self._queues[row].append(rid)
        self._last_active[row] = self.tick_num
        if self.reqtrace.enabled:
            self.reqtrace.event(rid, "admitted", row=row, entry=entry)

    def _drain_staged(self) -> None:
        """Admit every staged proposal (start of each tick, lock held).

        Re-entrancy guard: draining a request for a PAUSED group unpauses
        it, which under row pressure evicts via ``_pause_eligible`` — which
        itself drains staged work.  Without the guard that cycle double-
        unpauses a group (crash) or recurses once per staged cold item."""
        if self._draining:
            return
        self._draining = True
        try:
            while True:
                try:
                    (rid, name, payload, callback, stop, entry, deadline,
                     t_staged) = self._staged.popleft()
                except IndexError:
                    return
                if _overload.expired(deadline):
                    # deadline passed while staged: nobody is waiting, so
                    # admitting it would burn a device slot on dead work.
                    # RID_EXPIRED tells the edge to settle silently (the
                    # drop is counted ONCE, here at the detecting stage).
                    if callback is not None:
                        self._held_callbacks.append(
                            (callback, _overload.RID_EXPIRED, None))
                    self.stats["expired_drops"] += 1
                    _overload.count_expired("intake", self._ov_node)
                    continue
                row = self._resident_row(name)
                if row is None or row in self._stopped_rows:
                    # the group vanished or stopped between stage and drain
                    if callback is not None:
                        self._held_callbacks.append((callback, rid, None))
                    self.stats["failed_requests"] += 1
                    if self.reqtrace.enabled:
                        self.reqtrace.event(rid, "failed", name=name)
                    continue
                self._admit(rid, name, row, payload, callback, stop, entry,
                            t_staged)
        finally:
            self._draining = False

    def propose_stop(self, name: str, payload: bytes = b"", callback=None):
        return self.propose(name, payload, callback, stop=True)

    # -------------------------------------------------------- bulk (fast path)
    def _ensure_bulk(self) -> BulkStore:
        if self.bulk is None:
            self.bulk = BulkStore(self._bulk_cap)
            self._scr2_pos = np.zeros(self._bulk_cap, np.int64)
            self._scr2_gen = np.zeros(self._bulk_cap, np.int64)
        return self.bulk

    def _member_ordinals(self) -> np.ndarray:
        """[R, G] ordinal of each member within its group (cached; config
        changes invalidate)."""
        if self._member_ord is None:
            m = self._member_np.astype(np.int32)
            self._member_ord = np.cumsum(m, axis=0) - m
        return self._member_ord

    @_locked
    def propose_bulk(self, rows, payloads, stops=None,
                     callbacks=None, entries=None,
                     batch_sink=None,
                     cls: int = _overload.CLS_CLIENT) -> np.ndarray:
        """Vectorized propose: admit one request per entry of ``rows`` (row
        indices into the group table) in a single columnar operation.

        ``payloads``: one bytes object (shared by all — generated-load
        fan-out) or a sequence of per-request bytes.  Returns the assigned
        rid array (int64); negative entries were not admitted and no
        callback fires for them: -1 = target row unknown/stopped (client
        must re-resolve), -2 = store window full (transient backpressure —
        plain retry, nothing is wrong with the placement).
        ``callbacks``: optional per-request
        ``cb(rid, response_or_None)`` list aligned with ``rows``; fires
        through the durability-gated callback queue exactly like scalar
        proposes (log-before-respond).  Without callbacks, completion is
        observable through :meth:`bulk_stats` / :meth:`bulk_response`
        (the open-loop TESTPaxosClient model, testing/TESTPaxosClient.java:59).
        """
        if self._device_app and not getattr(self, "_in_kv_admit", False):
            raise ValueError(
                "device-app managers admit bulk work via propose_bulk_kv "
                "(a plain payload has no descriptor and could never place)"
            )
        if self.wal is not None and not self.wal.accepting_writes():
            # storage low-watermark / failed WAL: whole batch sheds with
            # the transient-backpressure code (-2, plain retry) — same
            # contract as a full store window; no callback fires
            self.wal.note_shed()
            n = len(rows)
            self.stats["shed_requests"] += n
            self.stats["failed_requests"] += n
            return np.full(n, -2, np.int64)
        if (cls == _overload.CLS_CLIENT and self.overload is not None
                and not self.overload.admit(cls)):
            # intake governor shed: whole batch refused with the transient
            # busy code (-2, retry the same active) — bulk is client-class
            # by default, the only control-class bulk caller is the RC
            # plane's own manager which passes CLS_CONTROL explicitly
            n = len(rows)
            self.stats["shed_requests"] += n
            _overload.count_shed(_overload.CLS_CLIENT, "intake",
                                 self._ov_node, n)
            return np.full(n, -2, np.int64)
        store = self._ensure_bulk()
        rows = np.asarray(rows, np.int64)
        out = np.full(len(rows), -1, np.int64)
        ok = (self._n_members_np[rows] > 0) & ~self._stopped_np[rows]
        if stops is not None:
            stops = np.asarray(stops, bool)
        if not ok.all():
            self.stats["failed_requests"] += int((~ok).sum())
            rows = rows[ok]
            if stops is not None:
                stops = stops[ok]
            if not isinstance(payloads, (bytes, bytearray)):
                payloads = [p for p, o in zip(payloads, ok) if o]
            if callbacks is not None:
                callbacks = [c for c, o in zip(callbacks, ok) if o]
        n = len(rows)
        if n == 0:
            return out
        # bounded-outstanding backpressure: admit only what the store
        # window can hold; the remainder returns -1 (retry later) instead
        # of raising mid-batch (MAX_OUTSTANDING_REQUESTS throttle analog)
        store._advance_lo()
        with self._rid_lock:
            rid0 = self._next_rid
            if store.n_live == 0:
                store.lo = rid0  # empty store: no slot can collide
                room = store.cap
            else:
                room = store.cap - (rid0 - store.lo)
            n_adm = max(0, min(n, room))
            self._next_rid += n_adm
        if self._next_rid >= 2**31:
            raise OverflowError("rid space exhausted (int32 device ids)")
        if n_adm == 0:
            self.stats["backpressured"] += n
            out[ok] = -2
            return out
        if n_adm < n:
            self.stats["backpressured"] += n - n_adm
            out[np.nonzero(ok)[0][n_adm:]] = -2
            rows = rows[:n_adm]
            if stops is not None:
                stops = stops[:n_adm]
            if not isinstance(payloads, (bytes, bytearray)):
                payloads = payloads[:n_adm]
        # spread entry duty across each group's members by rid rotation
        # (or pin it to a requested member — the edge node that owns the
        # client connection — falling back to rotation for non-members)
        nm = self._n_members_np[rows]
        k = ((rid0 + np.arange(n_adm)) % nm).astype(np.int32)
        om = self._member_ordinals()
        ent = np.zeros(n_adm, np.int32)
        for r in range(self.R):
            sel = self._member_np[r, rows] & (om[r, rows] == k)
            ent[sel] = r
        if entries is not None:
            e = int(entries)
            ent = np.where(self._member_np[e, rows], e, ent).astype(np.int32)
        if self.cfg.paxos.emulate_unreplicated:
            # measurement baseline (emulateUnreplicated,
            # PaxosManager.java:1751-1799): execute at the entry replica NOW,
            # respond, touch nothing else — no store, no tick, no journal
            resps = self._baseline_exec(
                rows, ent, payloads, rid0 + np.arange(n_adm, dtype=np.int64),
                callbacks, eager_fire=True,
            )
            if batch_sink is not None:
                # inline columnar delivery: no tick ever runs to route a
                # sink block, and the baseline has no durability to gate
                batch_sink(np.arange(n_adm, dtype=np.int64), resps)
            self.stats["decisions"] += n_adm
            out[np.nonzero(ok)[0][:n_adm]] = rid0 + np.arange(n_adm)
            return out
        if not isinstance(payloads, (bytes, bytearray)):
            # per-request bodies: intern so duplicates across the batch (and
            # across batches) collapse to one shared object before the store,
            # WAL, and batch frames ever see them
            payloads = [
                self._paystore.intern(p) if isinstance(p, bytes) else p
                for p in payloads
            ]
        rids = store.admit(rid0, rows.astype(np.int32), ent, stops,
                           payloads)
        if batch_sink is not None:
            # columnar completion: ONE sink call per tick delivers this
            # block's finished (offset, response) columns — no per-request
            # callback objects anywhere.  offsets are rid - rid0, i.e. the
            # caller's admitted-item order.
            self._sink_blocks.append([rid0, n_adm, n_adm, batch_sink])
        if callbacks is not None:
            for rid, cb in zip(rids, callbacks):
                if cb is not None:
                    self._bulk_cbs[int(rid)] = cb
        if self.cfg.paxos.lazy_propagation:
            # measurement baseline (emulateLazyPropagation /
            # EXECUTE_UPON_ACCEPT): the entry replica executes + responds
            # immediately; the admitted request still rides the normal
            # consensus stream, so the other replicas converge through
            # ordinary decisions (their mark_executed skips the entry's
            # pre-set bit — no double execution)
            idx = store.idx_of(rids)
            self._baseline_exec(rows, ent, store.payload[idx], rids,
                                callbacks=None, eager_fire=False,
                                store_idx=idx)
        self._bulk_chunks.append(rids)
        self._last_active[rows] = self.tick_num
        out[np.nonzero(ok)[0][:n_adm]] = rids
        return out

    def _baseline_exec(self, rows, ent, payloads, rids, callbacks,
                       eager_fire: bool, store_idx=None) -> list:
        """Entry-replica immediate execution for the two measurement
        baselines.  With ``store_idx`` (lazy mode) the store's entry exec
        bit + responded flag are pre-set so commit-time execution skips
        the entry replica and never re-responds.  Returns the responses
        aligned with the input order (b"" where the app returned none)."""
        if isinstance(payloads, (bytes, bytearray)):
            pa = np.empty(len(rows), object)
            pa[:] = bytes(payloads)
            payloads = pa
        payloads = np.asarray(payloads, object)
        rows = np.asarray(rows, np.int64)
        eager: list = []
        out_resps: list = [b""] * len(rows)
        for r in range(self.R):
            sel = ent == r
            if not sel.any():
                continue
            erb = getattr(self.apps[r], "execute_rows_batch", None)
            if erb is not None:
                resp = erb(rows[sel], payloads[sel], rids[sel])
            else:
                resp = self.apps[r].execute_batch(
                    self._row_name_np[rows[sel]], payloads[sel], rids[sel]
                )
            self.stats["executions"] += int(sel.sum())
            if store_idx is not None:
                si = store_idx[sel]
                self.bulk.exec_mask[si] |= np.int64(1) << r
                self.bulk.responded[si] = True
                if resp is not None:
                    ra = np.empty(len(si), object)
                    ra[:] = resp
                    self.bulk.response[si] = ra
                if self._bulk_cbs or self._sink_blocks:
                    self._bulk_fire(rids[sel],
                                    resp if resp is not None
                                    else [b""] * int(sel.sum()))
            else:
                for pos, j in enumerate(np.nonzero(sel)[0]):
                    r_j = resp[pos] if resp is not None else b""
                    out_resps[j] = r_j or b""
                    if eager_fire and callbacks is not None \
                            and callbacks[j] is not None:
                        # fired inline below — NEVER through the shared
                        # durability-gated queue, whose other occupants
                        # must keep waiting for their WAL sync
                        eager.append((callbacks[j], int(rids[j]),
                                      r_j or b""))
        if eager_fire:
            # the unreplicated baseline responds inline (no durability by
            # definition)
            for cb, rid, resp in eager:
                cb(rid, resp)
        return out_resps

    def _bulk_fire(self, rids, responses=None) -> None:
        """Queue completion callbacks for bulk rids that just reached their
        responded transition (durability-gated like every response)."""
        if self._sink_blocks:
            self._sink_route(rids, responses)
        if not self._bulk_cbs:
            return
        if responses is None:
            for rid in rids:
                cb = self._bulk_cbs.pop(int(rid), None)
                if cb is not None:
                    self._held_callbacks.append((cb, int(rid), None))
        else:
            import struct as _struct

            for rid, resp in zip(rids, responses):
                cb = self._bulk_cbs.pop(int(rid), None)
                if cb is not None:
                    if resp is not None and not isinstance(
                        resp, (bytes, bytearray)
                    ):
                        # device-app responses are i32 scalars
                        resp = _struct.pack("<i", int(resp))
                    self._held_callbacks.append((cb, int(rid), resp))

    def _sink_route(self, rids, responses) -> None:
        """Deliver completions to their rid-block sinks: vectorized block
        lookup, ONE durability-gated thunk per (sink, fire) instead of a
        Python callback per request.  ``responses`` None = failure."""
        a = np.asarray(rids, np.int64)
        if a.size == 0:
            return
        blocks = self._sink_blocks
        starts = np.fromiter((b[0] for b in blocks), np.int64,
                             count=len(blocks))
        bi = np.searchsorted(starts, a, "right") - 1
        gc = False
        for k in np.unique(bi):
            if k < 0:
                continue
            blk = blocks[k]
            sel = bi == k
            offs = (a[sel] - blk[0])
            inside = offs < blk[1]
            if not inside.any():
                continue
            offs = offs[inside]
            if responses is None:
                resp_sel = None
            else:
                idx = np.nonzero(sel)[0][inside]
                resp_sel = [responses[i] for i in idx]
            blk[2] -= len(offs)
            gc = gc or blk[2] <= 0
            sink = blk[3]

            def fire(_rid, _resp, s=sink, o=offs, rr=resp_sel):
                s(o, rr)

            self._held_callbacks.append((fire, -1, None))
        if gc:
            self._sink_blocks = [b for b in blocks if b[2] > 0]

    @_locked
    def propose_bulk_kv(self, rows, ops, keys, vals,
                        callbacks=None, entries=None) -> np.ndarray:
        """Device-app propose: admit requests whose execution is a KV
        descriptor (op, key, val) uploaded to the device table inside the
        fused tick — the decision stream never surfaces as host work.
        Returns rids like :meth:`propose_bulk` (-1 = rejected)."""
        assert self._device_app, "propose_bulk_kv needs cfg.paxos.device_app"
        self._in_kv_admit = True
        try:
            out = self.propose_bulk(rows, b"", callbacks=callbacks,
                                    entries=entries)
        finally:
            self._in_kv_admit = False
        adm = out >= 0
        if adm.any():
            self._kv_chunks.append((
                out[adm],
                np.asarray(ops, np.int32)[adm],
                np.asarray(keys, np.int32)[adm],
                np.asarray(vals, np.int32)[adm],
            ))
        return out

    def _take_kv_uploads(self):
        """Pull up to kv_reg_budget staged descriptors for this tick's
        fused upload; advances the placement watermark.  Returns padded
        [K] arrays (rid 0 = empty slot)."""
        K = self._kv_reg_budget
        take, total = [], 0
        while self._kv_chunks and total < K:
            c = self._kv_chunks[0]
            room = K - total
            if len(c[0]) <= room:
                take.append(c)
                total += len(c[0])
                self._kv_chunks.pop(0)
            else:
                take.append(tuple(a[:room] for a in c))
                self._kv_chunks[0] = tuple(a[room:] for a in c)
                total += room
        rids = np.zeros(K, np.int32)
        ops = np.zeros(K, np.int32)
        keys = np.zeros(K, np.int32)
        vals = np.zeros(K, np.int32)
        o = 0
        for c in take:
            n = len(c[0])
            rids[o:o + n] = c[0]
            ops[o:o + n] = c[1]
            keys[o:o + n] = c[2]
            vals[o:o + n] = c[3]
            o += n
        if o:
            self._kv_watermark = max(self._kv_watermark, int(rids[:o].max()))
        self._kv_uploaded = (rids[:o].copy(), ops[:o].copy(),
                             keys[:o].copy(), vals[:o].copy()) if o else None
        return rids, ops, keys, vals

    def bulk_response(self, rid: int):
        """Response payload of an entry-replica-completed bulk request.
        Retained until the request is fully executed everywhere and freed;
        None once freed (or unknown) — poll before the request completes on
        the LAST member, or use the scalar propose path for per-request
        callbacks.  Log-before-respond holds here exactly as for scalar
        callbacks: nothing is observable until the WAL covering the
        request's tick is fsynced."""
        if self.wal is not None and not self.wal.is_synced():
            return None
        s = self.bulk
        if s is None:
            return None
        i = rid & s.mask
        if s.valid[i] and s.rid[i] == rid:
            return s.response[i]
        return None

    def bulk_stats(self) -> dict:
        s = self.bulk
        return {
            "live": 0 if s is None else s.n_live,
            "done": 0 if s is None else s.done,
            "queued": int(self._bulk_leftover.size)
            + sum(len(c) for c in self._bulk_chunks),
        }

    def _first_occurrence(self, keys: np.ndarray, scr_pos, scr_gen) -> np.ndarray:
        """Mask of first occurrences of each key, order-preserving, O(n) —
        no sort (argsort/unique on the hot path was the round-3 lesson)."""
        self._gen += 1
        pos = np.arange(len(keys))
        # reversed scatter: the FIRST occurrence is written last and wins
        scr_pos[keys[::-1]] = pos[::-1]
        scr_gen[keys[::-1]] = self._gen
        return (scr_gen[keys] == self._gen) & (scr_pos[keys] == pos)

    def _purge_row_outstanding(self, row: int) -> None:
        """Drop placed-but-unfinished records of a removed group.  Without
        this the row's outstanding counter stays >0 forever (free_groups
        clears the member mask, so the sweep can never cover them) and the
        recycled row becomes permanently unpausable."""
        gone = [rid for rid, rec in self.outstanding.items() if rec.row == row]
        for rid in gone:
            rec = self.outstanding.pop(rid)
            if rec.callback is not None and not rec.responded:
                self._held_callbacks.append((rec.callback, rid, None))
        self._row_outstanding.pop(row, None)

    def _fail_queued(self, row: int) -> None:
        """Fail queued-but-never-committed requests for a stopped/removed
        group: fire callbacks with response None (client retries elsewhere,
        as the reference's clients do on an inactive-epoch error)."""
        q = self._queues.pop(row, None)
        if not q:
            return
        for rid in q:
            rec = self.outstanding.pop(rid, None)
            if rec is not None:
                self._row_outstanding[rec.row] -= 1
                if rec.callback is not None and not rec.responded:
                    self._held_callbacks.append((rec.callback, rid, None))
            self.stats["failed_requests"] += 1
            if self.reqtrace.enabled:
                self.reqtrace.event(rid, "failed", reason="group_fenced")

    # ------------------------------------------------------------------- tick
    def _build_inbox(self) -> TickInbox:
        self._drain_staged()
        # lazily clear last tick's placements instead of reallocating R*P*G
        req, stp = self._in_req, self._in_stp
        for _row, take in self._placed:
            for _rid, entry, p in take:
                req[entry, p, _row] = 0
                stp[entry, p, _row] = False
        if self._bulk_placed is not None:
            _r, _e, _p, _rw = self._bulk_placed
            req[_e, _p, _rw] = 0
            stp[_e, _p, _rw] = False
            self._bulk_placed = None
        now = time.perf_counter()  # one read for all of this tick's placements
        placed = []
        cols = []  # (entry, p, row, rid, stop) of each: scatter_inbox's rows
        emptied = []
        deferred = 0
        for row, q in self._queues.items():
            used = collections.Counter()
            take = []
            while q and len(take) < self.P:
                rid = q.popleft()
                rec = self.outstanding.get(rid)
                if rec is None:
                    continue
                if not self.alive[rec.entry]:
                    # re-home the request to a live *member* so the response
                    # callback is not orphaned on a dead entry node
                    ms = np.where(self._member_np[:, row])[0]
                    live = [m for m in ms if self.alive[m]]
                    if not live:
                        q.appendleft(rid)
                        break
                    rec.entry = int(live[0])
                entry = rec.entry
                p = used[entry]
                if p >= self.P:
                    q.appendleft(rid)
                    break
                used[entry] += 1
                req[entry, p, row] = rid
                stp[entry, p, row] = rec.stop
                take.append((rid, entry, p))
                cols.append((entry, p, row, rid, rec.stop))
                if not rec.t_placed:
                    rec.t_placed = now  # a rejected intake is placed again
                if self.reqtrace.enabled:
                    self.reqtrace.event(rid, "placed", tick=self.tick_num)
            if take:
                placed.append((row, take))
            if not q:
                emptied.append(row)
            elif len(take) == self.P:
                deferred += len(q)  # more for this row than one tick takes
        # a row's queue goes with its last request: this loop visits the
        # rows that have something queued, not every row a request ever
        # came for (at 1M groups and 1,000 req/s over fresh names that was
        # +1.4 us a tick for each name served since the start: the period
        # grew from 63 to 85 ms inside 20 s, PERF.md section 6, PR 36)
        for row in emptied:
            del self._queues[row]
        self._deferred_h.observe(deferred)
        self._placed = placed
        self._place_bulk(req, stp, placed)
        self._backlog = bool(self._bulk_leftover.size or self._bulk_chunks
                             or 0 < len(cols) <= deferred)
        alive = self.alive.copy()  # the WAL reads it: no device round-trip
        # The inbox is the list ``cols`` and what _place_bulk placed; a tick
        # that placed nothing in bulk and no more than the list holds hands
        # over the list.  A manager's first build is dense whatever it
        # placed: a plane is ready after its first tick (TickDriver
        # .wait_ready), and by then its tick has been dispatched with numpy
        # arrays and the resident copies below exist; the first short one,
        # one tick on, compiles scatter_inbox, before any traffic.
        if (self._bulk_placed is None and len(cols) <= _SHORT_INBOX
                and self._in_handed):
            return self._short_inbox(cols, alive)
        # hand the jit copies (the staging buffers get mutated next tick; a
        # zero-copy dispatch aliasing them would race the async step).  Two
        # copies taken in turn, not fresh ones: at most one tick is in flight when
        # the next is built (_pending_out), so the copy handed out two
        # builds ago has been consumed, and a fresh [R, P, G] array is
        # memory the kernel pages in anew every tick: most of this phase
        # at 1M groups, and what a process pays for a page differs two- to
        # threefold between processes (PERF.md section 6, PR 30).
        if not self._in_handed:
            self._in_handed = [(np.empty_like(req), np.empty_like(stp))
                               for _ in range(2)]
        out_req, out_stp = self._in_handed[0]
        self._in_handed.reverse()
        np.copyto(out_req, req)
        np.copyto(out_stp, stp)
        self._inbox_builds_c["dense"].inc()
        self._inbox_bytes_h.observe(out_req.nbytes + out_stp.nbytes)
        return TickInbox(out_req, out_stp, alive)

    def _short_inbox(self, cols: list, alive) -> TickInbox:
        """The inbox of a tick that placed at most ``_SHORT_INBOX`` requests
        and none in bulk: the device makes ``req`` / ``stop`` from the list
        (``ops.tick.scatter_inbox``: what the dense arrays hold, bit for
        bit) and the tick's program takes them as it takes numpy arrays,
        without the two [R, P, G] copies here and their upload (63 MB a
        tick at 1M groups).  The list is a fresh array each tick: the
        program that reads it may still be running when the next is
        built."""
        self._inbox_builds_c["short"].inc()
        if not cols and self._zero_inbox is not None:
            # every tick of an idle plane: no tick donates its inbox
            self._inbox_bytes_h.observe(0)
            return TickInbox(*self._zero_inbox, alive)
        x = np.zeros((5, _SHORT_INBOX), np.int32)
        x[2] = self.G_total  # padding: one past the rows, dropped
        x[:, :len(cols)] = np.array(cols, np.int32).reshape(-1, 5).T
        made = self._scatter_inbox(x, self.R, self.P, self.G_total)
        if not cols:
            self._zero_inbox = made
        self._inbox_bytes_h.observe(x.nbytes)
        return TickInbox(*made, alive)

    def _place_bulk(self, req, stp, placed) -> None:
        """Vectorized placement of the bulk queue into the staging arrays:
        first-occurrence per (entry, row) key (one new proposal per entry
        slot per tick on this path — at operating G that saturates the
        window), remainder stays queued in arrival order."""
        if not self._bulk_chunks and not self._bulk_leftover.size:
            return
        parts = ([self._bulk_leftover] if self._bulk_leftover.size else []) \
            + self._bulk_chunks
        self._bulk_chunks = []
        q = parts[0] if len(parts) == 1 else np.concatenate(parts)
        store = self.bulk
        idx, live = store.lookup(q)
        if not live.all():
            q, idx = q[live], idx[live]
        rows = store.row[idx]
        # rows gone dead under queued requests (removed/stopped): drop them
        bad = (self._n_members_np[rows] == 0) | self._stopped_np[rows]
        if bad.any():
            if self._bulk_cbs or self._sink_blocks:
                self._bulk_fire(q[bad])  # group gone: cb(None), client retries
            store.fail(idx[bad])
            self.stats["failed_requests"] += int(bad.sum())
            q, idx, rows = q[~bad], idx[~bad], rows[~bad]
        if not len(q):
            self._bulk_leftover = np.zeros(0, np.int64)
            return
        hold = np.zeros(0, np.int64)
        if self._device_app:
            # a request may only be placed once its descriptor upload is on
            # (or riding to) the device — rids beyond the watermark wait
            wm = q <= self._kv_watermark
            if not wm.all():
                hold = q[~wm]
                q, idx, rows = q[wm], idx[wm], rows[wm]
                if not len(q):
                    self._bulk_leftover = hold
                    return
        entries = store.entry[idx]
        if not self.alive.all():
            # re-home requests whose entry replica is dead to the first
            # live member of their group (response duty must stay live)
            dead = ~self.alive[entries]
            if dead.any():
                lm = self._member_np & self.alive[:, None]  # [R, G]
                has = lm.any(axis=0)
                flm = np.argmax(lm, axis=0).astype(np.int32)
                fixable = dead & has[rows]
                ei = idx[fixable]
                store.entry[ei] = flm[rows[fixable]]
                entries = store.entry[idx]
                # groups with no live member at all: keep queued
                keep = ~self.alive[entries]
                if keep.any():
                    sel = ~keep
                    qk = q[keep]
                    q, idx, rows, entries = (q[sel], idx[sel], rows[sel],
                                             entries[sel])
                else:
                    qk = np.zeros(0, np.int64)
            else:
                qk = np.zeros(0, np.int64)
        else:
            qk = np.zeros(0, np.int64)
        key = (entries.astype(np.int64) * self.G_total + rows).astype(np.intp)
        # up to P requests per (entry, row) per tick: P first-occurrence
        # passes assign p slots in arrival order (device admission is FIFO
        # across p for one entry, so per-key order is preserved)
        p = np.full(len(q), -1, np.int32)
        remaining = np.arange(len(q))
        for pp in range(self.P):
            if not len(remaining):
                break
            fo = self._first_occurrence(key[remaining], self._scr_pos,
                                        self._scr_gen)
            p[remaining[fo]] = pp
            remaining = remaining[~fo]
        # collision with slow-path placements at the same (entry, row):
        # shift this tick's bulk entries up past the used p slots
        if placed:
            used = collections.Counter()
            for row_, take in placed:
                for _rid, e_, _p in take:
                    used[(e_, row_)] += 1
            for (e_, row_), cnt in used.items():
                sel = (entries == e_) & (rows == row_) & (p >= 0)
                p[sel] += cnt
        fit = (p >= 0) & (p < self.P)
        if fit.any():
            # a tick's placements are journaled as ONE record, and a journal
            # scan believes none over MAX_RECORD: place what fits in half of
            # it, bodies and framing, in arrival order (so a key's order
            # holds), always the first; the others wait a tick.  A wave of
            # 262,144 bodies of 1 KB is over it (PERF.md section 6, PR 36).
            cost = np.where(fit, store.pay_len[idx].astype(np.int64)
                            + _BULK_JOURNAL_OVERHEAD, 0)
            fit &= np.cumsum(cost) - cost <= _WAL_MAX_RECORD // 2
            fe, fp, fr = entries[fit], p[fit], rows[fit]
            req[fe, fp, fr] = q[fit].astype(np.int32)
            stp[fe, fp, fr] = store.stop[idx[fit]]
            self._bulk_placed = (q[fit], fe, fp, fr)
        rest = q[~fit]
        parts = [p for p in (rest, hold, qk) if p.size]
        self._bulk_leftover = (np.concatenate(parts) if len(parts) > 1
                               else (parts[0] if parts else rest))

    def _run_due_laggard_syncs(self) -> None:
        """Run checkpoint transfers noticed during tick completion.

        Runs at the top of tick(), after draining the pipeline: the
        transfer must capture the donor's device exec watermark and host
        app state at the SAME point — inside completion the device is one
        pipelined tick ahead of the host apps, and a laggard adopting that
        skewed pair permanently skips the slots between them (found live:
        a released write missing on every sync-repaired replica)."""
        due, self._lag_sync_due = self._lag_sync_due, []
        repaired, self._repaired_last = self._repaired_last, set()
        if not due:
            return
        if self._use_compact and self.cfg.paxos.device_donor_sel:
            # Control-summary path: O(due) host work, no [R, G] pulls.  The
            # drain completes the in-flight tick, so _lag_pending becomes
            # the LATEST tick's device-computed laggard table — which by
            # construction matches the current device state exactly (no
            # further tick has been dispatched).  An entry absent from that
            # table is no longer lagging (typically: repaired last call and
            # re-flagged from the pre-repair pipelined outbox — filtered
            # via _repaired_last before paying the drain).
            cand, seen = [], set()
            for r_, row_ in due:
                key = (int(r_), int(row_))
                if key in seen or key in repaired or not self.alive[key[0]]:
                    continue
                seen.add(key)
                cand.append(key)
            if not cand:
                return
            self.drain_pipeline()  # host apps catch up; refresh _lag_pending
            latest = {
                (int(r_), int(w_)): (int(d_), int(de_), int(ds_), int(le_))
                for r_, w_, d_, de_, ds_, le_ in zip(*self._lag_pending)
            }
            for key in cand:
                info = latest.get(key)
                if info is None or info[0] < 0:  # healed / no live donor
                    continue
                name = self.rows.name(key[1])
                if name is None:
                    continue
                if self._sync_from_summary(key[0], key[1], name, *info):
                    self._repaired_last.add(key)
            return
        # legacy host scan (full-outbox mode / device_donor_sel off):
        # re-check lag against CURRENT state first: pipelined completion
        # re-enqueues from the pre-repair outbox, and paying a pipeline
        # drain just to have every sync refuse (donor not ahead) would
        # stall the device/host overlap on the tick after every repair
        exec_slot = self._dev_exec_np()
        still, seen = [], set()
        for r_, row_ in due:
            key = (int(r_), int(row_))
            if key in seen or not self.alive[key[0]]:
                continue
            seen.add(key)
            ms = self._member_np[:, key[1]]
            if not ms[key[0]]:
                continue
            # per-row window: a register row (W=1) can never ring-replay,
            # so ANY lag routes through checkpoint transfer
            if (exec_slot[ms, key[1]].max() - exec_slot[key]
                    >= self._w_np[key[1]]):
                still.append(key)
        if not still:
            return
        self.drain_pipeline()  # host apps catch up to the device watermark
        for r_, row_ in still:
            name = self.rows.name(row_)
            if name:
                self.sync_laggard(r_, name)

    def tick_program(self, inbox, kv_reg=None):
        """The program one tick of this manager dispatches and the arguments
        it is called with, ``fn(*args)``: the one place that decides it,
        from what the manager holds.  The device app has its fused program,
        a mesh its sharded pair of dispatches behind one callable, every
        other build the one served tick over the planes it holds.  Replay
        (``wal/logger.py``) runs the same planes through the same entry;
        ``chip_smoke.py`` asks here for the program to inspect."""
        if self._device_app:
            from ..models.device_kv import fused_compact

            if kv_reg is None:
                kv_reg = [np.zeros(self._kv_reg_budget, np.int32)] * 4
            return fused_compact, (self.state, self.kv, inbox, *kv_reg, -1,
                                   self._exec_budget, self._lag_budget)
        if self.mesh is not None:
            fn = self._mesh_tick_compact or self._mesh_tick
            if self._demand_dev is not None:
                return fn, (self.state, inbox, self._demand_dev)
            return fn, (self.state, inbox)
        return paxos_tick_planes, (
            TickPlanes(self.state, self.rstate, self._lease, self._rlease,
                       self._health, self._rhealth, self._demand_dev),
            inbox, self.tick_params())

    def tick_params(self) -> TickParams:
        """The static half of this manager's served tick."""
        return TickParams(
            own_row=-1,
            exec_budget=self._exec_budget if self._use_compact else 0,
            lag_budget=self._lag_budget, compact=self._use_compact,
            lease_horizon=self._lease_horizon,
            wedge_ticks=self._health_wedge,
            health_decay_shift=self._health_shift,
            health_topk=self._health_topk,
            demand_decay=(self._placement.decay
                          if self._demand_dev is not None else 0.0))

    def _feed_governor(self) -> None:
        """The intake governor's view of this node's client backlog: staged
        + queued + in-flight scalar work + the live bulk window
        (watermark-with-hysteresis shed, ISSUE 14).  Fed at the top of each
        tick and before a completed tick's responses are released."""
        if self.overload is not None:
            self.overload.update(
                self.pending_count() + len(self.outstanding)
                + (self.bulk.n_live if self.bulk is not None else 0))

    @_locked
    def tick(self):
        """One manager step.  Returns the tick's :class:`HostOutbox` (full
        mode) / :class:`CompactHostOutbox` (compact mode).

        Under ``pipeline_ticks`` a tick MAY HOLD its outbox for the next
        call: it does when its inbox left work behind that only another
        tick can place (``_build_inbox``), and the next call then completes
        it behind its own dispatch; otherwise it completes it here, as with
        the option off.  The return is the newest outbox this call
        completed; a call that completed none (it held its own and the one
        before held none) returns the oldest outbox completed earlier and
        not yet returned, or None."""
        pc = self._pc
        pc.begin()
        self._feed_governor()
        self._run_due_laggard_syncs()
        pc.mark("repair")
        reg = None
        if self._device_app:
            # descriptor upload rides the same fused program as the tick;
            # watermark must advance BEFORE the build so those rids place
            reg = self._take_kv_uploads()
        inbox = self._build_inbox()
        pc.mark("intake")
        # Holding this tick's outbox for the next call overlaps the device
        # with the next inbox's build (a period of max(host, device), not
        # their sum) and costs every request in it the hand-over.  Ticks per
        # second matter only to work that is waiting for a tick, so a tick
        # holds only when its inbox could not place all there was: a bulk
        # leftover, or requests left queued behind P placed for their name
        # that are at least as many as the requests it did place.  One hot
        # name's two or three behind seventy others is not that: at 1M
        # groups holding took 30 ms off the tick they waited for and added
        # 29 ms to every reply of the held one (PERF.md section 6, PR 36).
        hold = self.cfg.paxos.pipeline_ticks and self._backlog
        placed = self._placed
        bulk_placed = self._bulk_placed
        lease_pack = None
        health_pack = None
        # dispatch first, journal second: the jitted step runs asynchronously
        # while the WAL appends+fsyncs this tick's record (SURVEY §2.2 item 3,
        # the BatchedLogger overlap, AbstractPaxosLogger.java:99-107).  Safe
        # because responses stay held until is_synced() (log-before-respond).
        fn, args = self.tick_program(inbox, reg)
        with pc.part("launch"):
            res = fn(*args)
        # Let go of the donated planes HERE, not when this call returns:
        # the assignments below then drop the last references to them
        # inside this phase.  Measured at 1M on the chip (PERF.md section
        # 6, PR 32): that blocks about 12 ms (the program taking its
        # inputs) which the wait at completion is then shorter by, and the
        # whole tick is 6-16 ms shorter than when they are held until the
        # outbox is done.
        with pc.part("release"):
            del args
            if self._device_app:
                self.state, self.kv, packed = res
            elif self.mesh is not None:
                # a dense (numpy) inbox is committed to the mesh layout by
                # in_shardings on entry (a short one was made in it), as is
                # the state after any eager admin-op mutation
                self.state, packed, *demand = res
                if demand:
                    # placement: the demand EWMA folds inside the compact
                    # dispatch (decided_now is donated away otherwise)
                    self._demand_dev, = demand
                    self._placement.adopt_device(self._demand_dev)
                    self._mesh_dispatch_c["fold"].inc()
                self._mesh_dispatch_c["tick"].inc()
                if self._mesh_tick_compact is not None:
                    self._mesh_dispatch_c["compact"].inc()
            else:
                planes, packs = res
                (self.state, self.rstate, self._lease, self._rlease,
                 self._health, self._rhealth, demand) = planes
                if demand is not None:
                    self._demand_dev = demand
                    self._placement.adopt_device(demand)
                # a register plane makes each a (log, register) pair, pulled
                # and merged into composite rows at completion
                packed = one_or_pair(packs.out, packs.rout)
                lease_pack = one_or_pair(packs.lease_pack, packs.rlease_pack)
                health_pack = one_or_pair(packs.health_pack,
                                          packs.rhealth_pack)
        # Device sweep frontier: computed ONLY at the dispatch of a tick
        # whose completion runs _sweep_outstanding (1 in 64 ticks, by the
        # tick's own number: whichever call completes it), from THIS tick's
        # post-state — it travels with the packed outbox so the sweep
        # consumes amin/base exactly as of the tick it completes.
        # The O(rows) frontier_rows gather is dispatched HERE too, right
        # behind sweep_frontier and before the next tick program enters the
        # stream: the rows holding records are host state already known at
        # dispatch, and a completion-time gather would queue behind (and on
        # CPU contend with) the next tick's O(G) program — the one device
        # round-trip this plane exists to avoid.  By completion the [rows]
        # results are long finished and the sweep is memcpy + O(records).
        frontier = None
        done_at = self.tick_num + 1
        # mixed planes skip the device frontier: its [G]-indexed gathers
        # clip composite register rows onto log row G-1.  The host sweep
        # fallback reads the composite watermark via _dev_exec_np().
        if self.rstate is None and done_at % self._sweep_every == 0 and (
            self.outstanding or (self.bulk is not None and self.bulk.n_live)
        ):
            with pc.part("frontier"):
                fr = sweep_frontier(
                    self.state.exec_slot, self.state.member, inbox.alive
                )
                if fr is not None:
                    frontier = self._frontier_gather(fr)
        pc.mark("dispatch")
        this = (packed, placed, bulk_placed, frontier, lease_pack,
                health_pack, done_at)
        if self.wal is not None:
            self._wal_bytes_h.observe(
                self.wal.log_inbox(self.tick_num, inbox))
        pc.mark("wal_fsync")
        self.tick_num += 1
        self._completions_c["held" if hold else "same_call"].inc()
        done = []
        if self._pending_out is not None:
            # the previous call held its outbox: the device computed that
            # tick while the host built this one's inbox and the WAL synced
            # (SURVEY §2.2 item 3).  Cleared before completing: _complete_tick
            # may reach drain_pipeline (pause_idle) — must not re-enter
            prev, self._pending_out = self._pending_out, None
            done.append(self._complete_tick(*prev))
        if hold:
            # deferred unpack: the blocking device->host sync for this tick
            # happens in the next call
            self._pending_out = this
            # a due checkpoint must cover on-host effects of every tick the
            # device state contains — drain the one-tick pipeline first
            if self.wal is not None and self.wal.checkpoint_due():
                self.drain_pipeline()
        else:
            done.append(self._complete_tick(*this))
        if done:
            out = done.pop()
            self._unreturned.extend(done)
        else:
            # nothing completed in this call — but drain_pipeline (laggard
            # sync, checkpoint) or a call that completed two may have left
            # an outbox nobody was handed; hand the oldest out instead of
            # dropping it, so callers polling tick() never miss one
            out = self._unreturned.popleft() if self._unreturned else None
        if self.wal is not None:
            self.wal.maybe_checkpoint()
        pc.end()
        return out

    def _complete_tick(self, packed, placed: list, bulk_placed, frontier,
                       lease_pack, health_pack, done_at: int):
        """Consume one tick's outbox (unpacking = the device sync point):
        requeue rejected intake, execute the ordered decision stream,
        release durable callbacks, periodic GC.  ``done_at``: the tick's own
        number + 1, which is what the periodic work goes by: each tick runs
        it once, whichever call completes it."""
        pc = self._pc
        # re-arm without observing: drain_pipeline completes a deferred tick
        # outside tick(), and cross-call idle time must not land in "tally"
        pc.touch()
        # the wait for the program, apart from the pull that follows it
        # (both are "tally"); the interpreter lock is free meanwhile
        t0 = time.perf_counter()
        jax.block_until_ready(packed)
        t1 = time.perf_counter()
        self._device_wait_h.observe(t1 - t0)
        if lease_pack is not None:
            self._adopt_lease_pack(lease_pack)
        if health_pack is not None:
            self._adopt_health_pack(health_pack)
        if self._use_compact:
            e_resp = e_miss = None
            if self._device_app:
                # the app's extras ride the flat buffer: pulled whole, and
                # sliced through the shared layout descriptor —
                # fused_compact packs them through the same object
                flat = np.asarray(packed)
                out = unpack_compact(flat, self.R, self.G,
                                     self._exec_budget, self._lag_budget)
                self._count_compact(out, "full", self.W, self.G)
                e_resp, e_miss = self._compact_layout.kv_extras(flat)
            else:
                # a CompactPack per plane (mixed planes: a (log, register)
                # pair), each unpacked against its own geometry, then
                # merged with register rows re-offset into composite rows
                packs = ((packed,) if isinstance(packed, CompactPack)
                         else packed)
                cos = [self._pull_compact(pack, g, w) for pack, g, w in zip(
                    packs, (self.G, self.G_reg), (self.W, 1))]
                out = cos[0] if len(cos) == 1 else merge_compact_outbox(
                    *cos, self.G, self.G_reg)
            self._outbox_pull_h.observe(time.perf_counter() - t1)
            pc.mark("tally")
            self._process_compact(out, placed, bulk_placed, e_resp, e_miss)
        else:
            if isinstance(packed, HostOutbox):
                out = packed
            elif self.mesh is not None:
                # mesh full-outbox mode: the tick returns the raw sharded
                # TickOutbox — assemble per-field on the host (the on-device
                # pack miscompiles over mixed shardings; see shard_tick)
                from ..parallel.shard_tick import fetch_host_outbox

                out = fetch_host_outbox(packed)
            elif isinstance(packed, tuple):
                # mixed planes (full-outbox mode): unpack per plane —
                # register plane is W=1 / G_reg columns — and merge into a
                # composite [.., G_total] outbox (register exec lanes are
                # zero-padded up to W; exec_count there is at most 1)
                out_l = unpack_outbox(packed[0], self.R, self.P, self.W,
                                      self.G)
                out_r = unpack_outbox(packed[1], self.R, self.P, 1,
                                      self.G_reg)
                out = merge_outbox(out_l, out_r)
            else:
                out = unpack_outbox(packed, self.R, self.P, self.W, self.G)
            self._outbox_pull_h.observe(time.perf_counter() - t1)
            pc.mark("tally")
            self._process_outbox(out, placed, bulk_placed)
        pc.mark("execute")
        # again before the responses leave: a caller that sends its next
        # batch the moment the last was answered must meet a governor that
        # knows the batch is done, not one that sheds for it until the next
        # tick starts (the benchmark's preload sends its waves so and lost
        # that race at 1M groups: PERF.md section 6, PR 37)
        self._feed_governor()
        self._flush_callbacks()
        pc.mark("egress")
        if done_at % self._sweep_every == 0:
            self._sweep_outstanding(frontier)
        if (
            self.cfg.paxos.deactivation_ticks > 0
            and done_at % 256 == 0
            and len(self.rows) > 0
        ):
            self.pause_idle()
        pc.mark("sweep")
        return out

    def _pull_compact(self, pack, G: int, W: int) -> CompactHostOutbox:
        """One plane's compacted outbox from the device: its head, and the
        flat buffer only where the head's own header says the tick decided
        more than the head holds."""
        pull = "head"
        co = unpack_head(np.asarray(pack.head), self.R, G, self.P,
                         self._exec_budget, self._lag_budget)
        if co is None:
            pull = "full"
            co = unpack_compact(np.asarray(pack.flat), self.R, G,
                                self._exec_budget, self._lag_budget)
        self._count_compact(co, pull, W, G)
        return co

    def _count_compact(self, co, pull: str, W: int, G: int) -> None:
        """One compacted plane's pull -> ``outbox_pulls_total``, and its two
        lists -> ``compact_path_ticks_total``."""
        self._outbox_pull_c[pull].inc()
        for lst, n, cap, count in (
                ("exec", self.R * W * G, self._exec_budget, co.n_exec),
                ("lag", self.R * G, self._lag_budget, co.lag_n)):
            self._compact_path_c(list=lst,
                                 path=compact_path(n, cap, count)).inc()

    @_locked
    def drain_pipeline(self) -> None:
        """Synchronously finish the pending pipelined outbox (no-op when
        nothing is pending or pipelining is off).  The completed outbox is
        queued for a later tick() to return — draining (laggard sync, due
        checkpoint) must not make a tick's outbox vanish from the caller's
        point of view."""
        if self._pending_out is not None:
            prev, self._pending_out = self._pending_out, None
            self._unreturned.append(self._complete_tick(*prev))

    def _flush_callbacks(self) -> None:
        """Release client responses only once the WAL covering their tick is
        durable (log-before-respond; with sync_every_ticks > 1 responses ride
        the next group commit)."""
        if not self._held_callbacks:
            return
        if self.wal is not None and not self.wal.is_synced():
            return
        held, self._held_callbacks = self._held_callbacks, []
        closers = [h() for h in self._flush_scope_hooks]
        try:
            for cb, rid, resp in held:
                cb(rid, resp)
        finally:
            for c in closers:
                c()

    def _process_outbox(self, out: HostOutbox, placed=None,
                        bulk_placed=None) -> None:
        taken = out.intake_taken
        for row, take in (self._placed if placed is None else placed):
            for rid, entry, p in reversed(take):
                if not taken[entry, p, row] and rid in self.outstanding:
                    self._queues[row].appendleft(rid)  # retry next tick
        if bulk_placed is not None:
            b_rids, b_e, b_p, b_r = bulk_placed
            tk = taken[b_e, b_p, b_r]
            rej = b_rids[~tk]
            if rej.size:  # oldest first: rejected re-enter at the front
                self._bulk_leftover = (
                    np.concatenate([rej, self._bulk_leftover])
                    if self._bulk_leftover.size else rej
                )
        er, es, eb, ec = out.exec_req, out.exec_stop, out.exec_base, out.exec_count
        if ec.any():
            for row in np.where(ec.sum(axis=0) > 0)[0]:
                name = self.rows.name(int(row))
                if name is None:
                    continue
                self._last_active[row] = self.tick_num
                for r in range(self.R):
                    n = int(ec[r, row])
                    for j in range(n):
                        rid = int(er[r, j, row])
                        slot = int(eb[r, row]) + j
                        is_stop = bool(es[r, j, row])
                        self._execute_one(r, int(row), name, rid, slot, is_stop)
        np.maximum(self._host_exec,
                   np.asarray(out.exec_base) + np.asarray(out.exec_count),
                   out=self._host_exec)
        self.stats["decisions"] += int(out.decided_now.sum())
        if self._placement is not None and self._demand_dev is None:
            # host demand fold (full-outbox path): per-group decisions are
            # visible here, unlike the compact flat buffer.  Placement
            # covers the log plane only — slice off register columns.
            self._placement.observe_intake(
                np.asarray(out.decided_now)[:self.G])
        # Self-heal laggards in FULL-outbox mode too (the compact path has
        # the twin block in _process_compact): a replica >= W behind can
        # never catch up by ring sync — its missed slots rotated out of
        # every decision ring — and in a quiescent system no later tick
        # will surface the lag through new decisions, so the stall is
        # permanent without this.  During journal replay repairs must come
        # only from journaled OP_SYNC records (see _process_compact).
        # Deferred to tick() for watermark/blob consistency (see
        # _run_due_laggard_syncs).
        if (self.cfg.paxos.auto_laggard_sync
                and getattr(self, "_replay_process", None) is None):
            # per-row window: register rows (W=1) flag at any lag — their
            # single ring plane was already overwritten
            lag = np.asarray(out.lag)
            self._lag_sync_due.extend(
                zip(*np.where(lag >= self._w_np[None, :lag.shape[1]])))

    def _execute_one(self, r: int, row: int, name: str, rid: int, slot: int,
                     is_stop: bool) -> None:
        if is_stop and row not in self._stopped_rows:
            self._stopped_rows.add(row)
            self._stopped_np[row] = True
            self._fail_queued(row)  # nothing after a stop can ever commit
        if rid == NO_REQUEST:
            self.stats["noops"] += 1
            return
        seen = self._seen[(r, row)]
        if rid in seen:
            self.stats["dup_commits"] += 1
            return
        seen[rid] = slot
        while len(seen) > self._seen_cap:
            seen.popitem(last=False)
        rec = self.outstanding.get(rid)
        if rec is None:
            if self.bulk is not None:
                sidx = rid & self.bulk.mask
                if self.bulk.valid[sidx] and self.bulk.rid[sidx] == rid:
                    self._store_exec_one(r, row, rid, slot, sidx)
                    return
            self.stats["orphan_execs"] += 1  # payload GC'd (laggard)
            return
        rec.slot = slot
        response = self.apps[r].execute(name, rec.payload, rid)
        rec.executed_by.add(r)
        self.stats["executions"] += 1
        if self.reqtrace.enabled:
            self.reqtrace.event(rid, "executed", slot=slot, replica=r)
        if r == rec.entry and not rec.responded:
            rec.responded = True
            if rec.callback is not None:
                self._held_callbacks.append((rec.callback, rid, response))
            self._reply_bytes_h.observe(len(response or b""))
            if rec.t_staged and rec.t_placed:  # a recovered record has none
                self._stage_queue_h.observe(rec.t_placed - rec.t_staged)
                self._stage_commit_h.observe(
                    time.perf_counter() - rec.t_placed)
            if self.reqtrace.enabled:
                self.reqtrace.event(rid, "responded", slot=slot)
        members = int(self._n_members_np[row])
        if len(rec.executed_by) >= members and rec.responded:
            del self.outstanding[rid]
            self._row_outstanding[row] -= 1

    def _store_exec_one(self, r: int, row: int, rid: int, slot: int,
                        sidx: int) -> None:
        """Scalar execution of one bulk-store request (replay / full-outbox
        fallback; the compact hot path uses the vectorized twin below)."""
        s = self.bulk
        bit = np.int64(1) << r
        if s.exec_mask[sidx] & bit:
            self.stats["dup_commits"] += 1
            return
        s.exec_mask[sidx] |= bit
        if s.slot[sidx] < 0:
            s.slot[sidx] = slot
        name = self._row_name_np[row]
        payload = s.payload[sidx]
        desc_lost = False
        if self._device_app and len(payload or b"") == 0:
            # device-app store requests carry no host payload (the
            # descriptor lives in the device table); reaching the scalar
            # path with nothing to re-apply means the descriptor was lost
            # (sizing invariant violated).  Fail the request explicitly —
            # executing b"" would no-op into a silently-lost update
            # reported as an empty success.
            desc_lost = True
            resp = None
        else:
            resp = self.apps[r].execute(name, payload, rid)
            self.stats["executions"] += 1
        if s.entry[sidx] == r and not s.responded[sidx]:
            s.responded[sidx] = True
            s.response[sidx] = resp
            if desc_lost:
                self.stats["failed_requests"] += 1
                if self._bulk_cbs or self._sink_blocks:
                    self._bulk_fire([rid])  # cb(None): client-visible failure
            elif self._bulk_cbs or self._sink_blocks:
                self._bulk_fire([rid], [resp if resp is not None else b""])
        full = self._member_bits[row]
        if s.responded[sidx] and (s.exec_mask[sidx] & full) == full:
            s.valid[sidx] = False
            s.payload[sidx] = None
            s.response[sidx] = None
            s.n_live -= 1
            s.done += 1

    def _process_compact(self, co: CompactHostOutbox, placed=None,
                         bulk_placed=None, e_resp=None,
                         e_miss=None) -> None:
        """Vectorized twin of :meth:`_process_outbox` over the compacted
        stream: every lifecycle step is an index-array operation; only
        stops and non-store (dict) requests fall back to per-item code.

        e_resp/e_miss: device-app extras aligned with the exec stream —
        per-execution KV responses and descriptor-miss flags.  Misses
        route through the scalar path, whose app ``execute`` re-applies
        the descriptor host-side (or fails the request if the payload is
        gone)."""
        if placed:
            # one vectorized look at every placed position; a rejection is
            # rare (a full window, a closed group), and only then are the
            # rows walked to requeue in order
            at = np.array([(entry, row, p) for row, take in placed
                           for _, entry, p in take]).T
            if not taken_bit(co, *at).all():
                for row, take in placed:
                    for rid, entry, p in reversed(take):
                        if (not taken_bit(co, entry, row, p)
                                and rid in self.outstanding):
                            self._queues[row].appendleft(rid)
        if bulk_placed is not None:
            b_rids, b_e, b_p, b_r = bulk_placed
            tk = taken_bit(co, b_e, b_r, b_p)
            rej = b_rids[tk == 0]
            if rej.size:
                self._bulk_leftover = (
                    np.concatenate([rej, self._bulk_leftover])
                    if self._bulk_leftover.size else rej
                )
        n = co.n_exec
        store = self.bulk
        if n:
            rids = co.e_rid[:n].astype(np.int64)
            reps = co.e_rep[:n]
            rows = co.e_row[:n]
            slots = co.e_slot[:n]
            stops = co.e_stop[:n]
            # host-applied execution watermark (see _host_exec): these
            # entries are being delivered to the apps RIGHT NOW
            np.maximum.at(self._host_exec, (reps, rows),
                          slots.astype(np.int32) + 1)
            valid = rids != NO_REQUEST
            # noop decisions (gap fills): stats parity with _execute_one
            self.stats["noops"] += int((~valid & ~stops).sum())
            self._last_active[rows] = self.tick_num
            if store is not None:
                idx, ok = store.lookup(rids)
                ok &= valid
            else:
                idx, ok = None, np.zeros(n, bool)
            # stops, dict-path/orphan rids, and device-app descriptor
            # misses: scalar path (rare at scale)
            per_item = (valid & ~ok) | stops
            vec = ok & ~stops
            if e_miss is not None:
                miss = e_miss[:n].astype(bool) & valid
                if miss.any():
                    self.stats["kv_misses"] += int(miss.sum())
                    per_item |= miss
                    vec &= ~miss
            for i in np.nonzero(per_item)[0]:
                row = int(rows[i])
                name = self.rows.name(row)
                if name is None:
                    continue
                self._execute_one(int(reps[i]), row, name, int(rids[i]),
                                  int(slots[i]), bool(stops[i]))
            touched = []
            for r in range(self.R):
                sel = vec & (reps == r)
                if not sel.any():
                    continue
                idx_r = idx[sel]
                # same rid committed twice in one tick (turnover re-propose):
                # keep the first (lowest-slot) occurrence
                fo = self._first_occurrence(idx_r, self._scr2_pos,
                                            self._scr2_gen)
                if not fo.all():
                    self.stats["dup_commits"] += int((~fo).sum())
                    idx_r = idx_r[fo]
                rid_r = rids[sel][fo]
                row_r = rows[sel][fo]
                slot_r = slots[sel][fo]
                fresh = store.mark_executed(idx_r, r)
                if not fresh.all():
                    self.stats["dup_commits"] += int((~fresh).sum())
                    idx_r, rid_r, row_r, slot_r = (
                        idx_r[fresh], rid_r[fresh], row_r[fresh],
                        slot_r[fresh],
                    )
                if not len(idx_r):
                    continue
                ns = store.slot[idx_r] < 0
                store.slot[idx_r[ns]] = slot_r[ns]
                if e_resp is not None:
                    # device app: execution already happened on-device
                    # inside the fused tick; only responses surface
                    resp = e_resp[:n][sel][fo]
                    if not fresh.all():
                        resp = resp[fresh]
                else:
                    erb = getattr(self.apps[r], "execute_rows_batch", None)
                    if erb is not None:
                        resp = erb(row_r, store.payload[idx_r], rid_r,
                                   lens=store.pay_len[idx_r])
                    else:
                        resp = self.apps[r].execute_batch(
                            self._row_name_np[row_r], store.payload[idx_r],
                            rid_r
                        )
                self.stats["executions"] += len(idx_r)
                em = (store.entry[idx_r] == r) & ~store.responded[idx_r]
                ri = idx_r[em]
                if len(ri):
                    store.responded[ri] = True
                    if resp is not None:
                        ra = np.empty(len(resp), object)
                        ra[:] = resp
                        store.response[ri] = ra[em]
                        if self._bulk_cbs or self._sink_blocks:
                            self._bulk_fire(store.rid[ri], list(ra[em]))
                    elif self._bulk_cbs or self._sink_blocks:
                        self._bulk_fire(store.rid[ri],
                                        [b""] * len(ri))
                touched.append(idx_r)
            if touched:
                ti = np.concatenate(touched)
                store.free_done(ti, self._member_bits[store.row[ti]])
        self.stats["decisions"] += co.decided_total
        if self._placement is not None and self._demand_dev is None:
            # host demand fold (single-device compact path): per-group
            # decisions are gone from the flat buffer, so fold the intake
            # acceptance bits instead — popcount of each row's taken mask
            bits = taken_dense(co, self.G_total).astype(np.int64)
            per_row = np.zeros(bits.shape[1], np.int64)
            for _ in range(self.P):
                per_row += (bits & 1).sum(axis=0)
                bits >>= 1
            # placement covers the log plane only: composite register
            # columns (rows >= G) are sliced off before the demand fold
            self._placement.observe_intake(per_row[:self.G])
        self._lag_pending = (co.l_rep.copy(), co.l_row.copy(),
                             co.l_donor.copy(), co.l_dexec.copy(),
                             co.l_dstat.copy(), co.l_lexec.copy())
        # During journal replay (_replay_process installed) laggard repair
        # must come ONLY from journaled OP_SYNC records: the live run's
        # donor choice may have been constrained by liveness that replay
        # (alive all-True by default) cannot see, and a replay-chosen donor
        # would restore a different checkpoint/watermark than the crash run.
        if (self.cfg.paxos.auto_laggard_sync and co.lag_n
                and getattr(self, "_replay_process", None) is None):
            # self-heal: a replica >= W behind can never catch up by ring
            # sync — its missed slots have rotated out of every decision
            # ring.  The budget's fair ordering prevents self-inflicted
            # lag, but crashes/recoveries still produce it.  DEFERRED to
            # tick() (see _run_due_laggard_syncs): a transfer captured
            # inside completion pairs the donor's device watermark with a
            # host app state one pipelined tick behind it, and the laggard
            # would permanently skip the difference.
            self._lag_sync_due.extend(zip(*self._lag_pending[:2]))

    def _sweep_outstanding(self, frontier=None) -> None:
        """Drop responded records whose payload can never be needed again:
        every member has executed past the slot, OR the slot has rotated
        out of every decision ring (slot <= base - W), in which case any
        replica still behind it can only catch up by checkpoint transfer,
        which carries the state, not the payload.

        A slot still inside the ring window of a DEAD member's gap must
        keep its payload: when that member revives with gap < W it
        catches up by ring REPLAY, and executing a swept slot would
        silently skip it (found live: a released write missing on the
        revived replica, then spread to others by checkpoint donation).

        ``frontier`` is the device control summary for this sweep —
        ``(urows, amin, base, live)``: the record rows collected at
        dispatch and the matching [rows] gathers of the reductions
        ``ops.tick.sweep_frontier`` computed from the completing tick's
        post-state — and routes to the O(records) path below.  ``None``
        (off-schedule drains, full-outbox mode, direct test calls) keeps
        the original [R, G] host reductions."""
        if not self.outstanding and (self.bulk is None
                                     or self.bulk.n_live == 0):
            return
        if frontier is not None:
            self._sweep_with_frontier(frontier)
            return
        # "passed" is judged against the HOST-APPLIED watermark (see
        # _host_exec): device exec includes the in-flight pipelined tick's
        # executions, whose host deliveries still need their payloads
        exec_slot = self._host_exec
        dev_exec = self._dev_exec_np()
        if self.bulk is not None and self.bulk.n_live:
            # vectorized twin for the store
            s = self.bulk
            member_exec = np.where(self._member_np, exec_slot,
                                   np.iinfo(np.int32).max)
            amin = member_exec.min(axis=0)  # [G] min ALL-member watermark
            # rotation uses the DEVICE watermark (ring overwrite is a
            # device-side fact); repair blobs cover it because transfers
            # capture pipeline-drained, host==device state
            base = np.where(self._member_np, dev_exec,
                            np.iinfo(np.int32).min).max(axis=0)  # [G]
            any_live = (self._member_np & self.alive[:, None]).any(axis=0)
            # rotation bound is STRICT: executed-through base-1 only proves
            # decisions through base-1, and slot s's ring plane survives
            # until s+W is decided — so s == base-W can still ride the
            # ring to a revived replica and must keep its payload
            sel = np.nonzero(
                s.valid & s.responded & (s.slot >= 0) & any_live[s.row]
                & ((s.slot < amin[s.row])
                   | (s.slot < base[s.row] - self._w_np[s.row]))
            )[0]
            if len(sel):
                s.valid[sel] = False
                s.payload[sel] = None
                s.response[sel] = None
                s.n_live -= len(sel)
                s.done += len(sel)
                self.stats["swept"] += len(sel)
        if not self.outstanding:
            return
        member = self._member_np
        dead = []
        for rid, rec in self.outstanding.items():
            if not rec.responded or rec.slot < 0:
                continue
            ms = np.where(member[:, rec.row])[0]
            if not any(self.alive[m] for m in ms):
                continue
            marks = [int(exec_slot[m, rec.row]) for m in ms]
            dbase = max(int(dev_exec[m, rec.row]) for m in ms)
            if (all(mk > rec.slot for mk in marks)
                    or rec.slot < dbase - self._w_np[rec.row]):  # strict
                dead.append(rid)
        for rid in dead:
            self._row_outstanding[self.outstanding[rid].row] -= 1
            del self.outstanding[rid]
            self.stats["swept"] += 1

    def _frontier_gather(self, fr):
        """Dispatch-time half of the frontier sweep: collect the rows
        holding live records (EVERY valid/outstanding record's row, placed
        or not — a record in flight at dispatch may be responded by the
        completion that consumes this gather) and enqueue the O(rows)
        ``frontier_rows`` gather right behind ``sweep_frontier``, clip-
        padded to a power-of-two bucket so the gather jit doesn't retrace
        per count.  Returns ``(urows, amin, base, live)`` with the [rows]
        results still on device — the completing tick blocks on nothing
        bigger than this."""
        s = self.bulk
        rows_parts = []
        if s is not None and s.n_live:
            rws = s.row[s.valid]
            if len(rws):
                rows_parts.append(rws.astype(np.int32))
        if self.outstanding:
            rows_parts.append(np.fromiter(
                (rec.row for rec in self.outstanding.values()),
                np.int32, len(self.outstanding)))
        if not rows_parts:
            return None
        urows = np.unique(np.concatenate(rows_parts)
                          if len(rows_parts) > 1 else rows_parts[0])
        k = max(16, 1 << int(len(urows) - 1).bit_length())
        padded = np.zeros(k, np.int32)
        padded[:len(urows)] = urows
        am, bs, lv = frontier_rows(*fr, padded)
        return urows, am, bs, lv

    def _sweep_with_frontier(self, frontier) -> None:
        """O(records) sweep off the device control summary: the [G]
        reductions (all-member exec min, device exec base, member liveness)
        ran inside ``sweep_frontier`` on the completing tick's post-state —
        which at consumption time IS the host-applied watermark, deliveries
        having just run — and the rows holding records were gathered back
        at dispatch (:meth:`_frontier_gather`), so the host cost here is a
        [rows] memcpy plus the record loop: it scales with live records,
        never [R, G], and never queues a device program mid-tick.

        A record whose row is missing from the dispatch-time gather (can
        only arise from repair/test paths mutating records between dispatch
        and completion) is conservatively kept for the next sweep.

        Equivalences with the host path: ``slot < amin[row]`` ⇔ every
        member's watermark is past the slot; ``base`` here is the completed
        tick's exec (the host path reads the in-flight tick's — i.e. this
        sweeps a one-tick-older rotation bound: strictly conservative).
        ``live`` is dispatch-time liveness — at most one pipelined tick
        staler than the host path's read of self.alive, and only ever a
        keep-guard."""
        urows, am, bs, lv = frontier
        amin = np.asarray(am)[:len(urows)]
        base = np.asarray(bs)[:len(urows)]
        live = np.asarray(lv)[:len(urows)]
        s = self.bulk
        if s is not None and s.n_live:
            cand = np.nonzero(s.valid & s.responded & (s.slot >= 0))[0]
            if len(cand):
                crows = s.row[cand]
                ix = np.minimum(np.searchsorted(urows, crows),
                                len(urows) - 1)
                sel = cand[(urows[ix] == crows) & live[ix]
                           & ((s.slot[cand] < amin[ix])
                              | (s.slot[cand] < base[ix] - self.W))]
                if len(sel):
                    s.valid[sel] = False
                    s.payload[sel] = None
                    s.response[sel] = None
                    s.n_live -= len(sel)
                    s.done += len(sel)
                    self.stats["swept"] += len(sel)
        if not self.outstanding:
            return
        dead = []
        for rid, rec in self.outstanding.items():
            if not rec.responded or rec.slot < 0:
                continue
            i = int(np.searchsorted(urows, rec.row))
            if i >= len(urows) or urows[i] != rec.row or not live[i]:
                continue
            if rec.slot < amin[i] or rec.slot < base[i] - self.W:
                dead.append(rid)
        for rid in dead:
            self._row_outstanding[self.outstanding[rid].row] -= 1
            del self.outstanding[rid]
            self.stats["swept"] += 1

    # --------------------------------------------------------------- liveness
    def set_alive(self, r: int, up: bool) -> None:
        self.alive[r] = up

    @_locked
    def sync_laggard(self, r: int, name: str, donor: Optional[int] = None) -> bool:
        """Checkpoint transfer for a replica lagging >= W on a group
        (StatePacket/handleCheckpoint analog,
        PaxosInstanceStateMachine.java:1852-1861): copy exec watermark from
        the most advanced live member and restore its app state.

        The transfer mutates device state outside the journaled tick
        stream, so it is journaled itself — as the EXACT transferred values
        (donor exec watermark, status, checkpoint blob), not just the donor
        id: under pipelined ticks the sync lands one tick behind the
        OP_TICK record appended at dispatch, so re-deriving the transfer
        from the donor's replay-time state would adopt a skewed watermark
        and the divergence compounds through every later replayed tick.
        """
        # the captured (watermark, blob) pair must be consistent: with a
        # pipelined tick in flight the device watermark is ahead of the
        # host apps by that tick's executions
        self.drain_pipeline()
        row = self.rows.row(name)
        if row is None:
            return False
        exec_slot = self._dev_exec_col(row)
        if donor is None:
            members = np.where(self._member_np[:, row])[0]
            donors = [m for m in members if self.alive[m] and m != r]
            if not donors:
                return False
            donor = max(donors, key=lambda m: exec_slot[m])
        if exec_slot[donor] <= exec_slot[r]:
            return False
        # "ship the register": for a register row the checkpoint IS the
        # register value — the same transfer covers both planes
        ckpt = self.apps[donor].checkpoint(name)
        donor_exec = int(exec_slot[donor])
        pst, prow = self._plane_state(row)
        donor_status = int(np.asarray(pst.status[donor, prow]))
        if self.wal is not None:
            self.wal.log_sync(r, name, int(donor), donor_exec, donor_status,
                              ckpt)
        self._apply_sync_values(r, int(row), name, donor_exec, donor_status,
                                ckpt)
        self.stats["checkpoint_transfers"] += 1
        return True

    def _sync_from_summary(self, r: int, row: int, name: str, donor: int,
                           donor_exec: int, donor_status: int,
                           old_exec: int) -> bool:
        """Checkpoint transfer driven entirely by the device control summary
        (the compact buffer's l_* columns): donor id, donor watermark/status
        and the laggard's own watermark all come from the last completed
        tick — which, after the caller's pipeline drain, IS the current
        device state — so nothing here reads ``[R, G]`` arrays.  Journals
        the same OP_SYNC record (exact transferred values) the host-scan
        :meth:`sync_laggard` would."""
        if not self.alive[donor] or not self._member_np[r, row]:
            # liveness/membership moved between the tick and the repair —
            # rare enough to pay the host scan, which re-derives the donor
            # from current state
            return self.sync_laggard(r, name)
        if donor_exec <= old_exec:
            return False
        ckpt = self.apps[donor].checkpoint(name)
        if self.wal is not None:
            self.wal.log_sync(r, name, int(donor), int(donor_exec),
                              int(donor_status), ckpt)
        self._apply_sync_values(r, int(row), name, int(donor_exec),
                                int(donor_status), ckpt,
                                old_exec=int(old_exec))
        self.stats["checkpoint_transfers"] += 1
        return True

    @_locked
    def apply_sync(self, r: int, name: str, donor_exec: int,
                   donor_status: int, ckpt: bytes) -> bool:
        """Journal-replay entry: re-apply a checkpoint transfer verbatim
        from its OP_SYNC record (no donor-state re-derivation)."""
        row = self.rows.row(name)
        if row is None:
            return False
        self._apply_sync_values(r, int(row), name, donor_exec, donor_status,
                                ckpt)
        self.stats["checkpoint_transfers"] += 1
        return True

    def _apply_sync_values(self, r: int, row: int, name: str,
                           donor_exec: int, donor_status: int,
                           ckpt: bytes, old_exec: Optional[int] = None) -> None:
        if old_exec is None:
            old_exec = int(self._dev_exec_col(row)[r])
        self.apps[r].restore(name, ckpt)
        self._host_exec[r, row] = max(int(self._host_exec[r, row]),
                                      donor_exec)
        self._set_exec_status(r, row, donor_exec, donor_status)
        self._seen.pop((r, row), None)
        # a transfer skips slots [old, donor) on r without ever reporting
        # them executed — settle the store's books or those requests stay
        # live forever.  Entry-duty requests whose response was skipped are
        # marked responded with no payload (client retries; at-least-once).
        if self.bulk is not None:
            s = self.bulk
            lo, hi = old_exec, donor_exec
            sel = np.nonzero(
                s.valid & (s.row == row) & (s.slot >= lo) & (s.slot < hi)
            )[0]
            if len(sel):
                s.exec_mask[sel] |= np.int64(1) << r
                ent = (s.entry[sel] == r) & ~s.responded[sel]
                s.responded[sel[ent]] = True
                if (self._bulk_cbs or self._sink_blocks) and ent.any():
                    self._bulk_fire(s.rid[sel[ent]])  # duty skipped: None
                s.free_done(sel, self._member_bits[s.row[sel]])

    @_locked
    def auto_sync_laggards(self, out=None) -> int:
        """Run checkpoint transfers where ring sync cannot catch up
        (lag >= W).  Accepts a full outbox; with None or a compacted one,
        uses the device-compacted laggard list of the last completed tick."""
        if out is None or isinstance(out, CompactHostOutbox):
            if out is None and not self._use_compact:
                # _lag_pending is only fed by the compact path; silently
                # iterating its (empty) initial value would strand laggards
                raise ValueError(
                    "auto_sync_laggards() needs the tick's outbox in "
                    "full-outbox mode"
                )
            if out is None and self.cfg.paxos.device_donor_sel:
                # control-summary path: after the drain, _lag_pending is the
                # latest completed tick's table and its donor columns match
                # the current device state — repair straight from it, no
                # [R, G] pulls (see _sync_from_summary)
                self.drain_pipeline()
                n = 0
                for r_, row_, d_, de_, ds_, le_ in zip(*self._lag_pending):
                    r = int(r_)
                    if not self.alive[r] or int(d_) < 0:
                        continue
                    name = self.rows.name(int(row_))
                    if name and self._sync_from_summary(
                            r, int(row_), name, int(d_), int(de_),
                            int(ds_), int(le_)):
                        n += 1
                return n
            src = out if out is not None else None
            l_rep = src.l_rep if src is not None else self._lag_pending[0]
            l_row = src.l_row if src is not None else self._lag_pending[1]
            pairs = zip(l_rep, l_row)
        else:
            lag = np.array(out.lag)
            pairs = zip(*np.where(lag >= self._w_np[None, :lag.shape[1]]))
        n = 0
        for r, row in pairs:
            if not self.alive[r]:
                continue
            name = self.rows.name(int(row))
            if name and self.sync_laggard(int(r), name):
                n += 1
        return n

    # ------------------------------------------------------------ conveniences
    def run_ticks(self, n: int) -> None:
        for _ in range(n):
            self.tick()

    @_locked
    def pending_count(self) -> int:
        n = sum(len(q) for q in self._queues.values()) + len(self._staged)
        n += int(self._bulk_leftover.size)
        n += sum(len(c) for c in self._bulk_chunks)
        if self._pending_out is not None and (
                self.outstanding or self._held_callbacks
                or (self.bulk is not None and self.bulk.n_live)):
            # a held outbox somebody waits on needs a tick to complete (the
            # backlog it was held for may be gone by now: a stop failed
            # the queue); one that nobody waits on keeps no driver busy
            n += 1
        return n
