"""The fused consensus tick: one jitted step of multi-decree Paxos over every
group at once.

This replaces the entire per-packet dispatch pyramid of the reference
(``PaxosInstanceStateMachine.handlePaxosMessage``,
PaxosInstanceStateMachine.java:423-583, and the handlers it fans out to) with
a single branch-free dataflow over dense arrays:

  phase 0  coordinator-candidacy check   (checkRunForCoordinator, :2070-2130)
  phase 1  prepare/promise + carryover   (handlePrepare, PaxosAcceptor.java:239-273;
                                          combinePValuesOntoProposals,
                                          PaxosCoordinatorState.java:393)
  phase 2  intake + slot assignment      (RequestBatcher + PaxosCoordinatorState.propose :233)
           accept                         (acceptAndUpdateBallot, PaxosAcceptor.java:302-322)
           vote tally + quorum            (handleAcceptReplyMyBallot,
                                          PaxosCoordinatorState.java:597-640;
                                          WaitforUtility majority -> popcount over replica axis)
  phase 3  decision sync                  (syncLongDecisionGaps analog, :1550)
  phase 4  in-order execution extraction  (putAndRemoveNextExecutable,
                                          PaxosAcceptor.java:325-366)

Message passing is implicit: every cross-replica read is a reduction or
broadcast over the leading replica axis.  Run single-device, that axis is a
plain array dimension; sharded over a mesh axis ``replica``, XLA turns the
same reductions into ICI collectives (psum/all-gather) — the TPU-native
equivalent of the reference's NIO ACCEPT fan-out / ACCEPT_REPLY fan-in
(``nio/NIOTransport.java:65-114``).

Layout: all ring windows are ``[R, W, G]`` (G = lane axis; see state.py), and
ring gathers are one-hot selects over the W planes (``window.gather_planes``)
so the lane axis never participates in a hardware gather.

Failure model: ``inbox.alive`` is the host failure detector's liveness view
(``FailureDetection.isNodeUp``, FailureDetection.java:252-258).  A dead
replica contributes nothing and its state freezes; flipping it back alive
models crash-recovery with intact local state.  The tick is deterministic
given (state, inbox), which is what makes the WAL an inbox command log with
replay recovery (see ``wal/logger.py``).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..types import GroupStatus, NO_REQUEST
from .ballot import bal_ge, bal_gt
from .window import gather_planes, match_planes

I32 = jnp.int32

# numpy scalar, NOT jnp: a module-level jnp value would initialize the
# default backend at import time, and importing this module must not touch
# a device (a supervisor that imports it would take the chip its workers
# need)
NEG_INF = np.int32(-(2**31))


# Device phases by name.  Every op of a tick program carries, as metadata,
# the ``jax.named_scope`` it was traced under (``obs/phase.py`` TICK_SCOPES is
# the vocabulary, held to this file by tests/test_obs_coverage.py), so a
# profiler trace splits a program's device time by phase.  Metadata only: the
# compiled programs are otherwise the same, and outside a trace it costs
# nothing.  XLA fuses across scope boundaries and a fusion keeps one
# instruction's metadata, so the split is by op.
class _PhaseScopes:
    """One ``jax.named_scope`` at a time, switched the way the phase clock
    marks: ``scope(name)`` closes the open scope and opens ``name``, so the
    phases of one long function body are named without indenting it.  Used
    as a context manager, so an error while tracing leaves no scope open."""

    def __init__(self):
        self._open = contextlib.ExitStack()

    def __enter__(self):
        return self

    def __call__(self, name: str) -> None:
        self._open.close()
        self._open.enter_context(jax.named_scope(name))

    def __exit__(self, *exc) -> None:
        self._open.close()


def _scoped(name: str):
    """Decorator: trace the whole function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


class TickInbox(NamedTuple):
    """Per-tick inputs assembled by the host batcher.

    req:   int32 [R, P, G] — new client request ids that arrived at entry
           replica r for group g this tick (0 = empty slot).
    stop:  bool  [R, P, G] — request is a paxos stop (end-of-epoch).
    alive: bool  [R]       — failure-detector liveness per replica slot.
    """

    req: jnp.ndarray
    stop: jnp.ndarray
    alive: jnp.ndarray


class TickOutbox(NamedTuple):
    """Per-tick outputs consumed by the host (app execution, callbacks, WAL).

    exec_req:   int32 [R, W, G] — request ids executed this tick, plane j
                holds slot exec_base+j (0 = noop/empty).
    exec_stop:  bool  [R, W, G]
    exec_base:  int32 [R, G]    — first slot executed this tick.
    exec_count: int32 [R, G]    — number of slots executed this tick.
    intake_taken: bool [R, P, G] — which inbox requests got slots (host
                re-enqueues the rest, mirroring RequestBatcher backpressure).
    coord_id:   int32 [G]       — current effective coordinator (-1 if none).
    decided_now: int32 [G]      — decisions reaching quorum this tick (metric).
    lag:        int32 [R, G]    — how many slots this replica trails the live
                maximum.  lag >= W means ring sync cannot catch it up and the
                host must do a checkpoint transfer (StatePacket analog,
                PaxosInstanceStateMachine.handleCheckpoint :1852).
    donor:      int32 [R, G]    — control summary for that transfer: the best
                live member to copy from (argmax post-tick exec watermark over
                live members other than r, ties to the lowest replica id — the
                same choice manager.sync_laggard's host scan makes), or -1
                when no live member is strictly ahead.  Emitted for every
                (r, g) but only meaningful where lag >= W.
    donor_exec: int32 [R, G]    — the donor's post-tick exec watermark (the
                value a checkpoint transfer adopts; 0 where donor == -1).
    donor_status: int32 [R, G]  — the donor's post-tick group status.
    """

    exec_req: jnp.ndarray
    exec_stop: jnp.ndarray
    exec_base: jnp.ndarray
    exec_count: jnp.ndarray
    intake_taken: jnp.ndarray
    coord_id: jnp.ndarray
    decided_now: jnp.ndarray
    lag: jnp.ndarray
    donor: jnp.ndarray
    donor_exec: jnp.ndarray
    donor_status: jnp.ndarray


def _lexmax(n, c, axis):
    """Lexicographic (n, c) max along `axis` -> (n*, c*), masked entries must
    already be NEG_INF in `n`."""
    nmax = jnp.max(n, axis=axis, keepdims=True)
    cmax = jnp.max(jnp.where(n == nmax, c, NEG_INF), axis=axis)
    return jnp.squeeze(nmax, axis=axis), cmax


def _select_rows(x, idx):
    """``take_along_axis(x, clip(idx, 0, R - 1), axis=0)`` for ``[R, G]``
    operands as an R-way select over the static, small leading axis:
    elementwise and bandwidth-bound, where XLA:TPU runs the gather element
    by element (34 ms against 0.24 at R*G = 3M; PERF.md section 6)."""
    R = x.shape[0]
    sel = jnp.clip(idx, 0, R - 1)
    return sum(jnp.where(sel == m, x[m][None, :], 0) for m in range(R))


class LeaseState(NamedTuple):
    """Leader-lease columns (ISSUE 17): dense ``[G]`` lease state folded
    inside the fused tick, so grant/renew/expiry piggyback on the
    accept/heartbeat traffic the tick already emits — no per-group host
    work, vmapped across every group like everything else.

    Time is the lease clock itself: one tick = one unit, advanced inside
    the fold, so lease decisions are a pure function of (state, inbox)
    and WAL replay reproduces them bit for bit.

    clock:  int32 []   — lease clock; +1 per tick.
    holder: int32 [G]  — replica id holding the read lease (-1 = none).
    epoch:  int32 [G]  — grant counter; bumps whenever the holder changes.
    until:  int32 [G]  — expiry tick; reads are valid while clock < until.
    margin: int32 [G]  — skew allowance: a DIFFERENT coordinator may not
            admit new writes until ``clock >= until + margin``, so a
            holder whose clock runs up to ``margin`` ticks slow still
            stops serving reads before any conflicting write can be
            acked (the write-side fence of the classic lease argument).
    """

    clock: jnp.ndarray
    holder: jnp.ndarray
    epoch: jnp.ndarray
    until: jnp.ndarray
    margin: jnp.ndarray


#: lease_pack row indices (the [5, G] per-plane host summary a tick over
#: lease columns emits — ONE device->host pull per plane per tick)
LP_HOLDER, LP_EPOCH, LP_UNTIL, LP_ASN, LP_WAIT = range(5)
LP_ROWS = 5


def init_lease(n_groups: int, margin_ticks: int = 0) -> LeaseState:
    return LeaseState(
        clock=jnp.zeros((), I32),
        holder=jnp.full((n_groups,), -1, I32),
        epoch=jnp.zeros((n_groups,), I32),
        until=jnp.zeros((n_groups,), I32),
        margin=jnp.full((n_groups,), margin_ticks, I32),
    )


def _lease_clear_rows_impl(lease: LeaseState, rows):
    """Drop leases on the given rows (row lifecycle: create/remove/pause,
    placement migration).  Out-of-range rows (padding) are dropped."""
    return lease._replace(
        holder=lease.holder.at[rows].set(-1, mode="drop"),
        epoch=lease.epoch.at[rows].set(0, mode="drop"),
        until=lease.until.at[rows].set(0, mode="drop"),
    )


#: O(rows) scatter; the manager pads rows to power-of-two buckets so row
#: lifecycle events reuse a handful of compiles.
lease_clear_rows = jax.jit(_lease_clear_rows_impl, donate_argnums=(0,))


class HealthState(NamedTuple):
    """Group-health columns (ISSUE 18): dense ``[G]`` per-group health
    facts folded inside the fused tick, so "which of a million groups is
    sick" is answered by an on-device reduction instead of an O(G) host
    pull.  Observation-only: nothing here ever feeds back into the
    consensus dataflow, so the journal bytes of a health-on run are
    identical to a health-off run.

    Time is the tick clock (one tick = one unit, the LeaseState
    convention), so every column is a pure function of (state, inbox)
    and WAL replay reproduces it bit for bit.

    clock:       int32 []  — health clock; +1 per tick.
    last_active: int32 [G] — last tick the group made commit/exec progress
                 OR had no device-visible backlog (an idle group is
                 healthy); ``clock - last_active`` is the stall age.
    last_coord:  int32 [G] — last effective coordinator observed (-1 until
                 a first election); the churn detector's memory.
    churn:       int32 [G] — decaying coordinator-handoff score, Q4 fixed
                 point (one handoff adds 16; each tick decays by
                 ``1/2**decay_shift`` of the current value).
    heat:        int32 [G] — decaying offered-intake EWMA, Q4 fixed point
                 (the "hottest rows" ranking key).
    """

    clock: jnp.ndarray
    last_active: jnp.ndarray
    last_coord: jnp.ndarray
    churn: jnp.ndarray
    heat: jnp.ndarray


def init_health(n_groups: int) -> HealthState:
    return HealthState(
        clock=jnp.zeros((), I32),
        last_active=jnp.zeros((n_groups,), I32),
        last_coord=jnp.full((n_groups,), -1, I32),
        churn=jnp.zeros((n_groups,), I32),
        heat=jnp.zeros((n_groups,), I32),
    )


def _health_clear_rows_impl(health: HealthState, rows):
    """Reset health columns for freed/migrated rows: a recycled row must
    not inherit the previous occupant's stall age or churn score.
    Out-of-range rows (padding) are dropped."""
    return health._replace(
        last_active=health.last_active.at[rows].set(health.clock,
                                                    mode="drop"),
        last_coord=health.last_coord.at[rows].set(-1, mode="drop"),
        churn=health.churn.at[rows].set(0, mode="drop"),
        heat=health.heat.at[rows].set(0, mode="drop"),
    )


health_clear_rows = jax.jit(_health_clear_rows_impl, donate_argnums=(0,))


#: health_pack gauge indices (see :class:`HealthLayout`)
(HG_ALLOC, HG_BACKLOG, HG_WEDGED, HG_MAX_STALL, HG_MAX_CHURN,
 HG_LEASE_WAIT) = range(6)
HG_N = 6
#: log2 histogram buckets in the health pack — bucket i holds values with
#: ``int(v).bit_length() == i`` (the obs/metrics.py convention), bucket 31
#: is the overflow tail
HB = 32


def _log2_hist(v, mask):
    """[G] int32 values -> [HB] bucket counts over ``mask`` rows, bucketed
    by bit_length (matches obs/metrics.py Histogram).

    Computed as 31 vectorized ``>= 2^i`` count-sums and an adjacent diff
    rather than a scatter-add: bucket ``i+1`` (values in ``[2^i, 2^(i+1))``)
    is ``ge[i] - ge[i+1]`` and bucket 0 is the masked zero count.  Exact
    same counts, ~3x cheaper on CPU where 1-element scatter-adds over a
    million rows serialize."""
    vv = jnp.where(mask, jnp.maximum(v, 0), -1)  # masked negatives: bucket 0
    ge = jnp.stack([jnp.sum(vv >= (1 << i), dtype=I32)
                    for i in range(HB - 1)])
    n0 = jnp.sum(vv == 0, dtype=I32)
    counts = ge - jnp.concatenate([ge[1:], jnp.zeros(1, I32)])
    return jnp.concatenate([n0[None], counts])


def _health_pack_impl(stall, churn, heat, backlog, allocated, wait_n,
                      wedge_ticks: int, topk: int):
    """Reduce the [G] health columns into the flat host summary described
    by :class:`HealthLayout`: scalar gauges, two log2 histograms, and the
    top-K (value, row) columns per anomaly criterion."""
    wedged = allocated & (stall >= wedge_ticks)
    gauges = jnp.stack([
        jnp.sum(allocated.astype(I32)),
        jnp.sum(backlog.astype(I32)),
        jnp.sum(wedged.astype(I32)),
        jnp.max(jnp.where(allocated, stall, 0)),
        jnp.max(jnp.where(allocated, churn, 0)),
        wait_n,
    ]).astype(I32)
    parts = [gauges, _log2_hist(stall, allocated),
             _log2_hist(churn >> 4, allocated)]
    for v in (stall, churn, heat):
        # rank in f32: XLA CPU's TopK has a vectorized f32 path but falls
        # back to a ~100x slower generic sort for int32.  Values clamp at
        # 2^24 (exact in f32) — ranking saturates there, far beyond any
        # plausible stall age or Q4 churn/heat score
        vf = jnp.where(allocated, jnp.minimum(v, 1 << 24), -1).astype(
            jnp.float32)
        tv, ti = jax.lax.top_k(vf, topk)
        parts += [tv.astype(I32), ti.astype(I32)]
    return jnp.concatenate(parts)


def paxos_tick_impl(state, inbox: TickInbox, own_row: int = -1,
                    exec_budget: int = 0, group_axis: str | None = None,
                    fast_elect: bool = False, lease: LeaseState | None = None,
                    lease_horizon: int = 0,
                    health: HealthState | None = None,
                    wedge_ticks: int = 32, health_decay_shift: int = 6,
                    health_topk: int = 8):
    """Un-jitted tick body (jit/shard it yourself; `paxos_tick` below is the
    ready-made single-program jit with state donation).

    fast_elect: static flag enabling consecutive-ballot fast re-election
    (arxiv 2006.01885).  When False (default) the compiled graph is the
    legacy election path, bit for bit.  When True, three coupled rules
    activate:

    * **fast takeover** (phase 0): the candidate skips the prepare round
      and goes straight to ``coord_active`` when its own promised ballot
      already equals the group max over member rows — the new ballot is
      then the predecessor's immediate successor, so every accept the
      predecessor could have pushed is visible in the candidate's mirrors
      and the prepare snapshot would be redundant.  Such a reign is marked
      ``coord_fast`` (the bit rides the frame flags word).
    * **conflict refusal** (phase 2b): because a fast ballot never
      collected promises, an acceptor refuses a fast push that would
      overwrite a *different* accepted value (same value / empty slot
      accepts normally, and the refusal still raises the promise).  Any
      chosen value therefore stays held by a blocking set — a conflicting
      fast value can never reach a majority (quorum intersection), which
      is the safety argument for skipping prepare.
    * **adoption + consecutive bump** (between intake and 2b): a fast
      coordinator that can see a higher-ballot accepted value differing
      from its own proposal adopts that value and bumps its ballot by one
      (proposals carry no per-slot ballot, so re-pushing a different value
      under the SAME ballot would corrupt the per-ballot vote tally).  The
      bump keeps the ballot consecutive, so the reign stays fast.

    Liveness escape: a refused fast push can stall behind a refuser plus a
    dead node (the classical path would overwrite after fresh promises).
    When the coordinator can *prove* a refusal from its mirrors — a member
    promised at/above the pushed ballot while a conflicting lower-ballot
    value stays accepted — it demotes itself to an ordinary full prepare
    at the next ballot, which is always safe.

    Known residual window (why the flag defaults to False): with majority
    quorums, a recovery prepare cannot always distinguish "old prepared
    value chosen, fast value partially accepted" from the mirror-image
    world — the promise sets can be identical (the Fast Paxos quorum
    lower bound: safe uncoordinated rounds need ~3n/4 quorums or a
    Raft-style up-to-dateness vote).  Concretely, a value the dead
    coordinator pushed in its final frame RTT can be invisible to the
    taker's mirrors, and if that value was chosen AND its decision also
    never surfaced, a later classical recovery ranks the fast pvalue
    above it by ballot.  Exploiting the window needs a chosen-but-
    unlearned value younger than one frame RTT at takeover plus a second
    coordinator death before the demote resolves; the chaos soaks assert
    the per-slot ledger across every scheduled run, but the flag stays
    opt-in until the fast-quorum variant closes the window.

    group_axis: name of a mesh axis the group dimension G is sharded over
    when this body is traced inside a shard_map (``parallel/shard_tick``).
    Every per-group computation is oblivious to it; only the exec_budget
    ranking below crosses groups, and with ``group_axis`` set it exchanges
    per-(j, r) block counts over that axis so the global rank — and hence
    the kept execution set — is bit-identical to the unsharded program.

    exec_budget: 0 = unlimited.  > 0 caps the TOTAL executions extracted
    this tick across all (replica, group) pairs, cutting each group's
    in-order run at a prefix (flat enumeration order is (r, j, g), so the
    per-group prefix property is preserved).  Decisions beyond the budget
    stay in the decision ring — ``exec_slot`` does not advance past them,
    the window-arithmetic dwrite guard keeps them from being overwritten,
    and a full window throttles intake — so the cap is lossless
    backpressure, not drop.  This is what makes a *bounded* compacted
    outbox transfer safe (see :func:`_compact_outbox_impl`): the host
    never needs more than ``exec_budget`` execution records per tick.

    own_row: -1 for Mode A (all rows authoritative: the whole replica set is
    one device program, so same-tick cross-row writes ARE the messages).
    In Mode B (independent per-process nodes, ``modeb/``) peer rows are
    frame-derived mirrors, and every state *transition* must be confined to
    ``own_row``: a same-tick simulated peer promise/accept/candidacy/win is
    not a fact — counting it toward an election or quorum lets an isolated
    minority fabricate majorities (split brain), and a locally-"won" peer
    candidacy would push that peer's stale mirror proposals under a fresh
    ballot (conflicting values under one ballot).  With ``own_row >= 0`` the
    masks below restrict start_prep / promise-upgrade / prepare-win / intake
    / accept to the own row, so winning a prepare or deciding a slot
    requires real promises/votes carried by received frames — mirroring the
    reference, where a minority partition can never decide
    (PaxosCoordinatorState majority tally, WaitforUtility)."""
    with _PhaseScopes() as scope:
        return _tick_phases(scope, state, inbox, own_row, exec_budget,
                            group_axis, fast_elect, lease, lease_horizon,
                            health, wedge_ticks, health_decay_shift,
                            health_topk)


def _tick_phases(scope: _PhaseScopes, state, inbox: TickInbox, own_row: int,
                 exec_budget: int, group_axis, fast_elect: bool, lease,
                 lease_horizon: int, health, wedge_ticks: int,
                 health_decay_shift: int, health_topk: int):
    """The body of :func:`paxos_tick_impl`; ``scope(name)`` opens each
    phase's named scope (what comes before the first is shared set-up)."""
    R, G = state.exec_slot.shape
    W = state.acc_req.shape[1]
    P = inbox.req.shape[1]
    RP = R * P
    Wm = jnp.int32(W - 1)

    alive = inbox.alive
    r_idx = jnp.arange(R, dtype=I32)[:, None]  # [R, 1] broadcasts over G
    # Mode-B authority mask: transitions allowed only on the own row.
    own2 = (r_idx == own_row) if own_row >= 0 else jnp.ones((R, 1), jnp.bool_)
    member = state.member  # [R, G] bool
    is_active = state.status == int(GroupStatus.ACTIVE)  # [R, G]
    acc_ok = member & alive[:, None] & is_active  # live active member [R, G]
    # serve_ok: may serve decisions from its ring even after STOPPED, so a
    # laggard that missed the stop decision can still learn it (otherwise the
    # group wedges with one eternally-ACTIVE stuck replica).
    serve_ok = member & alive[:, None] & (state.status != int(GroupStatus.FREE))
    maj = state.n_members // 2 + 1  # [G]

    def alive_at(ids):
        """Liveness lookup by global node id ([..] int32; -1 -> False)."""
        out = jnp.zeros(ids.shape, jnp.bool_)
        for r in range(R):
            out = jnp.where(ids == r, alive[r], out)
        return out

    # Common window base: max exec slot among live members (all caught-up live
    # replicas share it; laggards resync in phase 3).
    exec_rel = jnp.where(acc_ok, state.exec_slot, NEG_INF)
    any_live = jnp.any(acc_ok, axis=0)  # [G]
    base = jnp.where(any_live, jnp.max(exec_rel, axis=0), 0).astype(I32)  # [G]
    # lag reference includes stopped-but-serving peers so a laggard behind a
    # finished group still reports its true gap to the host.
    base_serve = jnp.where(
        jnp.any(serve_ok, axis=0),
        jnp.max(jnp.where(serve_ok, state.exec_slot, NEG_INF), axis=0),
        0,
    ).astype(I32)
    jw = jnp.arange(W, dtype=I32)[:, None]  # [W, 1]
    s_j = base[None, :] + jw  # [W, G] absolute slots, window order
    i_j = jnp.bitwise_and(s_j, Wm)  # [W, G] ring indices (replica-agnostic)

    # ---------------- phase 0: candidacy ----------------
    scope("candidacy")
    coord_dead = ~alive_at(state.bal_coord)  # [R, G]
    caught_up = (state.exec_slot - base[None, :]) >= 0
    # candidate = first live *caught-up* member: a stuck laggard must not
    # hold the coordinatorship hostage (at least one live member is always
    # caught up, by definition of base).
    cand_ok = acc_ok & caught_up
    first_live = jnp.argmax(cand_ok, axis=0).astype(I32)  # [G]
    im_cand = (r_idx == first_live[None, :]) & cand_ok
    have_auth = (state.coord_active | state.coord_preparing) & bal_ge(
        state.coord_bnum, r_idx, state.bal_num, state.bal_coord
    )
    start_any = im_cand & coord_dead & ~have_auth & own2
    if fast_elect:
        # consecutive-ballot fast takeover: my promise is already the group
        # max among member rows (mirror facts included — they only ever
        # under-report), so max(bal_num, coord_bnum)+1 below is the
        # predecessor's immediate successor and prepare is skippable.
        gmax_bn = jnp.max(jnp.where(member, state.bal_num, NEG_INF), axis=0)
        consec = (state.bal_num == gmax_bn[None, :]) & (
            state.bal_num >= state.coord_bnum
        )
        fast_start = start_any & consec
        start_prep = start_any & ~consec
    else:
        start_prep = start_any
    coord_bnum = jnp.where(
        start_any,
        jnp.maximum(state.bal_num, state.coord_bnum) + 1,
        state.coord_bnum,
    )
    coord_preparing = state.coord_preparing | start_prep
    coord_active = state.coord_active
    coord_fast = state.coord_fast

    # ---------------- phase 1: prepare / promise / carryover ----------------
    scope("prepare")
    prep_mask = coord_preparing & acc_ok  # [R, G] candidates broadcasting
    pn = jnp.where(prep_mask, coord_bnum, NEG_INF)
    best_pn, best_pc = _lexmax(pn, jnp.broadcast_to(r_idx, (R, G)), axis=0)  # [G]
    upgrade = (
        acc_ok
        & own2
        & (best_pn[None, :] != NEG_INF)
        & bal_gt(best_pn[None, :], best_pc[None, :], state.bal_num, state.bal_coord)
    )
    bal_num = jnp.where(upgrade, best_pn[None, :], state.bal_num)
    bal_coord = jnp.where(upgrade, best_pc[None, :], state.bal_coord)
    if fast_elect:
        # a fast winner promises its own new ballot at once (the analog of
        # the promise a full winner collects from itself via prep_mask)
        bal_num = jnp.where(fast_start, coord_bnum, bal_num)
        bal_coord = jnp.where(
            fast_start, jnp.broadcast_to(r_idx, (R, G)), bal_coord
        )

    # promise match[r1, r2, g]: acceptor r2's promised ballot == candidate r1's
    match = (
        prep_mask[:, None, :]
        & acc_ok[None, :, :]
        & (bal_num[None, :, :] == coord_bnum[:, None, :])
        & (bal_coord[None, :, :] == r_idx[:, None])
    )
    promises = jnp.sum(match, axis=1).astype(I32)  # [R, G]
    won = prep_mask & (promises >= maj[None, :]) & own2  # ≤1 winner per g

    # Gather every replica's accepted window at the common base ring indices:
    # A_x[r, j, g] = acc_x[r, i_j[j, g], g].
    a_bnum = gather_planes(state.acc_bnum, i_j)
    a_bcoord = gather_planes(state.acc_bcoord, i_j)
    a_req = gather_planes(state.acc_req, i_j)
    a_slot = gather_planes(state.acc_slot, i_j)
    a_stop = gather_planes(state.acc_stop, i_j)
    acc_here = (a_slot == s_j[None, :, :]) & (a_bnum >= 0)  # [R, W, G]

    # carryover: among the winner's promisers, max-ballot accepted pvalue/slot
    promiser = jnp.einsum("rg,rsg->sg", won, match).astype(jnp.bool_)  # [R, G]
    if fast_elect:
        # a fast winner has no promisers; its carryover source is every
        # member row of its own mirrors (monotone facts — a stale mirror
        # under-reports, which only makes the seeded prefix shorter)
        fast_any = jnp.any(fast_start, axis=0)  # [G]
        sel_rows = jnp.where(fast_any[None, :], member, promiser)
        eff = sel_rows[:, None, :] & acc_here
    else:
        eff = promiser[:, None, :] & acc_here
    c_n, c_c = _lexmax(jnp.where(eff, a_bnum, NEG_INF), a_bcoord, axis=0)  # [W, G]
    c_exists = jnp.any(eff, axis=0)
    sel = eff & (a_bnum == c_n[None]) & (a_bcoord == c_c[None])
    c_req = jnp.max(jnp.where(sel, a_req, 0), axis=0)
    c_stop = jnp.any(sel & a_stop, axis=0)
    # noop-fill gaps below the highest carried slot so later slots can commit
    hi = jnp.max(jnp.where(c_exists, jw, -1), axis=0)  # [G], -1 if none
    if fast_elect:
        # a fast winner also covers the predecessor's visible assignment
        # frontier (max member next_slot): slots the predecessor assigned
        # whose accepts this candidate hasn't seen get noop proposals
        # instead of gaps (the refusal rule keeps any real value safe; the
        # adoption rule converges them).  Capped at base+W (ring capacity).
        next_mem = jnp.max(jnp.where(member, state.next_slot, NEG_INF), axis=0)
        fast_next = jnp.minimum(
            jnp.maximum(base + hi + 1, next_mem), base + W
        )  # [G]
        hi_eff = jnp.where(fast_any, fast_next - base - 1, hi)
        ns_win = jnp.where(fast_any, fast_next, base + hi + 1)
        c_valid = jw <= hi_eff[None, :]  # [W, G] window order
    else:
        ns_win = base + hi + 1
        c_valid = jw <= hi[None, :]  # [W, G] window order
    # window-order -> ring-order: ring plane i holds window offset (i-base)%W
    j_of_i = jnp.bitwise_and(jw - base[None, :], Wm)  # [W, G]

    def to_ring(v):  # [W, G] window-order -> ring-order
        return gather_planes(v, j_of_i)

    co_req, co_stop, co_valid, co_slot = (
        to_ring(c_req),
        to_ring(c_stop),
        to_ring(c_valid),
        to_ring(s_j),
    )
    won_any = (won | fast_start) if fast_elect else won
    won3 = won_any[:, None, :]
    prop_req = jnp.where(won3, co_req[None], state.prop_req)
    prop_slot = jnp.where(won3, co_slot[None], state.prop_slot)
    prop_valid = jnp.where(won3, co_valid[None], state.prop_valid)
    prop_stop = jnp.where(won3, co_stop[None], state.prop_stop)
    next_slot = jnp.where(won_any, ns_win[None, :], state.next_slot)

    coord_active = coord_active | won_any
    coord_preparing = coord_preparing & ~won
    if fast_elect:
        coord_fast = (coord_fast | fast_start) & ~won
    # retirement: somebody holds a promise above my ballot (preemption,
    # handleAcceptReplyHigherBallot analog, PaxosCoordinatorState.java:661)
    pm_n, pm_c = _lexmax(
        jnp.where(acc_ok, bal_num, NEG_INF), jnp.where(acc_ok, bal_coord, NEG_INF), axis=0
    )  # [G]
    retire = bal_gt(pm_n[None, :], pm_c[None, :], coord_bnum, r_idx)
    coord_active = coord_active & ~retire
    coord_preparing = coord_preparing & ~retire
    if fast_elect:
        coord_fast = coord_fast & ~retire
    prop_valid = prop_valid & ~retire[:, None, :]

    # ---------------- phase 2a: intake + slot assignment ----------------
    scope("intake")
    an = jnp.where(coord_active & acc_ok, coord_bnum, NEG_INF)
    w_n, w_c = _lexmax(an, jnp.broadcast_to(r_idx, (R, G)), axis=0)  # [G]
    has_coord = w_n != NEG_INF
    is_win = (r_idx == w_c[None, :]) & has_coord[None, :] & own2  # [R, G]

    req_flat = inbox.req.reshape(RP, G)
    stop_flat = inbox.stop.reshape(RP, G)
    src_alive = jnp.broadcast_to(
        alive[:, None, None], (R, P, G)
    ).reshape(RP, G)
    group_open = has_coord & jnp.any(is_win & is_active, axis=0)
    if lease is not None:
        # ---- lease write fence (ISSUE 17) ----
        # A coordinator that is NOT the lease holder may not admit new
        # writes until the prior lease has expired past its skew margin:
        # blocking intake here blocks slot assignment, so no write the
        # holder has not itself assigned (and thus counted into its
        # accepted frontier) can ever be acked while local reads are
        # still legal at the holder.  Already-assigned proposals keep
        # pushing — they are covered by the holder's frontier.
        lclock = lease.clock + 1
        lease_expired = lclock >= lease.until + lease.margin
        fence_ok = (lease.holder < 0) | (lease.holder == w_c) | lease_expired
        lease_wait = group_open & ~fence_ok
        group_open = group_open & fence_ok
    valid_in = (req_flat != NO_REQUEST) & src_alive & group_open[None, :]
    # FIFO admission without a sort (argsort over the request axis was ~2/3
    # of the whole tick on TPU): rank each valid entry by prefix count —
    # stable valid-first order over the index axis is exactly index order
    # restricted to valid entries, so prefix sums replace the permutation.
    vi = valid_in.astype(I32)
    p_rank = jnp.cumsum(vi, axis=0) - vi  # [RP, G] rank among valid
    k_total = jnp.sum(valid_in, axis=0).astype(I32)  # [G]
    w_next = jnp.sum(jnp.where(is_win, next_slot, 0), axis=0).astype(I32)  # [G]
    w_exec = jnp.sum(jnp.where(is_win, state.exec_slot, 0), axis=0).astype(I32)
    space = jnp.maximum(jnp.int32(W) - (w_next - w_exec), 0)
    k = jnp.minimum(k_total, space)  # [G]
    # stop-request fencing: nothing may be proposed after a stop; if a stop
    # is among the first k, truncate intake right after it.  The prefix of
    # taken stops in index order equals the sorted-order prefix (above).
    taken_pre = valid_in & (p_rank < k[None, :])
    stop_taken = stop_flat & taken_pre
    stop_before = (jnp.cumsum(stop_taken.astype(I32), axis=0)
                   - stop_taken.astype(I32))
    taken_flat = taken_pre & (stop_before == 0)  # [RP, G] in index order
    k = jnp.sum(taken_flat, axis=0).astype(I32)
    # rank among TAKEN entries == p_rank (taken is a rank prefix of valid);
    # mask non-taken entries out of the match domain
    q_key = jnp.where(taken_flat, p_rank, jnp.int32(-1))

    ji = jnp.bitwise_and(jw - w_next[None, :], Wm)  # [W, G]
    new_at_i = ji < k[None, :]  # [W, G] ring planes receiving new proposals
    nreq_i = match_planes(req_flat, q_key, ji)
    nstop_i = match_planes(stop_flat, q_key, ji)
    nslot_i = w_next[None, :] + ji
    wmask = is_win[:, None, :] & new_at_i[None, :, :]
    prop_req = jnp.where(wmask, nreq_i[None], prop_req)
    prop_stop = jnp.where(wmask, nstop_i[None], prop_stop)
    prop_slot = jnp.where(wmask, nslot_i[None], prop_slot)
    prop_valid = prop_valid | wmask
    next_slot = jnp.where(is_win, w_next[None, :] + k[None, :], next_slot)

    intake_taken = taken_flat.reshape(R, P, G)

    if fast_elect:
        # ---- fast-coordinator adoption + consecutive bump ----
        # A fast reign skipped the prepare snapshot, so a proposal seeded
        # from stale mirrors may conflict with a higher-ballot accepted
        # value that IS visible now.  Adopt the max-ballot accepted value
        # strictly below my own ballot wherever it differs from my
        # proposal, and bump my ballot by one per affected group: the
        # re-push must be a fresh ballot (vote tallies key on ballot —
        # two values under one ballot would corrupt them), and +1 keeps
        # the reign consecutive, hence still fast.
        vis = member[:, None, :] & acc_here  # [R, W, G] pre-tick facts
        m_n, m_c = _lexmax(jnp.where(vis, a_bnum, NEG_INF), a_bcoord, axis=0)
        m_sel = vis & (a_bnum == m_n[None]) & (a_bcoord == m_c[None])
        m_req = jnp.max(jnp.where(m_sel, a_req, 0), axis=0)  # [W, G]
        m_stop = jnp.any(m_sel & a_stop, axis=0)
        ad_req, ad_stop, ad_n, ad_c, ad_slot = (
            to_ring(m_req), to_ring(m_stop), to_ring(m_n), to_ring(m_c),
            to_ring(s_j),
        )
        fastc = coord_fast & coord_active & own2  # [R, G]
        below = bal_gt(
            coord_bnum[:, None, :], r_idx[:, None, :], ad_n[None], ad_c[None]
        )  # accepted ballot strictly under my own (my ballot's values are mine)
        adoptp = (
            fastc[:, None, :]
            & prop_valid
            & (ad_n[None] != NEG_INF)
            & (prop_slot == ad_slot[None])
            & below
            & (prop_req != ad_req[None])
        )
        prop_req = jnp.where(adoptp, ad_req[None], prop_req)
        prop_stop = jnp.where(adoptp, ad_stop[None], prop_stop)
        any_adopt = jnp.any(adoptp, axis=1)  # [R, G]
        coord_bnum = jnp.where(any_adopt, coord_bnum + 1, coord_bnum)

    # ---------------- phase 2b: accept ----------------
    scope("accept")
    pushing = (coord_active & acc_ok)[:, None, :] & prop_valid  # [R, W, G]
    cand_n = jnp.where(pushing, coord_bnum[:, None, :], NEG_INF)
    cand_c = jnp.broadcast_to(r_idx[:, None, :], (R, W, G))
    b_n, b_c = _lexmax(cand_n, cand_c, axis=0)  # [W, G] best pushed ballot
    psel = pushing & (cand_n == b_n[None]) & (cand_c == b_c[None])
    p_req = jnp.max(jnp.where(psel, prop_req, 0), axis=0)  # [W, G]
    p_slot = jnp.max(jnp.where(psel, prop_slot, NEG_INF), axis=0)
    p_stop = jnp.any(psel & prop_stop, axis=0)
    exists = b_n != NEG_INF

    d = p_slot[None, :, :] - state.exec_slot[:, None, :]  # [R, W, G]
    in_win = (d >= 0) & (d < W)
    acceptable = (
        exists[None]
        & in_win
        & bal_ge(b_n[None], b_c[None], bal_num[:, None, :], bal_coord[:, None, :])
        & acc_ok[:, None, :]
        & own2[:, None, :]
    )
    if fast_elect:
        # conflict refusal: a push under a fast ballot must not overwrite a
        # DIFFERENT accepted value — the fast reign never collected
        # promises, so the classical "prepare saw everything" overwrite
        # license does not apply.  Same-value pushes still accept (ballot
        # raise), and the refusal still promises (pr_mask below), so the
        # coordinator can later prove the refusal from its mirrors.
        src_fast = jnp.any(psel & coord_fast[:, None, :], axis=0)  # [W, G]
        conflict = (
            (state.acc_slot == p_slot[None])
            & (state.acc_bnum >= 0)
            & (state.acc_req != p_req[None])
            & src_fast[None]
        )  # [R, W, G]
        refused = acceptable & conflict
        acceptable = acceptable & ~conflict
        pr_mask = acceptable | refused
    else:
        pr_mask = acceptable
    # ring plane for pvalue at slot p_slot is its own plane position already
    # (coordinators store proposals ring-indexed by slot), so accept in place.
    acc_bnum = jnp.where(acceptable, b_n[None], state.acc_bnum)
    acc_bcoord = jnp.where(acceptable, b_c[None], state.acc_bcoord)
    acc_req = jnp.where(acceptable, p_req[None], state.acc_req)
    acc_slot = jnp.where(acceptable, p_slot[None], state.acc_slot)
    acc_stop = jnp.where(acceptable, p_stop[None], state.acc_stop)
    # promise-on-accept (acceptAndUpdateBallot raises the promised ballot)
    ab_n, ab_c = _lexmax(
        jnp.where(pr_mask, b_n[None], NEG_INF),
        jnp.where(pr_mask, b_c[None], NEG_INF),
        axis=1,
    )  # [R, G]
    raise_p = (ab_n != NEG_INF) & bal_gt(ab_n, ab_c, bal_num, bal_coord)
    bal_num = jnp.where(raise_p, ab_n, bal_num)
    bal_coord = jnp.where(raise_p, ab_c, bal_coord)
    if fast_elect:
        # liveness escape: a refuser plus a dead member can block a fast
        # quorum forever (classical prepare would overwrite).  A refusal is
        # PROVEN in my mirrors when a member's promise is at/above my
        # pushed ballot while a conflicting lower-ballot value stays
        # accepted; demote to an ordinary full prepare at the next ballot
        # (always safe).  A fresh adoption bump this tick can't false-
        # positive here: no mirror can already hold a promise at the
        # just-created ballot.
        seen_refusal = (
            conflict
            & member[:, None, :]
            & bal_ge(
                bal_num[:, None, :], bal_coord[:, None, :],
                b_n[None], b_c[None],
            )
        )
        ref_plane = jnp.any(seen_refusal, axis=0)  # [W, G]
        mine = b_c[None] == r_idx[:, None, :]  # [R, W, G] my push planes
        demote = (
            coord_fast & coord_active & own2
            & jnp.any(ref_plane[None] & mine, axis=1)
        )
        coord_active = coord_active & ~demote
        coord_fast = coord_fast & ~demote
        coord_preparing = coord_preparing | demote
        coord_bnum = jnp.where(demote, coord_bnum + 1, coord_bnum)

    # ---------------- phase 2c: tally + quorum ----------------
    scope("tally")
    A_bnum = gather_planes(acc_bnum, i_j)
    A_bcoord = gather_planes(acc_bcoord, i_j)
    A_req = gather_planes(acc_req, i_j)
    A_slot = gather_planes(acc_slot, i_j)
    A_stop = gather_planes(acc_stop, i_j)
    voteable = (A_slot == s_j[None]) & (A_bnum >= 0) & acc_ok[:, None, :]
    B_n, B_c = _lexmax(jnp.where(voteable, A_bnum, NEG_INF), A_bcoord, axis=0)
    votes = voteable & (A_bnum == B_n[None]) & (A_bcoord == B_c[None])
    cnt = jnp.sum(votes, axis=0).astype(I32)  # [W, G]
    decided = (cnt >= maj[None, :]) & (B_n != NEG_INF)  # [W, G] window order
    v_req = jnp.max(jnp.where(votes, A_req, 0), axis=0)
    v_stop = jnp.any(votes & A_stop, axis=0)
    D_slot = gather_planes(state.dec_slot, i_j)
    D_valid = gather_planes(state.dec_valid, i_j)
    already = jnp.any((D_slot == s_j[None]) & D_valid, axis=0)  # [W, G]
    decided_now = jnp.sum(decided & ~already, axis=0).astype(I32)  # [G]

    de_req, de_stop, de_valid, de_slot = (
        to_ring(v_req),
        to_ring(v_stop),
        to_ring(decided),
        to_ring(s_j),
    )
    # write decisions, but never clobber a laggard's still-undelivered ring
    rel_w = de_slot[None] - state.exec_slot[:, None, :]
    dwrite = de_valid[None] & (rel_w >= 0) & (rel_w < W) & acc_ok[:, None, :]
    dec_req = jnp.where(dwrite, de_req[None], state.dec_req)
    dec_slot = jnp.where(dwrite, de_slot[None], state.dec_slot)
    dec_stop = jnp.where(dwrite, de_stop[None], state.dec_stop)
    dec_valid = jnp.where(dwrite, True, state.dec_valid)

    # ---------------- phase 3: decision sync (laggard catch-up) ----------------
    scope("decision_sync")
    # latest decision per ring plane among live serving members, then each
    # replica adopts entries that fall inside its own forward window.
    rel = jnp.where(
        dec_valid & serve_ok[:, None, :], dec_slot - base[None, None, :], NEG_INF
    )  # [R, W, G] relative slots are small; max = latest
    rel_best = jnp.max(rel, axis=0)  # [W, G]
    sel_l = rel == rel_best[None]
    l_req = jnp.max(jnp.where(sel_l, dec_req, 0), axis=0)
    l_stop = jnp.any(sel_l & dec_stop, axis=0)
    l_slot = rel_best + base[None, :]  # [W, G] absolute
    have = dec_valid & (dec_slot == l_slot[None])
    d2 = l_slot[None] - state.exec_slot[:, None, :]
    adopt = (
        (rel_best[None] != NEG_INF)
        & (d2 >= 0)
        & (d2 < W)
        & ~have
        & acc_ok[:, None, :]
    )
    dec_req = jnp.where(adopt, l_req[None], dec_req)
    dec_slot = jnp.where(adopt, l_slot[None], dec_slot)
    dec_stop = jnp.where(adopt, l_stop[None], dec_stop)
    dec_valid = jnp.where(adopt, True, dec_valid)

    # ---------------- phase 4: in-order execution ----------------
    scope("execute")
    s_own = state.exec_slot[:, None, :] + jw[None]  # [R, W, G]
    i_own = jnp.bitwise_and(s_own, Wm)
    Dreq = gather_planes(dec_req, i_own)
    Dslot = gather_planes(dec_slot, i_own)
    Dstop = gather_planes(dec_stop, i_own)
    Dval = gather_planes(dec_valid, i_own)
    ready = Dval & (Dslot == s_own) & acc_ok[:, None, :]
    run = jnp.cumprod(ready.astype(I32), axis=1).astype(jnp.bool_)
    stop_hit = run & Dstop
    stop_before2 = jnp.cumsum(stop_hit.astype(I32), axis=1) - stop_hit.astype(I32)
    exec_mask = run & (stop_before2 == 0)
    if exec_budget > 0:
        # global budget cap: rank would-be executions in (j, r, g) order —
        # every replica's FIRST pending slot outranks anyone's second — and
        # keep the first `exec_budget`.  For fixed (r, g) the rank grows
        # with j, so the kept set is a per-group run prefix (in-order
        # execution preserved; the rest defers).  Fairness across the
        # replica axis matters: ranking (r, j, g)-first starves the highest
        # replica slots under sustained pressure until they fall > W behind
        # and their missed slots rotate out of every decision ring.
        em_t = exec_mask.transpose(1, 0, 2)  # [W, R, G]
        fi = em_t.reshape(-1).astype(I32)
        rank = (jnp.cumsum(fi) - fi).reshape(em_t.shape)
        if group_axis is not None:
            # G is a shard-local block of a mesh-sharded group axis, but the
            # flat (j, r, g) enumeration above must rank GLOBALLY (g is the
            # fastest-varying axis, so shard k's (j, r) row sits after the
            # same row on shards < k).  Exchange tiny [W, R] per-row counts
            # and rebase:  global rank = (count before this (j, r) row)
            # + (this row's count on earlier shards) + (local within-row
            # rank).  Exact, so budget decisions match the unsharded tick
            # bit for bit.
            blk = jnp.sum(em_t, axis=2).astype(I32)  # [W, R] local row counts
            allblk = jax.lax.all_gather(blk, group_axis)  # [S, W, R]
            nsh = allblk.shape[0]
            shard = jax.lax.axis_index(group_axis)
            total = jnp.sum(allblk, axis=0)  # [W, R] global row counts
            tf = total.reshape(-1)
            before_row = (jnp.cumsum(tf) - tf).reshape(total.shape)
            earlier = jnp.sum(
                jnp.where(
                    jnp.arange(nsh, dtype=I32)[:, None, None] < shard,
                    allblk, 0,
                ),
                axis=0,
            )  # [W, R] same row, shards before this one
            lf = blk.reshape(-1)
            row_start = (jnp.cumsum(lf) - lf).reshape(blk.shape)
            rank = (rank - row_start[:, :, None]
                    + (before_row + earlier)[:, :, None])
        exec_mask = exec_mask & (
            rank.transpose(1, 0, 2) < exec_budget
        )
    n_exec = jnp.sum(exec_mask, axis=1).astype(I32)  # [R, G]
    exec_req_out = jnp.where(exec_mask, Dreq, NO_REQUEST)
    exec_stop_out = exec_mask & Dstop
    exec_base = state.exec_slot
    exec_slot = state.exec_slot + n_exec
    stopped_now = jnp.any(exec_mask & Dstop, axis=1)
    status = jnp.where(stopped_now, jnp.int32(int(GroupStatus.STOPPED)), state.status)

    # coordinator GC: stop pushing proposals already executed locally
    prop_valid = prop_valid & (prop_slot - exec_slot[:, None, :] >= 0)

    # ---------------- freeze dead replica slots ----------------
    scope("freeze")
    al3 = alive[:, None, None]
    al2 = alive[:, None]

    def fr2(new, old):
        return jnp.where(al2, new, old)

    def fr3(new, old):
        return jnp.where(al3, new, old)

    new_state = state._replace(
        exec_slot=fr2(exec_slot, state.exec_slot),
        bal_num=fr2(bal_num, state.bal_num),
        bal_coord=fr2(bal_coord, state.bal_coord),
        status=fr2(status, state.status),
        acc_bnum=fr3(acc_bnum, state.acc_bnum),
        acc_bcoord=fr3(acc_bcoord, state.acc_bcoord),
        acc_req=fr3(acc_req, state.acc_req),
        acc_slot=fr3(acc_slot, state.acc_slot),
        acc_stop=fr3(acc_stop, state.acc_stop),
        dec_req=fr3(dec_req, state.dec_req),
        dec_slot=fr3(dec_slot, state.dec_slot),
        dec_valid=fr3(dec_valid, state.dec_valid),
        dec_stop=fr3(dec_stop, state.dec_stop),
        coord_active=fr2(coord_active, state.coord_active),
        coord_preparing=fr2(coord_preparing, state.coord_preparing),
        coord_fast=fr2(coord_fast, state.coord_fast),
        coord_bnum=fr2(coord_bnum, state.coord_bnum),
        next_slot=fr2(next_slot, state.next_slot),
        prop_req=fr3(prop_req, state.prop_req),
        prop_slot=fr3(prop_slot, state.prop_slot),
        prop_valid=fr3(prop_valid, state.prop_valid),
        prop_stop=fr3(prop_stop, state.prop_stop),
    )
    # ------------- laggard repair control summary (donor selection) --------
    scope("repair_summary")
    # The host repair path used to re-derive the donor from a full [R, G]
    # exec pull (manager.sync_laggard); emit it from the tick instead so the
    # host never touches [R, G] state for repair.  Donor for laggard r =
    # argmax post-tick exec over live members m != r, ties to the lowest m
    # (Python ``max`` over ascending member ids picks the first maximum —
    # match it exactly so journaled OP_SYNC records are bit-identical to the
    # host scan).  Computed as top-2 over the replica axis: r's donor is the
    # global best unless r IS the best, then the runner-up.
    post_exec = new_state.exec_slot
    ridx = jnp.broadcast_to(
        jnp.arange(post_exec.shape[0], dtype=I32)[:, None], post_exec.shape
    )
    d_cand = jnp.where(member & alive[:, None], post_exec, NEG_INF)
    t1_exec, t1_nid = _lexmax(d_cand, -ridx, axis=0)  # [G]
    t2_exec, t2_nid = _lexmax(
        jnp.where(ridx == -t1_nid[None, :], NEG_INF, d_cand), -ridx, axis=0
    )
    self_best = ridx == -t1_nid[None, :]
    d_exec = jnp.where(self_best, t2_exec[None, :], t1_exec[None, :])
    d_id = jnp.where(self_best, -t2_nid[None, :], -t1_nid[None, :])
    # a transfer only helps when the donor is STRICTLY ahead (sync_laggard
    # refuses otherwise); NEG_INF (no eligible donor) fails this too since
    # exec watermarks are never negative
    d_ok = d_exec > post_exec
    d_status = _select_rows(new_state.status, d_id)
    outbox = TickOutbox(
        exec_req=jnp.where(al3, exec_req_out, NO_REQUEST),
        exec_stop=jnp.where(al3, exec_stop_out, False),
        exec_base=exec_base,
        exec_count=jnp.where(al2, n_exec, 0),
        intake_taken=intake_taken,
        coord_id=jnp.where(has_coord, w_c, -1),
        decided_now=decided_now,
        lag=jnp.where(
            member & (state.status != int(GroupStatus.FREE)),
            jnp.maximum(base_serve[None, :] - exec_slot, 0),
            0,
        ),
        donor=jnp.where(d_ok, d_id, -1),
        donor_exec=jnp.where(d_ok, d_exec, 0),
        donor_status=jnp.where(d_ok, d_status, 0),
    )
    if lease is not None:
        # ---- lease grant/renew fold (ISSUE 17) ----
        scope("lease_fold")
        # Renewal piggybacks on the accept traffic this same tick pushed:
        # the effective winner keeps its lease alive just by staying the
        # winner.  A grant needs the previous lease gone (never held, or
        # expired past margin) — a dead holder's lease simply runs out.
        renew = has_coord & (lease.holder == w_c)
        grant = has_coord & ~renew & ((lease.holder < 0) | lease_expired)
        l_holder = jnp.where(grant, w_c, lease.holder)
        l_epoch = jnp.where(grant, lease.epoch + 1, lease.epoch)
        l_until = jnp.where(renew | grant,
                            lclock + jnp.int32(lease_horizon), lease.until)
        new_lease = LeaseState(lclock, l_holder, l_epoch, l_until,
                               lease.margin)
        # accepted frontier: max assigned slot over MEMBER rows (dead
        # included — a dead ex-coordinator's assignments are still
        # accepted facts).  The host's local-read validity check compares
        # the holder's executed watermark against this, both as-of the
        # same tick, so a read is served locally only when the holder has
        # executed every write any coordinator ever assigned (quiescent).
        asn = jnp.max(jnp.where(member, new_state.next_slot, 0), axis=0)
        lease_pack = jnp.stack([
            l_holder, l_epoch, l_until, asn, lease_wait.astype(I32),
        ])
    if health is not None:
        # ---- group health fold (ISSUE 18) ----
        scope("health_fold")
        # Read-only w.r.t. consensus: every input below is a fact the tick
        # already computed.  Device-visible backlog = offered intake (the
        # host re-places rejected requests every tick, so a wedged group
        # keeps offering), an assignment frontier ahead of the exec
        # frontier, or an election that has not resolved — which covers
        # the quorum-lost case where intake is never admitted at all.
        hclock = health.clock + 1
        allocated = jnp.any(member, axis=0)  # [G]
        offered = jnp.any(req_flat != NO_REQUEST, axis=0)  # [G]
        asn_h = jnp.max(jnp.where(member, new_state.next_slot, 0), axis=0)
        done_h = jnp.max(jnp.where(member, new_state.exec_slot, 0), axis=0)
        electing = jnp.any(
            member & alive[:, None] & new_state.coord_preparing, axis=0
        )
        backlog = (offered | (asn_h > done_h) | electing) & allocated
        progress = (decided_now > 0) | (jnp.max(n_exec, axis=0) > 0)
        h_last_active = jnp.where(progress | ~backlog, hclock,
                                  health.last_active)
        # coordinator churn: count real handoffs only — a first election
        # is not churn, and a coordinatorless gap collapses into the one
        # handoff its resolution is
        w_eff = jnp.where(has_coord, w_c, -1)
        handoff = has_coord & (health.last_coord >= 0) & (
            w_eff != health.last_coord
        )
        h_last_coord = jnp.where(has_coord, w_eff, health.last_coord)
        sh = jnp.int32(health_decay_shift)
        h_churn = (health.churn - (health.churn >> sh)
                   + (handoff.astype(I32) << 4))
        offered_n = jnp.sum((req_flat != NO_REQUEST).astype(I32), axis=0)
        h_heat = health.heat - (health.heat >> sh) + (offered_n << 4)
        new_health = HealthState(hclock, h_last_active, h_last_coord,
                                 h_churn, h_heat)
        stall = jnp.where(allocated & backlog, hclock - h_last_active, 0)
        wait_n = (jnp.sum(lease_wait.astype(I32)) if lease is not None
                  else jnp.zeros((), I32))
        health_pack = _health_pack_impl(
            stall, h_churn, h_heat, backlog, allocated, wait_n,
            wedge_ticks, health_topk,
        )
    if lease is not None and health is not None:
        return (new_state, outbox, new_lease, lease_pack, new_health,
                health_pack)
    if lease is not None:
        return new_state, outbox, new_lease, lease_pack
    if health is not None:
        return new_state, outbox, new_health, health_pack
    return new_state, outbox


paxos_tick = jax.jit(paxos_tick_impl, donate_argnums=(0,),
                     static_argnums=(2, 3, 4, 5))


class HostOutbox(NamedTuple):
    """Numpy mirror of :class:`TickOutbox` — what the host control loop
    actually consumes.  Produced by :func:`unpack_outbox` from ONE device
    transfer; the per-field ``np.array(out.x)`` pattern costs a fixed
    ~100-200us dispatch+sync per field and dominated the round-2 host
    profile (the pipeline analog of PaxosPacketBatcher: ship one buffer,
    not 26)."""

    exec_req: "np.ndarray"
    exec_stop: "np.ndarray"
    exec_base: "np.ndarray"
    exec_count: "np.ndarray"
    intake_taken: "np.ndarray"
    coord_id: "np.ndarray"
    decided_now: "np.ndarray"
    lag: "np.ndarray"
    donor: "np.ndarray"
    donor_exec: "np.ndarray"
    donor_status: "np.ndarray"


def pack_outbox_impl(out: TickOutbox) -> jnp.ndarray:
    """Flatten every outbox field into one i32 vector (single transfer)."""
    return jnp.concatenate([
        out.exec_req.ravel(),
        out.exec_stop.astype(I32).ravel(),
        out.exec_base.ravel(),
        out.exec_count.ravel(),
        out.intake_taken.astype(I32).ravel(),
        out.coord_id.ravel(),
        out.decided_now.ravel(),
        out.lag.ravel(),
        out.donor.ravel(),
        out.donor_exec.ravel(),
        out.donor_status.ravel(),
    ])


def unpack_outbox(flat, R: int, P: int, W: int, G: int) -> HostOutbox:
    """Host-side inverse of :func:`pack_outbox_impl` (zero-copy views)."""
    flat = np.asarray(flat)
    sizes = [R * W * G, R * W * G, R * G, R * G, R * P * G, G, G, R * G,
             R * G, R * G, R * G]
    offs = np.cumsum([0] + sizes)
    cut = [flat[offs[i]:offs[i + 1]] for i in range(len(sizes))]
    return HostOutbox(
        exec_req=cut[0].reshape(R, W, G),
        exec_stop=cut[1].reshape(R, W, G).astype(bool),
        exec_base=cut[2].reshape(R, G),
        exec_count=cut[3].reshape(R, G),
        intake_taken=cut[4].reshape(R, P, G).astype(bool),
        coord_id=cut[5],
        decided_now=cut[6],
        lag=cut[7].reshape(R, G),
        donor=cut[8].reshape(R, G),
        donor_exec=cut[9].reshape(R, G),
        donor_status=cut[10].reshape(R, G),
    )


# --------------------------------------------------------------------------
# Compacted outbox: the bounded-transfer tick for the at-scale host path.
#
# The full outbox is O(R*W*G) — ~220 MB/tick at the 1M-group design point,
# which would drown the host link no matter how fast the host loop is.  At
# steady state the host only needs (a) the executed decision stream, whose
# length the exec budget bounds, (b) which placed intake was taken (P bits
# per (r, g)), (c) the rare laggards needing checkpoint transfer, and (d)
# the decision counter.  The device compacts exactly that (the TPU-native
# analog of the reference shipping individual DECISION packets instead of
# whole acceptor state, PaxosInstanceStateMachine.java:1755-1842).
#
# What it costs (PERF.md sections 5 and 6, measured at 1M groups on one
# v5e).  On the device: while at most ``compact_blocks()`` entries were
# decided (8,192 of the 12.6M offered positions, 1,024 laggards), O(K)
# scatter updates on top of one bandwidth-bound pass over the masks, at the
# narrowest width K of :func:`compact_tiers` that holds the count: the
# whole compaction (both lists) takes about 2.2 ms with both at K = 128,
# 2.8 ms with the exec list at 1,024 (a served tick's few hundred) and
# 7.6 ms at 8,192; above that the dense prefix-sum scatter over the whole
# plane, which is O(R*W*G) per column whatever was decided (about 330 ms).
# The host mirrors which one ran in ``compact_path_ticks_total``.  The
# flat buffer is bounded but not O(decisions): ``CompactLayout.total_plain``
# words however few decided — ``taken_bits`` (R*G words) plus four exec
# columns of ``exec_budget`` (2G by default) words each, 46 MB at 1M.  So
# the compaction also returns a
# HEAD of it (``CompactLayout.total_head`` words, 1.7 MB at 1M: the
# acceptance bits packed ``32 // P`` groups to a word and the first
# ``head_exec`` entries of the exec columns), and the host pulls the head
# alone unless its header says the tick decided more than it holds
# (``outbox_pulls_total{pull=head|full}``); the flat buffer stays on the
# device and costs no transfer while nobody pulls it.
# --------------------------------------------------------------------------


class CompactHostOutbox(NamedTuple):
    """Host view of the compacted tick (all numpy, one transfer).

    Executed entries appear in flat (r, j, g) order — per (replica, group)
    they are slot-ordered, which is the only order execution needs.
    ``n_exec == budget`` means the budget may have bitten; deferred work
    arrives on later ticks (see exec_budget in :func:`paxos_tick_impl`).
    """

    n_exec: int
    decided_total: int
    lag_n: int            # total laggards (may exceed the recorded list)
    #: i32 acceptance words AS PULLED; read them through :func:`taken_bit`
    #: / :func:`taken_dense`.  From the flat buffer: [R, G], bit p of
    #: (r, g) = inbox slot p was taken.  From the head: [R, Gw], see
    #: ``taken_shift``.
    taken_bits: "np.ndarray"
    e_rid: "np.ndarray"   # i32 [n_exec]
    e_rep: "np.ndarray"   # i32 [n_exec]
    e_row: "np.ndarray"   # i32 [n_exec]
    e_slot: "np.ndarray"  # i32 [n_exec]
    e_stop: "np.ndarray"  # bool [n_exec]
    l_rep: "np.ndarray"   # i32 [min(lag_n, lag_budget)]
    l_row: "np.ndarray"   # i32 [min(lag_n, lag_budget)]
    # control summary per flagged laggard: everything a checkpoint transfer
    # needs, so repair never re-derives from [R, G] state (see TickOutbox)
    l_donor: "np.ndarray"  # i32 — device-selected donor replica (-1 = none)
    l_dexec: "np.ndarray"  # i32 — donor's post-tick exec watermark
    l_dstat: "np.ndarray"  # i32 — donor's post-tick group status
    l_lexec: "np.ndarray"  # i32 — the laggard's own post-tick exec watermark
    #: row g's bits sit in word ``g % Gw`` of its replica, shifted left by
    #: ``(g // Gw) * taken_shift`` (Gw = ``taken_bits.shape[1]``): P for the
    #: head's packed words, and of no account for the flat buffer's one
    #: word per row (g // G is 0)
    taken_shift: int = 0


def taken_bit(co: CompactHostOutbox, entry, row, p):
    """0/1: the tick took inbox slot ``p`` of ``row`` at replica ``entry``
    (scalars or index arrays of one shape), whichever buffer ``co`` came
    from."""
    k, w = np.divmod(row, co.taken_bits.shape[1])
    return (co.taken_bits[entry, w] >> (k * co.taken_shift + p)) & 1


def taken_dense(co: CompactHostOutbox, G: int) -> "np.ndarray":
    """The acceptance words one per row, i32 [R, G], as the flat buffer
    carries them.  O(R*G) host work from a head: for the consumers that
    need every row (the placement fold, a merge of two planes)."""
    words = co.taken_bits
    Gw = words.shape[1]
    if Gw >= G:
        return words[:, :G]
    mask = (1 << co.taken_shift) - 1
    return np.concatenate(
        [(words >> (k * co.taken_shift)) & mask for k in range(-(-G // Gw))],
        axis=1)[:, :G]


#: the block-sparse compaction views a flat mask as rows of one lane row
_BLOCK = 128
#: the most hits (hence non-empty blocks) the sparse code of
#: :func:`_compact_columns` takes; above it the dense code runs.  Chosen on
#: the chip (PERF.md section 6): the sparse code costs what K*_BLOCK
#: scatter updates cost whatever was decided, the dense one what the whole
#: plane costs.
_SPARSE_BLOCKS = 8192
#: the narrower widths of the sparse code, tried before ``_SPARSE_BLOCKS``:
#: a tick takes the narrowest K that holds its count.  Each was kept on the
#: chip for what it saves over the next width up (PERF.md section 6)
_SPARSE_TIERS = (128, 1024)


def compact_blocks(n: int, capacity: int) -> int:
    """The widest K of the block-sparse compaction of an ``n``-wide mask
    into ``capacity`` slots; 0 where the plane is too narrow for it to pay
    (the compaction is then the dense code alone)."""
    k = min(_SPARSE_BLOCKS, capacity)
    return k if n > k * _BLOCK else 0


def compact_tiers(n: int, capacity: int) -> tuple:
    """Every K of that compaction, narrowest first; empty where
    :func:`compact_blocks` is 0.  A function of shapes only: the device
    branches on it and the host mirrors it (:func:`compact_path`)."""
    k = compact_blocks(n, capacity)
    return tuple(t for t in _SPARSE_TIERS if t < k) + (k,) if k else ()


def compact_path(n: int, capacity: int, count: int) -> str:
    """Which branch :func:`_compact_columns` took for ``count`` hits:
    ``sparse<K>`` or ``dense``."""
    for k in compact_tiers(n, capacity):
        if count <= k:
            return f"sparse{k}"
    return "dense"


def _compact_columns(mask_flat, cols, capacity: int):
    """Compact ``cols`` (each flattened to the mask's width) to the
    positions where ``mask_flat`` holds, in flat order, zero-filled to
    ``capacity``; hits past ``capacity`` are dropped.  Returns ``(count,
    i32 [len(cols), capacity])``.

    One algorithm at several widths, chosen on the device from the mask's
    popcount.  XLA:TPU runs an element-granular scatter at about 4.6 ns per
    *offered* update, kept or dropped, so the dense code costs the plane's
    width per column whatever was decided.  With at most K hits at most K
    blocks of ``_BLOCK`` are non-empty: the sparse code row-gathers those
    blocks of the mask, ranks inside the ``[K, _BLOCK]`` tile, scatters each
    hit's *source position* to its output slot (the one K*_BLOCK-update
    scatter) and then gathers K elements per column.  So the count picks
    the narrowest K of :func:`compact_tiers` that holds it, and every width
    fills the same slots with the same words."""
    n = mask_flat.shape[0]
    mi = mask_flat.astype(I32)
    count = jnp.sum(mi)
    cols = [c.reshape(-1).astype(I32) for c in cols]
    tiers = compact_tiers(n, capacity)

    def dense():
        rank = jnp.cumsum(mi) - mi
        idx = jnp.where(mask_flat, rank, capacity)  # -> dropped
        return jnp.stack([
            jnp.zeros((capacity,), I32).at[idx].set(c, mode="drop")
            for c in cols
        ])

    if not tiers:
        return count, dense()

    def sparse(K):
        B = _BLOCK
        nb = -(-n // B)
        m2 = jnp.pad(mi, (0, nb * B - n)).reshape(nb, B)
        cnt = jnp.sum(m2, axis=1)  # [nb] hits per block
        off = jnp.cumsum(cnt) - cnt  # a block's first output slot
        used = (cnt > 0).astype(I32)
        ids = jnp.full((K,), nb, I32).at[
            jnp.where(cnt > 0, jnp.cumsum(used) - used, K)
        ].set(jnp.arange(nb, dtype=I32), mode="drop")  # non-empty blocks
        idc = jnp.minimum(ids, nb - 1)
        tile = jnp.where((ids < nb)[:, None], m2[idc], 0)  # [K, B] row gather
        slot = off[idc][:, None] + jnp.cumsum(tile, axis=1) - tile
        pos = idc[:, None] * B + jnp.arange(B, dtype=I32)[None, :]
        src = jnp.full((K,), n, I32).at[
            jnp.where(tile > 0, slot, K).reshape(-1)
        ].set(pos.reshape(-1), mode="drop")  # [K] source of each output
        hit = src < n
        srcc = jnp.minimum(src, n - 1)
        packed = jnp.stack([jnp.where(hit, c[srcc], 0) for c in cols])
        return jnp.pad(packed, ((0, 0), (0, capacity - K)))

    # the first tier that holds the count, or the dense code past the last
    branch = jnp.sum(count > jnp.asarray(tiers, I32))
    return count, jax.lax.switch(
        branch, [functools.partial(sparse, k) for k in tiers] + [dense])


def _exec_mask(out: TickOutbox):
    """[R, W, G] lanes of the outbox that hold an execution (post-cap)."""
    ji = jnp.arange(out.exec_req.shape[1], dtype=I32)[None, :, None]
    return ji < out.exec_count[:, None, :]


class CompactPack(NamedTuple):
    """One plane's compacted outbox on the device: the flat buffer and the
    head the host pulls first (sections: :class:`CompactLayout`)."""

    flat: Any
    head: Any


@_scoped("compact_outbox")
def _compact_outbox_impl(out: TickOutbox, exec_budget: int,
                         lag_budget: int) -> CompactPack:
    R, W, G = out.exec_req.shape
    P = out.intake_taken.shape[1]
    ji = jnp.arange(W, dtype=I32)[None, :, None]
    slot = out.exec_base[:, None, :] + ji
    rep = jnp.broadcast_to(jnp.arange(R, dtype=I32)[:, None, None], (R, W, G))
    row = jnp.broadcast_to(jnp.arange(G, dtype=I32)[None, None, :], (R, W, G))
    meta = rep | (out.exec_stop.astype(I32) << 8)
    n_exec, e_cols = _compact_columns(
        _exec_mask(out).reshape(-1), [out.exec_req, meta, slot, row],
        exec_budget)
    # intake: P bits per (r, g) — placed-and-taken; host knows what it placed
    pb = jnp.arange(P, dtype=I32)[None, :, None]
    taken_bits = jnp.sum(out.intake_taken.astype(I32) << pb, axis=1)  # [R,G]
    # laggards needing checkpoint transfer (lag >= W): compacted pair list
    rep2 = jnp.broadcast_to(jnp.arange(R, dtype=I32)[:, None], (R, G))
    row2 = jnp.broadcast_to(jnp.arange(G, dtype=I32)[None, :], (R, G))
    lag_n, l_cols = _compact_columns(
        (out.lag >= W).reshape(-1),
        [rep2, row2, out.donor, out.donor_exec, out.donor_status,
         out.exec_base + out.exec_count],  # last: laggard's post-tick exec
        lag_budget)
    header = jnp.stack([n_exec, jnp.sum(out.decided_now), lag_n]).astype(I32)
    flat = jnp.concatenate([
        header,
        taken_bits.reshape(-1),
        e_cols.reshape(-1),
        l_cols.reshape(-1),
    ])
    # the head: row g's P bits go to word g % Gw at shift (g // Gw) * P, so
    # packing is an OR of ``per`` contiguous [R, Gw] slices (no relayout);
    # both branches of _compact_columns fill slots 0 .. n_exec-1 in flat
    # order, so the first head_exec entries are the whole list up to there
    L = CompactLayout(R, G, exec_budget, lag_budget, P)
    tb = jnp.pad(taken_bits, ((0, 0), (0, L.per * L.Gw - G)))
    words = tb[:, :L.Gw]
    for k in range(1, L.per):
        words = words | (tb[:, k * L.Gw:(k + 1) * L.Gw] << (k * P))
    head = jnp.concatenate([
        header,
        words.reshape(-1),
        e_cols[:, :L.head_exec].reshape(-1),
        l_cols.reshape(-1),
    ])
    return CompactPack(flat, head)


class CompactLayout:
    """THE single source of truth for the compacted-outbox buffers: every
    offset any consumer needs, computed in one place.

    Producers (:func:`_compact_outbox_impl` and the device-app
    ``fused_compact``, which appends its per-execution extras) emit
    sections in exactly this order; consumers (:func:`unpack_compact`,
    :func:`unpack_head`, ``PaxosManager._complete_tick``, WAL device-app
    replay) slice through this object only — one field added to a packed
    buffer is one edit here, not silent corruption in a hand-computed twin
    offset.

    Flat buffer: header[3] | taken_bits[R*G] | e_rid[E] | e_meta[E] |
    e_slot[E] | e_row[E] | l_rep[Lb] | l_row[Lb] | l_donor[Lb] |
    l_dexec[Lb] | l_dstat[Lb] | l_lexec[Lb] | app extras
    (device-app: e_resp[E] | e_miss[E]).

    Head (what the host pulls of a served tick while ``n_exec <=
    head_exec``): header[3] | taken_words[R*Gw] | e_rid[Kh] | e_meta[Kh] |
    e_slot[Kh] | e_row[Kh] | the six l_*[Lb] columns whole.  ``taken_words``
    packs ``per = 32 // P`` groups to a word (``Gw = ceil(G / per)``; row
    g's P bits at shift ``(g // Gw) * P`` of word ``g % Gw``; read with
    :func:`taken_bit`); ``Kh = head_exec = min(E, _SPARSE_BLOCKS)``: what
    the sparse branch of :func:`_compact_columns` can fill.  ``P = 0``
    (consumers of the flat buffer alone) leaves the words unpacked."""

    HEADER = 3  # n_exec, decided_total, lag_n

    LAG_COLS = 6  # rep, row, donor, donor exec, donor status, laggard exec

    def __init__(self, R: int, G: int, exec_budget: int, lag_budget: int,
                 P: int = 0, head_exec: Optional[int] = None):
        self.R, self.G = R, G
        self.E, self.Lb = exec_budget, lag_budget
        self.o_taken = self.HEADER
        self.o_exec = self.o_taken + R * G      # 4 E-sized exec columns
        self.o_lag = self.o_exec + 4 * self.E   # LAG_COLS Lb-sized columns
        self.base = self.o_lag + self.LAG_COLS * self.Lb  # app extras
        self.o_resp = self.base                 # device-app: KV responses
        self.o_miss = self.base + self.E        # device-app: descriptor miss
        self.total_plain = self.base
        self.total_device = self.base + 2 * self.E
        # the head
        self.per = max(1, 32 // P) if P else 1  # groups to a word
        self.Gw = -(-G // self.per)
        self.head_exec = (min(self.E, _SPARSE_BLOCKS) if head_exec is None
                          else head_exec)
        self.h_exec = self.o_taken + R * self.Gw
        self.h_lag = self.h_exec + 4 * self.head_exec
        self.total_head = self.h_lag + self.LAG_COLS * self.Lb

    @classmethod
    def of_head(cls, words: int, R: int, G: int, exec_budget: int,
                lag_budget: int, P: int) -> "CompactLayout":
        """The layout of a head ``words`` long: ``head_exec`` is read from
        the buffer the program returned, not assumed."""
        L = cls(R, G, exec_budget, lag_budget, P, head_exec=0)
        Kh, rest = divmod(words - L.total_head, 4)
        if rest or not 0 <= Kh <= exec_budget:
            raise ValueError(f"no head of this plane is {words} words long")
        return cls(R, G, exec_budget, lag_budget, P, head_exec=Kh)

    def kv_extras(self, flat):
        """Device-app extras aligned with the exec stream: (e_resp, e_miss)."""
        return (flat[self.o_resp:self.o_resp + self.E],
                flat[self.o_miss:self.o_miss + self.E])


def _host_outbox(buf, taken, taken_shift: int, o_exec: int, E: int,
                 o_lag: int, Lb: int) -> CompactHostOutbox:
    """Views into one pulled buffer (flat or head): its header, four exec
    columns ``E`` apart from ``o_exec`` and six laggard columns ``Lb``
    apart from ``o_lag``, each trimmed to its live length."""
    n_exec, decided_total, lag_n = (int(buf[0]), int(buf[1]), int(buf[2]))
    e_rid, e_meta, e_slot, e_row = (
        buf[o_exec + i * E:o_exec + i * E + n_exec] for i in range(4))
    ln = min(lag_n, Lb)
    l_rep, l_row, l_donor, l_dexec, l_dstat, l_lexec = (
        buf[o_lag + i * Lb:o_lag + i * Lb + ln]
        for i in range(CompactLayout.LAG_COLS))
    return CompactHostOutbox(
        n_exec=n_exec,
        decided_total=decided_total,
        lag_n=lag_n,
        taken_bits=taken,
        e_rid=e_rid,
        e_rep=e_meta & 0xFF,
        e_row=e_row,
        e_slot=e_slot,
        e_stop=(e_meta >> 8).astype(bool),
        l_rep=l_rep,
        l_row=l_row,
        l_donor=l_donor,
        l_dexec=l_dexec,
        l_dstat=l_dstat,
        l_lexec=l_lexec,
        taken_shift=taken_shift,
    )


def unpack_compact(flat, R: int, G: int, exec_budget: int,
                   lag_budget: int) -> CompactHostOutbox:
    """Host-side inverse of :func:`_compact_outbox_impl`'s flat buffer
    (zero-copy views into the one transferred buffer)."""
    flat = np.asarray(flat)
    L = CompactLayout(R, G, exec_budget, lag_budget)
    return _host_outbox(
        flat, flat[L.o_taken:L.o_taken + R * G].reshape(R, G), 0,
        L.o_exec, L.E, L.o_lag, L.Lb)


def unpack_head(head, R: int, G: int, P: int, exec_budget: int,
                lag_budget: int) -> Optional[CompactHostOutbox]:
    """The same outbox from the head of the same tick (equal field by
    field, the acceptance words packed: :func:`taken_bit`), or None where
    the head's own header says the tick decided more than it holds: the
    flat buffer has the whole list."""
    head = np.asarray(head)
    L = CompactLayout.of_head(head.size, R, G, exec_budget, lag_budget, P)
    if int(head[0]) > L.head_exec:
        return None
    return _host_outbox(
        head, head[L.o_taken:L.h_exec].reshape(R, L.Gw), P,
        L.h_exec, L.head_exec, L.h_lag, L.Lb)


# --------------------------------------------------------------------------
# Control summaries beyond the compact buffer: payload-sweep frontier and the
# single-device demand fold.  Both keep the flat compact program byte-
# identical — they are SEPARATE dispatches (frontier) or fuse into the
# single-device program where no GSPMD partitioner is involved (demand).
# --------------------------------------------------------------------------


@_scoped("sweep_frontier")
def sweep_frontier_impl(exec_slot, member, alive):
    """Per-group payload-sweep frontier, the device twin of the host
    reductions ``_sweep_outstanding`` used to run over full ``[R, G]``
    numpy arrays:

    * ``amin``: min exec watermark over MEMBERS (dead included — a slot
      inside a dead member's ring-reach gap must keep its payload for ring
      replay on revival); int32 max where a group has no members.
    * ``base``: max exec watermark over members (the ring-rotation bound);
      int32 min where a group has no members.
    * ``live``: any member currently alive.

    Returns ``(amin[G], base[G], live[G])`` — device arrays.  The manager
    immediately gathers the rows with live outstanding records
    (:func:`frontier_rows`, enqueued in the same dispatch window, before
    the next tick program) and stashes only the [rows] results, so the
    host never transfers or reduces ``[R, G]`` and never queues a device
    program at tick completion."""
    amin = jnp.min(jnp.where(member, exec_slot, jnp.int32(2**31 - 1)), axis=0)
    base = jnp.max(jnp.where(member, exec_slot, NEG_INF), axis=0)
    live = jnp.any(member & alive[:, None], axis=0)
    return amin, base, live


#: Own dispatch on purpose: under the mesh the inputs are
#: P(replica, groups)-sharded and the replica-axis reductions become
#: collectives — correct in an ordinary global-view program; kept out of
#: the shard_map tick's jit like the compaction (see the parallel/shard_tick
#: module docstring).
sweep_frontier = jax.jit(sweep_frontier_impl)


@_scoped("frontier_rows")
def _frontier_rows_impl(amin, base, live, rows):
    return (jnp.take(amin, rows, mode="clip"),
            jnp.take(base, rows, mode="clip"),
            jnp.take(live, rows, mode="clip"))


#: O(rows) gather + device->host transfer of a stashed frontier.  One
#: compile per padded row-count bucket; the manager pads to powers of two.
frontier_rows = jax.jit(_frontier_rows_impl)


def make_inbox(n_replicas: int, n_groups: int, per_tick: int) -> TickInbox:
    """An empty inbox template (host fills rows it has traffic for)."""
    return TickInbox(
        req=jnp.zeros((n_replicas, per_tick, n_groups), I32),
        stop=jnp.zeros((n_replicas, per_tick, n_groups), jnp.bool_),
        alive=jnp.ones((n_replicas,), jnp.bool_),
    )


# --------------------------------------------------------------------------
# Mixed log/register planes (register mode, RMWPaxos arxiv 2001.03362).
#
# Register groups run the SAME tick kernel on a second dense state plane
# built with W=1: the ring degenerates to a single in-place consensus cell
# (space caps at one outstanding, prepare carryover IS carry-forward, and
# exec_slot counts versions instead of log length).  The composite row
# space the manager exposes is [0, G_log) log rows followed by
# [G_log, G_log + G_reg) register rows — the row index is the mode bit, so
# one fused program splits the inbox at the static plane boundary, runs
# paxos_tick_impl per plane, and the host merges the two outboxes back
# into the composite row space.  No mode mask inside the kernel: the
# W-generic ring math already IS the register semantics at W=1.
# --------------------------------------------------------------------------


def _split_inbox(inbox: TickInbox, g_log: int):
    return (
        TickInbox(inbox.req[:, :, :g_log], inbox.stop[:, :, :g_log],
                  inbox.alive),
        TickInbox(inbox.req[:, :, g_log:], inbox.stop[:, :, g_log:],
                  inbox.alive),
    )


def merge_outbox(out_l: HostOutbox, out_r: HostOutbox) -> HostOutbox:
    """Concatenate the two planes' full outboxes into the composite row
    space (register rows offset by G_log positionally — every field is
    indexed by row, so plain concatenation along the group axis is the
    whole merge).  The register plane's W=1 exec ring is zero-padded to
    the log plane's W; safe because consumers read only j < exec_count
    entries and a register row executes at most one slot per tick."""
    R, W, _ = out_l.exec_req.shape
    Rr, Wr, Gr = out_r.exec_req.shape

    def wide(a):
        if Wr == W:
            return a
        pad = np.zeros((Rr, W - Wr, Gr), a.dtype)
        return np.concatenate([a, pad], axis=1)

    cat = np.concatenate
    return HostOutbox(
        exec_req=cat([out_l.exec_req, wide(out_r.exec_req)], axis=2),
        exec_stop=cat([out_l.exec_stop, wide(out_r.exec_stop)], axis=2),
        exec_base=cat([out_l.exec_base, out_r.exec_base], axis=1),
        exec_count=cat([out_l.exec_count, out_r.exec_count], axis=1),
        intake_taken=cat([out_l.intake_taken, out_r.intake_taken], axis=2),
        coord_id=cat([out_l.coord_id, out_r.coord_id]),
        decided_now=cat([out_l.decided_now, out_r.decided_now]),
        lag=cat([out_l.lag, out_r.lag], axis=1),
        donor=cat([out_l.donor, out_r.donor], axis=1),
        donor_exec=cat([out_l.donor_exec, out_r.donor_exec], axis=1),
        donor_status=cat([out_l.donor_status, out_r.donor_status], axis=1),
    )


def merge_compact_outbox(co_l: CompactHostOutbox, co_r: CompactHostOutbox,
                         g_log: int,
                         g_reg: Optional[int] = None) -> CompactHostOutbox:
    """Merge two planes' compact outboxes into composite rows: counts sum,
    taken_bits stack along G, and the e_*/l_* columns (already trimmed to
    valid length by unpack_compact — no padding reaches the host) simply
    concatenate with the register plane's row ids offset by g_log.  The
    acceptance words come out one per composite row: a head's packed words
    are expanded (composite rows do not divide into either plane's words),
    for which the register plane's width ``g_reg`` is needed."""
    cat = np.concatenate
    if g_reg is None:  # from flat buffers: one word per row already
        if co_r.taken_shift:
            raise ValueError("merging a head's packed words needs g_reg")
        g_reg = co_r.taken_bits.shape[1]
    return CompactHostOutbox(
        n_exec=co_l.n_exec + co_r.n_exec,
        decided_total=co_l.decided_total + co_r.decided_total,
        lag_n=co_l.lag_n + co_r.lag_n,
        taken_bits=np.hstack([taken_dense(co_l, g_log),
                              taken_dense(co_r, g_reg)]),
        e_rid=cat([co_l.e_rid, co_r.e_rid]),
        e_rep=cat([co_l.e_rep, co_r.e_rep]),
        e_row=cat([co_l.e_row, co_r.e_row + g_log]),
        e_slot=cat([co_l.e_slot, co_r.e_slot]),
        e_stop=cat([co_l.e_stop, co_r.e_stop]),
        l_rep=cat([co_l.l_rep, co_r.l_rep]),
        l_row=cat([co_l.l_row, co_r.l_row + g_log]),
        l_donor=cat([co_l.l_donor, co_r.l_donor]),
        l_dexec=cat([co_l.l_dexec, co_r.l_dexec]),
        l_dstat=cat([co_l.l_dstat, co_r.l_dstat]),
        l_lexec=cat([co_l.l_lexec, co_r.l_lexec]),
    )


# --------------------------------------------------------------------------
# The served tick: ONE program over whatever planes a manager holds.
#
# A deployment holds the log plane and, by configuration, a register plane
# (W=1), lease columns per plane, health columns per plane and the placement
# demand array.  Absent members are None: an empty pytree, so they flatten
# to no operands and their folds are never traced.  The program a log-plane
# -only compact deployment compiles is `paxos_tick_impl` followed by
# `_compact_outbox_impl`, op for op.
# --------------------------------------------------------------------------


class TickPlanes(NamedTuple):
    """The device state one tick evolves; every member but ``state`` may be
    None.  ``demand`` is the [G_log] f32 placement EWMA (log plane only:
    register rows never migrate shards)."""

    state: Any
    rstate: Any = None
    lease: Any = None
    rlease: Any = None
    health: Any = None
    rhealth: Any = None
    demand: Any = None


class TickParams(NamedTuple):
    """The static half of a served tick (hashable: one compile per value).
    ``exec_budget`` caps executions per tick (0 = unlimited) and, with
    ``compact``, sizes the compact buffer's exec columns; without
    ``compact`` the packs are the full outbox (:func:`pack_outbox_impl`)."""

    own_row: int = -1
    exec_budget: int = 0
    lag_budget: int = 0
    compact: bool = False
    lease_horizon: int = 0
    wedge_ticks: int = 32
    health_decay_shift: int = 6
    health_topk: int = 8
    demand_decay: float = 0.0


class TickPacks(NamedTuple):
    """What one served tick hands the host, None where a plane is absent.
    ``out``/``rout``: the outbox per plane, a :class:`CompactPack` (flat
    buffer and head) or the full outbox's one flat buffer;
    ``*lease_pack``: [LP_ROWS, G]; ``*health_pack``: see ``HealthLayout``
    (top-K clamped to ``min(health_topk, G_plane)``, as the host unpacks
    it in ``PaxosManager._adopt_health_pack``)."""

    out: Any
    rout: Any = None
    lease_pack: Any = None
    rlease_pack: Any = None
    health_pack: Any = None
    rhealth_pack: Any = None


def one_or_pair(log, reg):
    """A per-plane result as the host's completion takes it: the log
    plane's alone, or a (log, register) pair with a register plane."""
    return log if reg is None else (log, reg)


def _tick_step(planes: TickPlanes, inbox: TickInbox, params: TickParams,
               compact_budget: int):
    """One tick of every plane held -> ``(TickPlanes, TickPacks)``.  The
    composite inbox splits at the static plane boundary; each plane runs
    :func:`paxos_tick_impl` with its own lease and health columns, then
    packs its outbox.  ``compact_budget``: width of the compact exec
    columns (the served tick's is ``exec_budget``; replay's is wider)."""

    def plane(st, ib, le, he):
        res = paxos_tick_impl(
            st, ib, params.own_row, params.exec_budget, lease=le,
            lease_horizon=params.lease_horizon, health=he,
            wedge_ticks=params.wedge_ticks,
            health_decay_shift=params.health_decay_shift,
            health_topk=min(params.health_topk, st.exec_slot.shape[1]))
        st, out, res = res[0], res[1], res[2:]
        lp = hp = None
        if le is not None:
            le, lp, res = res[0], res[1], res[2:]
        if he is not None:
            he, hp = res
        return st, le, he, out, lp, hp

    def pack(out):
        if params.compact:
            return _compact_outbox_impl(out, compact_budget,
                                        params.lag_budget)
        return pack_outbox_impl(out)

    ib_l, ib_r = inbox, None
    if planes.rstate is not None:
        ib_l, ib_r = _split_inbox(inbox, planes.state.exec_slot.shape[1])
    state, lease, health, out, lp_l, hp_l = plane(
        planes.state, ib_l, planes.lease, planes.health)
    demand = planes.demand
    if demand is not None:
        # per-row INTAKE (intake_taken summed over entry and p): what the
        # host fold counts from ``taken_bits``, so the samples match it
        demand = params.demand_decay * demand + jnp.sum(
            out.intake_taken.astype(demand.dtype), axis=(0, 1))
    pk_l = pack(out)
    rstate = rlease = rhealth = pk_r = lp_r = hp_r = None
    if planes.rstate is not None:
        # the register plane's compaction flags laggards at lag >= 1 for
        # free: the threshold inside _compact_outbox_impl is the plane's W
        rstate, rlease, rhealth, out_r, lp_r, hp_r = plane(
            planes.rstate, ib_r, planes.rlease, planes.rhealth)
        pk_r = pack(out_r)
    return (TickPlanes(state, rstate, lease, rlease, health, rhealth, demand),
            TickPacks(pk_l, pk_r, lp_l, lp_r, hp_l, hp_r))


def _paxos_tick_planes_impl(planes: TickPlanes, inbox: TickInbox,
                            params: TickParams):
    return _tick_step(planes, inbox, params, params.exec_budget)


#: THE served one-device tick: one dispatch, the planes donated.  The
#: chipbench finds its executions in a trace by the program name
#: (``^jit__?paxos_tick``): keep the impl's name in that pattern and out of
#: ``parallel/shard_tick.MESH_PROGRAMS``.
paxos_tick_planes = jax.jit(_paxos_tick_planes_impl, donate_argnums=(0,),
                            static_argnums=(2,))


def _paxos_tick_compact_impl(state, inbox: TickInbox, own_row: int,
                             exec_budget: int, lag_budget: int):
    planes, packs = _paxos_tick_planes_impl(
        TickPlanes(state), inbox,
        TickParams(own_row, exec_budget, lag_budget, compact=True))
    return planes.state, packs.out


#: A NAME ONLY, dispatched by no manager: ``chipbench/deployment.py`` traces
#: it for the harness's kernel cross-check and the benchmark's files are
#: not this repo's PRs' to edit (ROADMAP C13: point
#: ``deployment.tick_program`` at ``paxos_tick_planes``, then delete this).
paxos_tick_compact = jax.jit(_paxos_tick_compact_impl, donate_argnums=(0,),
                             static_argnums=(2, 3, 4))


# --------------------------------------------------------------------------
# Batched WAL replay (ISSUE 19): lax.scan over the tick axis.
#
# Journal replay re-runs the SAME fused tick body as the live run, but the
# record-at-a-time loop paid one host→device inbox upload, one device
# dispatch and one device→host outbox pull PER journaled tick.  Here a
# window of K tick inboxes arrives as padded COO columns (entry, lane,
# row, rid, stop — see wal/columnar.py), the scan body scatters each
# tick's dense inbox on device and runs the tick, and each tick emits the
# budgeted compact outbox — so a window costs ONE dispatch and one
# [K, total] pull, and the host processes the per-tick exec streams
# through the vectorized compact fold.
#
# The scan programs deliberately do NOT donate their inputs: the host
# keeps the pre-window state so a budget overflow (a tick whose true
# n_exec exceeds the scatter budget — detectable from the compact header)
# can discard the window's outputs and re-run it through the
# record-at-a-time reference arm without any loss.
# --------------------------------------------------------------------------


def _coo_inbox(x, R: int, P: int, g_total: int) -> TickInbox:
    """Scatter one tick's COO columns into the dense [R, P, G] inbox.
    Padding lanes target row == g_total, one past the composite row
    space, and fall out via mode="drop" — bit-identical to the host-side
    dense buffers the reference arm builds."""
    e, p, g = x["e"], x["p"], x["g"]
    req = jnp.zeros((R, P, g_total), I32).at[e, p, g].set(
        x["rid"], mode="drop")
    stop = jnp.zeros((R, P, g_total), jnp.bool_).at[e, p, g].set(
        x["stop"], mode="drop")
    return TickInbox(req, stop, x["alive"])


def _scatter_inbox_impl(cols, R: int, P: int, g_total: int):
    """A served tick's scalar placements -> the dense ``(req, stop)`` its
    program takes: ``cols`` is [5, K] i32, the rows (entry, lane, row, rid,
    stop) of :func:`_coo_inbox`'s columns, padded with row == g_total."""
    e, p, g, rid, stop = cols
    ib = _coo_inbox(dict(e=e, p=p, g=g, rid=rid, stop=stop != 0, alive=None),
                    R, P, g_total)
    return ib.req, ib.stop


#: The inbox of a tick that placed few requests, made on the device from a
#: list of them (``PaxosManager._build_inbox``): the host hands over K
#: placements, not [R, P, G] (63 MB at 1M groups).  One small program beside
#: the tick, whose own programs take its result as they take numpy arrays.
scatter_inbox = jax.jit(_scatter_inbox_impl, static_argnums=(1, 2, 3))


def _replay_scan_impl(planes: TickPlanes, xs, P: int, params: TickParams,
                      scat_budget: int):
    R, g_log = planes.state.exec_slot.shape
    g_reg = 0 if planes.rstate is None else planes.rstate.exec_slot.shape[1]

    def lp0(le, g):
        return None if le is None else jnp.zeros((LP_ROWS, g), I32)

    def body(carry, x):
        pl, _ = carry
        pl, pk = _tick_step(pl, _coo_inbox(x, R, P, g_log + g_reg), params,
                            scat_budget)
        # the flat buffers alone: a window's ticks are unpacked whole
        packed = (pk.out.flat if pk.rout is None
                  else jnp.concatenate([pk.out.flat, pk.rout.flat]))
        lps = [lp for lp in (pk.lease_pack, pk.rlease_pack) if lp is not None]
        waits = (sum(jnp.sum(lp[LP_WAIT]) for lp in lps).astype(I32)
                 if lps else None)
        return (pl, (pk.lease_pack, pk.rlease_pack)), (packed, waits)

    (planes, lp_last), (packs, waits) = jax.lax.scan(
        body, (planes, (lp0(planes.lease, g_log), lp0(planes.rlease, g_reg))),
        xs)
    return planes, packs, lp_last, waits


#: K journaled ticks of whatever planes are held in one device program:
#: ``(planes, packs[K, total], (lease_pack, rlease_pack), waits[K])``.
#: ``params.compact`` must be set; the exec columns are ``scat_budget`` wide.
#: With a register plane the two compact buffers ride one
#: ``[total_l + total_r]`` row (the host slices per plane via
#: ``CompactLayout``).  The lease packs are the FINAL tick's (the host
#: mirror only ever holds the latest) and ``waits`` the per-tick fence
#: counts for the metric; both None without a lease.
replay_scan_ticks = jax.jit(_replay_scan_impl, static_argnums=(2, 3, 4))


# --------------------------------------------------------------------------
# Sparse window replay (ISSUE 19): the tick fold is a pure per-group map —
# a row whose inbox is empty does not change AT ALL across a tick (no tick
# counter enters the fold, cross-replica reductions are row-local), so a
# replay window only needs the rows its journaled inboxes actually touch.
# The dispatcher gathers those rows into a narrow [R, .., A] plane (G is
# the minor axis of every state field), runs the SAME scan programs above
# at width A instead of G, and scatters the evolved columns back — per
# journaled tick the device fold costs O(active), not O(G).  This is what
# makes batched replay win at 1M groups: the dense scan still pays the
# full-plane tick body per journaled tick, which at G=1M dwarfs the
# dispatch overhead it saves.  The lease fold (per-tick countdown on every
# row) and the health fold (per-tick heat decay) violate the idle-row
# no-op and keep the dense scan path (wal/logger gates them out).
# --------------------------------------------------------------------------


def _gather_rows_impl(state, rows):
    return jax.tree.map(lambda a: jnp.take(a, rows, axis=a.ndim - 1), state)


#: columns `rows` of the G (minor) axis of every field, as a narrow state
replay_gather_rows = jax.jit(_gather_rows_impl)


def _scatter_rows_impl(full, compact, rows):
    return jax.tree.map(
        lambda f, c: f.at[..., rows].set(c), full, compact)


#: inverse of :func:`replay_gather_rows`; `rows` must be duplicate-free
replay_scatter_rows = jax.jit(_scatter_rows_impl)


# --------------------------------------------------------------------------
# Group-health plane (ISSUE 18): the host side of the health fold above —
# the flat health_pack layout, its unpack and the composite-plane merge.
# A build without health hands the served tick no health columns, and the
# fold is not in its program.
# --------------------------------------------------------------------------


class HealthLayout:
    """Single source of truth for the flat health_pack buffer (the
    :class:`CompactLayout` discipline): ``gauges[HG_N] | hist_stall[HB] |
    hist_churn[HB] | (val[K], row[K]) x (stuck, churny, hot)``."""

    def __init__(self, topk: int):
        self.K = topk
        self.o_hist_stall = HG_N
        self.o_hist_churn = self.o_hist_stall + HB
        self.o_top = self.o_hist_churn + HB
        self.total = self.o_top + 6 * topk


class HealthView(NamedTuple):
    """Host (numpy) view of one tick's health pack: the needle-finding
    summary the manager mirrors each tick at O(K) transfer cost."""

    alloc: int          # allocated groups
    backlog: int        # groups with device-visible backlog this tick
    wedged: int         # backlogged groups stalled >= wedge_ticks
    max_stall: int      # worst stall age (ticks)
    max_churn: int      # worst churn score (Q4 fixed point)
    lease_wait: int     # coordinators write-fenced behind a prior lease
    hist_stall: "np.ndarray"  # [HB] log2 buckets of stall age
    hist_churn: "np.ndarray"  # [HB] log2 buckets of handoff score (whole)
    stuck_val: "np.ndarray"   # [K] desc; -1 entries = fewer than K rows
    stuck_row: "np.ndarray"
    churn_val: "np.ndarray"
    churn_row: "np.ndarray"
    heat_val: "np.ndarray"
    heat_row: "np.ndarray"


def unpack_health(flat, topk: int) -> HealthView:
    """Host-side inverse of :func:`_health_pack_impl` (zero-copy views)."""
    flat = np.asarray(flat)
    L = HealthLayout(topk)
    o = L.o_top
    cols = []
    for _ in range(6):
        cols.append(flat[o:o + topk])
        o += topk
    return HealthView(
        alloc=int(flat[HG_ALLOC]),
        backlog=int(flat[HG_BACKLOG]),
        wedged=int(flat[HG_WEDGED]),
        max_stall=int(flat[HG_MAX_STALL]),
        max_churn=int(flat[HG_MAX_CHURN]),
        lease_wait=int(flat[HG_LEASE_WAIT]),
        hist_stall=flat[L.o_hist_stall:L.o_hist_stall + HB],
        hist_churn=flat[L.o_hist_churn:L.o_hist_churn + HB],
        stuck_val=cols[0], stuck_row=cols[1],
        churn_val=cols[2], churn_row=cols[3],
        heat_val=cols[4], heat_row=cols[5],
    )


def _merge_top(val_l, row_l, val_r, row_r, g_log: int, topk: int):
    """Merge two planes' top-K columns into composite-row top-K: register
    rows re-offset by g_log, then one partial sort over 2K entries."""
    vals = np.concatenate([val_l, val_r])
    rows = np.concatenate([row_l, row_r + g_log])
    order = np.argsort(-vals, kind="stable")[:topk]
    return vals[order], rows[order]


def merge_health(hv_l: HealthView, hv_r: HealthView, g_log: int,
                 topk: int) -> HealthView:
    """Compose the two planes' health views into the composite row space
    (counts sum, maxima max, histograms add, top-K re-ranks)."""
    sv, sr = _merge_top(hv_l.stuck_val, hv_l.stuck_row,
                        hv_r.stuck_val, hv_r.stuck_row, g_log, topk)
    cv, cr = _merge_top(hv_l.churn_val, hv_l.churn_row,
                        hv_r.churn_val, hv_r.churn_row, g_log, topk)
    hv, hr = _merge_top(hv_l.heat_val, hv_l.heat_row,
                        hv_r.heat_val, hv_r.heat_row, g_log, topk)
    return HealthView(
        alloc=hv_l.alloc + hv_r.alloc,
        backlog=hv_l.backlog + hv_r.backlog,
        wedged=hv_l.wedged + hv_r.wedged,
        max_stall=max(hv_l.max_stall, hv_r.max_stall),
        max_churn=max(hv_l.max_churn, hv_r.max_churn),
        lease_wait=hv_l.lease_wait + hv_r.lease_wait,
        hist_stall=hv_l.hist_stall + hv_r.hist_stall,
        hist_churn=hv_l.hist_churn + hv_r.hist_churn,
        stuck_val=sv, stuck_row=sr,
        churn_val=cv, churn_row=cr,
        heat_val=hv, heat_row=hr,
    )
