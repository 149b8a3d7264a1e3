"""TickDriver: the thread that pumps the device data plane.

The reference's data plane is driven by packet arrival (NIO threads call
``PaxosManager.handleIncomingPacket``); the dense design instead advances
*all* groups in one fused device step, so something must call
``manager.tick()`` repeatedly.  This driver is that something: it ticks
eagerly while work is pending (queued proposals, undelivered windows) and
backs off to a low idle rate otherwise — the RequestBatcher's adaptive-sleep
idea (``gigapaxos/RequestBatcher.java:25-60``) applied to the whole plane.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional

from ..utils.heap import grow_arenas_by_whole_heaps
from ..wal.logger import WalError
from .manager import PaxosManager

log = logging.getLogger(__name__)

#: process-wide hook for failures that end a tick loop.  A storage failure
#: (fsyncgate semantics: the kernel may have dropped dirty pages, so
#: retrying the write would ack data that never reached disk) or anything
#: else a tick raises — a program the device compiler refuses, HBM
#: exhaustion — stops the plane for good.  The cells worker installs a
#: handler that dumps the flight recorder and exits the process nonzero so
#: the supervisor restarts the cell; in-process embeddings (tests,
#: notebooks) leave it None and observe ``driver.fatal`` instead — the
#: driver thread stops ticking either way, which is exactly "the node stops
#: acking".
FATAL_HANDLER: Optional[Callable[[BaseException], None]] = None


#: an idle plane's probe ticks take at most this share of its time.  A tick
#: costs what the plane is wide, not what it carries (100 ms of host and
#: device at 1M groups), and the planes of one process share the interpreter
#: and the device: an idle plane probing every ``idle_sleep_s`` ran flat out
#: there and took from the busy one what the start-up's race gave it
#: (PERF.md section 6, PR 30).  Work still starts a tick within
#: ``idle_sleep_s``: the wait polls ``pending_count()`` at that period.
IDLE_DUTY = 0.25
#: and the longest an idle plane sleeps between probes, whatever a tick cost
#: (its first one compiles): timers that count ticks keep moving
IDLE_WAIT_MAX_S = 0.5


class PlaneDown(RuntimeError):
    """A plane did not come up: its first tick raised, or never finished
    within the start-up timeout."""


class TickDriver:
    def __init__(
        self,
        manager: PaxosManager,
        idle_sleep_s: float = 0.002,
        drain_ticks: int = 4,
    ):
        """``drain_ticks``: extra ticks after the queues empty so in-flight
        device state (accepted-but-undecided slots, ring-buffer deliveries)
        reaches quiescence before the driver goes idle."""
        self.manager = manager
        self.idle_sleep_s = idle_sleep_s
        self.drain_ticks = drain_ticks
        # the tick thread allocates from its own malloc arena (utils/heap.py)
        grow_arenas_by_whole_heaps()
        #: the exception that fail-stopped this driver, if any
        self.fatal: Optional[BaseException] = None
        #: wall time of the first tick — its compile, for a cold plane
        self.first_tick_s: Optional[float] = None
        self._stop = threading.Event()
        self._kick = threading.Event()
        self._first_tick = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="tick-driver", daemon=True
        )

    def start(self) -> "TickDriver":
        self._thread.start()
        return self

    def kick(self) -> None:
        """Wake the driver immediately (call after enqueuing proposals)."""
        self._kick.set()

    def wait_ready(self, timeout_s: float | None = None) -> bool:
        """Block until the first tick completed — i.e. the jitted step is
        compiled and the plane answers at interactive latency.

        Default timeout is 120s, tripled for mesh managers: the shard_map
        tick compiles one SPMD program per mesh plus the separate
        pack/compact dispatch, which takes several times longer than the
        single-device program (worst on the 8-way virtual CPU mesh the
        tests use)."""
        if timeout_s is None:
            timeout_s = 360.0 if getattr(self.manager, "mesh", None) \
                is not None else 120.0
        return self._first_tick.wait(timeout=timeout_s) and self.fatal is None

    def require_ready(self, timeout_s: float | None = None) -> None:
        """:meth:`wait_ready`, raising :class:`PlaneDown` when the plane
        is not serving — so a dead plane fails its owner's start-up
        instead of looking like client timeouts later."""
        if self.wait_ready(timeout_s):
            return
        if self.fatal is not None:
            raise PlaneDown(
                f"tick driver died on {type(self.fatal).__name__}: "
                f"{self.fatal}") from self.fatal
        raise PlaneDown("first tick did not complete within the start-up "
                        "timeout (still compiling, or the device hangs)")

    def abandon(self) -> None:
        """Ask the loop to end without waiting for it (its tick may be
        stuck in a compile or on a hung device)."""
        self._stop.set()
        self._kick.set()

    def stop(self) -> None:
        self.abandon()
        self._thread.join(timeout=10)
        # a pipelined manager may hold one final unprocessed outbox whose
        # callbacks clients are still waiting on
        drain = getattr(self.manager, "drain_pipeline", None)
        if drain is not None:
            drain()

    def _idle_wait(self, tick_s: float) -> None:
        """Sleep between an idle plane's probe ticks: ``idle_sleep_s`` at
        least, and as long as keeps the probes to ``IDLE_DUTY`` of the
        plane's time given what the last one cost; a kick or newly pending
        work ends it at once."""
        until = time.monotonic() + min(
            IDLE_WAIT_MAX_S, tick_s * (1.0 / IDLE_DUTY - 1.0))
        while not self._kick.wait(timeout=self.idle_sleep_s):
            if (time.monotonic() >= until
                    or self.manager.pending_count() > 0):
                break
        self._kick.clear()

    def _run(self) -> None:
        drain = self.drain_ticks
        lock = getattr(self.manager, "lock", None)
        counted = hasattr(lock, "waiters")
        min_ivl = getattr(
            getattr(self.manager.cfg, "paxos", None),
            "min_tick_interval_s", 0.0,
        ) or 0.0
        last = 0.0
        started = time.monotonic()
        while not self._stop.is_set():
            if min_ivl > 0:
                gap = min_ivl - (time.monotonic() - last)
                if gap > 0:
                    time.sleep(gap)  # coalesce: let requests accumulate
                last = time.monotonic()
            t_tick = time.monotonic()
            try:
                self.manager.tick()
            except Exception as e:
                # fail-stop: storage lost (or refused) a write the plane
                # was about to ack, or the tick itself cannot run (compile
                # refusal, device out of memory).  Stop ticking — no further
                # decision is acked from this node — and surface the failure
                # instead of dying as a silent daemon thread.
                self.fatal = e
                log.critical(
                    "tick driver fail-stop%s: %s",
                    " (WAL)" if isinstance(e, WalError) else "", e,
                    exc_info=not isinstance(e, WalError))
                self._first_tick.set()  # unblock wait_ready() callers
                handler = FATAL_HANDLER
                if handler is not None:
                    handler(e)
                return
            tick_s = time.monotonic() - t_tick
            if self.first_tick_s is None:
                self.first_tick_s = time.monotonic() - started
            self._first_tick.set()
            # CPython locks are unfair: without a yield window the driver
            # re-acquires manager.lock before any waiting control-plane
            # thread (propose, create, stop) gets scheduled, starving them
            # indefinitely.  Blocked acquirers register in lock.waiters
            # (utils/locking.py), so the window is paid per tick for as long
            # as someone is STILL waiting — not just once per flag edge.
            if not counted:
                time.sleep(0.0005)
            elif lock.waiters > 0:
                time.sleep(0.0005)
            else:
                # clients stage proposals without touching the lock now, so
                # lock contention no longer signals their presence: yield
                # the GIL so messenger/client threads run on few-core hosts
                time.sleep(0)
            busy = self.manager.pending_count() > 0
            if not busy:
                # decided_now needs a device sync; only check when draining
                drain -= 1
                if drain <= 0:
                    self._idle_wait(tick_s)
                    drain = 1  # idle wake: one probe tick, drain more if busy
            else:
                drain = self.drain_ticks
