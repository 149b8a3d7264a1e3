"""Failure-detection tests: real sockets, crash = close the messenger;
plus the adaptive (EWMA inter-arrival) timeout and the full
detector -> alive-mask -> tick-inbox -> election propagation path over
the deterministic SimNet."""

import time

import numpy as np

from gigapaxos_tpu.net import Messenger, NodeMap
from gigapaxos_tpu.net.failure_detection import FailureDetection
from gigapaxos_tpu.net.transport import JsonDemux


def cluster(ids, ping=0.05, timeout=0.4):
    nm = NodeMap()
    ms = {nid: Messenger(nid, ("127.0.0.1", 0), nm) for nid in ids}
    for nid, m in ms.items():
        nm.add(nid, "127.0.0.1", m.port)
    fds = {
        nid: FailureDetection(
            m, [x for x in ids if x != nid], ping_interval_s=ping, timeout_s=timeout
        )
        for nid, m in ms.items()
    }
    return nm, ms, fds


def _holds_within(cond, seconds: float = 60.0) -> bool:
    """Poll ``cond`` until it holds (True as soon as it does) or the
    deadline passes.  Everything a step asserts goes into ONE condition:
    with a 0.4 s timeout and the pinger threads of three nodes sharing a
    core with five other test workers, a live peer can read as down for a
    moment, so liveness read once right after a wait is a coin toss, and
    20 s was an idle box's deadline."""
    deadline = time.monotonic() + seconds
    while not cond():
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.05)
    return True


def test_all_up_then_crash_then_recover():
    ids = ["A", "B", "C"]
    nm, ms, fds = cluster(ids)
    try:
        assert _holds_within(lambda: (
            all(fds["A"].is_node_up(n) for n in ids)
            and list(fds["A"].alive_mask(ids)) == [True, True, True]))

        # crash B: close its messenger (no more pongs)
        port_b = ms["B"].port
        fds["B"].close()
        ms["B"].close()
        assert _holds_within(lambda: (
            not fds["A"].is_node_up("B") and not fds["C"].is_node_up("B")
            and fds["A"].is_node_up("C") and fds["C"].is_node_up("A")
            and list(fds["A"].alive_mask(ids)) == [True, False, True]))
        assert fds["A"].alive_mask(ids).dtype == np.bool_

        # recover B on the same port
        ms["B"] = Messenger("B", ("127.0.0.1", port_b), nm)
        fds["B"] = FailureDetection(
            ms["B"], ["A", "C"], ping_interval_s=0.05, timeout_s=0.4
        )
        assert _holds_within(lambda: fds["A"].is_node_up("B"))
    finally:
        for f in fds.values():
            f.close()
        for m in ms.values():
            m.close()


def test_on_change_edges():
    events = []
    nm = NodeMap()
    a = Messenger("A", ("127.0.0.1", 0), nm)
    nm.add("A", "127.0.0.1", a.port)
    # monitor a node that never existed: one down edge after the grace window
    fd = FailureDetection(
        a,
        ["GHOST"],
        ping_interval_s=0.05,
        timeout_s=0.3,
        on_change=lambda n, up: events.append((n, up)),
    )
    try:
        deadline = time.monotonic() + 5
        while not events and time.monotonic() < deadline:
            time.sleep(0.05)
        assert events and events[0] == ("GHOST", False)
        n_down = len(events)
        time.sleep(0.3)
        assert len(events) == n_down  # edge-triggered, not repeated
    finally:
        fd.close()
        a.close()


class FakeMessenger:
    """Minimal Messenger surface for detector unit tests: no sockets, no
    delivery — pings vanish."""

    def __init__(self, node_id="A"):
        self.node_id = node_id
        self.demux = JsonDemux()

    def register(self, ptype, handler):
        self.demux.register(ptype, handler)

    def send(self, dest, packet):
        pass


def test_adaptive_timeout_floor_and_lengthening():
    """The adaptive timeout is Jacobson-style (EWMA of inter-arrival gaps
    plus 4x their mean deviation, scaled by beta) and FLOORED at the
    configured value: jittery links lengthen the fuse, nothing ever
    shortens it below config."""
    fd = FailureDetection(FakeMessenger(), ping_interval_s=0.05,
                          timeout_s=0.5, adaptive=True, adaptive_beta=1.5)
    try:
        fd.monitor("B")
        # no samples yet -> configured floor
        assert fd.current_timeout("B") == 0.5
        # quiet link: tiny gaps estimate far below the floor -> floored
        fd._gap_mean["B"], fd._gap_dev["B"] = 0.01, 0.005
        assert fd.current_timeout("B") == 0.5
        # jittery WAN link: estimate above the floor wins
        fd._gap_mean["B"], fd._gap_dev["B"] = 0.4, 0.1
        want = 1.5 * (0.4 + 4 * 0.1)
        assert abs(fd.current_timeout("B") - want) < 1e-9
        # non-adaptive detector ignores the estimator entirely
        fd.adaptive = False
        assert fd.current_timeout("B") == 0.5
    finally:
        fd.close()


def test_adaptive_ewma_updates_and_unmonitor_resets():
    fd = FailureDetection(FakeMessenger(), ping_interval_s=0.05,
                          timeout_s=0.5, adaptive=True)
    try:
        fd.monitor("B")  # monitor() stamps last-heard: gaps accrue from here
        time.sleep(0.03)
        fd.heard_from("B")
        assert fd._gap_mean["B"] > 0.0
        assert fd._gap_dev["B"] > 0.0
        m1 = fd._gap_mean["B"]
        time.sleep(0.06)
        fd.heard_from("B")
        assert fd._gap_mean["B"] != m1  # EWMA moved
        # untracked peers (ephemeral client ids) accrete no state
        fd.heard_from("GHOST")
        assert "GHOST" not in fd._gap_mean
        fd.unmonitor("B")
        assert "B" not in fd._gap_mean and "B" not in fd._gap_dev
    finally:
        fd.close()


def test_alive_mask_propagates_to_election_over_simnet():
    """End to end over the deterministic simulator: partition a node, the
    (adaptive) detector flips it down within its current timeout, the mask
    reaches the tick inbox via attach_failure_detector, the election
    excludes it (a survivor takes over and commits), then heal and assert
    the detector re-admits the node and it converges."""
    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.models.replicable import KVApp
    from gigapaxos_tpu.modeb import ModeBNode
    from gigapaxos_tpu.testing.simnet import SimNet

    ids = ["N0", "N1", "N2"]
    net = SimNet(seed=2)
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    apps = {n: KVApp() for n in ids}
    ms = {n: net.messenger(n) for n in ids}
    nodes = {n: ModeBNode(cfg, ids, n, apps[n], ms[n],
                          anti_entropy_every=8) for n in ids}
    fds = {n: FailureDetection(ms[n], [x for x in ids if x != n],
                               ping_interval_s=0.05, timeout_s=0.4,
                               adaptive=True)
           for n in ids}
    for n in ids:
        nodes[n].attach_failure_detector(fds[n])

    def spin_until(pred, budget_s=20.0):
        deadline = time.monotonic() + budget_s
        while time.monotonic() < deadline:
            for nd in nodes.values():
                nd.tick()
            net.pump()
            if pred():
                return True
            time.sleep(0.02)
        return False

    try:
        for nd in nodes.values():
            nd.create_group("svc", [0, 1, 2])
        done = []
        nodes["N0"].propose("svc", b"PUT a 1",
                            lambda _r, x: done.append(x))
        assert spin_until(lambda: bool(done))
        row = nodes["N1"].rows.row("svc")
        # whoever leads (first ticks race the detectors' wall clock, so
        # don't assume N0), partition it away from the two survivors
        coord = int(nodes["N1"]._coord_view[row])
        dead = ids[coord]
        surv = [n for n in ids if n != dead]

        # -- partition the coordinator; survivors' detectors must flip it
        #    down within the adaptive timeout (floored at 0.4 s)
        net.partition({dead}, set(surv))
        t0 = time.monotonic()
        fuse = max(fds[surv[0]].current_timeout(dead), 0.4)
        assert spin_until(lambda: not fds[surv[0]].is_node_up(dead))
        assert time.monotonic() - t0 < fuse + 2.0  # detected promptly
        # the mask reached the tick inbox: the election excluded the dead
        # coordinator and a survivor committed
        done2 = []
        nodes[surv[0]].propose("svc", b"PUT b 2",
                               lambda _r, x: done2.append(x))
        assert spin_until(lambda: bool(done2))
        assert int(nodes[surv[0]]._coord_view[row]) != coord
        assert not fds[surv[0]].alive_mask(ids)[coord]
        assert not fds[surv[1]].is_node_up(dead)

        # -- heal: detectors re-admit the node and it converges on the
        #    log it missed
        net.heal()
        assert spin_until(lambda: fds[surv[0]].is_node_up(dead))
        assert spin_until(
            lambda: apps[dead].db.get("svc", {}).get("b") == "2")
    finally:
        for f in fds.values():
            f.close()
        for nd in nodes.values():
            nd.close()


def test_self_always_up_and_unmonitor():
    nm = NodeMap()
    a = Messenger("A", ("127.0.0.1", 0), nm)
    nm.add("A", "127.0.0.1", a.port)
    fd = FailureDetection(a, [], ping_interval_s=0.05, timeout_s=0.3)
    try:
        assert fd.is_node_up("A")
        fd.monitor("A")  # no-op
        fd.monitor("X")
        fd.unmonitor("X")
        assert "X" not in fd._monitored
    finally:
        fd.close()
        a.close()
