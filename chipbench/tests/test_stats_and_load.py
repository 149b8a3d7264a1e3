"""The end-to-end arithmetic, the generator's schedule and the due-instant
timing, with no cluster: a stand-in client answers after a fixed delay."""

import threading
import time

import numpy as np
import pytest

from chipbench import load, spec, stats
from chipbench.generators import open_poisson

PARAMS = spec.load_traffic("open1k-put-uniform")["params"]


def test_percentile_interpolates_between_order_statistics():
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert stats.percentile(range(1, 101), 95) == pytest.approx(95.05)
    assert stats.percentile([10, 0], 50) == 5.0
    a = np.random.default_rng(0).exponential(size=1001)
    assert stats.percentile(a, 95) == pytest.approx(np.percentile(a, 95))
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_end_to_end_counts_latency_from_the_due_instant_and_drops_failures():
    due = np.array([0.0, 1.0, 2.0, 3.0, 9.0])
    done = np.array([0.5, 2.0, np.nan, 3.25, 9.1])
    status = np.array([stats.OK, stats.OK, stats.PENDING, stats.BUSY, stats.OK])
    in_window = np.array([True, True, True, True, False])
    out = stats.end_to_end(due, done, status, in_window, seconds=4.0)
    assert out["attempted"] == 4 and out["failed"] == 2
    assert out["by_status"] == {"pending": 1, "ok": 2, "busy": 1,
                                "expired": 0, "error": 0}
    assert out["samples"] == 2            # a failed request has no sample
    assert out["goodput_ops"] == 2 / 4.0  # ... and is not in the goodput
    assert out["commit_p50_ms"] == pytest.approx(750.0)
    assert out["commit_p95_ms"] == pytest.approx(500 + 0.95 * 500)
    none = stats.end_to_end(due, done, np.full(5, stats.BUSY), in_window, 4.0)
    assert none["goodput_ops"] == 0 and "commit_p50_ms" not in none


def test_schedule_is_the_same_work_under_every_seed():
    a = open_poisson.schedule(PARAMS, 7, 2.0, 1000, 3)
    b = open_poisson.schedule(PARAMS, 7, 2.0, 1000, 3)
    c = open_poisson.schedule(PARAMS, 3_000_000_011, 2.0, 1000, 3)
    assert a.payload == b.payload and (a.due == b.due).all()
    assert len(a.due) == len(c.due) == 2000       # rate * seconds, exactly
    assert a.payload != c.payload
    assert (np.diff(a.due) >= 0).all() and 0 <= a.due[0] and a.due[-1] < 2.0
    assert set(np.unique(a.entry)) == {0, 1, 2} and a.name.max() < 1000
    assert len(set(a.value)) == 2000               # every value is unique
    assert all(len(v) == 32 for v in a.value)
    assert a.payload[0] == f"PUT k {a.value[0]}".encode()
    warm = open_poisson.schedule(PARAMS, 7, 2.0, 1000, 3, stream=1, seq0=10**9)
    assert not set(warm.value) & set(a.value)


class SlowClient:
    """Answers ``ok`` after ``delay_s``; ``send_request`` itself blocks for
    ``send_s`` (a stalled sender)."""

    def __init__(self, delay_s, send_s=0.0, refuse_every=0):
        self.delay_s, self.send_s, self.refuse_every = delay_s, send_s, refuse_every
        self.seen = []
        self.timers = []

    def send_request(self, name, payload, callback, active=None):
        self.seen.append((name, payload, active))
        time.sleep(self.send_s)
        n = len(self.seen)
        p = ({"ok": False, "error": "busy"}
             if self.refuse_every and n % self.refuse_every == 0
             else {"ok": True, "response": "T0s="})
        t = threading.Timer(self.delay_s, callback, args=(p,))
        t.start()
        self.timers.append(t)


def test_latency_counts_from_due_when_the_generator_stalls():
    sched = open_poisson.schedule(dict(PARAMS, rate_per_s=100), 1, 0.5, 8, 3)
    names, actives = [f"n{i}" for i in range(8)], ["AR0", "AR1", "AR2"]
    # each send blocks 20 ms against a 10 ms mean gap: the generator falls
    # behind, and the wait must show in the latency, not vanish
    client = SlowClient(delay_s=0.05, send_s=0.02, refuse_every=10)
    ld = load.Load(sched, names, actives)
    ld.offer(client, time.monotonic() + 0.01)
    assert ld.wait_replies(5.0)
    for t in client.timers:
        t.join()
    assert ld.n_sent == 50 and ld.answered() == 50
    assert client.seen[0] == (names[sched.name[0]], sched.payload[0],
                              actives[sched.entry[0]])
    late = ld.late_ms()
    assert late.min() >= 0 and late[-1] > 300   # ~50 * 10 ms behind by the end
    e2e = stats.end_to_end(ld.due, ld.done, ld.status, np.ones(50, bool), 0.5)
    assert e2e["failed"] == 5 and e2e["by_status"]["busy"] == 5
    assert e2e["goodput_ops"] == 45 / 0.5
    from_sent = np.nanmedian((ld.done - ld.sent) * 1e3)
    assert from_sent < 120                          # the send-instant clock hides it
    assert e2e["commit_p50_ms"] > from_sent + 100   # the due-instant clock does not
    assert e2e["commit_p95_ms"] >= e2e["commit_p50_ms"]


def test_offer_stops_when_told_and_wait_gives_up_at_the_deadline():
    sched = open_poisson.schedule(dict(PARAMS, rate_per_s=200), 1, 1.0, 8, 3)
    client = SlowClient(delay_s=30.0)
    ld = load.Load(sched, [f"n{i}" for i in range(8)], ["AR0", "AR1", "AR2"])
    ld.offer(client, time.monotonic(), stop=lambda: ld.n_sent >= 5)
    assert ld.n_sent == 5
    assert not ld.wait_replies(0.05)
    for t in client.timers:
        t.cancel()
