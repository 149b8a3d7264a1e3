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


# ------------------------------------------- the two spread rules of measure.py
@pytest.mark.parametrize("values, spread, why", [
    # one far-off run is left out: 100..104 is the side's range
    ([100.0, 101.0, 102.0, 103.0, 104.0, 150.0], 4.0 / 102.5, "far-off run"),
    # the same run on the low side
    ([50.0, 100.0, 101.0, 102.0, 103.0, 104.0], 4.0 / 101.5, "far-off low run"),
    # two runs share the far end: leaving one out does not narrow the range
    ([100.0, 100.0, 102.0, 103.0, 105.0, 105.0], 5.0 / 102.5, "no narrowing"),
    # both ends as far from the median: the one whose absence narrows more
    ([90.0, 95.0, 100.0, 101.0, 102.0, 111.0], 12.0 / 100.5, "tie"),
    ([500.0, 520.0], 20.0 / 510.0, "two values: nothing is left out"),
    ([512.3], 0.0, "one value"),
    ([], 0.0, "no value"),
    # ledger PR 28, the parent's six runs of commit_p95_ms: a range of 42.9 ms
    # over a median of 698.2 ms reads 6.1%, past the bound of 6%
    ([670.0, 690.0, 697.4, 699.0, 712.9, 760.0], 42.9 / 698.2, "ledger PR 28"),
])
def test_driver_spread_leaves_out_the_run_farthest_from_the_median(
        values, spread, why):
    from chipbench import measure

    assert measure.driver_spread(values) == pytest.approx(spread), why
    assert measure.driver_spread(values[::-1]) == pytest.approx(spread), why
    if why == "ledger PR 28":
        ratio = measure.driver_spread(values) / 0.06
        assert round(100 * measure.driver_spread(values), 1) == 6.1
        assert ratio > 1 and measure.verdict(ratio).startswith("UNRESOLVED")
        # the quartile rule reads the same six runs differently
        assert measure.spread(values) != pytest.approx(spread)


@pytest.mark.parametrize("values, spread, why", [
    # the far-off run is left out, then the quartiles of the other five
    ([100.0, 101.0, 102.0, 103.0, 104.0, 150.0], 3.0 / 102.5, "far-off run"),
    ([50.0, 100.0, 101.0, 102.0, 103.0, 104.0], 3.0 / 101.5, "far-off low run"),
    ([500.0, 520.0], 30.0 / 510.0, "two values: the quartile rule as it is"),
    ([512.3], 0.0, "one value"),
    # the check that refused PR 29 read 31.4628 ms of a median 517.809 ms
    # against a bound of 6%: over half of it
    ([500.0, 500.0, 504.1552, 531.4628, 531.4628, 600.0], 31.4628 / 517.809,
     "PR 29's refusal"),
])
def test_check_spread_is_the_quartile_rule_without_the_farthest_run(
        values, spread, why):
    from chipbench import measure

    assert measure.check_spread(values) == pytest.approx(spread), why
    assert measure.check_spread(values[::-1]) == pytest.approx(spread), why
    if why == "PR 29's refusal":
        assert measure.check_spread(values) / 0.06 > 0.5


def test_measure_reports_both_rules_against_the_benchmarks_bounds(capsys):
    from chipbench import measure

    bound_of = measure.bounds()
    assert set(bound_of) >= {"commit_p50_ms", "commit_p95_ms", "goodput_ops",
                             "setup_s"}
    p95 = [670.0, 690.0, 697.4, 699.0, 712.9, 760.0]
    lines = [{"metrics": {"commit_p95_ms": {"value": v, "unit": "ms"},
                          "extra": {"value": 1.0, "unit": "x"}}} for v in p95]
    med, spr, drv, n, chk = measure.summarise(lines)["commit_p95_ms"]
    assert chk == pytest.approx(measure.check_spread(p95))
    assert (med, n) == (pytest.approx(698.2), 6)
    assert spr == pytest.approx(measure.spread(p95))
    assert drv == pytest.approx(42.9 / 698.2)
    measure.report("set", lines, {"commit_p95_ms": 0.06})
    out = capsys.readouterr().out.splitlines()
    assert "driver_spread 6.14%" in out[0] and "bound 6%" in out[0]
    assert "driver_spread/bound 1.02 (UNRESOLVED" in out[0]
    assert "bound" not in out[1]          # a metric with no bound: spreads only
    assert measure.verdict(0.5) == "ok" and "half" in measure.verdict(0.51)
    assert measure.diag_of("x\n[ 1.0s] diag: {\"ar\": {\"tick0\": 3}}\ny") == {
        "ar": {"tick0": 3}}
    assert measure.diag_of("nothing here") is None


def test_window_diagnostics_counts_the_periodic_ticks_and_the_gaps():
    from chipbench import harness

    gen = [{"collections": 10}, {"collections": 4}, {"collections": 1}]
    end = [{"collections": 90}, {"collections": 11}, {"collections": 3}]
    d = harness.window_diagnostics((250, 60), (350, 130), 20.0, gen, end)
    assert d["ar"] == {"tick0": 250, "tick1": 350, "period_ms": 200.0,
                       "multiples_of_64": 2, "multiples_of_256": 1}
    assert d["rc"]["multiples_of_64"] == 2 and d["rc"]["multiples_of_256"] == 0
    assert d["gc_collections"] == [80, 7, 2]
    idle = harness.window_diagnostics((5, 5), (5, 9), 1.0, gen, gen)
    assert idle["ar"]["period_ms"] is None and idle["rc"]["period_ms"] == 250.0
    # a timeline sampled every 10 ms: the data plane ticks every 100 ms but
    # for one gap of 400 ms, the control plane never; of the oldest
    # generation's collections only those that began inside the window count
    t = np.arange(0.0, 3.0, 0.01)
    ends = np.array([0.1, 0.2, 0.3, 0.4, 0.8, 0.9, 1.0, 1.1, 1.2, 1.3])
    rows = [(10.0 + x, int((ends <= x + 1e-9).sum()), 0) for x in t]
    k = harness.timeline_diagnostics(
        rows, [(9.5, 0.2), (10.5, 0.15), (11.5, 0.05), (12.5, 0.3)], 10.0, 2.0)
    assert k["ar"] == {"longest_gap_ms": pytest.approx(400.0),
                       "median_gap_ms": pytest.approx(100.0), "long_gaps": 1,
                       "long_gaps_excess_ms": pytest.approx(300.0),
                       "longest_gap_at_s": pytest.approx(0.4)}
    assert "rc" not in k
    assert k["gc_oldest_ms"] == {"n": 2, "sum": pytest.approx(200.0),
                                 "longest": pytest.approx(150.0)}
    assert harness.timeline_diagnostics([], [], 10.0, 2.0) == {
        "gc_oldest_ms": {"n": 0, "sum": 0.0, "longest": 0.0}}
