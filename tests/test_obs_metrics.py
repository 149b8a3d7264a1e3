"""Flight-deck metrics core: histogram bucket math, registry semantics,
Prometheus rendering, phase clocks, StatsReporter restart and the Mode A
``node_stats_source`` fix (ISSUE 9 satellites 1/3/6)."""

import threading
import time

from gigapaxos_tpu.config import GigapaxosTpuConfig
from gigapaxos_tpu.models.replicable import KVApp
from gigapaxos_tpu.obs.metrics import (Histogram, NullRegistry, Registry,
                                       _NULL_METRIC)
from gigapaxos_tpu.obs.phase import DRIVER_PHASES, PhaseClock
from gigapaxos_tpu.obs.prom import merge_scrapes, render_registry
from gigapaxos_tpu.paxos.manager import PaxosManager
from gigapaxos_tpu.utils.observability import (StatsReporter,
                                               node_stats_source)


# ---------------------------------------------------------------- histogram
def test_histogram_log_buckets_and_percentiles():
    h = Histogram("lat_seconds")
    for v in (0.001, 0.001, 0.002, 0.004, 0.1):
        h.observe(v)
    assert h.count == 5
    assert abs(h.total - 0.108) < 1e-9
    # log-bucket percentile: upper bound of the rank's bucket, so the
    # answer is within 2x of the true value, never below it
    p50 = h.percentile(0.50)
    assert 0.001 <= p50 <= 0.002 * 2
    p99 = h.percentile(0.99)
    assert 0.1 <= p99 <= 0.2
    # monotone in q
    assert h.percentile(0.1) <= p50 <= p99


def test_histogram_edge_cases():
    h = Histogram("x_seconds")
    assert h.percentile(0.5) == 0.0  # empty
    h.observe(-1.0)      # clamped into the zero bucket, not a crash
    h.observe(0.0)
    assert h.count == 2
    assert h.percentile(0.99) == 0.0
    # raw-unit histogram (writev batch sizes): no 1e6 scaling
    b = Histogram("batch", unit="")
    for n in (1, 2, 8, 64):
        b.observe(n)
    assert 64 <= b.percentile(0.99) <= 128


def test_registry_get_or_create_and_null_twin():
    r = Registry()
    a = r.counter("c_total", node="n0")
    b = r.counter("c_total", node="n0")
    assert a is b
    assert r.counter("c_total", node="n1") is not a
    a.inc()
    a.inc(3)
    assert a.value == 4
    g = r.gauge("g", help="x")
    g.set(7)
    g.inc(-2)
    assert g.value == 5
    assert r.help_text("g") == "x"
    snap = r.snapshot()
    assert snap['c_total{node=n0}'] == 4
    # the compiled-out twin hands every caller the same no-op object and
    # renders to nothing
    n = NullRegistry()
    m = n.histogram("anything", weird="label")
    assert m is _NULL_METRIC and m is n.counter("other")
    m.observe(1.0)
    m.inc()
    m.set(2)  # all no-ops
    assert n.metrics() == [] and n.snapshot() == {}
    assert render_registry(n) == ""


def test_snapshot_carries_sparse_buckets_and_two_give_a_windows_maximum():
    import json

    r = Registry()
    h = r.histogram("tick_seconds", driver="modea", plane="t")
    key = "tick_seconds{driver=modea,plane=t}"
    # before the window: one long first tick (a compile), many short ones
    h.observe(2.5)
    for _ in range(10):
        h.observe(0.090)
    snap0 = r.snapshot()
    b0 = snap0[key]["buckets"]
    # only the buckets that hold a sample, index as a string (a JSON key)
    assert b0 == {str(int(0.090 * 1e6).bit_length()): 10,
                  str(int(2.5 * 1e6).bit_length()): 1}
    assert json.loads(json.dumps(snap0)) == snap0
    assert sum(b0.values()) == snap0[key]["count"]
    # the window: short ticks and one stall of 0.7 s
    for _ in range(20):
        h.observe(0.095)
    h.observe(0.7)
    b1 = r.snapshot()[key]["buckets"]
    rose = [int(i) for i, c in b1.items() if c > b0.get(i, 0)]
    longest = h.bucket_upper(max(rose))
    # the window's maximum to its bucket (within 2x above the sample): the
    # since-start p99 still says 2.5 s, the first tick's
    assert 0.7 <= longest < 1.4
    assert h.percentile(0.99) > 2.5
    # an empty histogram has no bucket to show
    r.histogram("idle_seconds")
    assert r.snapshot()["idle_seconds"]["buckets"] == {}


# ---------------------------------------------------------------- rendering
def test_render_registry_prometheus_text():
    r = Registry()
    r.counter("req_total", help="requests", node="n0").inc(3)
    h = r.histogram("lat_seconds", help="latency")
    for v in (0.001, 0.002, 0.004):
        h.observe(v)
    body = render_registry(r, extra_labels={"cell": "1"})
    lines = body.splitlines()
    assert "# HELP req_total requests" in lines
    assert "# TYPE req_total counter" in lines
    assert 'req_total{cell="1",node="n0"} 3' in lines
    assert "# TYPE lat_seconds histogram" in lines
    assert any(l.startswith('lat_seconds_bucket{cell="1",le="')
               for l in lines)
    assert 'lat_seconds_bucket{cell="1",le="+Inf"} 3' in lines
    assert 'lat_seconds_count{cell="1"} 3' in lines
    assert any(l.startswith('lat_seconds_p50{cell="1"}') for l in lines)
    assert any(l.startswith('lat_seconds_p99{cell="1"}') for l in lines)
    # bucket counts are cumulative (monotone non-decreasing)
    counts = [int(l.rsplit(" ", 1)[1]) for l in lines
              if l.startswith("lat_seconds_bucket")]
    assert counts == sorted(counts)
    # an existing label is never clobbered by the extra labels
    body2 = render_registry(r, extra_labels={"node": "OTHER"})
    assert 'req_total{node="n0"} 3' in body2


def test_merge_scrapes_dedups_metadata():
    r1, r2 = Registry(), Registry()
    r1.counter("x_total", help="x", cell="0").inc()
    r2.counter("x_total", help="x", cell="1").inc(2)
    merged = merge_scrapes([render_registry(r1), render_registry(r2)])
    lines = merged.splitlines()
    assert lines.count("# HELP x_total x") == 1
    assert lines.count("# TYPE x_total counter") == 1
    assert 'x_total{cell="0"} 1' in lines
    assert 'x_total{cell="1"} 2' in lines


# -------------------------------------------------------------- phase clock
def test_phase_clock_marks_declared_phases():
    r = Registry()
    pc = PhaseClock("modea", plane="t", reg=r)
    pc.begin()
    for ph in DRIVER_PHASES["modea"]:
        time.sleep(0.001)
        pc.mark(ph)
    pc.end()
    for ph in DRIVER_PHASES["modea"]:
        hs = [m for m in r.find("tick_phase_seconds")
              if dict(m.labels).get("phase") == ph]
        assert len(hs) == 1 and hs[0].count == 1, ph
        assert hs[0].total > 0
    ticks = r.find("tick_seconds")
    assert len(ticks) == 1 and ticks[0].count == 1
    # whole-tick covers the sum of its phases
    assert ticks[0].total >= sum(
        m.total for m in r.find("tick_phase_seconds"))


def test_phase_clock_touch_rearms_without_observing():
    r = Registry()
    pc = PhaseClock("modea", plane="t2", reg=r)
    pc.begin()
    pc.mark("intake")
    time.sleep(0.005)
    pc.touch()  # pipelined completion entry: drop the gap on the floor
    pc.mark("tally")
    tally = [m for m in r.find("tick_phase_seconds")
             if dict(m.labels).get("phase") == "tally"][0]
    # the 5ms gap before touch() must not be attributed to "tally"
    assert tally.total < 0.005


# ------------------------------------------------------------ StatsReporter
def test_stats_reporter_stop_then_start_restarts(monkeypatch):
    """Satellite 6: a stop/start cycle (supervisor-driven cell restart)
    must spin a fresh loop thread — the old code kept the set Event and
    dead Thread, so the second start() was a silent no-op."""
    seen = []
    rep = StatsReporter("n0", interval_s=0.5, sink=seen.append)
    monkeypatch.setattr(rep, "interval_s", 0.01)  # fast loop for the test
    rep.add_source("k", lambda: {"v": 1})
    rep.start()
    t1 = rep._thread
    assert t1 is not None and t1.is_alive()
    rep.stop()
    assert rep._thread is None and not t1.is_alive()
    n0 = len(seen)
    rep.start()
    t2 = rep._thread
    assert t2 is not None and t2 is not t1 and t2.is_alive()
    deadline = time.monotonic() + 5
    while len(seen) <= n0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(seen) > n0, "restarted reporter never ticked"
    rep.stop()
    assert seen and seen[-1]["k"] == {"v": 1}


def test_stats_reporter_sink_errors_do_not_kill_loop(monkeypatch):
    hits = []

    def bad_sink(snap):
        hits.append(snap)
        raise RuntimeError("boom")

    rep = StatsReporter("n0", interval_s=0.5, sink=bad_sink)
    monkeypatch.setattr(rep, "interval_s", 0.01)
    rep.start()
    deadline = time.monotonic() + 5
    while len(hits) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    rep.stop()
    assert len(hits) >= 2  # survived the first sink explosion


def test_node_stats_source_over_modea_manager():
    """Satellite 1: the source must work over a Mode A PaxosManager (a
    RowAllocator has ``names()``, not ``items()``; stats is a Counter)."""
    cfg = GigapaxosTpuConfig()
    cfg.paxos.max_groups = 8
    m = PaxosManager(cfg, 3, [KVApp() for _ in range(3)])
    m.create_paxos_instance("a", [0, 1, 2])
    m.create_paxos_instance("b", [0, 1, 2])
    done = threading.Event()
    m.propose("a", b"PUT k v", lambda rid, r: done.set())
    for _ in range(64):
        m.tick()
        if done.is_set():
            break
    m.drain_pipeline()
    assert done.is_set()
    snap = node_stats_source(m)()
    assert snap["groups"] == 2
    assert snap["ticks"] >= 1
    assert snap["alive"] == [True, True, True]
    assert snap["stats"].get("decisions", 0) >= 1
    import json
    json.dumps(snap)  # reporter emits JSON lines: must be serialisable
