"""The readers that put the chip's runs and idle time down to the program's
own host spans, on hand-made raw traces: ``trace_plane_runs`` (each run of
the tick program paired with the launch that enqueued it) and
``trace_idle_by_phase`` (the chip's idle time inside each host span).

Times below are in ms and turned into the trace's ns by ``ms``.  A Mode A
tick of a plane is a launch inside ``dispatch`` and, later in the same call,
one completion (``tally``) that waits for the program; the device runs what
it is given in the order it was given.
"""

import os
import types

import pytest

from chipbench import rawtrace, spec, tracing
from chipbench.readers import trace_idle_by_phase, trace_plane_runs

CHIP = "/device:TPU:0"
TICK = "jit__paxos_tick_planes_impl(1)"
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tick-1m.rawtrace.json.gz")
#: cut from a traced run of ``probe-1m-open1k`` on a v5e chip (seed
#: 3000004101, the first tree with the parts of ``dispatch``):
#: ``RawTrace.cut(1440e6, 1720e6)`` + ``to_json(name_chars=24)``, 280 ms,
#: nine runs of the tick program, both planes' phases and parts
CHIP_CUT = os.path.join(os.path.dirname(__file__), "data",
                        "plane-runs-1m.rawtrace.json.gz")
#: cut from a traced run of ``probe-1m-mesh4-open1k`` on a v5e-4 host (seed
#: 3000004103): ``RawTrace.cut(4795e6, 5030e6)`` with the ops left out
#: (pairing reads none), ten ticks on each of four chips
MESH_CUT = os.path.join(os.path.dirname(__file__), "data",
                        "plane-runs-1m-mesh4.rawtrace.json.gz")


def ms(x: float) -> float:
    return x * 1e6


def launch(plane, start, end):
    return (f"gptpu/modea/{plane}/dispatch/launch", ms(start), ms(end - start))


def tally(plane, start, end):
    return (f"gptpu/modea/{plane}/tally", ms(start), ms(end - start))


def runs_on(chip_runs, program=TICK):
    """XLA Modules and XLA Ops of one chip: one op filling each run."""
    mods = [(program if len(r) == 2 else r[2], ms(r[0]), ms(r[1] - r[0]))
            for r in chip_runs]
    ops = [("%fusion", "jit(f)/accept/x:", s, d) for _, s, d in mods]
    return mods, ops


#: both planes on one chip: ar (data plane) ticks at 10, 60, 110 and 160 ms,
#: rc (control plane) at 30 and 130.  One run starts before the slice's
#: first launch (dropped), one was launched before the slice and is still
#: queued at its first launch (12-20: it leads the slice's own runs).
LAUNCHES = [launch("ar", 10, 12), launch("rc", 30, 33), launch("ar", 60, 62),
            launch("ar", 110, 112), launch("rc", 130, 132),
            launch("ar", 160, 162)]
TALLIES = [tally("ar", 36, 50), tally("rc", 40, 58), tally("ar", 76, 85),
           tally("ar", 126, 130), tally("rc", 151, 155),
           tally("ar", 176, 180)]
RUNS = [(0, 8), (12, 20), (20, 35), (35, 55), (62, 75), (112, 125),
        (132, 150), (162, 175)]


def raw_of(runs=RUNS, host=None, chips=(CHIP,), program=TICK):
    mods, ops = runs_on(runs, program)
    host = sorted(host if host is not None else LAUNCHES + TALLIES,
                  key=lambda e: e[1])
    return rawtrace.RawTrace({c: list(ops) for c in chips},
                             {c: list(mods) for c in chips}, host)


def traced(monkeypatch, raw, held=0):
    """A traced run whose raw trace is ``raw`` and whose window's counters
    say each plane held ``held`` outboxes."""
    monkeypatch.setattr(rawtrace, "of_this_run", lambda: raw)
    key = "tick_completions_total{mode=held,plane=%s}"
    snap0 = {key % p: 5 for p in ("ar", "rc")}
    snap1 = {key % p: 5 + held for p in ("ar", "rc")}
    return types.SimpleNamespace(trace=object(), snap0=snap0, snap1=snap1)


def test_fifo_pairing_across_both_planes_with_a_run_from_before_the_slice():
    pairs = trace_plane_runs.pair(raw_of())
    got = [(p, ls / 1e6, s / 1e6) for p, ls, _, s, _, _ in pairs[CHIP]]
    assert all(known for *_, known in pairs[CHIP])
    assert got == [("ar", 10, 20), ("rc", 30, 35), ("ar", 60, 62),
                   ("ar", 110, 112), ("rc", 130, 132), ("ar", 160, 162)]


def test_the_queue_and_the_share_of_each_plane(monkeypatch, capsys):
    run = traced(monkeypatch, raw_of())
    # ar waited 8 ms once (behind the run from before the slice), then 0
    assert trace_plane_runs.read(run, "queue", "ar") == pytest.approx(2.0)
    assert trace_plane_runs.read(run, "queue", "rc") == pytest.approx(1.0)
    # busy from 20 to 175: 92 ms, of which rc's two runs 38
    assert trace_plane_runs.read(run, "share", "rc") == pytest.approx(
        100 * 38 / 92)
    assert trace_plane_runs.read(run, "share", "ar") == pytest.approx(
        100 * 54 / 92)
    err = capsys.readouterr().err
    assert "6 runs paired on 1 chip(s), 4 of ar" in err
    for metric, value in (("exec_queue_ms", 2.0),
                          ("rc_busy_pct", 100 * 38 / 92)):
        m = spec.layer_metric(metric)
        assert spec.reader(m["reader"]).read(run, **m["args"]) == (
            pytest.approx(value))


def test_a_recorded_chip_trace_pairs_every_run_with_its_launch():
    """On the chip the profiler's alignment of the two clocks puts most
    runs up to 0.7 ms before the launch that enqueued them opened; two of
    the data plane's runs queued behind the control plane's program."""
    raw = rawtrace.RawTrace.from_json(CHIP_CUT)
    assert os.path.getsize(CHIP_CUT) < 100_000
    pairs = trace_plane_runs.pair(raw)[CHIP]
    assert [p for p, *_ in pairs] == ["ar", "rc", "ar", "ar", "ar", "rc",
                                      "ar", "ar", "ar"]
    early = [(s - ls) / 1e6 for _, ls, _, s, _, _ in pairs]
    assert -0.71 < min(early) < 0 and sum(e < 0 for e in early) == 6
    queued = [(s - le) / 1e6 for p, _, le, s, _, _ in pairs if p == "ar"]
    assert [round(q, 1) for q in queued if q > 0] == [11.4, 15.6]
    shares = trace_plane_runs.shares(raw, {CHIP: pairs})
    assert shares["rc"] == pytest.approx(21.90, abs=0.01)
    assert shares["ar"] + shares["rc"] == pytest.approx(98.51, abs=0.01)
    # a run taken out of the middle breaks the pairing, not shifts it
    mods = raw.modules[CHIP]
    ticks = [i for i, m in enumerate(mods) if "paxos_tick" in m[0]]
    lost = rawtrace.RawTrace(raw.ops, {CHIP: [
        m for i, m in enumerate(mods) if i != ticks[4]]}, raw.host)
    with pytest.raises(trace_plane_runs.Unpaired):
        trace_plane_runs.pair(lost)


def test_on_four_chips_overlapping_launches_are_told_apart_by_the_clock():
    """A four-chip launch is two dispatches, the sharded tick first; the
    control plane's launch at 4,848.6 ms began before the data plane's and
    returned after it, and its tick ran first on every chip.  In this trace
    no run of a launch that overlapped nothing started sooner than 0.63 ms
    after its launch, so the data plane's launch cannot have the run that
    started 3.7 ms before it: one order of the pair fits, on every chip."""
    raw = rawtrace.RawTrace.from_json(MESH_CUT)
    ls = trace_plane_runs.launches(raw)
    assert sum(1 for a, b in zip(ls, ls[1:]) if b[1] < a[2]) == 2
    pairs = trace_plane_runs.pair(raw)
    assert all(known for ps in pairs.values() for *_, known in ps)
    assert sorted(pairs) == [f"/device:TPU:{i}" for i in range(4)]
    planes = [[p for p, *_ in ps] for ps in pairs.values()]
    assert planes == [["ar", "ar", "rc", "ar", "ar", "ar", "ar", "ar", "rc",
                       "ar"]] * 4
    # every tick ran before its launch returned: the compaction's dispatch
    # is the rest of the launch
    assert all(s < le for ps in pairs.values() for _, _, le, s, _, _ in ps)


def test_a_pair_of_overlapping_launches_the_checks_cannot_order(
        monkeypatch, capsys):
    """rc's launch at 110-114 and ar's at 111-113 overlap, both runs start
    after both launches and end before both completions: either could be
    either's.  The pair is given in the order the launches began and marked
    not known; the share counts it (one run of each plane either way), the
    queue leaves it out."""
    launches = [launch("ar", 10, 12), launch("rc", 110, 114),
                launch("ar", 111, 113), launch("ar", 160, 162)]
    tallies = [tally("ar", 36, 50), tally("rc", 150, 155),
               tally("ar", 151, 156), tally("ar", 176, 180)]
    runs = [(20, 35), (115, 130), (130, 145), (162, 175)]
    raw = raw_of(runs, launches + tallies)
    pairs = trace_plane_runs.pair(raw)[CHIP]
    assert [(p, k) for p, *_, k in pairs] == [
        ("ar", True), ("rc", False), ("ar", False), ("ar", True)]
    run = traced(monkeypatch, raw)
    # ar's two known runs waited 8 and 0 ms
    assert trace_plane_runs.read(run, "queue", "ar") == pytest.approx(4.0)
    assert trace_plane_runs.read(run, "share", "rc") == pytest.approx(
        100 * 15 / 58)
    assert "3 of ar, 2 of them to a known launch" in capsys.readouterr().err


def test_a_run_that_starts_before_its_launch_returned_waited_for_nothing(
        monkeypatch):
    late = [launch("ar", 10, 25) if e[0].endswith("launch") and e[1] == ms(10)
            else e for e in LAUNCHES]
    run = traced(monkeypatch, raw_of(host=late + TALLIES))
    # ar's first run starts at 20, inside its launch: 0, not -5
    assert trace_plane_runs.read(run, "queue", "ar") == 0.0


def test_a_window_with_a_held_outbox_or_without_counters_is_not_paired(
        monkeypatch, capsys):
    """A held outbox is completed in the plane's next call, where the rule
    looks for the completion of that call's own tick; the reader does not
    pair such a window, nor one whose counters it was not given."""
    run = traced(monkeypatch, raw_of(), held=3)
    assert trace_plane_runs.read(run, "queue", "ar") is None
    assert "held for a later call" in capsys.readouterr().err
    bare = types.SimpleNamespace(trace=object())
    assert trace_plane_runs.read(bare, "share", "rc") is None


def test_a_missing_run_gives_nothing_and_not_a_shifted_pairing(
        monkeypatch, capsys):
    lost = [r for r in RUNS if r != (112, 125)]
    with pytest.raises(trace_plane_runs.Unpaired):
        trace_plane_runs.pair(raw_of(lost))
    run = traced(monkeypatch, raw_of(lost))
    assert trace_plane_runs.read(run, "queue", "ar") is None
    assert trace_plane_runs.read(run, "share", "rc") is None
    assert "trace_plane_runs: no pairing" in capsys.readouterr().err


def test_a_run_with_no_launch_in_the_middle_gives_nothing():
    extra = sorted(RUNS + [(90, 100)])
    with pytest.raises(trace_plane_runs.Unpaired):
        trace_plane_runs.pair(raw_of(extra))


def test_a_call_that_completed_none_or_two_ticks_gives_nothing():
    """ar's call at 60 shows no completion before ar's next launch and the
    call at 110 two: a held tick, which the counters said there was none
    of.  The rule does not hold; no pairing is given."""
    runs = [(12, 20), (20, 35), (35, 55), (62, 100), (112, 125),
            (132, 150), (162, 175)]
    host = [e for e in LAUNCHES + TALLIES if e != tally("ar", 76, 85)]
    with pytest.raises(trace_plane_runs.Unpaired, match="completed 0 ticks"):
        trace_plane_runs.pair(raw_of(runs, host))
    host += [tally("ar", 113, 114)]
    with pytest.raises(trace_plane_runs.Unpaired, match="completed 0 ticks"):
        trace_plane_runs.pair(raw_of(runs, host))
    two = LAUNCHES + TALLIES + [tally("ar", 86, 90)]
    with pytest.raises(trace_plane_runs.Unpaired, match="completed 2 ticks"):
        trace_plane_runs.pair(raw_of(host=two))


def test_four_chips_with_two_programs_per_launch():
    """On a mesh a launch enqueues the sharded tick and the compaction
    behind it on every chip; only the tick is paired, per chip."""
    chips = [f"/device:TPU:{i}" for i in range(4)]
    runs = []
    for s, e in RUNS:
        runs += [(s, s + (e - s) / 2, "jit_mesh_paxos_tick(7)"),
                 (s + (e - s) / 2, e, "jit_mesh_compact_outbox(8)")]
    raw = raw_of(runs, chips=chips)
    pairs = trace_plane_runs.pair(raw)
    assert sorted(pairs) == chips
    for dev in chips:
        assert [(p, s / 1e6) for p, _, _, s, _, _ in pairs[dev]] == [
            ("ar", 20), ("rc", 35), ("ar", 62), ("ar", 112), ("rc", 132),
            ("ar", 162)]
    shares = trace_plane_runs.shares(raw, pairs)
    # busy from the first paired tick's start (20) to the last one's end
    # (168.5): 85.5 ms, of which rc's two ticks 19; the compaction behind a
    # tick is no tick's
    assert shares["rc"] == pytest.approx(100 * 19 / 85.5)


def test_a_trace_without_launch_spans_or_without_a_trace_gives_nothing(
        monkeypatch):
    recorded = rawtrace.RawTrace.from_json(DATA)   # a program of PR 26
    assert recorded.host and not trace_plane_runs.launches(recorded)
    run = traced(monkeypatch, recorded)
    for what in ("queue", "share"):
        assert trace_plane_runs.read(run, what, "ar") is None
    assert trace_idle_by_phase.read(run, "ar", "dispatch") is None
    off_chip = types.SimpleNamespace(trace=None)
    assert trace_plane_runs.read(off_chip, "queue", "ar") is None
    assert trace_idle_by_phase.read(off_chip, "ar", "dispatch") is None
    monkeypatch.setattr(rawtrace, "of_this_run", lambda: None)
    assert trace_plane_runs.read(run, "queue", "ar") is None
    assert trace_idle_by_phase.read(run, "ar", "dispatch") is None


# ------------------------------------------------------ idle by host span
def phase(plane, name, start, end):
    return (f"gptpu/modea/{plane}/{name}", ms(start), ms(end - start))


def test_the_idle_split_by_phase_adds_up_to_the_chips_idle_time(
        monkeypatch, capsys):
    """ar's phases cover the chip's span (0-200 ms) back to back, with the
    parts of dispatch inside it; rc's cover part of it.  The chip ran ops
    at 0-20, 50-60, 100-140 and 190-200: 120 ms idle, as trace_idle_pct
    counts it."""
    busy = [(0, 20), (50, 60), (100, 140), (190, 200)]
    ar = [("repair", 0, 10), ("intake", 10, 40), ("dispatch", 40, 70),
          ("wal_fsync", 70, 80), ("tally", 80, 150), ("execute", 150, 170),
          ("egress", 170, 190), ("sweep", 190, 200)]
    host = [phase("ar", *p) for p in ar]
    host += [phase("ar", "dispatch/launch", 40, 45),
             phase("ar", "dispatch/release", 45, 68)]
    host += [phase("rc", "dispatch", 20, 30), phase("rc", "tally", 30, 50)]
    mods, ops = runs_on(busy)
    raw = rawtrace.RawTrace({CHIP: ops}, {CHIP: mods},
                            sorted(host, key=lambda e: e[1]))
    inside, counts, idle, uncovered = trace_idle_by_phase.split(raw)
    trace = tracing.Trace({CHIP: {tracing.OPS: [(n, s, d)
                                                for n, _, s, d in ops]}})
    busy_s, window_s = tracing.busy_and_window_s(trace)
    assert idle == pytest.approx(1e9 * (window_s - busy_s))
    assert idle == pytest.approx(ms(120))
    assert sum(inside[f"ar/{p}"] for p, _, _ in ar) == pytest.approx(idle)
    assert uncovered == 0.0
    # dispatch (40-70) is idle but for 50-60; its launch part (40-45) wholly
    assert inside["ar/dispatch"] == pytest.approx(ms(20))
    assert inside["ar/dispatch/launch"] == pytest.approx(ms(5))
    assert inside["ar/dispatch/release"] == pytest.approx(ms(13))
    assert inside["rc/tally"] == pytest.approx(ms(20))
    run = traced(monkeypatch, raw)
    assert trace_idle_by_phase.read(run, "ar", "dispatch") == pytest.approx(
        20.0)
    m = spec.layer_metric("idle_in_dispatch_ms")
    assert spec.reader(m["reader"]).read(run, **m["args"]) == pytest.approx(
        20.0)
    err = capsys.readouterr().err
    assert "0.0% of it under no program span" in err
    assert "ar/dispatch/launch 5.000 ms over 1" in err


def test_idle_time_under_no_span_is_counted_apart():
    mods, ops = runs_on([(0, 10), (40, 50)])
    host = [phase("ar", "dispatch", 10, 20), launch("ar", 10, 12)]
    raw = rawtrace.RawTrace({CHIP: ops}, {CHIP: mods}, host)
    inside, counts, idle, uncovered = trace_idle_by_phase.split(raw)
    assert idle == pytest.approx(ms(30))
    assert inside["ar/dispatch"] == pytest.approx(ms(10))
    assert uncovered == pytest.approx(ms(20))
    assert counts == {"ar/dispatch": 1, "ar/dispatch/launch": 1}


def test_the_new_metric_files_name_their_readers_and_families():
    want = {
        "dispatch_launch_ms": ("histogram_mean", "program_span", "host loop"),
        "dispatch_release_ms": ("histogram_mean", "program_span",
                                "host loop"),
        "dispatch_blocked_ms": ("histogram_mean_diff", "program_span",
                                "host loop"),
        "exec_queue_ms": ("trace_plane_runs", "device_trace",
                          "tick programs"),
        "rc_busy_pct": ("trace_plane_runs", "device_trace", "control plane"),
        "idle_in_dispatch_ms": ("trace_idle_by_phase", "device_trace",
                                "host loop"),
    }
    for name, (reader, source, layer) in want.items():
        m = spec.layer_metric(name)
        assert (m["reader"], m["source"], m["layer"], m["moves"]) == (
            reader, source, layer, "commit_p50_ms")
    labels = {"driver": "modea", "plane": "ar", "phase": "dispatch"}
    for name, part in (("dispatch_launch_ms", "launch"),
                       ("dispatch_release_ms", "release")):
        assert spec.layer_metric(name)["args"] == {
            "family": "tick_part_seconds", "labels": dict(labels, part=part)}
    assert spec.layer_metric("dispatch_blocked_ms")["args"] == {
        "outer": {"family": "tick_phase_seconds", "labels": labels},
        "inner": {"family": "tick_phase_cpu_seconds", "labels": labels}}
