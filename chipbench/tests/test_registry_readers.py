"""The readers that take a window from two snapshots of the program's obs
registry, on made-up snapshots: a mean, the difference of two means, the
share of the window a family's seconds leave free, and the longest sample to
its bucket.  Each is also shown to give nothing, not a guess, where the
program has no such family (a parent commit from before the span existed).
"""

import types

import pytest

from chipbench import spec
from chipbench.readers import (histogram_mean, histogram_mean_diff,
                               histogram_sum_delta, histogram_window_max,
                               tick_period)


def hist(count, total, buckets=None):
    h = {"count": count, "sum": total, "p50": 0.0, "p90": 0.0, "p99": 0.0}
    if buckets is not None:
        h["buckets"] = {str(i): c for i, c in buckets.items()}
    return h


def run_of(snap0, snap1, window_s=20.0):
    return types.SimpleNamespace(snap0=snap0, snap1=snap1, window_s=window_s,
                                 trace=None)


STAGE = "request_stage_seconds{plane=ar,stage=%s}"


def test_histogram_mean_is_sum_delta_over_count_delta():
    run = run_of({STAGE % "queue": hist(100, 50.0),
                  STAGE % "commit": hist(100, 150.0),
                  "request_stage_seconds{plane=rc,stage=queue}": hist(9, 9.0)},
                 {STAGE % "queue": hist(20100, 50.0 + 20000 * 0.4),
                  STAGE % "commit": hist(20100, 150.0 + 20000 * 1.5),
                  "request_stage_seconds{plane=rc,stage=queue}": hist(9, 9.0)})
    args = {"family": "request_stage_seconds",
            "labels": {"plane": "ar", "stage": "queue"}}
    assert histogram_mean.read(run, **args) == pytest.approx(400.0)
    args["labels"]["stage"] = "commit"
    assert histogram_mean.read(run, **args) == pytest.approx(1500.0)
    # nothing observed in the window, or no such family: nothing, no guess
    assert histogram_mean.read(run, "request_stage_seconds",
                               {"plane": "rc"}) is None
    assert histogram_mean.read(run, "no_such_seconds") is None
    # a metric file's arguments go through as they stand
    for name in ("req_queue_ms", "req_commit_ms"):
        m = spec.layer_metric(name)
        assert spec.reader(m["reader"]).read(run, **m["args"]) > 0


def test_a_family_without_labels_is_keyed_by_its_bare_name_and_a_labelled_one_sums():
    snap0 = {"client_commit_latency_seconds": hist(10, 20.0),
             "commit_latency_seconds{node=AR0}": hist(4, 7.0),
             "commit_latency_seconds{node=AR1}": hist(3, 6.0),
             "commit_latency_seconds{node=AR2}": hist(3, 6.0),
             "commit_latency_seconds_total": 7}
    snap1 = {"client_commit_latency_seconds": hist(1010, 20.0 + 1000 * 2.0),
             "commit_latency_seconds{node=AR0}": hist(404, 7.0 + 400 * 1.98),
             "commit_latency_seconds{node=AR1}": hist(303, 6.0 + 300 * 1.98),
             "commit_latency_seconds{node=AR2}": hist(303, 6.0 + 300 * 1.98),
             "commit_latency_seconds_total": 9}
    run = run_of(snap0, snap1)
    assert histogram_mean.window(run, "commit_latency_seconds")[0] == 1000
    assert histogram_mean.read(
        run, "commit_latency_seconds") == pytest.approx(1980.0)
    assert histogram_mean.read(
        run, "commit_latency_seconds", {"node": "AR1"}) == pytest.approx(1980.0)
    m = spec.layer_metric("client_net_ms")
    assert m["reader"] == "histogram_mean_diff"
    assert histogram_mean_diff.read(run, **m["args"]) == pytest.approx(20.0)
    # a side that is missing gives nothing
    del snap1["client_commit_latency_seconds"]
    assert histogram_mean_diff.read(run_of(snap0, snap1), **m["args"]) is None


def test_a_series_born_inside_the_window_counts_from_zero():
    run = run_of({}, {"tick_seconds{driver=modea,plane=ar}": hist(26, 19.5)})
    assert histogram_mean.read(run, "tick_seconds") == pytest.approx(750.0)


def test_compile_free_share_reads_100_when_nothing_compiled_and_never_0_for_it():
    fam = "jit_compile_seconds{stage=%s}"
    before = {fam % s: hist(90, 80.0) for s in ("trace", "lower", "backend")}
    m = spec.layer_metric("compile_free_pct")
    assert m["unit"] == "%" and m["better"] == "higher"
    quiet = run_of(before, dict(before))
    assert histogram_sum_delta.read(quiet, **m["args"]) == 100.0
    after = dict(before)
    after[fam % "trace"] = hist(92, 80.2)
    after[fam % "lower"] = hist(92, 80.1)
    after[fam % "backend"] = hist(92, 82.1)   # 2.4 s of a 20 s window
    assert histogram_sum_delta.read(
        run_of(before, after), **m["args"]) == pytest.approx(88.0)
    # floored: a compile longer than the window is 0, not negative
    after[fam % "backend"] = hist(92, 180.0)
    assert histogram_sum_delta.read(run_of(before, after), **m["args"]) == 0.0
    # the parent has no such counter: the metric is left out
    assert histogram_sum_delta.read(run_of({}, {}), **m["args"]) is None


def test_window_max_is_the_highest_bucket_that_rose():
    key = "tick_seconds{driver=modea,plane=ar}"
    m = spec.layer_metric("tick_longest_ms")
    # start-up left a 2.9 s first tick in bucket 22; the window's ticks of
    # 769 ms fall in bucket 20, whose upper bound is 1,048.575 ms
    snap0 = {key: hist(70, 60.0, {20: 69, 22: 1})}
    snap1 = {key: hist(96, 80.0, {20: 95, 22: 1})}
    assert histogram_window_max.read(
        run_of(snap0, snap1), **m["args"]) == pytest.approx(1048.575)
    # one stalled tick of 2.6 s in the window shows, whatever the mean says
    snap1 = {key: hist(96, 82.0, {20: 94, 22: 2})}
    assert histogram_window_max.read(
        run_of(snap0, snap1), **m["args"]) == pytest.approx(4194.303)
    # a program whose snapshots carry no buckets (the parent's): nothing
    assert histogram_window_max.read(
        run_of({key: hist(70, 60.0)}, {key: hist(96, 80.0)}),
        **m["args"]) is None
    assert histogram_window_max.read(
        run_of(snap0, dict(snap0)), **m["args"]) is None


def test_the_control_planes_tick_period_is_the_old_reader_on_plane_rc():
    m = spec.layer_metric("rc_tick_period_ms")
    assert m["reader"] == "tick_period" and m["args"]["plane"] == "rc"
    run = run_of({"tick_seconds{driver=modea,plane=rc}": hist(100, 70.0)},
                 {"tick_seconds{driver=modea,plane=rc}": hist(126, 90.0)})
    assert tick_period.read(run, **m["args"]) == pytest.approx(20000 / 26)
