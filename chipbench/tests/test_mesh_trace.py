"""The readers of the four-chip cell's device metrics, on a recorded
four-chip trace and on made-up ones.

``data/tick-1m-mesh4.trace.json.gz`` is cut from PR 30's first traced run of
the four-chip 1M cell on a v5e-4 host (seed 3000003102): the ``XLA Modules``
and ``XLA Ops`` lines of ``/device:TPU:0`` to ``3`` over four ticks, two of
each plane; a tick is an execution of ``jit_mesh_paxos_tick`` and one of
``jit_mesh_compact_outbox`` (``Trace.cut(186e6, 420e6)`` + ``Trace.to_json``,
op names cut to 260 characters).
"""

import json
import os
import re
import types

import pytest

from chipbench import spec, tracing
from chipbench.readers import (kernel_roofline, trace_collective_ms,
                               trace_idle_pct, trace_module_mean)

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tick-1m-mesh4.trace.json.gz")
CHIPS = [f"/device:TPU:{i}" for i in range(4)]


@pytest.fixture(scope="module")
def recorded():
    return tracing.Trace.from_json(DATA)


@pytest.fixture(scope="module")
def run(recorded):
    return types.SimpleNamespace(trace=recorded,
                                 device={"kind": "TPU v5 lite"})


def args_of(metric: str) -> dict:
    return spec.layer_metric(metric)["args"]


def test_recorded_trace_is_four_chips_running_the_two_mesh_programs(recorded):
    assert sorted(recorded.devices) == CHIPS
    for lines in recorded.devices.values():
        names = [re.sub(r"\(.*", "", n) for n, _, _ in lines[tracing.MODULES]]
        assert names == ["jit_mesh_paxos_tick", "jit_mesh_paxos_tick",
                         "jit_mesh_compact_outbox", "jit_mesh_compact_outbox",
                         "jit_mesh_paxos_tick", "jit_mesh_compact_outbox",
                         "jit_mesh_paxos_tick", "jit_mesh_compact_outbox"]
        assert len(lines[tracing.OPS]) > 1000


def test_each_mesh_program_is_timed_under_its_own_name(run):
    tick = trace_module_mean.read(run, **args_of("mesh_tick_device_ms"))
    compact = trace_module_mean.read(run, **args_of("mesh_compact_device_ms"))
    # hand-checked on the recording: 3.6 ms of tick, 10.6 of compaction
    assert 3.5 < tick < 3.7 and 10.4 < compact < 10.7
    whole = tracing.whole_executions(run.trace, "^jit_mesh_")
    assert len(whole) == 4 * 6  # per chip the edges' two are left out
    assert sum(whole) / 4 / 3 / 1e6 == pytest.approx(tick + compact)
    # the one-chip programs' metrics read nothing here, and say nothing
    for metric in ("device_tick_ms", "compact_device_ms",
                   "protocol_device_ms"):
        module = args_of(metric)["module"]
        assert trace_module_mean.read(run, module=module) is None
        assert tracing.whole_executions(run.trace, module) == []


def test_collective_time_is_per_tick_and_per_chip(run):
    args = args_of("mesh_collective_ms")
    chips = trace_collective_ms.per_tick_ms(run.trace, **args)
    assert len(chips) == 4 and max(chips) - min(chips) < 0.01
    assert trace_collective_ms.read(run, **args) == pytest.approx(
        sum(chips) / 4)
    # by hand on chip 0: the all-gathers of the compaction and the one small
    # all-reduce of the tick (the exec-budget ranking's [W, R] block), inside
    # the three whole ticks
    lines = run.trace.devices[CHIPS[0]]
    whole = [(s, s + d) for _, s, d in lines[tracing.MODULES][1:-1]]
    by_kind = {"all-gather": 0.0, "all-reduce": 0.0}
    for name, s, d in lines[tracing.OPS]:
        kind = re.match(r"^%(all-gather|all-reduce)", name)
        if kind and any(a <= s < b for a, b in whole):
            by_kind[kind.group(1)] += d
    assert chips[0] == pytest.approx(sum(by_kind.values()) / 3 / 1e6)
    assert by_kind["all-gather"] > 100 * by_kind["all-reduce"] > 0
    assert 2.0 < chips[0] < 2.3
    # the full-width operands the partitioner gathers onto every chip
    assert any(re.match(r"^%all-gather\S* = s32\[3,4,1048576\]", n)
               for n, _, _ in lines[tracing.OPS])


def test_collective_reader_says_nothing_where_there_is_nothing_to_say(run):
    args = args_of("mesh_collective_ms")
    no_trace = types.SimpleNamespace(trace=None, device=run.device)
    assert trace_collective_ms.read(no_trace, **args) is None
    one_chip = tracing.Trace.from_json(os.path.join(
        os.path.dirname(DATA), "tick-1m.trace.json.gz"))
    assert trace_collective_ms.read(types.SimpleNamespace(
        trace=one_chip, device=run.device), **args) is None
    # a mesh program with no collective in it: none, not a zero
    assert trace_collective_ms.read(run, **dict(args, op="^%no-such-op")) is None


def test_collectives_on_a_made_up_trace():
    mods = [("jit_mesh_paxos_tick(1)", 0.0, 10.0),       # edge: left out
            ("jit_mesh_paxos_tick(1)", 100.0, 10.0),
            ("jit_mesh_compact_outbox(2)", 120.0, 30.0),
            ("jit_other(3)", 200.0, 50.0),
            ("jit_mesh_paxos_tick(1)", 300.0, 10.0),
            ("jit_mesh_compact_outbox(2)", 320.0, 30.0),
            ("jit_mesh_compact_outbox(2)", 400.0, 30.0)]  # edge: left out
    ops = [("%all-gather.1 = s32[8]{0} all-gather(s32[2]{0} %p)", 2.0, 5.0),
           ("%all-reduce.2 = s32[4]{0} all-reduce(s32[4]{0} %p)", 101.0, 1.0),
           ("%fusion.3 = s32[4]{0} fusion(s32[4]{0} %p)", 103.0, 4.0),
           ("%all-gather-start.4 = s32[8]{0} all-gather-start(s32[2]{0} %p)",
            121.0, 2.0),
           ("%all-gather-done.4 = s32[8]{0} all-gather-done(s32[8]{0} %q)",
            130.0, 3.0),
           ("%all-gather.9 = s32[8]{0} all-gather(s32[2]{0} %p)", 210.0, 40.0),
           ("%collective-permute.5 = s32[2]{0} collective-permute(s32[2]{0} "
            "%p)", 325.0, 6.0),
           ("%all-to-all.6 = s32[2]{0} all-to-all(s32[2]{0} %p)", 405.0, 9.0)]
    tr = tracing.Trace({"/device:TPU:0": {tracing.MODULES: mods,
                                          tracing.OPS: ops},
                        "/device:TPU:1": {tracing.MODULES: mods[:1],
                                          tracing.OPS: []}})
    args = {"module": "^jit_mesh_(paxos_tick|compact_outbox)",
            "per": "^jit_mesh_paxos_tick",
            "op": "^%(all-gather|all-reduce|collective-permute|all-to-all)"}
    # chip 0: two whole ticks hold 1 + 2 + 3 + 6 ns; the other program's
    # all-gather and the edges' are left out; chip 1 shows no whole tick
    assert trace_collective_ms.per_tick_ms(tr, **args) == [12.0 / 2 / 1e6]


def test_the_gather_kernels_share_is_per_chip_on_four_chips(run, recorded):
    """``gather_roofline`` sums bytes and seconds over the chips' calls, so
    four chips running a quarter of the width each read one chip's share:
    the whole trace's equals chip 0's alone, and stays under 100%."""
    args = args_of("gather_roofline")
    share = kernel_roofline.read(run, **args)
    alone = kernel_roofline.read(types.SimpleNamespace(
        trace=tracing.Trace({CHIPS[0]: recorded.devices[CHIPS[0]]}),
        device=run.device), **args)
    assert share == pytest.approx(alone, rel=0.01)
    assert 20 < share < 45
    # a call moves a quarter of the one-chip planes
    calls = [n for n, _, _ in recorded.devices[CHIPS[0]][tracing.OPS]
             if re.match(args["op"], n)]
    assert calls and all("262144]" in n and "1048576]" not in n for n in calls)
    idle = trace_idle_pct.read(run)
    assert 50 < idle < 100


def test_the_cell_reports_the_mesh_metrics_and_not_the_one_chip_programs():
    with open(spec.BENCHMARK) as f:
        bench = json.load(f)
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 1
    reported = {m["name"] for m in spec.load_cell(four[0]).per_layer}
    assert {"mesh_tick_device_ms", "mesh_compact_device_ms",
            "mesh_collective_ms", "mesh_dispatch_ms", "gather_roofline",
            "device_idle_pct", "tick_period_ms"} <= reported
    assert not {"device_tick_ms", "compact_device_ms",
                "protocol_device_ms"} & reported
    one = [w["name"] for w in bench["workloads"] if w["chips"] == 1]
    for cell in one:
        names = {m["name"] for m in spec.load_cell(cell).per_layer}
        assert not {n for n in names if n.startswith("mesh_")}
        assert {"device_tick_ms", "compact_device_ms",
                "protocol_device_ms"} <= names
