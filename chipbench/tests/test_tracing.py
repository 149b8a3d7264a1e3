"""The trace -> metrics reduction, on a recorded trace and on made-up ones.

``data/tick-1m.trace.json.gz`` is cut from PR 24's first traced run of the 1M
cell on a v5e chip (seed 3000000014): the ``XLA Modules`` and ``XLA Ops``
lines of ``/device:TPU:0`` over four back-to-back executions of the tick
program, two per plane (``Trace.cut(56.4e6, 1.5956e9)`` + ``Trace.to_json``,
op names cut to 260 characters).
"""

import os
import types

import pytest

from chipbench import spec, tracing
from chipbench.readers import kernel_roofline

DATA = os.path.join(os.path.dirname(__file__), "data", "tick-1m.trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    return tracing.Trace.from_json(DATA)


def test_recorded_trace_has_the_lines_the_metrics_read(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    mods = recorded.line(tracing.MODULES)
    assert len(mods) >= 3
    assert all(n.startswith("jit__paxos_tick_compact_impl(") for n, _, _ in mods)
    assert len(recorded.line(tracing.OPS)) > 1000


def test_busy_window_and_idle_share(recorded):
    busy_s, window_s = tracing.busy_and_window_s(recorded)
    assert 0 < busy_s <= window_s
    # hand-checked: the recorded slice is back-to-back executions
    assert busy_s / window_s > 0.99
    gaps = tracing.idle_gaps(recorded, 10)
    assert len(gaps) == 10 and all(g[0] == tracing.UNATTRIBUTED for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert sum(g[1] for g in gaps) <= window_s - busy_s + 1e-9


def test_whole_executions_leave_out_the_edges(recorded):
    mods = recorded.line(tracing.MODULES)
    whole = tracing.whole_executions(recorded, "^jit__?paxos_tick")
    assert len(whole) == len(mods) - 2
    assert whole == [d for _, _, d in mods[1:-1]]
    assert tracing.whole_executions(recorded, "^jit_other") == []
    # one execution of the 1M tick program is some hundreds of ms
    assert 200e6 < sum(whole) / len(whole) < 600e6


def test_kernel_bytes_come_from_each_calls_shapes(recorded):
    n, moved, secs = tracing.op_bytes_and_seconds(
        recorded, "^%gather_planes_pallas")
    assert n > 0 and secs > 0
    # calls differ in shape ([3,4,G] and [1,4,G] planes, shared and per-lead
    # index planes): the mean is not a whole [3,4,G] pair
    assert moved / n < 2 * 4 * 3 * 4 * (1 << 20) * 1.6
    # buffers the compiler placed on chip (S(1)) do not cross HBM
    _, hbm, _ = tracing.op_bytes_and_seconds(
        recorded, "^%gather_planes_pallas", hbm_only=True)
    assert 0 < hbm < moved
    run = types.SimpleNamespace(trace=recorded, device={"kind": "TPU v5 lite"})
    share = kernel_roofline.read(run, op="^%gather_planes_pallas")
    assert share == pytest.approx(100 * hbm / secs / 819e9)
    assert 0 < share < 100
    with pytest.raises(KeyError):
        kernel_roofline.read(types.SimpleNamespace(
            trace=recorded, device={"kind": "TPU v9"}),
            op="^%gather_planes_pallas")


def test_top_ops_are_sorted_and_named_as_the_trace_prints(recorded):
    top = tracing.top_ops(recorded, 10)
    assert len(top) == 10
    assert [t[1] for t in top] == sorted((t[1] for t in top), reverse=True)
    assert all(t[0].startswith("%") for t in top)


def test_hlo_io_bytes():
    gather = ("%gather_planes_pallas.44 = s32[3,4,1048576]{2,1,0:T(4,128)} "
              "custom-call(s32[3,4,1048576]{2,1,0:T(4,128)} %a, "
              "s32[4,1048576]{1,0:T(4,128)S(1)} %b), custom_call_target=\"x\"")
    assert tracing.hlo_io_bytes(gather) == 4 * (1 << 20) * (12 + 12 + 4)
    assert tracing.hlo_io_bytes(gather, hbm_only=True) == 4 * (1 << 20) * 24
    on_chip = gather.replace("T(4,128)}", "T(4,128)S(1)}")
    assert tracing.hlo_io_bytes(on_chip, hbm_only=True) == 0
    assert tracing.hlo_io_bytes(
        "%f = pred[4,8]{1,0} fusion(bf16[2,8]{1,0} %a, s32[]{:T(128)} %c), "
        "kind=kLoop") == 32 + 32 + 4
    tup = ("%s = ((s32[8]{0}), s32[2]{0:T(4)S(1)}, s32[]{:S(2)}) "
           "async-start(s32[8]{0} %x), calls=%c")
    assert tracing.hlo_io_bytes(tup) == 32 + 8 + 4 + 32
    assert tracing.hlo_io_bytes("not an instruction") is None
    assert tracing.hlo_io_bytes(gather[:90]) is None  # cut short


def test_union_and_gaps_on_a_made_up_trace():
    ops = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0),
           ("d", 31.0, 1.0), ("e", 50.0, 10.0)]
    assert tracing.union_ns(ops) == 15 + 5 + 10
    tr = tracing.Trace({"/device:TPU:0": {tracing.OPS: ops,
                                          tracing.MODULES: []}})
    busy_s, window_s = tracing.busy_and_window_s(tr)
    assert (busy_s, window_s) == (30e-9, 60e-9)
    assert tracing.idle_gaps(tr, 10) == [[tracing.UNATTRIBUTED, 15e-9],
                                         [tracing.UNATTRIBUTED, 15e-9]]
    # no device op at all: nothing to report, not a zero
    assert tracing.busy_and_window_s(tracing.Trace({})) == (0.0, 0.0)


def test_gaps_are_named_by_the_host_phase_open_in_them():
    ops = [("a", 1000.0, 100.0), ("b", 2000.0, 100.0), ("c", 2500.0, 10.0)]
    tr = tracing.Trace({"/device:TPU:0": {tracing.OPS: ops}}, sync_ns=500.0)
    ar = types.SimpleNamespace(plane="ar", spans=[
        ("intake", 10_600, 11_700), ("tally", 11_700, 12_600)])
    rc = types.SimpleNamespace(plane="rc", spans=[("tally", 10_000, 13_000)])
    # perf_counter 10_000 is trace time 500
    label = tracing.phase_labeller([ar, rc], 10_000, tr.sync_ns)
    gaps = tracing.idle_gaps(tr, 10, label)
    assert gaps == [["host:ar.intake+rc.tally", 900e-9],
                    ["host:ar.tally+rc.tally", 400e-9]]


def test_json_round_trip(tmp_path, recorded):
    path = str(tmp_path / "t.json.gz")
    small = recorded.cut(*[f(e[1] for e in recorded.line(tracing.MODULES))
                           for f in (min, max)])
    small.to_json(path)
    back = tracing.Trace.from_json(path)
    assert back.devices == small.devices and back.sync_ns == small.sync_ns


def test_phase_recorder_passes_through_and_keeps_spans():
    calls = []
    inner = types.SimpleNamespace(
        begin=lambda: calls.append("begin"), touch=lambda: calls.append("touch"),
        mark=lambda p: calls.append(p), end=lambda: calls.append("end"),
        driver="modea")
    rec = tracing.PhaseRecorder(inner, "ar")
    rec.begin(); rec.mark("intake"); rec.touch(); rec.mark("tally"); rec.end()
    assert calls == ["begin", "intake", "touch", "tally", "end"]
    assert [s[0] for s in rec.spans] == ["intake", "tally"]
    assert all(b >= a for _, a, b in rec.spans)
    assert rec.driver == "modea"


def test_trace_readers_return_nothing_without_a_trace():
    run = types.SimpleNamespace(trace=None, device={"kind": "TPU v5 lite"})
    for name, args in (("trace_module_mean", {"module": "x"}),
                       ("trace_idle_pct", {}),
                       ("kernel_roofline", {"op": "x"})):
        assert spec.reader(name).read(run, **args) is None
