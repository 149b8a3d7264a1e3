"""``rawtrace`` and ``trace_scope_ms``: the wire reader on a hand-made XSpace
(checked against ``jax.profiler.ProfileData`` where that can see), and the
split of device time by scope on a recorded cut.

``data/tick-1m.rawtrace.json.gz`` is cut from PR 26's first traced run of
the 1M cell on a v5e chip (seed 3000000101): four back-to-back executions of
the tick program on ``/device:TPU:0``, two per plane, with every op's scope,
and the ``gptpu/...`` host annotations that lie inside them
(``RawTrace.cut(338889824.75, 1877900563.422)`` + ``to_json``, op names cut
to 100 characters).
"""

import os
import time
import types

import pytest

from chipbench import rawtrace, spec, tracing
from chipbench.readers import trace_scope_ms

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tick-1m.rawtrace.json.gz")
TICK = "^jit__?paxos_tick"


# ------------------------------------------------ a hand-made XSpace, encoded
def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, float):  # a fixed64 the reader has to step over
        import struct
        return varint(number << 3 | 1) + struct.pack("<d", value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def entry(key: int, message: bytes) -> bytes:
    return field(1, key) + field(2, message)


def stat_md(i: int, name: str) -> bytes:
    return field(5, entry(i, field(1, i) + field(2, name)))


def event_md(i: int, name: str, *stats: bytes) -> bytes:
    return field(4, entry(i, field(1, i) + field(2, name)
                          + b"".join(field(5, s) for s in stats)))


def event(md: int, offset_ps: int, dur_ps: int, *stats: bytes) -> bytes:
    return field(4, field(1, md) + field(2, offset_ps) + field(3, dur_ps)
                 + b"".join(field(4, s) for s in stats))


def line(name: str, t0_ns: int, *events: bytes) -> bytes:
    return field(3, field(1, 7) + field(2, name) + field(3, t0_ns)
                 + b"".join(events))


@pytest.fixture(scope="module")
def xspace(tmp_path_factory):
    """One device plane with two ops under scopes (one by ``str_value``, one
    by ``ref_value``), one op the compiler made (no ``tf_op``), a module
    event; one host plane with an annotation of the program and one of
    somebody else's."""
    TF_OP, OTHER, REF = 1, 2, 3
    device = (
        field(1, 1) + field(2, "/device:TPU:0")
        + stat_md(TF_OP, "tf_op") + stat_md(OTHER, "flops")
        + stat_md(REF, "jit(f)/accept/reduce_max:")
        + event_md(10, "%fusion.1 = s32[8] fusion(...)",
                   field(1, OTHER) + field(3, 99),
                   field(1, OTHER) + field(2, 2.5),
                   field(1, TF_OP) + field(5, "jit(f)/compact_outbox/scatter:"))
        + event_md(11, "%reduce.2 = s32[8] reduce(...)",
                   field(1, TF_OP) + field(7, REF))
        + event_md(12, "%copy.3 = s32[8] copy(...)")
        + event_md(20, "jit_f(123)")
        + line("XLA Modules", 1000, event(20, 0, 9_000_000))
        + line("XLA Ops", 1000,
               event(11, 5_000_000, 1_500_000),
               event(10, 0, 4_000_250, field(1, OTHER) + field(3, 5)),
               event(12, 7_000_000, 2_000_000))
        + line("Steps", 1000, event(20, 0, 1)))
    host = (
        field(1, 2) + field(2, "/host:CPU")
        + event_md(1, "gptpu/modea/ar/intake") + event_md(2, "PjitFunction(f)")
        + line("python3", 500, event(2, 0, 10_000), event(1, 2_000, 3_000_000)))
    other = field(1, 3) + field(2, "/host:metadata") + event_md(1, "x")
    path = tmp_path_factory.mktemp("xspace") / "hand.xplane.pb"
    path.write_bytes(field(1, device) + field(1, host) + field(1, other))
    return str(path)


def test_the_wire_reader_finds_scopes_in_the_metadatas_stats(xspace):
    raw = rawtrace.load(xspace)
    assert list(raw.ops) == list(raw.modules) == ["/device:TPU:0"]
    # in start order, nanoseconds: the line's timestamp plus the offset
    assert raw.ops["/device:TPU:0"] == [
        ("%fusion.1 = s32[8] fusion(...)", "jit(f)/compact_outbox/scatter:",
         1000.0, 4000.25),
        ("%reduce.2 = s32[8] reduce(...)", "jit(f)/accept/reduce_max:",
         6000.0, 1500.0),
        ("%copy.3 = s32[8] copy(...)", "", 8000.0, 2000.0)]
    assert raw.modules["/device:TPU:0"] == [("jit_f(123)", 1000.0, 9000.0)]
    assert raw.host == [("gptpu/modea/ar/intake", 502.0, 3000.0)]


def test_the_wire_reader_agrees_with_profile_data_where_that_can_see(xspace):
    """``ProfileData`` reads names and times (whole nanoseconds), not the
    metadata's stats: the reason the file is read off the wire at all."""
    reduced = tracing.load_xplane(xspace)
    raw = rawtrace.load(xspace)
    for plane, ops in raw.ops.items():
        theirs = reduced.devices[plane][tracing.OPS]
        assert [n for n, _, _, _ in ops] == [n for n, _, _ in theirs]
        for (_, _, s, d), (_, s2, d2) in zip(ops, theirs):
            assert abs(s - s2) < 1 and abs(d - d2) < 1
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xspace)
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops_line = next(ln for ln in dev.lines if ln.name == tracing.OPS)
    seen = {k for e in ops_line.events for k, _ in e.stats}
    assert "tf_op" not in seen


# ------------------------------------------------------------ the recorded cut
@pytest.fixture(scope="module")
def recorded():
    return rawtrace.RawTrace.from_json(DATA)


def test_recorded_cut_has_scopes_and_both_planes_host_phases(recorded):
    assert os.path.getsize(DATA) < 100_000
    mods = recorded.modules["/device:TPU:0"]
    assert len(mods) == 4
    assert all(n.startswith("jit__paxos_tick_compact_impl(") for n, _, _ in mods)
    ops = recorded.ops["/device:TPU:0"]
    assert len(ops) > 3000
    # the four s32[2G] fusions and the s32[1024] lag fusions that PR 24 read
    # off their shapes say themselves where they come from
    for shape in ("s32[2097152]", "s32[1024]"):
        fusions = [sc for n, sc, _, _ in ops
                   if n.startswith("%fusion") and f"= {shape}" in n]
        assert fusions and all("/compact_outbox/" in sc for sc in fusions)
    names = {n for n, _, _ in recorded.host}
    for plane in ("ar", "rc"):
        for phase in ("repair", "intake", "dispatch", "wal_fsync", "tally",
                      "execute", "egress", "sweep"):
            assert f"gptpu/modea/{plane}/{phase}" in names


def test_the_scopes_split_the_programs_time_and_add_up_to_it(recorded):
    compact = spec.layer_metric("compact_device_ms")["args"]
    protocol = spec.layer_metric("protocol_device_ms")["args"]
    assert compact["module"] == protocol["module"] == TICK
    n, c_ms, c_beside, c_none, bare, held = trace_scope_ms.split_ms(
        recorded, **compact)
    n2, p_ms, p_beside, p_none, _, _ = trace_scope_ms.split_ms(
        recorded, **protocol)
    assert n == n2 == 2                      # the edges are left out
    assert held == 0                         # PR 26's program had no conditional
    assert c_beside == p_ms and p_beside == c_ms and c_none == p_none
    whole = [d for _, _, d in recorded.modules["/device:TPU:0"][1:-1]]
    program_ms = sum(whole) / len(whole) / 1e6
    # ops run one after another: their times add up to the execution's
    assert c_ms + p_ms + c_none == pytest.approx(program_ms, rel=1e-3)
    # hand-checked on the cut: compaction is 84.6% of the program, the
    # protocol 13.2%, and what carries no scope 2.2% (the compiler's copies
    # and the cumulative sum it rewrote into reduce-windows)
    assert c_ms == pytest.approx(325.42, abs=0.01)
    assert p_ms == pytest.approx(50.70, abs=0.01)
    assert c_none == pytest.approx(8.62, abs=0.01)
    assert max(bare, key=bare.get).startswith("%reduce-window")
    assert trace_scope_ms.split_ms(recorded, "^jit_other", ["x"], []) is None


def test_an_op_that_holds_other_ops_is_counted_in_no_sum():
    """PR 27's tick program: the trace lists each ``conditional`` beside the
    ops of the branch it ran (ISSUE 29).  Only leaves are counted, so the
    three parts add up to the execution again and the scopes' own sums are
    what they were."""
    plane = "/device:TPU:0"
    mods = [("jit__paxos_tick(1)", t, 100.0) for t in (0.0, 100.0, 200.0, 300.0)]

    def ops(with_conditional: bool) -> list:
        out = []
        for t0 in (100.0, 200.0):        # the two whole executions
            out += [("%fusion.1", "jit(f)/tally/max:", t0, 30.0),
                    ("%copy.2", "", t0 + 30.0, 10.0)]
            if with_conditional:
                out.append(("%cond.8.clone", "", t0 + 40.0, 50.0))
            out += [("%fusion.3", "jit(f)/compact_outbox/scatter:", t0 + 41.0, 40.0),
                    ("%copy.4", "", t0 + 81.0, 8.0),
                    ("%fusion.5", "jit(f)/tally/sum:", t0 + 90.0, 10.0)]
        return out

    args = dict(module=TICK, scopes=["compact_outbox"], beside=["tally"])
    plain = trace_scope_ms.split_ms(
        rawtrace.RawTrace({plane: ops(False)}, {plane: mods}, []), **args)
    held = trace_scope_ms.split_ms(
        rawtrace.RawTrace({plane: ops(True)}, {plane: mods}, []), **args)
    n, inside, beside, none, bare, holders = held
    assert (n, holders) == (2, pytest.approx(50e-6)) and plain[5] == 0
    assert held[:5] == plain[:5]         # what the reader returns did not move
    assert inside == pytest.approx(40e-6) and beside == pytest.approx(40e-6)
    assert none == pytest.approx(18e-6) and set(bare) == {"%copy.2", "%copy.4"}
    assert inside + beside + none == pytest.approx(98e-6)   # of a 100 ns program


def test_the_two_metric_files_cover_the_programs_vocabulary():
    phase = pytest.importorskip("gigapaxos_tpu.obs.phase")
    vocabulary = getattr(phase, "TICK_SCOPES", None)
    if vocabulary is None:
        pytest.skip("a program from before TICK_SCOPES")
    compact = spec.layer_metric("compact_device_ms")["args"]
    protocol = spec.layer_metric("protocol_device_ms")["args"]
    assert compact["scopes"] == protocol["beside"] == ["compact_outbox"]
    assert compact["beside"] == protocol["scopes"]
    # the programs of their own (one jit each) are not inside the tick's
    own_program = {"sweep_frontier", "frontier_rows"}
    assert set(compact["scopes"] + protocol["scopes"]) == (
        set(vocabulary) - own_program)


def test_json_round_trip_and_cut(recorded, tmp_path):
    path = str(tmp_path / "again.json.gz")
    recorded.to_json(path)
    again = rawtrace.RawTrace.from_json(path)
    assert again == recorded
    mods = recorded.modules["/device:TPU:0"]
    inner = recorded.cut(mods[1][1], mods[2][1] + mods[2][2] + 1)
    assert len(inner.modules["/device:TPU:0"]) == 2
    assert 0 < len(inner.ops["/device:TPU:0"]) < len(recorded.ops["/device:TPU:0"])


# --------------------------------------------------- the reader's three Nones
def run_with(trace):
    return types.SimpleNamespace(trace=trace)


def test_the_reader_gives_nothing_rather_than_a_guess(recorded, monkeypatch):
    args = spec.layer_metric("compact_device_ms")["args"]
    traced = run_with(object())
    monkeypatch.setattr(rawtrace, "of_this_run", lambda: recorded)
    assert trace_scope_ms.read(traced, **args) == pytest.approx(325.42, abs=0.01)
    # the CPU rehearsal: the harness gave the run no trace
    assert trace_scope_ms.read(run_with(None), **args) is None
    # no raw trace of this process is found
    monkeypatch.setattr(rawtrace, "of_this_run", lambda: None)
    assert trace_scope_ms.read(traced, **args) is None
    # a program without scopes, or an executable with another commit's
    # metadata: no op carries the scope
    bare = rawtrace.RawTrace(
        {p: [(n, "", s, d) for n, _, s, d in ops]
         for p, ops in recorded.ops.items()}, recorded.modules, recorded.host)
    monkeypatch.setattr(rawtrace, "of_this_run", lambda: bare)
    assert trace_scope_ms.read(traced, **args) is None


def test_find_takes_the_newest_trace_this_process_could_have_written(
        tmp_path, monkeypatch, xspace):
    monkeypatch.setattr(rawtrace.tempfile, "gettempdir", lambda: str(tmp_path))
    assert rawtrace.find() is None and rawtrace.of_this_run() is None

    def trace_file(run: str, stamp: str):
        d = tmp_path / run / "trace" / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        p = d / "host.xplane.pb"
        with open(xspace, "rb") as f:
            p.write_bytes(f.read())
        return p

    stale = trace_file("chipbench_old", "2026_01_01")
    long_ago = time.time() - 7 * 86400  # from before this process
    os.utime(stale, (long_ago, long_ago))
    assert rawtrace.find() is None
    trace_file("elsewhere", "2026_09_30")   # not a run of the harness
    assert rawtrace.find() is None
    mine = trace_file("chipbench_abc", "2026_09_30")
    assert rawtrace.find() == str(mine)
    assert rawtrace.of_this_run().host == [("gptpu/modea/ar/intake", 502.0,
                                            3000.0)]


# ----------------------------------- the harness's stand-in on the new clock
def test_the_phase_recorder_still_works_wrapped_around_the_programs_clock():
    phase = pytest.importorskip("gigapaxos_tpu.obs.phase")
    from gigapaxos_tpu.obs.metrics import Registry

    runs = getattr(phase, "PHASE_RUNS", None)
    if runs is None:
        pytest.skip("a program from before the clock's annotations")
    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            pass

    reg = Registry()
    clock = phase.PhaseClock("modea", plane="ar", reg=reg,
                             annotation=Annotation)
    rec = tracing.PhaseRecorder(clock, "ar")
    first, second = runs["modea"]
    rec.begin()
    for p in first:
        rec.mark(p)
    rec.touch()
    for p in second:
        rec.mark(p)
    rec.end()
    # the recorder keeps its spans, the clock under it its histograms and
    # its annotations, under the recorder's own unchanged calls
    assert [p for p, _, _ in rec.spans] == list(first + second)
    assert opened == [f"gptpu/modea/ar/{p}" for p in first + second]
    assert all(h.count == 1 for h in reg.find("tick_phase_seconds"))
    label = tracing.phase_labeller([rec], 0, 0.0)
    a, b = rec.spans[1][1], rec.spans[1][2]
    assert label(a, b) == "host:ar.intake"
