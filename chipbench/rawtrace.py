"""What ``tracing.load_xplane`` throws away: each device op's scope, and the
host's annotations.

``tracing.Trace`` keeps ``(name, start, duration)`` of the ``XLA Modules``
and ``XLA Ops`` lines, which is enough to time a program or a kernel.  To
split a program's device time by the phases the program itself names
(``jax.named_scope`` in ``ops/tick.py``, vocabulary ``TICK_SCOPES`` in
``obs/phase.py``), and to see the program's own host spans
(``gptpu/<driver>/<plane>/<phase>`` annotations from ``obs/phase.py``) on
the device's clock, a reader needs the op events' scope and the
``/host:CPU`` plane.  ``load`` reads the raw ``.xplane.pb`` again and keeps,
as plain lists,

* per device op ``(name, scope, start_ns, dur_ns)``,
* per execution of a program ``(name, start_ns, dur_ns)``,
* per host annotation whose name starts with ``gptpu/``
  ``(name, start_ns, dur_ns)``,

and ``RawTrace.to_json`` / ``from_json`` store that form, so the readers are
tested on a recorded cut (``tests/data/``) without a chip.

Where the scope lives (looked at by hand on a v5e trace, jax 0.9.0, libtpu
0.0.34, PR 26): not in the event.  An ``XLA Ops`` event carries three stats
(``device_offset_ps``, ``device_duration_ps``, ``Time Scale Multiplier``),
which is all ``jax.profiler.ProfileData`` shows.  The event's *metadata*
(one per HLO instruction of a program, shared by its events) carries
``hlo_category``, ``program_id``, ``flops``, ``bytes_accessed``, ``source``,
``source_stack`` and ``tf_op``, and ``tf_op`` is the instruction's
``op_name``: ``jit(_paxos_tick_compact_impl)/compact_outbox/scatter:`` (a
trailing colon; instructions the compiler made itself, copies and slices,
have none, and a few it rewrote keep only the primitive,
``reduce_window_sum:``).  ``ProfileData`` does not reach metadata stats, so
the file is read off the wire below.  The host's annotations are plain
events of ``/host:CPU``, on the line of the thread that ticks (named after
the process, one line per plane's tick thread), on the same clock.

**Finding the file is a stop-gap** (README-tracing.md): ``harness.Run``
carries no path to the raw trace and the harness may not be edited in the PR
that adds this, so ``find`` looks where ``harness.run`` is known to put it,
``<tempfile.gettempdir()>/chipbench_*/trace``, and takes the newest
``.xplane.pb`` that is not older than this process.  A ``benchmark`` PR
should carry the path on ``Run`` and delete ``find``.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import gzip
import json
import os
import tempfile

from . import tracing

HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "gptpu/"
#: the stat of an ``XLA Ops`` event's metadata that holds the op's scope path
SCOPE_STAT = "tf_op"


@dataclasses.dataclass
class RawTrace:
    """``ops``: device plane -> [(name, scope, start_ns, dur_ns)];
    ``modules``: device plane -> [(name, start_ns, dur_ns)]; ``host``:
    [(name, start_ns, dur_ns)] of the program's annotations; all in start
    order, nanoseconds from the start of the trace."""

    ops: dict
    modules: dict
    host: list

    def to_json(self, path: str, name_chars: int | None = None) -> None:
        names: dict = {}

        def ix(s: str) -> int:
            return names.setdefault(s, len(names))

        out = {
            "ops": {p: [[ix(n[:name_chars]), ix(sc), s, d]
                        for n, sc, s, d in evs]
                    for p, evs in self.ops.items()},
            "modules": {p: [[ix(n), s, d] for n, s, d in evs]
                        for p, evs in self.modules.items()},
            "host": [[ix(n), s, d] for n, s, d in self.host],
        }
        out["names"] = list(names)
        with gzip.open(path, "wt") as f:
            json.dump(out, f, separators=(",", ":"))

    @classmethod
    def from_json(cls, path: str) -> "RawTrace":
        with gzip.open(path, "rt") as f:
            raw = json.load(f)
        names = raw["names"]
        return cls(
            {p: [(names[n], names[sc], s, d) for n, sc, s, d in evs]
             for p, evs in raw["ops"].items()},
            {p: [(names[n], s, d) for n, s, d in evs]
             for p, evs in raw["modules"].items()},
            [(names[n], s, d) for n, s, d in raw["host"]])

    def cut(self, start_ns: float, end_ns: float) -> "RawTrace":
        """The events that lie wholly inside [start_ns, end_ns)."""
        def inside(evs):
            return [e for e in evs if e[-2] >= start_ns
                    and e[-2] + e[-1] <= end_ns]
        return RawTrace({p: inside(e) for p, e in self.ops.items()},
                        {p: inside(e) for p, e in self.modules.items()},
                        inside(self.host))


def _process_started() -> float:
    """Unix time at which this process started (Linux ``/proc``); 0.0, which
    lets every file through, where that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # the command name may hold spaces: fields count from its ")"
            ticks = int(f.read().rpartition(")")[2].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(ln.split()[1]) for ln in f
                        if ln.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return 0.0


def find() -> str | None:
    """The raw trace this process wrote, or None (module docstring)."""
    pattern = os.path.join(tempfile.gettempdir(), "chipbench_*", "trace",
                           "plugins", "profile", "*", "*.xplane.pb")
    # a second of grace: a file's time and the process's come off two clocks
    started = _process_started() - 1.0
    paths = [p for p in glob.glob(pattern) if os.path.getmtime(p) >= started]
    return max(paths, key=os.path.getmtime) if paths else None


# ------------------------------------------------- the xplane, off the wire
# ``ProfileData`` shows an event's own stats only, and the scope is a stat of
# the event's *metadata*; nothing else that reads an XSpace is a dependency of
# this repository.  The format is protobuf and the few fields needed are
# stable (tsl/profiler/protobuf/xplane.proto), so they are read off the wire:
#   XSpace.planes=1
#   XPlane.name=2 .lines=3 .event_metadata=4 .stat_metadata=5  (maps: key=1, value=2)
#   XLine.name=2 .timestamp_ns=3 .events=4
#   XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3
#   XEventMetadata.id=1 .name=2 .stats=5
#   XStatMetadata.id=1 .name=2
#   XStat.metadata_id=1 .str_value=5 .ref_value=7
def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"xplane: wire type {kind} at byte {i}")
        yield tag >> 3, value


def _message(buf, wanted: dict) -> dict:
    """One message as {name: value}, ``wanted`` being {field number: name};
    a name ending in ``[]`` collects a repeated field into a list."""
    out: dict = {name[:-2]: [] for name in wanted.values()
                 if name.endswith("[]")}
    for number, value in _fields(buf):
        name = wanted.get(number)
        if name is None:
            continue
        if name.endswith("[]"):
            out[name[:-2]].append(value)
        else:
            out[name] = value
    return out


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map(entries: list, wanted: dict) -> dict:
    """A protobuf map<int64, message> as {key: _message(value)}."""
    out = {}
    for entry in entries:
        kv = _message(entry, {1: "key", 2: "value"})
        if "value" in kv:
            out[kv.get("key", 0)] = _message(kv["value"], wanted)
    return out


def _plane(buf) -> tuple:
    """(lines, scope of each event metadata id, name of each id) of one
    XPlane: lines as [(line name, [(metadata id, start_ns, dur_ns)])]."""
    plane = _message(buf, {3: "lines[]", 4: "event_md[]", 5: "stat_md[]"})
    stat_names = {k: _text(v.get("name", b"")) for k, v in _map(
        plane["stat_md"], {2: "name"}).items()}
    names, scopes = {}, {}
    for mid, md in _map(plane["event_md"], {2: "name", 5: "stats[]"}).items():
        names[mid] = _text(md.get("name", b""))
        for raw in md["stats"]:
            stat = _message(raw, {1: "id", 5: "str", 7: "ref"})
            if stat_names.get(stat.get("id")) != SCOPE_STAT:
                continue
            if "str" in stat:
                scopes[mid] = _text(stat["str"])
            elif "ref" in stat:
                scopes[mid] = stat_names.get(stat["ref"], "")
    lines = []
    for raw in plane["lines"]:
        line = _message(raw, {2: "name", 3: "t0", 4: "events[]"})
        t0 = line.get("t0", 0)
        events = []
        for ev in line["events"]:
            e = _message(ev, {1: "md", 2: "offset_ps", 3: "dur_ps"})
            events.append((e.get("md", 0), t0 + e.get("offset_ps", 0) / 1e3,
                           e.get("dur_ps", 0) / 1e3))
        lines.append((_text(line.get("name", b"")), events))
    return lines, scopes, names


@functools.lru_cache(maxsize=1)  # two metrics read one run's trace
def load(path: str) -> RawTrace:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    ops: dict = {}
    modules: dict = {}
    host: list = []
    for number, raw in _fields(space):
        if number != 1:
            continue
        plane_name = _text(_message(raw, {2: "name"}).get("name", b""))
        on_device = bool(tracing.DEVICE_PLANE.match(plane_name))
        if not on_device and plane_name != HOST_PLANE:
            continue
        lines, scopes, names = _plane(raw)
        for line_name, events in lines:
            if on_device and line_name == tracing.OPS:
                ops[plane_name] = sorted(
                    ((names.get(m, ""), scopes.get(m, ""), s, d)
                     for m, s, d in events), key=lambda e: e[2])
            elif on_device and line_name == tracing.MODULES:
                modules[plane_name] = sorted(
                    ((names.get(m, ""), s, d) for m, s, d in events),
                    key=lambda e: e[1])
            elif not on_device:
                host += [(names[m], s, d) for m, s, d in events
                         if names.get(m, "").startswith(ANNOTATION_PREFIX)]
    host.sort(key=lambda e: e[1])
    return RawTrace(ops, modules, host)


def of_this_run() -> RawTrace | None:
    """``load(find())``, or None where no trace is found."""
    path = find()
    return None if path is None else load(path)
