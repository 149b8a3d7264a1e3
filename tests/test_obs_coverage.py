"""Tier-1 observability coverage gate (ISSUE 9 satellite 5).

Static source checks that keep the flight-deck honest as the code grows:
every phase a driver DECLARES (obs/phase.py DRIVER_PHASES — the contract
dashboards are built against) is actually marked in that driver's tick
path; every WAL durability point goes through the instrumented ``_sync``
(a bare ``journal.sync()`` would be an unmetered fsync); and the metric
families the README documents exist at their declared wiring sites.

Greps over source, not runtime: a forgotten ``pc.mark`` or a new direct
fsync fails here in milliseconds instead of silently holing a dashboard.
"""

import os
import re

from gigapaxos_tpu.obs.phase import (DRIVER_PARTS, DRIVER_PHASES, PHASE_RUNS,
                                     TICK_SCOPES)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DRIVER_FILES = {
    "modea": "gigapaxos_tpu/paxos/manager.py",
    "modeb": "gigapaxos_tpu/modeb/manager.py",
    "chain": "gigapaxos_tpu/chain/manager.py",
    "chain_modeb": "gigapaxos_tpu/chain/modeb.py",
}


def _src(rel: str) -> str:
    with open(os.path.join(ROOT, rel)) as f:
        return f.read()


def test_driver_phases_contract_is_sane():
    assert set(DRIVER_PHASES) == set(DRIVER_FILES)
    for driver, phases in DRIVER_PHASES.items():
        assert phases, driver
        assert len(phases) == len(set(phases)), f"{driver}: duplicate phase"
    # every driver journals and executes — the two phases any SLO story
    # starts from
    for driver, phases in DRIVER_PHASES.items():
        assert "wal_fsync" in phases, driver
        assert "execute" in phases, driver


def test_every_declared_phase_is_marked_in_its_driver():
    for driver, rel in DRIVER_FILES.items():
        src = _src(rel)
        assert re.search(r"phase_clock\(", src), f"{rel}: no phase clock"
        marked = set(re.findall(r'\.mark\(\s*["\']([a-z_]+)["\']', src))
        missing = set(DRIVER_PHASES[driver]) - marked
        assert not missing, f"{rel}: declared but never marked: {missing}"
        undeclared = marked - set(DRIVER_PHASES[driver])
        assert not undeclared, (
            f"{rel}: marks {undeclared} not in DRIVER_PHASES[{driver!r}] — "
            f"add them to obs/phase.py so dashboards see the contract")
        # begin/end bracket the marks
        assert ".begin()" in src and ".end()" in src, rel


def test_phase_runs_are_the_order_the_driver_marks_in():
    """A trace annotation is named when it opens, so the clock opens the
    successor of the phase just marked (obs/phase.py PHASE_RUNS): the runs
    must be the declared phases, in the order the driver's source marks
    them, or the trace would show a phase under its neighbour's name."""
    for driver, runs in PHASE_RUNS.items():
        flat = [p for run in runs for p in run]
        assert flat == list(DRIVER_PHASES[driver]), driver
        marks = re.findall(r'\bpc\.mark\(\s*["\']([a-z_]+)["\']',
                           _src(DRIVER_FILES[driver]))
        # a phase marked in both arms of a branch shows twice in a row
        in_source = [p for i, p in enumerate(marks)
                     if i == 0 or marks[i - 1] != p]
        assert in_source == flat, (driver, in_source)


def test_every_declared_part_is_timed_in_its_phase_and_none_other():
    """DRIVER_PARTS (obs/phase.py) against the drivers, both ways: every
    part a driver declares is timed with ``pc.part`` in its tick, none it
    times is undeclared, and each sits between the mark that opens its
    phase and the mark that closes it (the clock names a part after the
    phase that is open, known only for a phase of ``PHASE_RUNS``)."""
    for driver, rel in DRIVER_FILES.items():
        src = _src(rel)
        used = re.findall(r'\bpc\.part\(\s*["\']([a-z_]+)["\']', src)
        phases = DRIVER_PARTS.get(driver, {})
        declared = [p for parts in phases.values() for p in parts]
        assert len(declared) == len(set(declared)), driver
        assert sorted(used) == sorted(declared), (rel, used, declared)
        runs = [list(run) for run in PHASE_RUNS.get(driver, ())]
        for phase_name, parts in phases.items():
            run = next(r for r in runs if phase_name in r)
            i = run.index(phase_name)
            assert i > 0, "a part of the first phase of a run"
            opened = src.index(f'pc.mark("{run[i - 1]}")')
            closed = src.index(f'pc.mark("{phase_name}")', opened)
            for part in parts:
                at = src.index(f'pc.part("{part}")')
                assert opened < at < closed, (driver, phase_name, part)


def test_tick_scopes_are_the_scopes_of_the_tick_programs():
    """TICK_SCOPES (obs/phase.py) against ops/tick.py, both ways: a scope a
    trace reader is promised must exist, and a scope the source opens must
    be in the vocabulary a reader splits device time by."""
    src = _src("gigapaxos_tpu/ops/tick.py")
    opened = re.findall(r'^\s*(?:scope|@_scoped)\(\s*"([a-z_]+)"\)', src,
                        re.M)
    assert len(opened) == len(set(opened)), "a scope is opened twice"
    assert set(opened) == set(TICK_SCOPES), (
        set(opened) ^ set(TICK_SCOPES))
    assert len(TICK_SCOPES) == len(set(TICK_SCOPES))
    # the phases keep the names and the order of the source's own banners
    banners = re.findall(r"# -+ (?:phase \w+: )?([a-z][a-z +/()\-]*?) -+\n"
                         r'\s*scope\("([a-z_]+)"\)', src)
    assert [s for _, s in banners] == list(TICK_SCOPES[:len(banners)])
    assert len(banners) == 9, banners


def test_the_mesh_programs_run_the_scoped_code_under_their_own_names():
    """parallel/shard_tick.py, statically: a sharded plane's tick is the
    scoped tick body inside the ``shard_map`` and the scoped compaction as
    the second program, so every op of both carries a TICK_SCOPES name; it
    opens no scope of its own (a reader would not know it); the jitted
    functions carry the names the trace readers match
    (``jit_mesh_paxos_tick``, ``jit_mesh_compact_outbox``), which the
    one-device programs' pattern ``^jit__?paxos_tick`` must not; and the
    manager counts one dispatch per program it enqueues, under the
    vocabulary the module declares."""
    src = _src("gigapaxos_tpu/parallel/shard_tick.py")
    assert "tk.paxos_tick_impl(" in src and "tk._compact_outbox_impl(" in src
    assert "named_scope(" not in src and "_scoped(" not in src
    tick = _src("gigapaxos_tpu/ops/tick.py")
    assert re.search(r'@_scoped\("compact_outbox"\)\ndef _compact_outbox_impl',
                     tick)
    named = re.findall(r"^\s*def (mesh_[a-z_]+)\(", src, re.M)
    assert named == ["mesh_paxos_tick", "mesh_compact_outbox",
                     "mesh_demand_fold"]
    for fn in named:
        assert re.search(rf"(jax\.jit|jax\.shard_map)\(\s*{fn}\b", src), fn
        assert not re.match(r"^jit__?paxos_tick", f"jit_{fn}")
    from gigapaxos_tpu.parallel.shard_tick import MESH_PROGRAMS

    assert MESH_PROGRAMS == ("tick", "compact", "fold")
    counted = re.findall(r'_mesh_dispatch_c\["([a-z]+)"\]\.inc\(\)',
                         _src(DRIVER_FILES["modea"]))
    assert set(counted) == set(MESH_PROGRAMS)
    # one mesh branch: the tick always, the compaction where there is one
    # (the full-outbox mesh dispatches the tick alone), the fold with demand
    assert sorted(counted) == ["compact", "fold", "tick"]


def test_tick_completion_families_carry_their_labels():
    """``tick_completions_total{plane,mode=same_call|held}`` rises once per
    dispatched tick with the side it took, whether ``pipeline_ticks`` is on
    or off (off: every tick is ``same_call``);
    ``tick_device_wait_seconds{plane}`` is observed once per completion,
    inside the ``tally`` phase and before the pull: the benchmark's
    ``device_wait_ms`` reads its mean.  What follows it in ``tally``, the
    pull and the unpack, is ``tick_outbox_pull_seconds{plane}``
    (``outbox_pull_ms``), and ``outbox_pulls_total{plane,pull=head|full}``
    rises once per completed tick and compacted plane buffer with the
    buffer that was pulled (ISSUE 34): the head first, the flat buffer
    only when the head refuses itself."""
    import json

    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.models.replicable import KVApp
    from gigapaxos_tpu.obs.metrics import registry
    from gigapaxos_tpu.paxos.manager import PaxosManager

    src = _src(DRIVER_FILES["modea"])
    assert re.search(r'_completions_c\["held" if hold else "same_call"\]'
                     r'\.inc\(\)', src)
    body = src[src.index("def _complete_tick"):src.index(
        "def _pull_compact")]
    wait = body.index("self._device_wait_h.observe(")
    assert body.index("jax.block_until_ready(packed)") < wait
    pulled = [body.index("np.asarray(packed"),
              body.index("self._pull_compact(pack")]
    assert wait < min(pulled) and max(pulled) < body.index(
        "self._outbox_pull_h.observe(") < body.index('pc.mark("tally")')
    # both arms of the completion close the span before they mark "tally"
    assert body.count("self._outbox_pull_h.observe(") == body.count(
        'pc.mark("tally")') == 2
    pull = src[src.index("def _pull_compact"):src.index(
        "def _count_compact")]
    assert pull.index("np.asarray(pack.head)") < pull.index(
        "if co is None:") < pull.index("np.asarray(pack.flat)")

    plane = "t_completion_labels"
    cfg = GigapaxosTpuConfig()
    cfg.paxos.compact_outbox = True
    m = PaxosManager(cfg, 3, [KVApp() for _ in range(3)], spill_ns=plane)
    m.create_paxos_instance("svc", [0, 1, 2])
    m.run_ticks(3)
    snap = registry().snapshot()
    assert snap[f"tick_completions_total{{mode=same_call,plane={plane}}}"] == 3
    assert snap[f"tick_completions_total{{mode=held,plane={plane}}}"] == 0
    assert snap[f"tick_device_wait_seconds{{plane={plane}}}"]["count"] == 3
    assert snap[f"tick_outbox_pull_seconds{{plane={plane}}}"]["count"] == 3
    assert snap[f"outbox_pulls_total{{plane={plane},pull=head}}"] == 3
    assert snap[f"outbox_pulls_total{{plane={plane},pull=full}}"] == 0
    for name, family in (("device_wait_ms", "tick_device_wait_seconds"),
                         ("outbox_pull_ms", "tick_outbox_pull_seconds")):
        with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                               name + ".json")) as f:
            metric = json.load(f)
        assert metric["reader"] == "histogram_mean"
        assert metric["args"] == {"family": family,
                                  "labels": {"plane": "ar"}}


def test_body_and_backlog_histograms_carry_the_plane_and_have_readers():
    """``inbox_deferred_requests{plane}`` once a tick (0 where nothing was
    left behind), ``wal_append_bytes{plane}`` once a journaled tick,
    ``app_reply_bytes{plane}`` once a released scalar request; the
    benchmark's three metrics of ISSUE 36 name them, data plane ``ar``."""
    import json

    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.models.replicable import KVApp
    from gigapaxos_tpu.obs.metrics import registry
    from gigapaxos_tpu.paxos.manager import PaxosManager

    plane = "t_body_labels"
    cfg = GigapaxosTpuConfig()
    cfg.paxos.compact_outbox = True
    m = PaxosManager(cfg, 3, [KVApp() for _ in range(3)], spill_ns=plane)
    m.create_paxos_instance("svc", [0, 1, 2])
    got = []
    m.propose("svc", b"PUT k " + b"v" * 50, lambda rid, resp: got.append(resp))
    m.run_ticks(2)
    burst = 3 * m.P   # one name, one entry: P a tick, the others left behind
    for i in range(burst):
        m.propose("svc", b"GET k", lambda rid, resp: got.append(resp), entry=0)
    m.run_ticks(6)
    m.drain_pipeline()
    assert got == [b"OK"] + [b"v" * 50] * burst
    snap = registry().snapshot()
    deferred = snap[f"inbox_deferred_requests{{plane={plane}}}"]
    assert deferred["count"] == m.tick_num == 8
    # 12 queued: the builds left 8, then 4 behind; the other six none
    assert deferred["sum"] == 2 * m.P + m.P and deferred["buckets"]["0"] == 6
    replies = snap[f"app_reply_bytes{{plane={plane}}}"]
    assert (replies["count"], replies["sum"]) == (1 + burst, 2 + 50 * burst)
    assert snap[f"wal_append_bytes{{plane={plane}}}"]["count"] == 0  # no WAL
    for name, reader, family in (
            ("inbox_clear_pct", "histogram_zero_share",
             "inbox_deferred_requests"),
            ("wal_bytes_per_tick", "histogram_mean_value",
             "wal_append_bytes"),
            ("reply_bytes_mean", "histogram_mean_value", "app_reply_bytes")):
        with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                               name + ".json")) as f:
            metric = json.load(f)
        assert metric["reader"] == reader
        assert metric["args"] == {"family": family,
                                  "labels": {"plane": "ar"}}


def test_inbox_path_families_carry_their_labels_and_have_a_reader():
    """``inbox_builds_total{plane,path=short|dense}`` rises once per tick
    with how its inbox reached the device, and ``inbox_upload_bytes{plane}``
    is observed once per tick with the bytes of the host arrays
    ``_build_inbox`` handed to the dispatch (ISSUE 37): the dense pair, a
    list of ``_SHORT_INBOX`` placements, or nothing where the resident
    all-zero inbox went out again.  The benchmark's ``inbox_bytes_per_tick``
    reads the histogram's mean on the data plane."""
    import json

    import numpy as np

    from gigapaxos_tpu.config import GigapaxosTpuConfig
    from gigapaxos_tpu.models.replicable import KVApp
    from gigapaxos_tpu.obs.metrics import registry
    from gigapaxos_tpu.paxos import manager as manager_mod
    from gigapaxos_tpu.paxos.manager import PaxosManager

    src = _src(DRIVER_FILES["modea"])
    build = src[src.index("def _build_inbox"):src.index("def _place_bulk")]
    # each way out of the build counts itself once and observes its bytes
    assert build.count('self._inbox_builds_c["dense"].inc()') == 1
    assert build.count('self._inbox_builds_c["short"].inc()') == 1
    assert build.count("self._inbox_bytes_h.observe(") == 3

    plane = "t_inbox_labels"
    cfg = GigapaxosTpuConfig()
    cfg.paxos.compact_outbox = True
    m = PaxosManager(cfg, 3, [KVApp() for _ in range(3)], spill_ns=plane)
    m.create_paxos_instance("svc", [0, 1, 2])
    m.run_ticks(2)  # the first build is dense; the second placed nothing
    m.propose("svc", b"PUT k v")
    m.run_ticks(1)
    m.propose_bulk(np.array([m.rows.row("svc")]), b"PUT k w")
    m.run_ticks(2)
    snap = registry().snapshot()
    assert snap[f"inbox_builds_total{{path=short,plane={plane}}}"] == 3
    assert snap[f"inbox_builds_total{{path=dense,plane={plane}}}"] == 2
    up = snap[f"inbox_upload_bytes{{plane={plane}}}"]
    dense = 5 * 3 * m.P * m.G_total  # int32 req and bool stop
    # the first empty list made the resident inbox; the second handed it out
    assert up["count"] == m.tick_num == 5 and up["buckets"]["0"] == 1
    assert up["sum"] == 2 * dense + 2 * 5 * 4 * manager_mod._SHORT_INBOX
    with open(os.path.join(ROOT, "chipbench", "layer_metrics",
                           "inbox_bytes_per_tick.json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "histogram_mean_value"
    assert metric["args"] == {"family": "inbox_upload_bytes",
                              "labels": {"plane": "ar"}}
    assert (metric["unit"], metric["better"], metric["layer"],
            metric["moves"]) == ("B", "lower", "host loop", "commit_p50_ms")


def test_wal_fsync_goes_through_instrumented_sync_only():
    """Every durability point must flow through ``_sync`` (timed +
    stall-counted); a bare ``journal.sync()`` anywhere else is an
    unmetered fsync."""
    for rel in ("gigapaxos_tpu/wal/logger.py",
                "gigapaxos_tpu/modeb/logger.py"):
        src = _src(rel)
        bare = len(re.findall(r"\.journal\.sync\(\)", src))
        defs = len(re.findall(r"def _sync\(", src))
        # modeb's logger may inherit _sync; either way the only permitted
        # journal.sync() calls are the bodies of _sync definitions
        assert bare == defs, (
            f"{rel}: {bare} journal.sync() calls vs {defs} _sync defs — "
            f"route new durability points through self._sync()")
    # across the rest of the tree nobody reaches around the logger
    for base, _dirs, files in os.walk(os.path.join(ROOT, "gigapaxos_tpu")):
        for fn in files:
            rel = os.path.relpath(os.path.join(base, fn), ROOT)
            if not fn.endswith(".py") or rel in (
                    "gigapaxos_tpu/wal/logger.py",
                    "gigapaxos_tpu/modeb/logger.py"):
                continue
            assert ".journal.sync()" not in _src(rel), (
                f"{rel}: direct journal.sync() bypasses wal_fsync_seconds")


WIRING = {
    # metric family -> file that must create it
    "tick_phase_seconds": "gigapaxos_tpu/obs/phase.py",
    "tick_seconds": "gigapaxos_tpu/obs/phase.py",
    # the thread's CPU time per phase, and the parts of a phase
    "tick_phase_cpu_seconds": "gigapaxos_tpu/obs/phase.py",
    "tick_part_seconds": "gigapaxos_tpu/obs/phase.py",
    # where a request's time goes and what stalls a tick (ISSUE 26)
    "request_stage_seconds": "gigapaxos_tpu/paxos/manager.py",
    # which branch the device's outbox compaction took (ISSUE 27)
    "compact_path_ticks_total": "gigapaxos_tpu/paxos/manager.py",
    # how many programs a sharded plane's tick enqueued (ISSUE 30)
    "mesh_dispatches_total": "gigapaxos_tpu/paxos/manager.py",
    # which call completed a pipelined tick's outbox, and how long the
    # completion was blocked for the program before the pull (ISSUE 31)
    "tick_completions_total": "gigapaxos_tpu/paxos/manager.py",
    "tick_device_wait_seconds": "gigapaxos_tpu/paxos/manager.py",
    # the rest of "tally": the pull and the unpack, and which buffer a
    # compacted plane's completion pulled (ISSUE 34)
    "tick_outbox_pull_seconds": "gigapaxos_tpu/paxos/manager.py",
    "outbox_pulls_total": "gigapaxos_tpu/paxos/manager.py",
    # what a deployment's bodies and skew cost a plane: requests an inbox
    # left behind, bytes a tick journaled, bytes of a reply (ISSUE 36)
    "inbox_deferred_requests": "gigapaxos_tpu/paxos/manager.py",
    "wal_append_bytes": "gigapaxos_tpu/paxos/manager.py",
    "app_reply_bytes": "gigapaxos_tpu/paxos/manager.py",
    # how a tick's inbox reached the device, a list of its placements or
    # dense, and the bytes handed to the dispatch (ISSUE 37)
    "inbox_builds_total": "gigapaxos_tpu/paxos/manager.py",
    "inbox_upload_bytes": "gigapaxos_tpu/paxos/manager.py",
    "jit_compile_seconds": "gigapaxos_tpu/obs/compiles.py",
    "compile_cache_lookups_total": "gigapaxos_tpu/obs/compiles.py",
    "wal_fsync_seconds": "gigapaxos_tpu/wal/logger.py",
    "wal_fsync_stalls_total": "gigapaxos_tpu/wal/logger.py",
    "wal_appended_bytes_total": "gigapaxos_tpu/wal/logger.py",
    "wal_checkpoint_seconds": "gigapaxos_tpu/wal/logger.py",
    "transport_writev_batch_frames": "gigapaxos_tpu/net/transport.py",
    # overload plane (ISSUE 14): per-class backpressure sheds at the
    # transport edge; deadline drops / admission NACKs in overload.py
    "transport_backpressure_drop_class_total":
        "gigapaxos_tpu/net/transport.py",
    "overload_expired_drops_total": "gigapaxos_tpu/overload.py",
    "overload_admission_shed_total": "gigapaxos_tpu/overload.py",
    "overload_intake_shedding": "gigapaxos_tpu/overload.py",
    # ordering/dissemination split (ISSUE 12): coordinator egress economics
    # and ring-hop latency live in the Mode B manager
    "egress_bytes_per_decision": "gigapaxos_tpu/modeb/manager.py",
    "ring_hop_seconds": "gigapaxos_tpu/modeb/manager.py",
    # register mode (ISSUE 16): paystore sharing rates are first-class at
    # millions of register groups; the gauge sizes the register plane
    "paystore_hits_total": "gigapaxos_tpu/paxos/paystore.py",
    "paystore_misses_total": "gigapaxos_tpu/paxos/paystore.py",
    "paystore_evictions_total": "gigapaxos_tpu/paxos/paystore.py",
    "register_groups": "gigapaxos_tpu/paxos/manager.py",
    # lease plane (ISSUE 17): local-read economics — holder gauge, the
    # local/fallback split, and writes parked behind a prior holder's lease
    "lease_holder_groups": "gigapaxos_tpu/paxos/manager.py",
    "reads_local_total": "gigapaxos_tpu/paxos/manager.py",
    "reads_fallback_total": "gigapaxos_tpu/paxos/manager.py",
    "lease_waits_total": "gigapaxos_tpu/paxos/manager.py",
    "client_read_latency_seconds": "gigapaxos_tpu/client.py",
    "client_commit_latency_seconds": "gigapaxos_tpu/client.py",
    "client_batch_rtt_seconds": "gigapaxos_tpu/client.py",
    "commit_latency_seconds":
        "gigapaxos_tpu/reconfiguration/active_replica.py",
    "cell_up": "gigapaxos_tpu/cells/supervisor.py",
    "cell_restarts_total": "gigapaxos_tpu/cells/supervisor.py",
    "supervisor_restart_backoff_seconds":
        "gigapaxos_tpu/cells/supervisor.py",
    "supervisor_heartbeat_timeout_seconds":
        "gigapaxos_tpu/cells/supervisor.py",
    # group health plane (ISSUE 18): device-side fold gauges in the Mode A
    # manager (the Mode B twin registers its own subset), and the scenario
    # timeline recorder's sample/event counters
    "health_backlogged_groups": "gigapaxos_tpu/paxos/manager.py",
    "health_wedged_groups": "gigapaxos_tpu/paxos/manager.py",
    "health_max_stall_ticks": "gigapaxos_tpu/paxos/manager.py",
    "health_max_churn": "gigapaxos_tpu/paxos/manager.py",
    "health_lease_wait_groups": "gigapaxos_tpu/paxos/manager.py",
    "timeline_samples_total": "gigapaxos_tpu/obs/timeline.py",
    "timeline_events_total": "gigapaxos_tpu/obs/timeline.py",
}


def test_documented_metric_families_exist_at_their_sites():
    for name, rel in WIRING.items():
        assert f'"{name}"' in _src(rel), f"{name} not wired in {rel}"
    # transport mirrors its stats counters into transport_<key>_total, and
    # per-peer byte accounting (the once-per-peer-link verification
    # instrument) into transport_peer_<key>_total
    assert 'f"transport_{key}_total"' in _src("gigapaxos_tpu/net/transport.py")
    assert 'f"transport_peer_{key}_total"' in _src(
        "gigapaxos_tpu/net/transport.py")


def test_scrape_surfaces_are_wired():
    worker = _src("gigapaxos_tpu/cells/worker.py")
    # per-cell export over the control socket, cell-labelled
    assert "render_registry" in worker and '"cell": str(cell)' in worker
    for cmd in ('cmd == "metrics"', 'cmd == "trace"', 'cmd == "flight"',
                'cmd == "healthz"', 'cmd == "health"', 'cmd == "group"',
                'cmd == "timeline"'):
        assert cmd in worker, cmd
    sup = _src("gigapaxos_tpu/cells/supervisor.py")
    assert "merge_scrapes" in sup and "MetricsServer" in sup
    assert "merge_timelines" in sup  # /timeline composes per-cell series
    server = _src("gigapaxos_tpu/server.py")
    assert "MetricsServer" in server and "FlightRecorder" in server
    assert "TimelineRecorder" in server
    http = _src("gigapaxos_tpu/obs/http.py")
    for route in ('"/metrics"', '"/trace"', '"/flight"', '"/healthz"',
                  '"/health"', '"/group/"', '"/timeline"'):
        assert route in http, route


def test_every_http_route_is_documented_in_module_docstring():
    """Every route string obs/http.py serves must appear in its module
    docstring — the docstring is the route inventory operators read, and
    an undocumented route is an unowned surface."""
    import gigapaxos_tpu.obs.http as http_mod

    doc = http_mod.__doc__ or ""
    src = _src("gigapaxos_tpu/obs/http.py")
    handler = src[src.index("def do_GET"):src.index("do_HEAD")]
    routes = set(re.findall(r'"(/[a-z]+/?)"', handler))
    assert routes, "no routes parsed out of do_GET"
    for route in routes:
        assert route.rstrip("/") in doc, (
            f"obs/http.py serves {route} but its module docstring does not "
            f"document it")


def test_readme_documents_the_observability_plane():
    readme = _src("README.md")
    assert "## Observability" in readme
    for name in ("tick_phase_seconds", "commit_latency_seconds",
                 "wal_fsync_seconds", "GPTPU_METRICS"):
        assert name in readme, f"README Observability section missing {name}"
