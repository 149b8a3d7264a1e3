"""One run of one cell: set-up, warm-up, the measured window, the drain, the
check against the reference, and the result line.

Everything a cell is made of comes from data files (``spec.py``); this file
holds no cell's name or number.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import faulthandler
import gc
import json
import os
import resource
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from . import deployment, load, references, spec, stats, tracing

#: a later run of a cell must exit within 360 s, the first (compiling) within
#: 1200 s; past this the process dumps every thread's stack and exits non-zero
DEADLINE_S = 1150
#: the profiler traces this much of the middle of the window (a whole 20 s
#: window at 1M groups would be a 10 MB trace)
TRACE_SLICE_S = 5.0
#: the warm-up's schedule ends here whatever the ticks did (a plane that
#: needs longer than this for its first ticks is broken, not cold)
WARMUP_MAX_S = 150.0
#: lead between the last set-up step and the window's first due instant
LEAD_S = 0.05
#: the payload sweep's row buckets are compiled in set-up up to this many rows
#: (``deployment.warm_sweep_buckets``): the outstanding records of 8 s of
#: backlog at 1,000 req/s; a run that holds more has stalled for other reasons
SWEEP_ROWS_MAX = 8192
#: tick counts on whose multiples a plane does periodic work, for the window
#: diagnostics only: the payload sweep (``PaxosManager._sweep_every``) and
#: ``pause_idle`` (``tick_num % 256`` in ``PaxosManager._complete_tick``)
PERIODIC_TICKS = (64, 256)
#: the two planes of a Mode A node, data then control, as the program's own
#: labels name them
PLANES = ("ar", "rc")


_T0 = time.monotonic()


def note(msg: str) -> None:
    """Progress goes to stderr: stdout's last line is the result."""
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What the readers of per-layer metrics are given."""

    cell: spec.Cell
    device: dict
    load: load.Load            # the window's offered schedule and replies
    window_s: float            # between the two registry snapshots
    snap0: dict                # obs registry at the window's start
    snap1: dict                # ... and end
    trace: tracing.Trace | None


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="a workload of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--config-file", help="rehearsal only: a configuration "
                    "file in no cell, with --traffic")
    ap.add_argument("--traffic", help="rehearsal only: a traffic mix")
    return ap.parse_args(argv)


def device_report(n_chips: int) -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": deployment.memory_peak_bytes(n_chips)}


def align_planes(cluster, settle_ticks: int = 3) -> None:
    """Start every window from the same interleaving of the two planes.

    Both planes' pipelined ticks share one device queue, and at start-up they
    lock into one of two stable orders: data, data, control, control (about
    7 of 10 starts at 1M groups) or strictly alternating, which differ by
    17% in ``commit_p95_ms`` and stay for a whole window (my chip runs, PR
    24).  A run that left it to that race would measure the race.  Holding
    the control plane's lock for a few data-plane ticks and releasing it
    right after a data-plane dispatch gives the first, commoner and slower
    order every time: the control plane then dispatches twice (about 70 and
    170 ms on) before the data plane's next dispatch (about 385 ms on).  No
    traffic is in flight while it is held, and the window starts after it."""
    m = cluster.manager
    with cluster.rc_manager.lock:
        until = m.tick_num + settle_ticks
        give_up = time.monotonic() + 30.0
        while m.tick_num < until and time.monotonic() < give_up:
            time.sleep(0.001)


def warm_up(cell, gen, seed: int, m, client, names: list, actives: list):
    """Offer the cell's own traffic until the data plane has carried it for
    ``warmup_ticks`` ticks and is two ticks past the next multiple of
    ``warmup_past_multiple_of`` (the traffic file says why), then wait for
    the replies.  Returns the load."""
    warm = load.Load(gen.schedule(cell.traffic["params"], seed, WARMUP_MAX_S,
                                  len(names), len(actives), stream=1,
                                  seq0=10 ** 9), names, actives)
    warm_ticks = int(cell.traffic["warmup_ticks"])
    multiple = int(cell.traffic["warmup_past_multiple_of"])
    first: dict = {}

    def warmed() -> bool:
        # counted from the first send: idle ticks before it carry nothing
        if not first:
            first["tick"] = m.tick_num
            return False
        past = (first["tick"] // multiple + 1) * multiple + 2
        return m.tick_num >= max(first["tick"] + warm_ticks, past)

    warm.offer(client, time.monotonic() + LEAD_S, stop=warmed)
    answered = warm.wait_replies(client.default_deadline_s)
    note(f"warm-up: {warm.n_sent} requests over ticks {first['tick']}-"
         f"{m.tick_num}, {warm.answered()} answered"
         + ("" if answered else " (the rest count as unknown writes)"))
    return warm


def _install_recorders(cluster) -> list:
    recorders = []
    for plane, m in zip(PLANES, (cluster.manager, cluster.rc_manager)):
        inner = getattr(m, "_pc", None)
        if inner is None or not hasattr(inner, "mark"):
            continue
        rec = tracing.PhaseRecorder(inner, plane)
        m._pc = rec
        recorders.append((m, rec))
    return recorders


def _remove_recorders(recorders: list) -> None:
    for m, rec in recorders:
        m._pc = rec._inner


def _trace_slice(t0: float, seconds: float, trace_dir: str) -> int:
    """Trace ``TRACE_SLICE_S`` from the middle of the window.  Returns the
    ``perf_counter_ns`` at which the clock-sync annotation opened."""
    import jax

    length = min(TRACE_SLICE_S, seconds / 2)
    start = t0 + (seconds - length) / 2
    time.sleep(max(0.0, start - time.monotonic()))
    # no Python-function events: they slow the host loop under observation
    # and the metrics read none; TraceMe annotations (the sync mark) stay
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        sync_perf_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(tracing.SYNC_MARK):
            time.sleep(0.001)
        time.sleep(length)
    finally:
        jax.profiler.stop_trace()
    return sync_perf_ns


class _TickTimeline:
    """Diagnostics under ``CHIPBENCH_KEEP=<dir>``: both planes' tick numbers
    sampled every 5 ms through the window and the drain, saved with every
    request's due, sent and done instants, for a look by hand at which ticks
    carried which requests, and the instant and length of every collection of
    the collector's oldest generation (a ``gc.callbacks`` entry that reads the
    clock; it changes nothing the collector does).  Not part of any metric."""

    def __init__(self, managers: tuple):
        self._managers = managers
        self._rows: list = []
        #: (start, seconds) of every collection of the oldest
        #: generation: it stops every thread of the process while it runs
        self.collections: list = []
        self._gc_t = 0.0
        gc.callbacks.append(self._on_gc)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._sample, daemon=True,
                                        name="chipbench-timeline")
        self._thread.start()

    def _sample(self) -> None:
        while not self._stop.wait(0.005):
            self._rows.append((time.monotonic(),
                               *(m.tick_num for m in self._managers)))

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] < 2:
            return
        if phase == "start":
            self._gc_t = time.monotonic()
        else:
            self.collections.append((self._gc_t, time.monotonic() - self._gc_t))

    def stop(self) -> list:
        """End the sampling; the rows (time, data tick, control tick)."""
        self._stop.set()
        self._thread.join()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        return self._rows

    def save(self, path: str, window) -> None:
        self.stop()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        np.savez_compressed(path, ticks=np.array(self._rows), t0=window.t0,
                            collections=np.array(self.collections),
                            due=window.due, sent=window.sent, done=window.done,
                            status=window.status, entry=window.sched.entry)


def window_diagnostics(ticks0: tuple, ticks1: tuple, window_s: float,
                       gc0: list, gc1: list,
                       every: tuple = PERIODIC_TICKS) -> dict:
    """Where a run's window lay on each plane's tick count, for stderr and
    the sets' ``.jsonl`` (never a metric): the tick numbers at its two ends
    (data plane, control plane), the period over it (window / ticks
    completed: ``readers/tick_period``'s arithmetic on the managers' own
    counts), how many multiples of each of ``every`` fell inside (``first <
    t <= last``: the program does periodic work on such ticks), and from
    ``gc.get_stats()`` at both ends the collections of each generation."""
    out: dict = {"gc_collections": [b["collections"] - a["collections"]
                                    for a, b in zip(gc0, gc1)]}
    for plane, first, last in zip(PLANES, ticks0, ticks1):
        out[plane] = {"tick0": int(first), "tick1": int(last),
                      "period_ms": 1e3 * window_s / (last - first)
                      if last > first else None}
        for n in every:
            out[plane][f"multiples_of_{n}"] = int(last // n - first // n)
    return out


def host_usage() -> dict:
    """This process's ``getrusage`` counters, for ``host_in_window``."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"user_s": ru.ru_utime, "sys_s": ru.ru_stime,
            "minor_faults": ru.ru_minflt, "major_faults": ru.ru_majflt,
            "waits": ru.ru_nvcsw, "preempted": ru.ru_nivcsw}


def host_in_window(u0: dict, u1: dict) -> dict:
    """What the host did for this process between two ``host_usage()``
    readings: CPU seconds in user and kernel mode (all threads), page faults,
    voluntary and involuntary context switches (the second count rises where
    other work takes the cores; the chip tool's sandbox reports the CPU
    seconds and zeros).  For the question why one process of a seed ticks
    some per cent slower than the next; never a metric."""
    return {k: (round(u1[k] - u0[k], 3) if k.endswith("_s") else u1[k] - u0[k])
            for k in u0}


def compiles_in_window(snap0: dict, snap1: dict) -> dict:
    """What compiled between the two registry snapshots, traced run or not:
    the observations of ``jit_compile_seconds`` (trace, lower and backend
    stages together; a persistent-cache hit still traces and lowers) and
    their seconds.  ``compile_free_pct`` reads the same in a traced run."""
    from .readers import histogram_mean

    shim = Run(None, {}, None, 0.0, snap0, snap1, None)
    w = histogram_mean.window(shim, "jit_compile_seconds")
    return {"n": int(w[0]), "s": float(w[1])} if w else {"n": 0, "s": 0.0}


def timeline_diagnostics(rows: list, collections: list, t0: float,
                         seconds: float) -> dict:
    """What only the ``CHIPBENCH_KEEP`` timeline shows of the window ``t0``
    to ``t0 + seconds``: per plane the longest and the median gap between two
    ticks, how many gaps were over 1.5 medians with the time they held beyond
    one, and where the longest lay; and the time the collections of the
    oldest generation took (each stops every thread of the process)."""
    took = [1e3 * d for t, d in collections if t0 <= t <= t0 + seconds]
    out: dict = {"gc_oldest_ms": {"n": len(took), "sum": float(sum(took)),
                                  "longest": float(max(took, default=0.0))}}
    a = np.asarray(rows, dtype=np.float64).reshape(-1, 1 + len(PLANES))
    a = a[(a[:, 0] >= t0) & (a[:, 0] <= t0 + seconds)]
    for i, plane in enumerate(PLANES):
        at = a[1:, 0][np.diff(a[:, 1 + i]) > 0]  # instants a tick ended
        gaps = np.diff(at) * 1e3
        if not gaps.size:
            continue
        med = float(np.median(gaps))
        long = gaps[gaps > 1.5 * med]
        out[plane] = {"longest_gap_ms": float(gaps.max()),
                      "median_gap_ms": med, "long_gaps": int(long.size),
                      "long_gaps_excess_ms": float((long - med).sum()),
                      "longest_gap_at_s": float(at[int(gaps.argmax())] - t0)}
    return out


def _collect_ops(loads: list, names: list) -> dict:
    """name -> [``references.Op``] over every request the run sent, warm-up
    included, in the order sent."""
    from gigapaxos_tpu.reconfiguration import packets as pkt

    ops: dict = {}
    for ld in loads:
        s = ld.sched
        for i in range(ld.n_sent):
            st = int(ld.status[i])
            status = ("ok" if st == stats.OK else
                      "unknown" if st == stats.PENDING else "refused")
            reply = (pkt.b64d(ld.reply[i]) or b"") if st == stats.OK else None
            ops.setdefault(names[s.name[i]], []).append(references.Op(
                s.kind[i], s.key[i], s.value[i], float(ld.sent[i]),
                float(ld.done[i]), status, reply))
    return ops


def check(cell, cluster, client, loads: list, names: list, actives: list,
          seed: int, initial) -> list:
    """The configuration's reference against the replies, the replicas and a
    read-back through the client; returns the problems found."""
    ops = _collect_ops(loads, names)
    acked = sorted(n for n, os_ in ops.items()
                   if any(o.status == "ok" for o in os_))
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(acked), replace=False, size=min(
        len(acked), int(cell.traffic["readback_names"])))
    # each name picked is read back at the key of its first request
    by_key: dict = {}
    for i in pick:
        by_key.setdefault(ops[acked[i]][0].key, []).append(acked[i])
    readback: dict = {}
    for key, picked in by_key.items():
        got = load.read_back(client, picked, actives, key,
                             client.default_deadline_s)
        readback.update((name, {key: value}) for name, value in got.items())
    problems = spec.reference(cell.config["reference"]).check_run(
        ops, lambda n: deployment.replica_tables(cluster, n), readback,
        initial)
    for p in problems[:10]:
        note(f"WRONG: {p}")
    by_kind = collections.Counter(o.kind for os_ in ops.values() for o in os_
                                  if o.status == "ok")
    note("acknowledged by kind: " + json.dumps(by_kind, sort_keys=True))
    note(f"check: {len(ops)} names touched on {cluster.manager.R} replicas,"
         f" {sum(by_kind.values())} replies, {len(readback)} read back by GET; "
         f"{len(problems)} problem(s)")
    return problems


def run(args, t_start: float) -> dict:
    rehearsal = os.environ.get("CHIPBENCH_REHEARSAL") == "1"
    if args.config_file or args.traffic:
        if not rehearsal:
            raise SystemExit("--config-file/--traffic are for rehearsals "
                             "(CHIPBENCH_REHEARSAL=1); a measured run names "
                             "a --workload of BENCHMARK.json")
        cell = spec.rehearsal_cell(args.config_file, args.traffic)
    else:
        cell = spec.load_cell(args.workload)

    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not rehearsal:
        raise SystemExit(f"JAX's default backend is {backend!r}, not a TPU; "
                         f"no result (CHIPBENCH_REHEARSAL=1 rehearses)")
    if jax.device_count() < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chip(s); JAX shows "
                         f"{jax.device_count()}")
    from gigapaxos_tpu import compile_cache
    from gigapaxos_tpu.client import ReconfigurableAppClient
    from gigapaxos_tpu.obs.metrics import registry

    cache_dir = compile_cache.configure()
    note(f"{cell.name}: backend {backend}, compile cache {cache_dir}")

    gen = spec.generator(cell.traffic["generator"])
    run_dir = tempfile.mkdtemp(prefix="chipbench_")
    cluster = client = None
    recorders: list = []
    try:
        cfg = deployment.make_config(cell.config)
        cluster = deployment.build_cluster(cell.config, cfg, run_dir)
        m = cluster.manager
        note(f"cluster up: R={m.R} G={m.G} W={m.W} P={m.P}; first ticks "
             f"{cluster.driver.first_tick_s:.1f}s / "
             f"{cluster.rc_driver.first_tick_s:.1f}s")
        names = deployment.populate(cluster, int(cell.config["populate_groups"]))
        actives = list(cfg.nodes.active_ids())
        note(f"populated and adopted {len(names):,} groups")
        client = ReconfigurableAppClient(cfg.nodes)
        initial: dict = {}
        if "preload" in cell.traffic:
            t = time.monotonic()
            initial = deployment.preload(cluster, names,
                                         cell.traffic["preload"], args.seed)
            note(f"preload: {len(initial):,} records through consensus and "
                 f"the journal in {time.monotonic() - t:.2f}s")

        warm = warm_up(cell, gen, args.seed, m, client, names, actives)
        t = time.monotonic()
        n = deployment.warm_sweep_buckets(m, SWEEP_ROWS_MAX)
        note(f"sweep: {n} row buckets up to {SWEEP_ROWS_MAX} compiled in "
             f"{time.monotonic() - t:.2f}s")
        align_planes(cluster)

        # ---- the window
        window = load.Load(gen.schedule(cell.traffic["params"], args.seed,
                                        args.seconds, len(names),
                                        len(actives)), names, actives)
        if args.trace:
            recorders = _install_recorders(cluster)
        reg = registry()
        usage0 = host_usage()
        t0 = time.monotonic() + LEAD_S
        setup_s = t0 - t_start
        sender = threading.Thread(target=window.offer, args=(client, t0),
                                  name="chipbench-generator")
        time.sleep(max(0.0, t0 - time.monotonic()))
        planes = (m, cluster.rc_manager)
        snap0, t_snap0 = reg.snapshot(), time.monotonic()
        ticks0, gc0 = tuple(p.tick_num for p in planes), gc.get_stats()
        sender.start()
        keep = os.environ.get("CHIPBENCH_KEEP")
        timeline = _TickTimeline(planes) if keep else None
        sync_perf_ns = None
        trace_dir = os.path.join(run_dir, "trace")
        if args.trace:
            sync_perf_ns = _trace_slice(t0, args.seconds, trace_dir)
        time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
        snap1, t_snap1 = reg.snapshot(), time.monotonic()
        ticks1, gc1 = tuple(p.tick_num for p in planes), gc.get_stats()
        usage1 = host_usage()
        sender.join()
        answered = window.wait_replies(client.default_deadline_s)
        if timeline is not None:
            timeline.save(os.path.join(
                keep, f"{cell.name}.{args.seed}.t{args.trace}.npz"), window)
        _remove_recorders(recorders)
        # every request of the window's schedule is due inside the window
        e2e = stats.end_to_end(window.due, window.done, window.status,
                               np.ones(len(window.due), bool), args.seconds)
        note(f"window: {e2e['attempted']} due, {e2e['by_status']}, all "
             f"answered: {answered}; data-plane tick {m.tick_num}")
        diag = window_diagnostics(ticks0, ticks1, t_snap1 - t_snap0, gc0, gc1)
        diag["compiles"] = compiles_in_window(snap0, snap1)
        diag["host"] = host_in_window(usage0, usage1)
        if timeline is not None:
            kept = timeline_diagnostics(timeline.stop(), timeline.collections,
                                        t0, args.seconds)
            for key, value in kept.items():
                diag.setdefault(key, {}).update(value)
        note("diag: " + json.dumps(diag))

        problems = check(cell, cluster, client, [warm, window], names,
                         actives, args.seed, initial)
        device = device_report(cell.chips)
        result = {"correct": not problems and e2e["attempted"] > 0,
                  "attempted": e2e["attempted"], "failed": e2e["failed"],
                  "metrics": {}, "device": device}
        if not args.trace:
            values = dict(e2e, setup_s=setup_s)
            for metric in cell.end_to_end:
                if metric["name"] in values:
                    result["metrics"][metric["name"]] = {
                        "value": values[metric["name"]], "unit": metric["unit"]}
        else:
            trace_report(result, Run(cell, device, window,
                                     t_snap1 - t_snap0, snap0, snap1, None),
                         trace_dir, [r for _, r in recorders], sync_perf_ns,
                         m, rehearsal)
        # what `correct` compared, each number beside its limit; last in the line
        result["compared"] = {
            "wrong_answers": {"value": len(problems), "limit": 0},
            "requests_due": {"value": e2e["attempted"], "limit": ">0"}}
        return result
    finally:
        if client is not None:
            client.close()
        if cluster is not None:
            cluster.close()
        shutil.rmtree(run_dir, ignore_errors=True)


def trace_report(result: dict, the_run: Run, trace_dir: str, recorders: list,
                 sync_perf_ns, manager, rehearsal: bool) -> None:
    """Fill ``result`` with the per-layer metrics, the device's busy time and
    the breakdown of a traced run."""
    cell, device = the_run.cell, the_run.device
    trace = None
    try:
        trace = tracing.load_xplane(tracing.newest_xplane(trace_dir))
    except Exception as e:  # no trace: the trace metrics are left out
        note(f"trace: {type(e).__name__}: {e}")
    if rehearsal:
        trace = None  # no device metric is printed off the chip
    the_run.trace = trace
    for metric in cell.per_layer:
        value = spec.reader(metric["reader"]).read(the_run, **metric["args"])
        if value is not None:
            result["metrics"][metric["name"]] = {"value": value,
                                                 "unit": metric["unit"]}
    if trace is None:
        return
    busy_s, window_s = tracing.busy_and_window_s(trace)
    device.update(busy_s=busy_s, window_s=window_s)
    label = None
    if trace.sync_ns is not None and recorders:
        label = tracing.phase_labeller(recorders, sync_perf_ns, trace.sync_ns)
    result["breakdown"] = {"device_ops": tracing.top_ops(trace, 10),
                           "idle_gaps": tracing.idle_gaps(trace, 10, label)}
    _report_kernels(manager, trace)
    keep = os.environ.get("CHIPBENCH_KEEP")
    if keep:
        trace.to_json(os.path.join(keep, f"{cell.name}.trace.json.gz"))
        shutil.copy(tracing.newest_xplane(trace_dir),
                    os.path.join(keep, f"{cell.name}.xplane.pb"))


def _report_kernels(m, trace) -> None:
    """Cross-check on stderr: the kernels the dispatched program carries
    against the kernel events the trace shows per execution."""
    try:
        fn, fn_args = deployment.tick_program(m)
        k = deployment.kernels_in(fn, *fn_args)
    except Exception as e:
        note(f"kernels_in: {type(e).__name__}: {e}")
        return
    execs = len(trace.line(tracing.MODULES))
    for which in ("gather", "match"):
        events, secs = tracing.op_seconds(trace, f"^%{k[which + '_name']}")
        note(f"kernel {k[which + '_name']}: {k['mosaic_' + which]} Mosaic "
             f"calls in the program, {events} events over {execs} executions "
             f"in the trace, {secs:.4f}s")
    note(f"kernels: {k['pallas_calls']} pallas calls traced, "
         f"{k['interpreted']} interpreted")


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    if not (args.workload or (args.config_file and args.traffic)):
        print("chipbench: --workload is required", file=sys.stderr)
        return 2
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    result = run(args, t_start)
    faulthandler.cancel_dump_traceback_later()
    for name, c in result["compared"].items():
        print(f"compared: {name} {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
