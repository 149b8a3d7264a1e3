"""Flight-deck plane: low-overhead runtime metrics, tracing, postmortems.

The reference operates through periodic dumps (``DelayProfiler`` stats from
the execution loop, outstanding/unpaused counts from ``PaxosManager``) and a
per-request hop accumulator (``RequestInstrumenter``).  This package is that
story made production-shaped for the dense TPU stack:

* :mod:`.metrics` — counters / gauges / fixed log-bucket histograms with an
  allocation-free hot path and a process-wide registry; compiled out entirely
  under ``GPTPU_METRICS=0`` (the overhead A/B in
  ``benchmarks/obs_overhead.py`` flips exactly this switch).
* :mod:`.phase` — per-tick phase clocks for the Mode A / Mode B / chain tick
  drivers.  Host-timestamped at dispatch and completion, so it adds **no
  device sync**; under a profiler the same phases are trace annotations, and
  the tick programs' device phases are named scopes (``TICK_SCOPES``).
* :mod:`.compiles` — JAX's trace / lower / compile durations and persistent
  cache lookups as metrics: what stalls a tick from inside.
* :mod:`.prom` — Prometheus text exposition, including per-cell label
  injection so a CellSupervisor can serve one host-level scrape.
* :mod:`.http` — the scrape endpoint (``/metrics``, ``/trace/<id>``,
  ``/flight``).
* :mod:`.flight` — the crash flight recorder: a bounded ring of recent
  StatsReporter snapshots + transport/chaos events, persisted continuously
  and dumped on SIGUSR2, so a SIGKILL'd cell still leaves a postmortem.
"""

from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Registry,
    metrics_enabled,
    registry,
)
from .phase import PhaseClock, phase_clock  # noqa: F401
from .prom import render_registry  # noqa: F401
