"""What interrupts a tick: JAX tracing, lowering and compiling, as metrics.

A jitted program met with a new shape (a ``frontier_rows`` bucket, a
point-clear size class) traces, lowers and compiles on the thread that
called it, which in the served path is the thread that ticks.  JAX reports
each of the three as a ``jax.monitoring`` duration event and each lookup of
the persistent compilation cache as a hit or a miss event; one listener,
installed once per process by the first manager, turns them into

* ``jit_compile_seconds{stage=trace|lower|backend}``: a histogram, so its
  count is programs and its sum is seconds stalled.  ``backend`` also fires,
  short, when the persistent cache answers.
* ``compile_cache_lookups_total{result=hit|miss}``.

There is no per-function label: function names are unbounded.  To name the
program behind a count, run with ``JAX_LOG_COMPILES=1``.  Under
``GPTPU_METRICS=0`` nothing is registered with JAX.
"""

from __future__ import annotations

import threading

from .metrics import METRICS_ENABLED, registry

#: JAX's duration events (``jax/_src/dispatch.py``) -> our ``stage`` label
STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
#: JAX's persistent-cache events -> our ``result`` label
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

_install_lock = threading.Lock()
_installed = False


def install() -> None:
    """Register the listener with ``jax.monitoring``; every call after the
    first is a no-op.  It feeds the process's default registry for the life
    of the process."""
    global _installed
    with _install_lock:
        if _installed or not METRICS_ENABLED:
            return
        import jax.monitoring

        reg = registry()
        stages = {event: reg.histogram(
            "jit_compile_seconds",
            help="JAX tracing / lowering / backend compile time per program",
            stage=stage) for event, stage in STAGE_EVENTS.items()}
        lookups = {event: reg.counter(
            "compile_cache_lookups_total",
            help="persistent compilation cache lookups",
            result=result) for event, result in CACHE_EVENTS.items()}

        def on_duration(event: str, duration_secs: float, **_kw) -> None:
            h = stages.get(event)
            if h is not None:
                h.observe(duration_secs)

        def on_event(event: str, **_kw) -> None:
            c = lookups.get(event)
            if c is not None:
                c.inc()

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        _installed = True


def cache_lookups() -> tuple:
    """(hits, misses) of the persistent compilation cache so far."""
    reg = registry()
    return (reg.counter("compile_cache_lookups_total", result="hit").value,
            reg.counter("compile_cache_lookups_total", result="miss").value)
