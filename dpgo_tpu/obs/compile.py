"""Compile counters: JAX's own compile events, forwarded to the ambient run.

JAX reports the durations of tracing a function to a jaxpr, lowering it
to an MLIR module and building the executable through ``jax.monitoring``.
The build is timed around the persistent-cache lookup, so a program
loaded from the cache reports a ``backend`` duration too (the load), and
a ``cache_hits`` event inside it.  ``install()`` registers one
process-wide listener pair, once (``start_run`` calls it); the listeners
do nothing unless a telemetry run is installed.  With one installed they
feed the run's registry:

* ``jax_compile_seconds_total{stage=trace|lower|backend, program, span}``
  — ``program`` is the name JAX gives the event, ``span`` the innermost
  open ``obs.trace`` span on the compiling thread (``none`` outside any),
  so every compile lands in the step that paid for it;
* ``jax_programs_total{source=cache|compiled, program}`` — one per built
  executable: ``cache`` when the persistent cache served it, else
  ``compiled``.
"""

from __future__ import annotations

import threading

from .run import get_run

__all__ = ["install"]

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_installed = False
# A cache hit is reported inside the build it serves, on the same thread,
# before the build's own duration event.
_tls = threading.local()


def install() -> None:
    """Register the listeners with ``jax.monitoring`` (idempotent)."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT and get_run() is not None:
        _tls.hit = True


def _on_duration(event: str, secs: float, fun_name=None, **_) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    run = get_run()
    if run is None:
        return
    from .trace import current_span

    sp = current_span()
    program = str(fun_name)
    run.counter("jax_compile_seconds_total",
                "seconds JAX spent tracing, lowering and building (or "
                "loading from the persistent cache) programs",
                unit="s").inc(float(secs), stage=stage, program=program,
                              span=sp.name if sp is not None else "none")
    if stage == "backend":
        hit = getattr(_tls, "hit", False)
        _tls.hit = False
        run.counter("jax_programs_total",
                    "executables built, by where they came from").inc(
            source="cache" if hit else "compiled", program=program)
