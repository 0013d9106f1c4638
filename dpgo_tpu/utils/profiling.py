"""Profiling / tracing hooks — the framework's observability layer.

The reference's "tracing" is wall-clock + objective bookkeeping in
``ROPTResult`` (``DPGO_types.h:40-59``, filled at
``QuadraticOptimizer.cpp:36-54``) plus verbose printouts.  The TPU-native
equivalents here (SURVEY.md section 5):

* ``trace(logdir)`` — context manager around ``jax.profiler`` capturing a
  device timeline (XLA op breakdown, HBM traffic) viewable in
  TensorBoard/Perfetto.  Works on CPU and TPU backends.
* named regions inside the timeline are ``dpgo_tpu.obs.trace.span``:
  with a telemetry run installed each span also opens a
  ``jax.profiler.TraceAnnotation`` named ``dpgo.<name>``; the solve
  path's phases (prepare, enqueue, fetch, certificate) already carry one.
* ``RoundTimer`` — lightweight host-side per-phase wall-clock accumulator
  for driver loops: ``stop`` optionally blocks on a device value by
  materializing it.

The per-iteration *metrics* (cost, gradient norm, relative change,
per-agent readiness) are first-class solver outputs — ``RBCDResult.
cost_history`` / ``grad_norm_history`` and the gossiped status arrays —
not a tracing concern; this module is about *where the time goes*.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a JAX device/host profile into ``logdir``.

    Usage::

        with profiling.trace("/tmp/dpgo-trace"):
            state = rbcd.rbcd_steps(state, graph, 100, meta, params)
            np.asarray(state.X)   # materialize inside the trace window
    """
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class RoundTimer:
    """Host-side per-phase wall-clock accumulator for driver loops.

    ``stop(phase, sync=x)`` materializes ``x`` (device->host readback)
    before taking the timestamp.  On a local TPU ``block_until_ready``
    fences as well; the readback also works on values that are not JAX
    arrays.
    """

    def __init__(self):
        self.totals: dict[str, float] = {}
        self.counts: dict[str, int] = {}
        self._t0: dict[str, float] = {}

    def start(self, phase: str) -> None:
        self._t0[phase] = time.perf_counter()

    def stop(self, phase: str, sync=None) -> float:
        if phase not in self._t0:
            # Checked BEFORE the sync materialization: a mistyped phase
            # must fail fast with the clear error, not first pay a
            # device->host transfer for a window that was never opened.
            open_ = ", ".join(sorted(self._t0)) or "none"
            raise ValueError(
                f"stop({phase!r}) without a matching start() "
                f"(open phases: {open_})")
        if sync is not None:
            np.asarray(sync)
        dt = time.perf_counter() - self._t0.pop(phase)
        self.totals[phase] = self.totals.get(phase, 0.0) + dt
        self.counts[phase] = self.counts.get(phase, 0) + 1
        return dt

    @contextlib.contextmanager
    def phase(self, name: str, sync_fn=None):
        """``with timer.phase("solve", lambda: state.X): ...`` — the sync
        callable (if given) produces the device value to materialize at
        exit."""
        self.start(name)
        try:
            yield
        finally:
            self.stop(name, sync=sync_fn() if sync_fn is not None else None)

    def summary(self) -> str:
        rows = [f"{k}: {v:.4f}s / {self.counts[k]} "
                f"({1e3 * v / max(self.counts[k], 1):.2f} ms avg)"
                for k, v in sorted(self.totals.items(),
                                   key=lambda kv: -kv[1])]
        return "\n".join(rows)

    def as_dict(self) -> dict[str, dict[str, float]]:
        """Machine-readable accumulated timings:
        ``{phase: {"total_s", "count", "avg_ms"}}`` — the payload the
        telemetry event stream carries as ``phase_timings`` (the print-only
        ``summary()`` renders the same numbers)."""
        return {k: {"total_s": v, "count": self.counts[k],
                    "avg_ms": 1e3 * v / max(self.counts[k], 1)}
                for k, v in self.totals.items()}

    def reset(self) -> None:
        """Drop all accumulated totals/counts and any in-flight ``start``
        marks, so one timer instance can be reused across runs/windows."""
        self.totals.clear()
        self.counts.clear()
        self._t0.clear()
