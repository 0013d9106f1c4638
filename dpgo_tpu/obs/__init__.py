"""Run-scoped telemetry: metrics registry, JSONL event stream, exporters.

The reference's only observability is ``ROPTResult`` wall-clock bookkeeping
plus verbose printouts; both source papers evaluate convergence through
per-iteration cost/gradient trajectories and per-agent status — signals the
solvers here already compute but (before this subsystem) never collected,
correlated, or exported.  This package is the substrate every perf and
robustness change reports through:

* ``MetricsRegistry`` (``metrics.py``) — thread-safe counters / gauges /
  histograms with labels, safe to call from the agent's background
  optimization thread (``agent.start_optimization_loop``).
* ``EventStream`` (``events.py``) — structured JSONL: every line carries the
  run id, wall + monotonic timestamps, a sequence number, and the solver
  phase.  ``metric_record`` is the shared ``metric``/``value``/``unit``
  record schema (``bench.py`` emits its final line through it, so bench and
  telemetry records parse identically).
* ``TelemetryRun`` (``run.py``) — one registry + one event stream scoped to
  a run directory, installed as the process-ambient run (``start_run`` /
  ``get_run`` / ``run_scope``).  Instrumented hot paths resolve the ambient
  run and take a no-telemetry early exit when none is installed: with
  telemetry off there are zero events, zero registry calls, and — by
  construction — zero added device->host transfers (every device readback
  the instrumentation performs goes through ``materialize``, which is only
  reached behind a ``get_run() is not None`` guard; see
  ``tests/test_obs.py::test_telemetry_off_is_zero_overhead``).
* Exporters (``exporters.py``) — Prometheus text exposition, optional
  TensorBoard scalars (gated on an available writer), and the JSON metrics
  snapshot.  ``python -m dpgo_tpu.obs.report <run_dir>`` renders a
  human-readable report from the persisted artifacts (``--json`` for
  machine-readable output).
* Distributed tracing (``trace.py`` / ``timeline.py``) — lightweight
  spans emitted through the event stream behind the same telemetry-off
  fence; trace context propagates across processes as optional wire
  entries, and ``python -m dpgo_tpu.obs.timeline <run_dir>...`` merges
  per-robot streams (pairwise clock-offset estimation from the
  send/receive stamps riding heartbeats and traced frames) into a
  Perfetto-loadable Chrome trace with cross-robot flow arrows.  A span
  entered with ``with trace.span(name)`` also opens a
  ``jax.profiler.TraceAnnotation`` named ``dpgo.<name>`` (on the
  profiler trace's host plane, on the device ops' clock), and every
  ended span observes ``span_seconds{span=<name>}``.  The solve path's
  spans: ``rbcd.prepare`` (``rbcd.partition``, ``rbcd.build_graph``,
  ``rbcd.init`` > ``rbcd.init.factor``, the host chordal factorization;
  counter ``chordal_init_total{path, reason}``), ``rbcd.dispatch``
  (``rbcd.programs``, ``rbcd.enqueue``,
  ``rbcd.fetch{what=word|history|epilogue|eval}``,
  ``cert.decide``), and inside the certificate ``cert.f64``,
  ``cert.f64.assemble``, ``cert.f64.sa``, ``cert.f64.lobpcg``,
  ``cert.f64.shift_invert`` (> ``cert.f64.inertia``, the symmetric
  factorization whose pivots decide; counter
  ``cert_inertia_total{outcome}``).
* Compile counters (``compile.py``) — JAX's trace / lower / build
  durations and persistent-cache hits, forwarded from ``jax.monitoring``
  as ``jax_compile_seconds_total{stage, program, span}`` and
  ``jax_programs_total{source=cache|compiled, program}``; the ``span``
  label is the innermost open span, the step that paid for the compile.
* Numerical health (``health.py``) — in-band anomaly detectors fed by
  the scalars the driver already reads back (NaN/Inf sentinel,
  per-GNC-stage cost monotonicity, gradient-norm explosion, stall,
  inlier-fraction collapse, certification REFUSE loops), emitting
  structured ``anomaly`` events with optional callback/abort policy.
* Flight recorder (``recorder.py``) — bounded ring buffer of recent
  eval scalars + exact state snapshots, dumped as a self-contained
  ``blackbox.npz`` + context JSONL on anomaly or crash;
  ``python -m dpgo_tpu.obs.recorder --replay`` resumes from the last
  healthy snapshot and reproduces the recorded trajectory bit-for-bit
  on the deterministic CPU backend.
* Convergence regression gate (``regress.py``) — ``report --compare
  runA runB`` checks run B's convergence against run A's tail noise
  bands, refuses apples-to-oranges comparisons on the config
  fingerprint (``TelemetryRun.set_fingerprint``), and exits non-zero on
  regression — CI's convergence analog of the perf smoke.

Instrumentation discipline on accelerator hot paths: never add a host sync
inside jitted code.  The solvers extend their *existing* phase-boundary
readbacks (the ``run_rbcd`` eval fetch, ``PGOAgent.iterate``'s host-side
state update) with telemetry scalars stacked into the same transfer, so a
telemetry-on run costs one slightly-larger readback per phase boundary and
a telemetry-off run is byte-identical to the uninstrumented driver.
"""

from __future__ import annotations

from .events import (
    EventStream,
    metric_record,
    nonfinite_str,
    read_events,
    read_events_meta,
    restore_nonfinite,
)
from .exporters import to_prometheus_text, write_tensorboard_scalars
from .health import HealthConfig, HealthMonitor, SolverHealthError, monitor_for
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .recorder import FlightRecorder
from .run import (
    TelemetryRun,
    end_run,
    get_run,
    materialize,
    run_scope,
    start_run,
)
from . import profile  # noqa: E402  (serving compile/device profiling)
from . import trace  # noqa: E402  (span API: trace.span / trace.start_span)

__all__ = [
    "profile",
    "Counter",
    "EventStream",
    "FlightRecorder",
    "Gauge",
    "HealthConfig",
    "HealthMonitor",
    "Histogram",
    "MetricsRegistry",
    "SolverHealthError",
    "TelemetryRun",
    "end_run",
    "get_run",
    "materialize",
    "metric_record",
    "monitor_for",
    "nonfinite_str",
    "read_events",
    "read_events_meta",
    "restore_nonfinite",
    "run_scope",
    "start_run",
    "to_prometheus_text",
    "trace",
    "write_tensorboard_scalars",
]
