"""Compile & device profiling for the serving plane.

A batched Burer–Monteiro RBCD service has two dominant costs the flat
serving events never showed: XLA compiles filling the
``serve.cache.ExecutableCache`` (seconds per bucket on CPU, tens of
seconds on TPU) and device time/HBM per padded bucket.  This module makes
both observable without touching the solver math:

* ``ProfiledExecutable`` wraps a jitted program from the executable
  cache.  With telemetry on, each distinct static-argument combination is
  lowered and AOT-compiled exactly once, the compile wall-time split into
  trace/lower vs. XLA compile, and the compiled executable's
  ``cost_analysis()`` / ``memory_analysis()`` (flops, bytes accessed,
  temp/argument/output HBM) recorded as one ``compile_profile`` event per
  fingerprint key plus ``serve_compile_seconds_total`` /
  ``serve_compile_flops`` metrics.  The AOT-compiled executable is then
  what every later dispatch calls, so the profiled path compiles each
  program once — same count as the unprofiled jit path.  With telemetry
  off the wrapper is never constructed (the cache stores the bare jit
  wrapper), so the fence stays airtight: no ``lower()``/``cost_analysis``
  calls exist on the off path for the zero-overhead boom test to trip.

* ``ProfilerWindow`` is the opt-in ``jax.profiler`` trace window: started
  before the first batch dispatch, stopped after the first K, writing a
  TensorBoard-loadable device profile under ``profile_dir``.  Constructed
  only behind the telemetry fence (``SolveServer`` refuses to build one
  with telemetry off, even when ``--profile-dir`` is set).

Analysis extraction is defensive throughout: backends differ in what
``cost_analysis``/``memory_analysis`` expose (dict vs. list-of-dict vs.
unimplemented), and profiling must never break a solve — every probe
degrades to "field absent", never to an exception on the dispatch path.
"""

from __future__ import annotations

import threading
import time

from .run import get_run

__all__ = [
    "ProfiledExecutable",
    "ProfilerWindow",
    "aot_compile_profile",
]

#: memory_analysis attributes worth recording, exported under these keys.
_MEMORY_FIELDS = (
    ("temp_size_in_bytes", "temp_bytes"),
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("generated_code_size_in_bytes", "code_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
)

#: cost_analysis keys worth recording, exported under these names.
_COST_FIELDS = (
    ("flops", "flops"),
    ("transcendentals", "transcendentals"),
    ("bytes accessed", "bytes_accessed"),
)


def _cost_fields(compiled) -> dict:
    """Flatten ``compiled.cost_analysis()`` to the stable field subset.
    Older jax returns a list with one dict per device program; newer
    returns the dict directly; some backends raise."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return {}
    out = {}
    for key, name in _COST_FIELDS:
        v = cost.get(key)
        if isinstance(v, (int, float)):
            out[name] = float(v)
    return out


def _memory_fields(compiled) -> dict:
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for attr, name in _MEMORY_FIELDS:
        v = getattr(mem, attr, None)
        if isinstance(v, (int, float)):
            out[name] = float(v)
    return out


def aot_compile_profile(run, jitfn, args, kwargs, key: str, label: str,
                        phase: str = "serve", metric_prefix: str = "serve",
                        **extra):
    """Lower + AOT-compile ``jitfn`` for these arguments, recording the
    compile profile under fingerprint ``key``; returns the compiled
    executable (the thing to dispatch from now on).

    One ``compile_profile`` event carries: the fingerprint key, the
    program label (segment/metrics/finalize), trace/lower vs. XLA compile
    wall seconds, whatever cost/memory analysis the backend exposes, and
    — when both flops and bytes-accessed are known — the bytes-per-flop
    roofline ratio (arithmetic intensity's reciprocal: how memory-bound
    the program is).  ``phase``/``metric_prefix`` scope the event and
    metric names to the emitting plane (``serve`` for the executable
    cache, ``sharded`` via ``devprof.profiled_program``).
    ``run`` is the caller's already-resolved ambient run — the caller's
    fence, like ``emit_span``."""
    t0 = time.monotonic()
    lowered = jitfn.lower(*args, **kwargs)
    t_lower = time.monotonic()
    compiled = lowered.compile()
    t_done = time.monotonic()
    fields = {"key": key, "label": label,
              "lower_s": t_lower - t0, "compile_s": t_done - t_lower,
              "total_s": t_done - t0}
    fields.update(_cost_fields(compiled))
    fields.update(_memory_fields(compiled))
    if fields.get("flops", 0) > 0 and "bytes_accessed" in fields:
        fields["bytes_per_flop"] = fields["bytes_accessed"] / fields["flops"]
    fields.update(extra)
    run.event("compile_profile", phase=phase, **fields)
    run.counter(f"{metric_prefix}_compile_seconds_total",
                "wall-clock spent in XLA compiles of profiled executables",
                unit="s").inc(t_done - t0, label=label)
    if "flops" in fields:
        run.gauge(f"{metric_prefix}_compile_flops",
                  "XLA cost-analysis flops of the last compiled "
                  "executable").set(fields["flops"], label=label)
    if "temp_bytes" in fields:
        run.gauge(f"{metric_prefix}_compile_temp_bytes",
                  "XLA memory-analysis temp allocation of the last "
                  "compiled executable",
                  unit="bytes").set(fields["temp_bytes"], label=label)
    if "bytes_per_flop" in fields:
        run.gauge(f"{metric_prefix}_bytes_per_flop",
                  "roofline ratio (bytes accessed / flop) of the last "
                  "compiled executable").set(fields["bytes_per_flop"],
                                             label=label)
    return compiled


class ProfiledExecutable:
    """A cache entry that profiles its compiles.

    Wraps the jitted program the executable cache would otherwise store
    directly.  Each distinct static-argument combination (``uw``/``rs``
    for RBCD segments) is AOT-compiled exactly once through
    ``aot_compile_profile``; later calls dispatch the compiled executable
    with the static kwargs stripped (they are baked into the program).
    If telemetry vanished since construction, falls back to the plain jit
    wrapper — correctness never depends on the run outliving the cache.
    """

    def __init__(self, jitfn, key: str, label: str,
                 static_names: tuple = (), **extra):
        self._jitfn = jitfn
        self._key = str(key)
        self._label = str(label)
        self._static = tuple(static_names)
        self._extra = dict(extra)
        self._compiled: dict[tuple, object] = {}
        self._lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        run = get_run()
        if run is None:
            return self._jitfn(*args, **kwargs)
        combo = tuple(sorted(
            (k, kwargs[k]) for k in self._static if k in kwargs))
        with self._lock:
            compiled = self._compiled.get(combo)
        if compiled is None:
            compiled = aot_compile_profile(
                run, self._jitfn, args, kwargs, self._key, self._label,
                static=dict(combo) or None, **self._extra)
            with self._lock:
                self._compiled.setdefault(combo, compiled)
        dyn = {k: v for k, v in kwargs.items() if k not in self._static}
        return compiled(*args, **dyn)


class ProfilerWindow:
    """Opt-in ``jax.profiler`` capture of the first K batch dispatches.

    ``batch_begin()`` starts the trace before the first profiled batch;
    ``batch_end()`` counts it down and stops the trace after the K-th —
    one contiguous window covering exactly the cold-start batches where
    compiles and first dispatches happen.  Start/stop failures disable
    the window (profiling must never take the server down) and are
    reported as a ``profiler_error`` event when a run is live."""

    def __init__(self, profile_dir: str, num_batches: int = 3):
        self.profile_dir = str(profile_dir)
        self.remaining = max(1, int(num_batches))
        self._active = False
        self._dead = False
        self._lock = threading.Lock()

    def batch_begin(self) -> None:
        with self._lock:
            if self._dead or self._active or self.remaining <= 0:
                return
            try:
                import jax

                jax.profiler.start_trace(self.profile_dir)
                self._active = True
            except Exception as e:
                self._dead = True
                run = get_run()
                if run is not None:
                    run.event("profiler_error", phase="serve",
                              error=repr(e))

    def batch_end(self) -> None:
        with self._lock:
            if not self._active:
                return
            self.remaining -= 1
            if self.remaining > 0:
                return
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:
                self._dead = True
                run = get_run()
                if run is not None:
                    run.event("profiler_error", phase="serve",
                              error=repr(e))
            finally:
                self._active = False
                run = get_run()
                if run is not None and not self._dead:
                    run.event("profiler_window", phase="serve",
                              profile_dir=self.profile_dir)

    def close(self) -> None:
        """Stop a still-open window (server shutting down mid-capture)."""
        with self._lock:
            if self._active:
                try:
                    import jax

                    jax.profiler.stop_trace()
                except Exception:
                    pass
                self._active = False
