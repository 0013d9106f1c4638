"""Milliseconds per solve that JAX spent tracing, lowering and building
or loading programs (``jax_compile_seconds_total``, every stage, program
and span; telemetry run installed)."""

from benchmark.metrics._program import delta


def read(rec):
    n = rec["driver"].get("solves")
    s = delta(rec["program"], "jax_compile_seconds_total")
    return None if s is None or not n else 1e3 * s / n
