"""Host milliseconds per round spent enqueueing segments, verdict evals
and the terminal epilogue (the ``rbcd.enqueue`` spans), less the compile
seconds paid inside them (``jax_compile_seconds_total{span=rbcd.enqueue}``,
read by ``jit_ms.solve``)."""

from benchmark.metrics._program import delta, span_seconds


def read(rec):
    rounds = rec["driver"].get("rounds")
    s = span_seconds(rec["program"], "rbcd.enqueue")
    if s is None or not rounds:
        return None
    jit = delta(rec["program"], "jax_compile_seconds_total",
                span="rbcd.enqueue") or 0.0
    return 1e3 * (s - jit) / sum(rounds)
