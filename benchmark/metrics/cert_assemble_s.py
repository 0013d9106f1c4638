"""Host seconds per solve assembling the sparse certificate matrix S for
the f64 recheck (the ``cert.f64.assemble`` span)."""

from benchmark.metrics._program import span_seconds


def read(rec):
    n = rec["driver"].get("solves")
    s = span_seconds(rec["program"], "cert.f64.assemble")
    return None if s is None or not n else s / n
