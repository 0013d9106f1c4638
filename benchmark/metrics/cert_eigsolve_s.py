"""Host seconds per solve in the f64 recheck's eigensolvers: the SA
Lanczos pass, LOBPCG and the shift-invert Lanczos with its sparse LU
(the ``cert.f64.sa``, ``cert.f64.lobpcg`` and ``cert.f64.shift_invert``
spans)."""

from benchmark.metrics._program import span_seconds


def read(rec):
    n = rec["driver"].get("solves")
    s = span_seconds(rec["program"], "cert.f64.sa", "cert.f64.lobpcg",
                     "cert.f64.shift_invert")
    return None if s is None or not n else s / n
