"""Host seconds per solve in the program's f64 certificate fallback
(``cert_f64_fallback_seconds_total``, telemetry run installed): the
recheck that runs when the f32 device eigensolve REFUSEs."""

from benchmark.drivers.common import counter_delta


def read(rec):
    n = rec["driver"].get("solves")
    if not rec["program"] or not n:
        return None
    return counter_delta(rec["program"],
                         "cert_f64_fallback_seconds_total") / n
