"""Host milliseconds per round of the verdict loop: the benchmark's span
around ``rbcd.dispatch_prepared`` less the program's host f64
certificate seconds, over the rounds, averaged over the window."""

from benchmark.drivers.common import counter_delta


def read(rec):
    d = rec["spans"].get("bench.dispatch")
    rounds = rec["driver"].get("rounds")
    if not d or not rounds:
        return None
    cert = counter_delta(rec["program"], "cert_f64_fallback_seconds_total") \
        if rec["program"] else 0.0
    return 1e3 * (sum(d) - cert) / sum(rounds)
