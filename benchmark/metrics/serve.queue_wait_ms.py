"""Mean submit -> dispatch wait of the window's requests, from the
program's ``serve_queue_wait_seconds`` histogram (telemetry run)."""

from benchmark.drivers.common import histogram_delta_mean


def read(rec):
    if not rec["program"]:
        return None
    m = histogram_delta_mean(rec["program"], "serve_queue_wait_seconds")
    return None if m is None else 1e3 * m
