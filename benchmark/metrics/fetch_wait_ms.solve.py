"""Milliseconds per solve the host spent blocked in the verdict loop's
device->host fetches (the ``rbcd.fetch`` spans: verdict words, history,
terminal epilogue)."""

from benchmark.metrics._program import span_seconds


def read(rec):
    n = rec["driver"].get("solves")
    s = span_seconds(rec["program"], "rbcd.fetch")
    return None if s is None or not n else 1e3 * s / n
