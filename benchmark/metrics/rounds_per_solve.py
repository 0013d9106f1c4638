"""RBCD rounds per solve (``RBCDResult.iterations``), a count that
repeats exactly for a seed."""


def read(rec):
    r = rec["driver"].get("rounds")
    return sum(r) / len(r) if r else None
