"""Window deltas of labelled program series, for the readers of the
solve path's spans and compile counters.  ``program`` holds the
telemetry run's ``registry.snapshot()`` before (``counters_before``) and
after (``counters``) the window."""


def _total(snap: dict, family: str, key: str, match: dict) -> float:
    fam = snap.get(family)
    if not fam:
        return 0.0
    return sum(float(s[key]) for s in fam["series"]
               if all(s["labels"].get(k) == v for k, v in match.items()))


def delta(program: dict, family: str, key: str = "value",
          **match) -> float | None:
    """Growth over the window of ``family``'s series whose labels include
    ``match`` (a counter's ``value``, a histogram's ``sum`` or ``count``);
    None when the family is absent, as it is in a program that lacks it."""
    if not program or family not in program["counters"]:
        return None
    return (_total(program["counters"], family, key, match)
            - _total(program["counters_before"], family, key, match))


def span_seconds(program: dict, *names: str) -> float | None:
    """Host seconds the window spent in the named spans."""
    parts = [delta(program, "span_seconds", "sum", span=n) for n in names]
    return None if parts[0] is None else sum(parts)
