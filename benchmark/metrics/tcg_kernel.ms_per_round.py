"""Device milliseconds of the tCG/RTR kernel per round, from the trace of
one whole solve: the kernel events' durations over the solve's rounds."""

#: Op names of the kernel in a TPU trace (``ops/pallas_tcg.py``).
KERNELS = {"tcg": r"^%[\w.]*(tcg|rtr)[\w.]*_call"}


def read(rec):
    k = rec["trace"]["kernels"].get("tcg")
    rounds = rec["driver"].get("traced_rounds")
    if not k or not k["count"] or not rounds:
        return None
    return 1e3 * k["seconds"] / rounds
