"""Share of the kernel's roofline: the least time of one round's work
(``_work.py``: one pass over the real edges and poses, at the chip's
peaks from ``peaks.json``) over the kernel's device time per round."""

from benchmark.metrics import _work as work

KERNELS = {"tcg": r"^%[\w.]*(tcg|rtr)[\w.]*_call"}


def read(rec):
    k = rec["trace"]["kernels"].get("tcg")
    rounds = rec["driver"].get("traced_rounds")
    peaks = rec["peaks"]
    if not k or not k["count"] or not rounds or not k["seconds"] or not peaks:
        return None
    g = rec["graph"]
    cfg = rec["config"]
    flops, bytes_ = work.round_work(g.i, g.j, cfg["poses"],
                                    cfg["agents"], cfg["rank"], cfg["d"])
    return 100.0 * work.least_seconds(flops, bytes_, peaks) \
        / (k["seconds"] / rounds)
