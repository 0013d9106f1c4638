"""Host milliseconds of the problem build per solve: the benchmark's span
around ``rbcd.prepare_problem`` (partition, per-agent graph, chordal or
odometry init), averaged over the window's solves."""


def read(rec):
    s = rec["spans"].get("bench.build")
    return 1e3 * sum(s) / len(s) if s else None
