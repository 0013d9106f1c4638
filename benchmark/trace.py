"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Reads the file with ``jax.profiler.ProfileData`` alone.  On a TPU the
device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation that ran, with its device start and duration.
Host threads live on the ``/host:CPU`` plane; the benchmark's own
``TraceAnnotation`` spans (``bench.*``) appear there on the same clock.

* busy: the union of the op intervals inside the traced window, per chip,
  averaged over the chips that ran an operation;
* per-op and per-kernel device seconds: the sum of their events'
  durations inside the window;
* idle gaps: the holes between busy intervals, each named by the
  ``bench.*`` host span that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _events(line):
    for ev in line.events:
        yield short_name(ev.name), float(ev.start_ns), float(ev.duration_ns)


def short_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%fusion.3 = f32[...] fusion(...)``); keep the instruction's name."""
    return name.split(" = ", 1)[0]


def read(path: str) -> dict:
    """The trace as plain lists: ``devices`` maps each TPU plane to its
    op events ``(name, start_ns, dur_ns)``; ``spans`` lists the host's
    ``bench.*`` annotations the same way."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e[0].startswith("bench."))
    return {"devices": devices, "spans": spans}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ops, t0, t1):
    for name, s, d in ops:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def _cover(spans, a, b) -> str:
    """The innermost ``bench.*`` span (not the window) covering most of
    [a, b], or ``none``."""
    best, best_key = "none", (0.0, 0.0)
    for name, s, d in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(b, s + d) - max(a, s)
        key = (ov, -d)
        if ov > 0 and key > best_key:
            best, best_key = name, key
    return best


def summarize(tr: dict, kernels: dict[str, str] | None = None,
              top: int = 10) -> dict:
    """Device summary of the ``bench.window`` span (of the whole trace
    where the host recorded none).

    ``kernels`` maps a kernel's name to a regular expression matched
    against op names.  Returns ``busy_s``, ``window_s``, ``kernels``
    {name: {"seconds", "count"}} averaged over the chips, and the
    ``breakdown`` lists of the result line."""
    devs = {k: v for k, v in tr["devices"].items() if v}
    if not devs:
        raise ValueError("the trace holds no operation on a TPU")
    win = [(s, s + d) for n, s, d in tr["spans"] if n == WINDOW_SPAN]
    if win:
        t0, t1 = min(w[0] for w in win), max(w[1] for w in win)
    else:
        t0 = min(s for ops in devs.values() for _, s, _ in ops)
        t1 = max(s + d for ops in devs.values() for _, s, d in ops)
    pats = {k: re.compile(p) for k, p in (kernels or {}).items()}
    busy, op_ns, gaps = 0.0, {}, []
    kern = {k: {"seconds": 0.0, "count": 0} for k in pats}
    for ops in devs.values():
        clipped = list(_clip(ops, t0, t1))
        merged = _union([(a, b) for _, a, b in clipped])
        busy += sum(b - a for a, b in merged)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps.extend((edges[k], edges[k + 1])
                    for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k])
        for name, a, b in clipped:
            op_ns[name] = op_ns.get(name, 0.0) + (b - a)
            for k, p in pats.items():
                if p.search(name):
                    kern[k]["seconds"] += (b - a) * 1e-9
                    kern[k]["count"] += 1
    nd = len(devs)
    for k in kern:
        kern[k]["seconds"] /= nd
    by_host: dict[str, float] = {}
    for a, b in gaps:
        name = _cover(tr["spans"], a, b)
        by_host[name] = by_host.get(name, 0.0) + (b - a) * 1e-9 / nd
    ops_top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy * 1e-9 / nd,
        "window_s": (t1 - t0) * 1e-9,
        "chips": nd,
        "kernels": kern,
        "breakdown": {
            "device_ops": [[n, s * 1e-9 / nd] for n, s in ops_top],
            "idle_gaps": [[n, s] for n, s in gaps_top],
        },
    }
