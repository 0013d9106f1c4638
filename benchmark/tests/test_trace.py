"""The trace reduction, by hand on a made-up trace and on a small trace
recorded on a TPU v5e in PR 22: a 600-pose, 4-agent library solve of 40
rounds under ``bench.*`` spans.  The recorded ``.xplane.pb`` was 10.6 MB;
``data/tiny_trace.json.gz`` keeps what ``trace.read`` took from it (the
TPU plane's 20,160 op events and the host's 3 spans)."""

import gzip
import json
import os

import pytest

from benchmark import trace

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny_trace.json.gz")
KERNEL = {"tcg": r"^%[\w.]*(tcg|rtr)[\w.]*_call"}


def test_union_gaps_and_kernels_by_hand():
    ms = 1e6
    tr = {"devices": {"/device:TPU:0": [
        ("%while.1", 10 * ms, 50 * ms),              # 10..60
        ("%vmap_jit_rtr_full_call__.2", 20 * ms, 10 * ms),  # nested
        ("%fusion.3", 70 * ms, 20 * ms),             # 70..90
        ("%fusion.4", 150 * ms, 100 * ms),           # clipped at 200
    ]}, "spans": [("bench.window", 0.0, 200 * ms),
                  ("bench.dispatch", 0.0, 120 * ms),
                  ("bench.build", 110 * ms, 40 * ms)]}
    s = trace.summarize(tr, KERNEL)
    assert s["window_s"] == pytest.approx(0.2)
    assert s["busy_s"] == pytest.approx(0.05 + 0.02 + 0.05)
    assert s["kernels"]["tcg"] == {"seconds": pytest.approx(0.01),
                                   "count": 1}
    gaps = dict(s["breakdown"]["idle_gaps"])
    # A gap goes whole to the span covering most of it: 0..10 and
    # 60..70 to dispatch; 90..150 to build (40 ms of it, dispatch 30).
    assert gaps["bench.dispatch"] == pytest.approx(0.02)
    assert gaps["bench.build"] == pytest.approx(0.06)
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["%fusion.4"] == pytest.approx(0.05)


def test_short_names():
    assert trace.short_name("%fusion.3 = f32[2]{0} fusion(%a)") == \
        "%fusion.3"


def test_no_tpu_operation_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize({"devices": {"/device:TPU:0": []}, "spans": []})


def test_recorded_tpu_trace():
    with gzip.open(TINY, "rt") as fh:
        tr = json.load(fh)
    assert list(tr["devices"]) == ["/device:TPU:0"]
    names = {n for n, _, _ in tr["spans"]}
    assert {"bench.window", "bench.build", "bench.dispatch"} <= names
    s = trace.summarize(tr, KERNEL)
    win = [d for n, _, d in tr["spans"] if n == "bench.window"][0]
    assert s["window_s"] == pytest.approx(win * 1e-9)
    assert 0 < s["busy_s"] < s["window_s"]
    k = s["kernels"]["tcg"]
    assert k["count"] > 0 and 0 < k["seconds"] < s["busy_s"]
    assert all(n.startswith("%") for n, _ in s["breakdown"]["device_ops"])
    assert {n for n, _ in s["breakdown"]["idle_gaps"]} <= names | {"none"}
    assert len(s["breakdown"]["device_ops"]) <= 10
    # As read on the chip's host when it was recorded (my chip run, PR 22).
    assert k["count"] == 40
    assert s["busy_s"] == pytest.approx(0.098846765, rel=1e-6)
