"""Every per-layer reader, on a record made by hand."""

import glob
import os

import numpy as np
import pytest

from benchmark import run as R
from benchmark.gen.synthetic import Problem


def _rec():
    snap = lambda cert, wait_sum, wait_n: {
        "cert_f64_fallback_seconds_total": {
            "series": [{"labels": {}, "value": cert}]},
        "serve_queue_wait_seconds": {
            "series": [{"labels": {}, "sum": wait_sum, "count": wait_n}]}}
    i, j = np.array([0, 1, 2, 0]), np.array([1, 2, 3, 3])
    return {
        "config": {"poses": 4, "agents": 2, "rank": 3, "d": 2},
        "spans": {"bench.build": [0.01, 0.03], "bench.dispatch": [2.0, 4.0]},
        "driver": {"solves": 2, "rounds": [100, 200], "traced_rounds": 100},
        "program": {"counters_before": snap(1.0, 0.5, 5),
                    "counters": snap(3.0, 1.5, 15),
                    "events": [{"event": "serve_batch", "occupancy": 0.5},
                               {"event": "serve_batch", "occupancy": 1.0}]},
        "trace": {"busy_s": 1.0, "window_s": 4.0,
                  "kernels": {"tcg": {"seconds": 0.2, "count": 100}}},
        "graph": Problem(d=2, n=4, i=i, j=j, R=None, t=None, kappa=None,
                         tau=None),
        "peaks": {"flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11},
    }


EXPECT = {
    "build_ms.solve": 20.0,
    "rounds_per_solve": 150.0,
    "round_ms": 1e3 * (6.0 - 2.0) / 300,
    "cert_host_s": 1.0,
    "tcg_kernel.ms_per_round": 2.0,
    "device_idle.solve": 75.0,
    "device_idle.served": 75.0,
    "serve.queue_wait_ms": 100.0,
    "serve.batch_occupancy": 75.0,
    # (6 edges x 40 B + 12 pose reads x 36 B) / 819 GB/s over 2 ms.
    "tcg_roofline": 100.0 * (6 * 40 + 12 * 36) / 8.19e11 / 2e-3,
}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader(name):
    mod = R.load_module(os.path.join(R.HERE, "metrics", name + ".py"),
                        "m_" + name.replace(".", "_"))
    assert mod.read(_rec()) == pytest.approx(EXPECT[name])


def test_every_reader_is_tested():
    files = {os.path.basename(f)[:-3]
             for f in glob.glob(os.path.join(R.HERE, "metrics", "*.py"))}
    assert files - {"_work", "__init__"} == set(EXPECT)
