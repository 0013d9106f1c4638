"""A run with the timed path broken underneath reads ``correct`` false.

Each cell's run, past the look for a chip, at a size a CPU test holds,
with the real limits (``benchmark/limits``), once sound and once for each
fault that the cell can have:

* a step that returns its state unchanged;
* half of the batch left out (half of the agents' blocks for a library
  solve, half of a served batch's replies);
* an answer altered where it is produced.

No cell spans chips, so no exchange between chips can be left out.
"""

import argparse
import dataclasses
import json
import os
import shutil
import tempfile

import numpy as np
import pytest

from benchmark import run as R

SMALL = {"city10000": dict(poses=600, edges=1199, loop_closures=600,
                           agents=4),
         "sphere2500": dict(poses=200, edges=399, loop_closures=200,
                            agents=2)}
# GNC diverges on a 200-pose graph (the program's own gradient grows to
# 2e4 in 600 rounds, my CPU run, PR 22); at 1,000 poses it converges.
SMALL_GNC = dict(poses=1000, edges=1999, loop_closures=1000, agents=4)


# Not cells yet (no chip readings in PR 22): their traffic is kept, with
# limits for this test alone.
PENDING = {
    "sphere2500.gnc": ({"name": "sphere2500.gnc", "config": "sphere2500",
                        "traffic": "gnc_outliers10", "chips": 1},
                       {"grad_X": {"limit": 0.3}, "grad_T": {"limit": 0.3},
                        "outliers_kept": {"limit": 0},
                        "inliers_dropped": {"limit": 10}}),
    "sphere2500.served": ({"name": "sphere2500.served",
                           "config": "sphere2500",
                           "traffic": "served_closed8", "chips": 1},
                          {"grad_T": {"limit": 3.0}}),
}


def run_small(cell_name, capsys, seconds=3.0, pool=4):
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    if cell_name in PENDING:
        cell, limits = PENDING[cell_name]
    else:
        cell = R.find(bench["workloads"], cell_name, "workload")
        limits = R.load_json(R.HERE, "limits",
                             cell_name + ".json")["numbers"]
    config = R.load_json(R.HERE, "configs", cell["config"] + ".json")
    config.update(SMALL_GNC if cell_name == "sphere2500.gnc"
                  else SMALL[config["name"]])
    traffic = R.load_json(R.HERE, "traffic", cell["traffic"] + ".json")
    if traffic["driver"] == "served":
        traffic["pool"] = pool
    args = argparse.Namespace(workload=cell_name, seed=2**31 + 99,
                              seconds=seconds, trace=0)
    tmp = tempfile.mkdtemp(prefix="bench-test-")
    cache = R.CACHE
    try:
        R.set_up_environment(tmp)
        R.CACHE = os.path.join(tmp, "cache")   # no executable of another run
        capsys.readouterr()
        R.run_cell(args, bench, cell, config, traffic, limits, tmp, "cpu",
                   None)
    finally:
        R.CACHE = cache
        shutil.rmtree(tmp, ignore_errors=True)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _unchanged(monkeypatch):
    from dpgo_tpu.models import rbcd

    monkeypatch.setattr(rbcd, "rbcd_segment",
                        lambda state, *a, **k: state)
    monkeypatch.setattr(rbcd, "_rbcd_segment", lambda state, *a, **k: state)


def _half_blocks(monkeypatch):
    import jax.numpy as jnp
    from dpgo_tpu.models import rbcd

    orig = rbcd.rbcd_segment

    def seg(state, *a, **k):
        new = orig(state, *a, **k)
        A = state.X.shape[0]
        keep = (jnp.arange(A) < A // 2)[:, None, None, None]
        return new._replace(X=jnp.where(keep, new.X, state.X))

    monkeypatch.setattr(rbcd, "rbcd_segment", seg)


def _half_batch(monkeypatch):
    from dpgo_tpu.serve import server

    orig = server.run_bucket

    def run_bucket(padded, *a, **k):
        results, info = orig(padded, *a, **k)
        for b in range(len(results) // 2):
            other = results[len(results) - 1 - b]
            results[b] = dataclasses.replace(results[b], T=other.T)
        if len(results) == 1:
            results[0] = dataclasses.replace(results[0],
                                             T=np.zeros_like(results[0].T))
        return results, info

    monkeypatch.setattr(server, "run_bucket", run_bucket)


def _altered_library(monkeypatch):
    from dpgo_tpu.models import rbcd

    orig = rbcd.dispatch_prepared

    def dispatch(prob, *a, **k):
        res = orig(prob, *a, **k)
        T = np.array(res.T)
        T[T.shape[0] // 2, :, -1] += 1.0
        res.T = T
        return res

    monkeypatch.setattr(rbcd, "dispatch_prepared", dispatch)


def _altered_reply(monkeypatch):
    from dpgo_tpu.serve import frontend

    orig = frontend._result_reply

    def reply(res, ticket=None):
        out = orig(res, ticket)
        T = np.array(out["T"])
        T[T.shape[0] // 2, :, -1] += 1.0
        out["T"] = T
        return out

    monkeypatch.setattr(frontend, "_result_reply", reply)


LIBRARY = ["city10000.certified", "sphere2500.gnc"]
FAULTS = [(c, f) for c in LIBRARY
          for f in (_unchanged, _half_blocks, _altered_library)] + \
         [("sphere2500.served", f)
          for f in (_unchanged, _half_batch, _altered_reply)]


@pytest.mark.parametrize("cell", LIBRARY + ["sphere2500.served"])
def test_sound_run_is_correct(cell, capsys):
    res = run_small(cell, capsys)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_reads_incorrect(cell, fault, capsys, monkeypatch):
    fault(monkeypatch)
    res = run_small(cell, capsys)
    assert not res["correct"], res["checks"]
