"""The f64 reference agrees with chip_smoke's host checks (PR 21) on a
small solve of the program on the CPU, and reads a broken answer as one."""

import numpy as np
import pytest

from benchmark.drivers.common import to_measurements
from benchmark.drivers.library import global_iterate
from benchmark.gen import synthetic as gen
from benchmark.reference import pgo


@pytest.fixture(scope="module")
def solved():
    import jax.numpy as jnp
    from dpgo_tpu.config import AgentParams, SolverParams
    from dpgo_tpu.models import rbcd

    p, _ = gen.make_measurements_vectorized(
        np.random.default_rng(0), n=600, d=2, num_lc=600, rot_noise=0.01,
        trans_noise=0.01, lc_radius=1.0)
    meas = to_measurements(p)
    params = AgentParams(d=2, r=3, num_robots=4, rel_change_tol=0.0,
                         solver=SolverParams(grad_norm_tol=1e-5),
                         certify_mode="device")
    prob = rbcd.prepare_problem(meas, 4, params=params, dtype=jnp.float32)
    res = rbcd.dispatch_prepared(prob, max_iters=400, grad_norm_tol=0.1,
                                 eval_every=5, verdict_every=20)
    return p, meas, global_iterate(np.asarray(res.X, np.float64), p.n), res


def test_matches_chip_smoke_host_checks(solved):
    import chip_smoke

    p, meas, X, res = solved
    e = chip_smoke.host_edges(meas)
    eta = 1e-5
    gn, certified, lam, tol = chip_smoke.host_certificate(X, e, eta)
    assert pgo.cost(p, X) == pytest.approx(chip_smoke.host_cost(X, e),
                                           rel=1e-12)
    assert pgo.cost(p, np.asarray(res.T)) == pytest.approx(
        chip_smoke.host_cost(np.asarray(res.T), e), rel=1e-12)
    assert pgo.grad_norm(p, X) == pytest.approx(gn, rel=1e-9)
    assert eta * pgo.weight_scale(p) == pytest.approx(tol, rel=1e-12)
    lam_ref, resid = pgo.lambda_min(p, X, tol)
    assert abs(lam_ref - lam) <= 1e-8 + 1e-3 * abs(lam)
    assert certified and lam_ref - resid >= -tol


def test_global_iterate_matches_program(solved):
    from dpgo_tpu.utils.partition import (gather_poses_to_global,
                                          partition_contiguous)

    p, meas, X, res = solved
    Xa = np.asarray(res.X, np.float64)
    np.testing.assert_array_equal(
        X, gather_poses_to_global(Xa, partition_contiguous(meas, 4)))


def test_broken_answers_read_far_off(solved):
    import ml_dtypes

    p, _, X, res = solved
    T = np.asarray(res.T, np.float64)
    bad = T.copy()
    bad[p.n // 2, :, -1] += 1.0                      # one pose moved 1 m
    bf16 = X.astype(ml_dtypes.bfloat16).astype(np.float64)
    assert pgo.grad_norm(p, X) < 0.1
    assert pgo.grad_norm(p, bad) > 10 * pgo.grad_norm(p, T)
    assert pgo.grad_norm(p, bf16) > 10 * pgo.grad_norm(p, X)
