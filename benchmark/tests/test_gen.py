"""The benchmark's generator copies draw what the program's draw, today."""

import numpy as np
import pytest

from benchmark.gen import synthetic as gen
from benchmark.gen.g2o import g2o_bytes


def _same(p, meas):
    assert p.n == meas.num_poses and p.d == meas.d
    np.testing.assert_array_equal(p.i, meas.p1)
    np.testing.assert_array_equal(p.j, meas.p2)
    np.testing.assert_array_equal(p.R, meas.R)
    np.testing.assert_array_equal(p.t, meas.t)
    np.testing.assert_array_equal(p.kappa, meas.kappa)
    np.testing.assert_array_equal(p.tau, meas.tau)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
@pytest.mark.parametrize("d", [2, 3])
def test_make_measurements_matches_program(seed, d):
    from dpgo_tpu.utils import synthetic

    kw = dict(n=60, d=d, num_lc=30, rot_noise=0.01, trans_noise=0.01,
              outlier_lc=5)
    p, _ = gen.make_measurements(np.random.default_rng(seed), **kw)
    m, _ = synthetic.make_measurements(np.random.default_rng(seed), **kw)
    _same(p, m)


@pytest.mark.parametrize("radius", [None, 1.5])
@pytest.mark.parametrize("d", [2, 3])
def test_vectorized_matches_program(radius, d):
    from dpgo_tpu.utils import synthetic

    kw = dict(n=400, d=d, num_lc=150, rot_noise=0.01, trans_noise=0.01,
              lc_radius=radius)
    p, _ = gen.make_measurements_vectorized(np.random.default_rng(3), **kw)
    m, _ = synthetic.make_measurements_vectorized(np.random.default_rng(3),
                                                  **kw)
    _same(p, m)


@pytest.mark.parametrize("d", [2, 3])
def test_g2o_round_trip(d):
    from dpgo_tpu.utils.g2o import read_g2o

    p, _ = gen.make_measurements(np.random.default_rng(1), n=40, d=d,
                                 num_lc=20, rot_noise=0.05, trans_noise=0.1)
    m = read_g2o(g2o_bytes(p))
    np.testing.assert_array_equal(p.i, m.p1)
    np.testing.assert_array_equal(p.j, m.p2)
    np.testing.assert_allclose(p.R, m.R, atol=1e-12)
    np.testing.assert_allclose(p.t, m.t, atol=0)
    np.testing.assert_allclose(p.kappa, m.kappa, rtol=1e-12)
    np.testing.assert_allclose(p.tau, m.tau, rtol=1e-12)


def test_noise_follows_the_seed_and_sizes_do_not():
    from benchmark.drivers import common

    cfg = {"name": "t", "poses": 300, "d": 2, "loop_closures": 100,
           "edges": 399, "kappa": 100.0, "tau": 10.0, "lc_radius": 2.0,
           "rot_noise": 0.01, "trans_noise": 0.01}
    base, rows = common.base_problem(cfg, 7, outliers=10)
    a = common.seeded_problem(base, rows, cfg, np.random.default_rng(1))
    b = common.seeded_problem(base, rows, cfg, np.random.default_rng(1))
    c = common.seeded_problem(base, rows, cfg, np.random.default_rng(2))
    np.testing.assert_array_equal(a.t, b.t)
    assert not np.array_equal(a.t, c.t)
    np.testing.assert_array_equal(a.i, c.i)
    np.testing.assert_array_equal(a.j, c.j)
    assert np.abs(a.t[rows]).max() > 1.0   # gross outliers, last rows
