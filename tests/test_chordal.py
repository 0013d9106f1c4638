"""Tests for chordal / odometry initialization (reference DPGO_utils.cpp:377-476)."""

import jax.numpy as jnp
import numpy as np
import pytest

from dpgo_tpu import obs
from dpgo_tpu.config import AgentParams
from dpgo_tpu.models import certify, rbcd
from dpgo_tpu.ops import chordal
from dpgo_tpu.types import edge_set_from_measurements
from dpgo_tpu.utils.lie import project_to_rotation_np
from dpgo_tpu.utils.partition import partition_contiguous
from synthetic import make_measurements, trajectory_error


def _chordal(meas, path):
    if path == "host":
        return chordal.chordal_initialization_host(meas)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    return np.asarray(chordal.chordal_initialization(edges, meas.num_poses))


def test_odometry_init_recovers_chain(rng):
    meas, (Rs, ts) = make_measurements(rng, n=20, d=3, num_lc=0)
    T = chordal.odometry_initialization(jnp.asarray(meas.R), jnp.asarray(meas.t))
    assert trajectory_error(T, Rs, ts) < 1e-10


@pytest.mark.parametrize("path", ["device", "host"])
def test_chordal_init_exact_on_noiseless_graph(rng, path):
    # With exact measurements the chordal relaxation is tight: recovery up to
    # the anchored gauge (analog of testTriangleGraph's 1e-4 golden check,
    # but property-based).
    for d in (2, 3):
        meas, (Rs, ts) = make_measurements(rng, n=15, d=d, num_lc=8)
        T = _chordal(meas, path)
        assert trajectory_error(T, Rs, ts) < 1e-6, f"d={d}"


def _dense_chordal(meas):
    """The two pinned least-squares problems written out as dense residual
    systems, row by row, and solved by ``lstsq``: rotations with R_0 = I,
    projected to SO(d), then translations with t_0 = 0."""
    n, d = meas.num_poses, meas.d
    wk = np.sqrt(meas.weight * meas.kappa)
    wt = np.sqrt(meas.weight * meas.tau)
    nv = n * d * d  # R_k[a, b] at (k d + a) d + b
    A = np.zeros((len(meas) * d * d, nv))
    for e, (i, j) in enumerate(zip(meas.p1, meas.p2)):
        for a in range(d):
            for c in range(d):
                row = (e * d + a) * d + c  # residual (R_j - R_i Rt)[a, c]
                A[row, (j * d + a) * d + c] += wk[e]
                for b in range(d):
                    A[row, (i * d + a) * d + b] -= wk[e] * meas.R[e, b, c]
    rhs = -A[:, :d * d] @ np.eye(d).ravel()
    x = np.linalg.lstsq(A[:, d * d:], rhs, rcond=None)[0]
    R = project_to_rotation_np(
        np.concatenate([np.eye(d).ravel(), x]).reshape(n, d, d))
    A = np.zeros((len(meas) * d, n * d))  # t_k[a] at k d + a
    rhs = np.zeros(len(meas) * d)
    for e, (i, j) in enumerate(zip(meas.p1, meas.p2)):
        o = R[i] @ meas.t[e]
        for a in range(d):
            A[e * d + a, j * d + a] += wt[e]
            A[e * d + a, i * d + a] -= wt[e]
            rhs[e * d + a] = wt[e] * o[a]
    t = np.linalg.lstsq(A[:, d:], rhs, rcond=None)[0]
    return np.concatenate(
        [R, np.concatenate([np.zeros(d), t]).reshape(n, d, 1)], axis=-1)


@pytest.mark.parametrize("d", [2, 3])
def test_host_chordal_matches_dense_lstsq(rng, d):
    """The host factorization solves the same two pinned least-squares
    problems as a dense lstsq, with per-edge weight * kappa and
    weight * tau."""
    meas, _ = make_measurements(rng, n=30, d=d, num_lc=15, rot_noise=0.05,
                                trans_noise=0.05)
    meas.weight = rng.uniform(0.5, 1.0, len(meas))
    meas.kappa = rng.uniform(50.0, 150.0, len(meas))
    meas.tau = rng.uniform(5.0, 15.0, len(meas))
    T = chordal.chordal_initialization_host(meas)
    np.testing.assert_allclose(T, _dense_chordal(meas), rtol=0, atol=1e-9)


@pytest.mark.parametrize("d", [2, 3])
def test_host_chordal_matches_device_cg(rng, d):
    """In float64 the device CG converges to the host factorization's
    answer on a 300-pose graph with closures."""
    meas, _ = make_measurements(rng, n=300, d=d, num_lc=150, rot_noise=0.05,
                                trans_noise=0.05)
    np.testing.assert_allclose(_chordal(meas, "host"),
                               _chordal(meas, "device"), rtol=0, atol=1e-7)


def _init_counts(run):
    fam = run.registry.snapshot().get("chordal_init_total")
    return {(s["labels"]["path"], s["labels"]["reason"]): s["value"]
            for s in (fam["series"] if fam else [])}


def _init_both_ways(meas, tmp_path):
    """``centralized_chordal_init`` under a telemetry run, and the device
    CG program on the same global edges."""
    params = AgentParams(d=meas.d, r=meas.d + 1, num_robots=2)
    part = partition_contiguous(meas, 2)
    graph, meta = rbcd.build_graph(part, params.r, jnp.float64)
    obs.end_run()
    with obs.run_scope(str(tmp_path / "run")) as run:
        X0 = rbcd.centralized_chordal_init(part, meta, graph, jnp.float64)
        counts = _init_counts(run)
    edges_g = edge_set_from_measurements(part.meas_global, dtype=jnp.float64)
    X_cg = rbcd.lifted_init(edges_g, graph, meta, meas.num_poses, "chordal")
    return np.asarray(X0), np.asarray(X_cg), counts


def test_disconnected_component_falls_back_to_device_cg(rng, tmp_path):
    """A component not tied to pose 0 makes the pinned system singular:
    the init takes the device CG and returns what it returns."""
    meas, _ = make_measurements(rng, n=40, d=3, num_lc=20, rot_noise=0.02,
                                trans_noise=0.02)
    meas = meas.select((meas.p1 < 20) == (meas.p2 < 20))
    with pytest.raises(chordal.SingularChordalSystem):
        chordal.chordal_initialization_host(meas)
    X0, X_cg, counts = _init_both_ways(meas, tmp_path)
    assert counts == {("device_cg", "singular"): 1}
    assert X0.tobytes() == X_cg.tobytes()


def test_fill_guard_falls_back_to_device_cg(rng, tmp_path, monkeypatch):
    """Where the certificate's fill guard trips on the global edges, the
    init is never factored: the device CG runs, counted as guarded."""
    def boom(_meas):
        raise AssertionError("factored a graph the guard rules out")
    monkeypatch.setattr(certify, "_expander_guard", lambda *_: True)
    monkeypatch.setattr(chordal, "chordal_initialization_host", boom)
    meas, _ = make_measurements(rng, n=30, d=2, num_lc=10, rot_noise=0.02,
                                trans_noise=0.02)
    X0, X_cg, counts = _init_both_ways(meas, tmp_path)
    assert counts == {("device_cg", "guarded"): 1}
    assert X0.tobytes() == X_cg.tobytes()


def test_connected_graph_takes_host_factor(rng, tmp_path):
    """A connected graph is factored on the host; its lifted init agrees
    with the device CG's to the CG's convergence."""
    meas, _ = make_measurements(rng, n=30, d=2, num_lc=10, rot_noise=0.02,
                                trans_noise=0.02)
    X0, X_cg, counts = _init_both_ways(meas, tmp_path)
    assert counts == {("host_factor", "factored"): 1}
    np.testing.assert_allclose(X0, X_cg, rtol=0, atol=1e-7)


def test_chordal_init_noisy_graph_close(rng):
    meas, (Rs, ts) = make_measurements(rng, n=30, d=3, num_lc=15,
                                       rot_noise=0.02, trans_noise=0.02)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    T = np.asarray(chordal.chordal_initialization(edges, meas.num_poses))
    # Rotations must stay valid and the trajectory near truth.
    R = T[..., :3]
    eye = np.broadcast_to(np.eye(3), R.shape)
    assert np.allclose(np.swapaxes(R, -1, -2) @ R, eye, atol=1e-8)
    assert trajectory_error(T, Rs, ts) < 0.5


def test_chordal_on_real_dataset(data_dir):
    # smallGrid3D end-to-end: init must produce valid rotations and a
    # drastically lower cost than a random start.
    from dpgo_tpu.utils.g2o import read_g2o
    from dpgo_tpu.ops import quadratic
    from dpgo_tpu.models.local_pgo import lift

    meas = read_g2o(f"{data_dir}/smallGrid3D.g2o")
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    T = chordal.chordal_initialization(edges, meas.num_poses)
    X = lift(T, jnp.eye(3, dtype=jnp.float64))
    f_chordal = float(quadratic.cost(X, edges))

    rng = np.random.default_rng(0)
    Xr = jnp.asarray(rng.standard_normal(np.asarray(X).shape))
    f_rand = float(quadratic.cost(Xr, edges))
    assert f_chordal < 0.01 * f_rand
    R = np.asarray(T[..., :3])
    eye = np.broadcast_to(np.eye(3), R.shape)
    assert np.allclose(np.swapaxes(R, -1, -2) @ R, eye, atol=1e-8)
