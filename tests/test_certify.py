"""M6 tests: dual certificate + Riemannian staircase (beyond-reference;
scoped from the T-RO 2021 paper per SURVEY.md section 7, M6 — the reference
repo contains no certification code to mirror, so these tests validate
against first principles: dense eigensolves and a constructed suboptimal
critical point)."""

import numpy as np
import pytest

import jax.numpy as jnp

from dpgo_tpu.config import SolverParams
from dpgo_tpu.models import certify, local_pgo
from dpgo_tpu.ops import solver
from dpgo_tpu.types import Measurements, edge_set_from_measurements
from synthetic import make_measurements


def dense_certificate(X, edges):
    """Assemble S explicitly by applying the operator to basis vectors."""
    n, _, dh = X.shape
    lam = certify.dual_blocks(X, edges)
    m = n * dh
    eye = jnp.eye(m).reshape(m, n, dh).transpose(1, 0, 2)  # [n, m, d+1]
    S_cols = certify.certificate_matvec(eye, edges, lam)
    return np.asarray(S_cols.transpose(1, 0, 2).reshape(m, m))


def test_certificate_operator_matches_dense_eig(rng):
    meas, _ = make_measurements(rng, n=10, d=3, num_lc=5,
                                rot_noise=0.05, trans_noise=0.05)
    res = local_pgo.solve_local(meas, rank=5, grad_norm_tol=1e-9,
                                max_iters=500)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    S = dense_certificate(res.X, edges)
    assert np.allclose(S, S.T, atol=1e-9)
    lam_dense = float(np.linalg.eigvalsh(S)[0])
    cert = certify.certify_solution(res.X, edges)
    assert abs(cert.lambda_min - lam_dense) < 1e-6 * max(1.0, abs(lam_dense))
    # Gauge: global-translation directions are in S's nullspace.
    v = np.zeros((10, 4)); v[:, 3] = 1.0
    assert np.abs(S @ v.reshape(-1)).max() < 1e-9


def test_optimal_solution_certifies(rng):
    meas, _ = make_measurements(rng, n=20, d=3, num_lc=8,
                                rot_noise=0.05, trans_noise=0.05)
    res = local_pgo.solve_local(meas, rank=5, grad_norm_tol=1e-9, max_iters=500)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    cert = certify.certify_solution(res.X, edges)
    assert cert.stationarity_gap < 1e-6
    assert cert.certified
    assert cert.lambda_min > -1e-6 * cert.sigma


def _winding_cycle(n=12, kappa=10.0, tau=1.0):
    """SE(2) cycle graph whose measurements are all identity — the global
    optimum is the all-identity trajectory (cost 0), but the 'winding'
    configuration R_k = rot(2 pi k / n) is a rank-2 critical point (a
    genuine local minimum for n > 4): the classic suboptimal critical point
    of angular synchronization on a cycle."""
    edges = [(k, (k + 1) % n) for k in range(n)]
    m = len(edges)
    e = np.asarray(edges)
    meas = Measurements(
        d=2, num_poses=n,
        r1=np.zeros(m, np.int32), p1=e[:, 0].astype(np.int64),
        r2=np.zeros(m, np.int32), p2=e[:, 1].astype(np.int64),
        R=np.tile(np.eye(2), (m, 1, 1)), t=np.zeros((m, 2)),
        kappa=np.full(m, kappa), tau=np.full(m, tau),
        weight=np.ones(m), is_known_inlier=np.zeros(m, bool),
    )
    th = 2 * np.pi * np.arange(n) / n
    Rw = np.stack([np.stack([np.cos(th), -np.sin(th)], -1),
                   np.stack([np.sin(th), np.cos(th)], -1)], -2)  # [n, 2, 2]
    Xw = np.concatenate([Rw, np.zeros((n, 2, 1))], axis=-1)  # rank 2 = d
    return meas, jnp.asarray(Xw)


def test_winding_local_minimum_fails_certificate_and_staircase_escapes():
    meas, Xw = _winding_cycle()
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    n = meas.num_poses
    params = SolverParams(initial_radius=1e1, max_inner_iters=50)
    problem = local_pgo.make_problem(edges, n, params.precond_shift)

    # The winding configuration is critical at rank 2: RTR does not move.
    out = solver.rtr_solve(problem, Xw, params, max_iters=200,
                           grad_norm_tol=1e-9)
    assert float(out.grad_norm) < 1e-9
    f_wind = float(out.f)
    assert f_wind > 1.0  # far from the global optimum (cost 0)

    # The certificate must detect suboptimality...
    cert = certify.certify_solution(out.X, edges)
    assert not cert.certified
    assert cert.lambda_min < -1e-3

    # ...and climbing the staircase must reach the certified global optimum
    # (cost 0).  Each escape strictly decreases the cost; this instance
    # passes through a SECOND suboptimal critical point at rank 3 (cost
    # exactly half the winding cost) before certifying at rank 4.
    X = out.X
    costs = [f_wind]
    for _ in range(3):
        X = certify.escape_rank(X, cert.direction, edges)
        out = solver.rtr_solve(problem, X, params, max_iters=400,
                               grad_norm_tol=1e-9)
        X = out.X
        costs.append(float(out.f))
        assert costs[-1] < costs[-2]
        cert = certify.certify_solution(X, edges)
        if cert.certified:
            break
    assert cert.certified
    assert costs[-1] < 1e-9


def test_solve_staircase_end_to_end(rng):
    meas, (Rs, ts) = make_measurements(rng, n=24, d=3, num_lc=10,
                                       rot_noise=0.05, trans_noise=0.05)
    res = certify.solve_staircase(meas, grad_norm_tol=1e-8)
    assert res.certificate.certified
    assert res.rank <= 6
    # Certified solution equals the plain high-rank solve's optimum.
    ref = local_pgo.solve_local(meas, rank=5, grad_norm_tol=1e-8, max_iters=500)
    assert res.cost <= ref.cost * (1 + 1e-8) + 1e-12


def test_staircase_rounding_handles_rotated_basis(rng):
    # After an escape the solution may leave the initial lifted subspace;
    # rounding must still recover a valid SE(d) trajectory.
    meas, Xw = _winding_cycle()
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    params = SolverParams(initial_radius=1e1, max_inner_iters=50)
    problem = local_pgo.make_problem(edges, meas.num_poses, params.precond_shift)
    out = solver.rtr_solve(problem, Xw, params, max_iters=100, grad_norm_tol=1e-9)
    cert = certify.certify_solution(out.X, edges)
    X3 = certify.escape_rank(out.X, cert.direction, edges)
    out3 = solver.rtr_solve(problem, X3, params, max_iters=300, grad_norm_tol=1e-9)
    ylift = certify._recover_rounding_basis(out3.X, 2)
    T = local_pgo.round_solution(out3.X, ylift)
    R = np.asarray(T[..., :2])
    RtR = np.einsum("nab,nac->nbc", R, R)
    assert np.allclose(RtR, np.eye(2), atol=1e-8)
    assert np.allclose(np.linalg.det(R), 1.0, atol=1e-8)


def test_lambda_min_f64_matches_dense(rng):
    """The host-f64 LOBPCG (the large-sigma verification path) must agree
    with the dense f64 eigensolve on a problem small enough to assemble."""
    meas, _ = make_measurements(rng, n=12, d=3, num_lc=6,
                                rot_noise=0.05, trans_noise=0.05)
    res = local_pgo.solve_local(meas, rank=5, grad_norm_tol=1e-9,
                                max_iters=500)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    S = dense_certificate(res.X, edges)
    lam_dense = float(np.linalg.eigvalsh(S)[0])
    lam64, vec, resid = certify.lambda_min_f64(
        np.asarray(res.X, np.float64), edges)
    assert resid < 1e-5
    assert abs(lam64 - lam_dense) < 1e-8 * max(1.0, abs(lam_dense))
    # The returned vector is a genuine eigenvector of S at lam64.
    v = vec.reshape(-1)
    resid = np.abs(S @ v - lam64 * v).max()
    assert resid < 1e-6


def test_certificate_weight_scale_tolerance_and_decidability(rng):
    """Round-5 semantics (VERDICT r4 item 3): tol rides the per-edge
    weight scale, not the spectral radius, and an f32 eigensolve whose
    dtype error exceeds that tolerance must either verify in f64 or
    refuse to certify — never claim a vacuous certificate."""
    meas, _ = make_measurements(rng, n=15, d=3, num_lc=6,
                                rot_noise=0.05, trans_noise=0.05)
    res = local_pgo.solve_local(meas, rank=5, grad_norm_tol=1e-9,
                                max_iters=500)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    cert = certify.certify_solution(res.X, edges)
    ws = certify.weight_scale(edges)
    assert cert.weight_scale == ws
    assert cert.tol == pytest.approx(1e-5 * ws)
    assert cert.decidable  # f64 solve: eps * sigma is tiny
    # f32 path on the same problem: force a tolerance far below what an
    # f32 eigensolve can resolve (tiny eta) WITHOUT the f64 fallback —
    # the certificate must refuse rather than claim.
    e32 = edge_set_from_measurements(meas, dtype=jnp.float32)
    X32 = jnp.asarray(res.X, jnp.float32)
    # eta chosen so tol sits BELOW the f32 eigensolve's error band
    # (10 ulps of sigma) but ABOVE what the f64 LOBPCG resolves.
    small_eta = 5e-8
    cert32 = certify.certify_solution(X32, e32, eta=small_eta,
                                      f64_verify="never")
    assert not cert32.decidable
    assert not cert32.certified
    # With the f64 verification enabled (default), the same call decides.
    cert32v = certify.certify_solution(X32, e32, eta=small_eta)
    assert cert32v.decidable
    assert cert32v.lambda_min_f64 is not None
    assert cert32v.certified  # the optimum genuinely certifies
    # An eta below what even f64 resolves must NEVER certify.  Under the
    # two-sided interval rule the outcome is a SOUND FAIL rather than a
    # refusal: the gauge "zeros" are only numerically zero (~1e-7), and
    # at tol ~1e-9 an eigenvalue below -tol genuinely exists
    # (lam_f64 + resid < -tol decides it).  Either refusal or a decided
    # FAIL honors the invariant; certification would not.
    tiny_eta = float(jnp.finfo(jnp.float32).eps) / max(1.0, ws) * 0.01
    cert32r = certify.certify_solution(X32, e32, eta=tiny_eta)
    assert not cert32r.certified


def test_lambda_min_f64_deflated_matches_dense():
    """The gauge-deflated LOBPCG path (auto-enabled at 100k scale, where
    the zero cluster stalls the unconstrained solve — round 5) must agree
    with the dense f64 eigensolve: full-space lambda_min is
    min(complement eigenvalue, gauge zeros), decided on a problem small
    enough to assemble but run with deflate=True explicitly."""
    from dpgo_tpu.utils.synthetic import make_stitched_winding

    meas, Xw = make_stitched_winding(3, 12)   # wound: decisively negative
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    X = jnp.asarray(Xw, jnp.float64)
    S = dense_certificate(X, edges)
    lam_dense = float(np.linalg.eigvalsh(S)[0])
    assert lam_dense < -1e-3                  # genuine negative curvature
    lam64, vec, resid = certify.lambda_min_f64(
        np.asarray(X, np.float64), edges, deflate=True)
    assert resid < 1e-5
    assert abs(lam64 - lam_dense) < 1e-6 * max(1.0, abs(lam_dense))


def test_sparse_certificate_matches_dense(rng):
    """The sparse CSR assembly of S (the shift-invert verification path)
    must equal the dense certificate entry-for-entry, and the
    shift-invert eigensolve must agree with the dense minimum eigenvalue
    on wound (negative) and optimal (certified) micro problems."""
    from dpgo_tpu.utils.synthetic import make_stitched_winding

    meas, _ = make_measurements(rng, n=12, d=3, num_lc=6,
                                rot_noise=0.05, trans_noise=0.05)
    res = local_pgo.solve_local(meas, rank=5, grad_norm_tol=1e-9,
                                max_iters=500)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    S_dense = np.asarray(dense_certificate(res.X, edges))
    S_sp = certify.sparse_certificate(np.asarray(res.X), edges)
    assert np.abs(S_sp.toarray() - S_dense).max() < 1e-9

    # Wound SE(2) micro: decisively negative lambda_min.
    measw, Xw = make_stitched_winding(3, 12)
    edgesw = edge_set_from_measurements(measw, dtype=jnp.float64)
    Sd = np.asarray(dense_certificate(jnp.asarray(Xw, jnp.float64), edgesw))
    lam_dense = float(np.linalg.eigvalsh(Sd)[0])
    lam, vec, resid = certify.lambda_min_f64_shift_invert(
        np.asarray(Xw, np.float64), edgesw, tol_cert=1e-4)
    assert resid < 1e-8
    assert abs(lam - lam_dense) < 1e-8 * max(1.0, abs(lam_dense))


# ---------------------------------------------------------------------------
# The host f64 recheck's inertia pass (``lambda_min_f64_shift_invert``)
# ---------------------------------------------------------------------------

def _optimum(rng):
    meas, _ = make_measurements(rng, n=12, d=3, num_lc=6,
                                rot_noise=0.05, trans_noise=0.05)
    res = local_pgo.solve_local(meas, rank=5, grad_norm_tol=1e-9,
                                max_iters=500)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    return np.asarray(res.X, np.float64), edges


def _saddle():
    from dpgo_tpu.utils.synthetic import make_stitched_winding

    meas, Xw = make_stitched_winding(3, 12)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    return np.asarray(Xw, np.float64), edges


def _spy_inertia(monkeypatch):
    """Record what every ``_inertia_factor`` call returned."""
    seen = []
    real = certify._inertia_factor

    def spy(S, tol_cert):
        out = real(S, tol_cert)
        seen.append(out)
        return out
    monkeypatch.setattr(certify, "_inertia_factor", spy)
    return seen


@pytest.mark.parametrize("case", ["optimum", "saddle", "shifted"])
def test_inertia_counts_eigenvalues_below_tol(case, rng, monkeypatch):
    """The pivots <= 0 of the symmetric factor of ``S + tol I`` count
    exactly the eigenvalues of S below -tol (Sylvester's law): none at a
    certified optimum, some at a wound saddle, and one just below the
    threshold in an S shifted to put it there."""
    tol = 1e-4
    X, edges = _saddle() if case == "saddle" else _optimum(rng)
    S = certify.sparse_certificate(X, edges)
    if case == "shifted":
        # Move the 8th eigenvalue (a gapped one) to tol * 1e-3 below -tol.
        lam = np.linalg.eigvalsh(S.toarray())
        t = 7 + int(np.argmax(np.diff(lam[7:20]) > 1e-2))
        shift = lam[t] + tol * (1.0 + 1e-3)
        S_shifted = S - shift * np.eye(S.shape[0])
        from scipy import sparse
        S = sparse.csr_matrix(S_shifted)
        monkeypatch.setattr(certify, "sparse_certificate",
                            lambda X64, e: S)
    seen = _spy_inertia(monkeypatch)
    certify.lambda_min_f64_shift_invert(X, edges, tol)
    (lu, outcome), = seen
    below = int(np.sum(np.linalg.eigvalsh(S.toarray()) < -tol))
    assert np.array_equal(lu.perm_r, lu.perm_c)
    assert int(np.sum(lu.U.diagonal() <= 0)) == below
    assert outcome == ("pass" if below == 0 else "fail")
    assert (below == 0) == (case == "optimum")
    if case == "shifted":
        assert below == t + 1


def test_inertia_pass_skips_krylov_passes(rng, tmp_path):
    """On a certified optimum the inertia decides: no SA or LOBPCG span
    is recorded, and the verdict and lambda_min agree with dense
    ``eigvalsh``."""
    from dpgo_tpu import obs

    tol = 1e-4
    X, edges = _optimum(rng)
    lam_dense = float(np.linalg.eigvalsh(
        certify.sparse_certificate(X, edges).toarray())[0])
    obs.end_run()
    with obs.run_scope(str(tmp_path / "run")) as run:
        certified, decidable, lam, _ = certify.f64_recheck(
            lambda t: certify.lambda_min_f64_shift_invert(X, edges, tol), tol)
        snap = run.registry.snapshot()
    spans = {s["labels"]["span"] for s in snap["span_seconds"]["series"]}
    assert "cert.f64.inertia" in spans
    assert not spans & {"cert.f64.sa", "cert.f64.lobpcg"}
    assert certified and decidable and lam_dense >= -tol
    assert abs(lam - lam_dense) < 1e-8 * max(1.0, abs(lam_dense))


def test_inertia_fail_falls_through_to_a_sound_direction():
    """A wound saddle has a pivot <= 0: the Krylov passes run and FAIL
    with a direction whose Rayleigh quotient is below -tol."""
    tol = 1e-4
    X, edges = _saddle()
    S = certify.sparse_certificate(X, edges)
    certified, decidable, lam, vec = certify.f64_recheck(
        lambda t: certify.lambda_min_f64_shift_invert(X, edges, tol), tol)
    assert not certified and decidable
    v = vec.reshape(-1) / np.linalg.norm(vec)
    assert float(v @ (S @ v)) < -tol


def test_expander_guard_never_factors(monkeypatch):
    """Past the expander guard (over 100,000 dims with > 5% long-range
    edges) the symmetric factorization is never attempted and the Krylov
    passes decide."""
    from dpgo_tpu.utils.synthetic import make_measurements_vectorized

    def boom(*_):
        raise AssertionError("factored a graph the guard rules out")
    monkeypatch.setattr(certify, "_inertia_factor", boom)
    meas, (Rs, ts) = make_measurements_vectorized(
        np.random.default_rng(3), n=25_001, d=3, num_lc=5_000)
    X = np.concatenate([Rs, ts[:, :, None]], axis=2)  # rank 3 = d
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    assert certify._expander_guard(edges, 25_001, 4)
    # The Krylov passes at 100,000 dims are not what is tested here.
    monkeypatch.setattr(certify, "_krylov_passes",
                        lambda *a: (0.0, None, 1.0))
    assert certify.lambda_min_f64_shift_invert(X, edges, 1e-3) \
        == (0.0, None, 1.0)


@pytest.mark.parametrize("outcome", ["fail", "pivoted"])
def test_undecided_inertia_refactors_stably(outcome, rng, monkeypatch):
    """Where the pivots do not prove a PASS, the Krylov passes' window
    does not solve with the symmetric factor (unbounded growth on an
    indefinite matrix) but through eigsh's own LU.  After a pivot <= 0 a
    window pair that would pass is refused, with no vector (its RQ is
    >= -tol, no descent direction); after off-diagonal pivoting it
    passes."""
    tol = 1e-4
    X, edges = _optimum(rng)
    monkeypatch.setattr(certify, "_inertia_factor",
                        lambda S, t: (None, outcome))
    windows = []
    real = certify._shift_invert_window

    def spy(S, k, tol_cert, maxiter, lu=None):
        windows.append(lu)
        return real(S, k, tol_cert, maxiter, lu)
    monkeypatch.setattr(certify, "_shift_invert_window", spy)
    lam, vec, resid = certify.lambda_min_f64_shift_invert(X, edges, tol)
    assert windows == [None]
    if outcome == "fail":
        assert vec is None
        assert lam - resid < -tol <= lam + resid  # refused
    else:
        assert vec is not None and lam - resid >= -tol  # passes
