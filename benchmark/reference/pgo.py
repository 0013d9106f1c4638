"""Plain f64 reference for pose-graph optimization (numpy / scipy only).

Independent of the program: it reads the benchmark's own ``Problem``
(``benchmark/gen``) and the answer under test, and imports nothing of
``dpgo_tpu``.  The problem is the rank-r relaxation of SE(d)
synchronization (SE-Sync, Rosen et al. IJRR 2019; DPGO, Tian et al.
T-RO 2021):

    f(X) = 1/2 sum_e w_e (kappa_e ||Y_j - Y_i R_e||^2
                          + tau_e ||p_j - p_i - Y_i t_e||^2)

over X_i = [Y_i p_i] with Y_i in St(d, r).  A trajectory [n, d, d+1] is
the case r = d.  Writing X as the r x n(d+1) matrix, f = 1/2 tr(X Q X^T),
the Euclidean gradient is X Q, the Riemannian gradient on each pose is
[G_Y - Y sym(Y^T G_Y), G_p], and the dual certificate is
S = Q - blockdiag(sym(Y_i^T G_Yi), 0).  X is a global optimum of the
relaxation when it is stationary and S is positive semidefinite.
"""

from __future__ import annotations

import numpy as np


def _edges(prob, w):
    w = np.ones(len(prob)) if w is None else np.asarray(w, np.float64)
    return (np.asarray(prob.i), np.asarray(prob.j),
            np.asarray(prob.R, np.float64), np.asarray(prob.t, np.float64),
            w * np.asarray(prob.kappa, np.float64),
            w * np.asarray(prob.tau, np.float64))


def _residuals(prob, X, w):
    i, j, R, t, wk, wt = _edges(prob, w)
    X = np.asarray(X, np.float64)
    d = R.shape[-1]
    Y, p = X[..., :d], X[..., d]
    rR = Y[j] - Y[i] @ R                                   # [m, r, d]
    rt = p[j] - p[i] - np.einsum("erd,ed->er", Y[i], t)    # [m, r]
    return i, j, R, t, wk, wt, Y, rR, rt


def cost(prob, X, w=None) -> float:
    """f(X) in f64, for a lifted iterate [n, r, d+1] or a trajectory."""
    *_, wk, wt, _, rR, rt = _residuals(prob, X, w)
    return float(0.5 * np.sum(wk * np.sum(rR ** 2, axis=(1, 2))
                              + wt * np.sum(rt ** 2, axis=1)))


def euclidean_gradient(prob, X, w=None) -> np.ndarray:
    """d f / d X, [n, r, d+1]."""
    i, j, R, t, wk, wt, Y, rR, rt = _residuals(prob, X, w)
    n, r, dh = np.asarray(X).shape
    G = np.zeros((n, r, dh))
    gj = np.concatenate([wk[:, None, None] * rR, (wt[:, None] * rt)[..., None]],
                        axis=2)
    gi = np.concatenate(
        [-wk[:, None, None] * rR @ np.swapaxes(R, 1, 2)
         - wt[:, None, None] * rt[:, :, None] * t[:, None, :],
         (-wt[:, None] * rt)[..., None]], axis=2)
    np.add.at(G, j, gj)
    np.add.at(G, i, gi)
    return G


def _sym(A):
    return 0.5 * (A + np.swapaxes(A, -1, -2))


def riemannian_gradient(prob, X, w=None) -> np.ndarray:
    X = np.asarray(X, np.float64)
    d = X.shape[-1] - 1
    G = euclidean_gradient(prob, X, w)
    Y, GY = X[..., :d], G[..., :d]
    out = G.copy()
    out[..., :d] = GY - Y @ _sym(np.swapaxes(Y, 1, 2) @ GY)
    return out


def grad_norm(prob, X, w=None) -> float:
    """Norm of the Riemannian gradient of f at X (the solver's gate)."""
    return float(np.linalg.norm(riemannian_gradient(prob, X, w)))


def weight_scale(prob, w=None) -> float:
    """The certificate's yardstick: the median weighted concentration
    over edges of nonzero weight (rotation and translation), at least 1."""
    _, _, _, _, wk, wt = _edges(prob, w)
    on = (wk != 0) | (wt != 0)
    if not on.any():
        return 1.0
    return float(max(np.median(wk[on]), np.median(wt[on]), 1.0))


def certificate_matrix(prob, X, w=None):
    """S = Q - Lambda(X) as a scipy CSR matrix over n(d+1) coordinates."""
    from scipy import sparse

    i, j, R, t, wk, wt = _edges(prob, w)
    X = np.asarray(X, np.float64)
    n, r, dh = X.shape
    d = dh - 1
    m = i.shape[0]
    eye = np.eye(d)
    Hjj = np.zeros((m, dh, dh))
    Hjj[:, :d, :d] = wk[:, None, None] * eye
    Hjj[:, d, d] = wt
    Hii = np.zeros((m, dh, dh))
    Hii[:, :d, :d] = wk[:, None, None] * eye \
        + wt[:, None, None] * t[:, :, None] * t[:, None, :]
    Hii[:, :d, d] = Hii[:, d, :d] = wt[:, None] * t
    Hii[:, d, d] = wt
    Hij = np.zeros((m, dh, dh))
    Hij[:, :d, :d] = -wk[:, None, None] * R
    Hij[:, :d, d] = -wt[:, None] * t
    Hij[:, d, d] = -wt
    G = euclidean_gradient(prob, X, w)
    Lam = np.zeros((n, dh, dh))
    Lam[:, :d, :d] = _sym(np.swapaxes(X[..., :d], 1, 2) @ G[..., :d])

    a = np.arange(dh)
    def blocks(rows, cols, vals):
        rr = (rows[:, None, None] * dh + a[None, :, None]).repeat(dh, 2)
        cc = (cols[:, None, None] * dh + a[None, None, :]).repeat(dh, 1)
        return rr.ravel(), cc.ravel(), vals.ravel()

    parts = [blocks(i, i, Hii), blocks(j, j, Hjj), blocks(i, j, Hij),
             blocks(j, i, np.swapaxes(Hij, 1, 2)),
             blocks(np.arange(n), np.arange(n), -Lam)]
    rows = np.concatenate([p[0] for p in parts])
    cols = np.concatenate([p[1] for p in parts])
    vals = np.concatenate([p[2] for p in parts])
    N = n * dh
    return sparse.csr_matrix((vals, (rows, cols)), shape=(N, N))


def lambda_min(prob, X, tol: float, w=None) -> tuple[float, float]:
    """(lambda_min(S), residual of its eigenpair) near the threshold -tol.

    Shift-invert Lanczos at -tol returns the eigenvalues nearest the
    threshold, those that decide certification.  An eigenvalue far below
    it could hide behind a cluster near zero, so a coarse pass shifted to
    minus the weight scale screens for one: the Rayleigh quotient of any
    vector bounds lambda_min from above."""
    from scipy.sparse.linalg import ArpackNoConvergence, eigsh

    S = certificate_matrix(prob, X, w)
    vals, vecs = eigsh(S, k=6, sigma=-tol, which="LM", tol=1e-10,
                       maxiter=5000)
    k = int(np.argmin(vals))
    lam, v = float(vals[k]), vecs[:, k] / np.linalg.norm(vecs[:, k])
    resid = float(np.linalg.norm(S @ v - lam * v))
    try:
        far = eigsh(S, k=1, sigma=-weight_scale(prob, w), which="LM",
                    tol=1e-4, maxiter=300)[1]
    except ArpackNoConvergence as e:
        far = e.eigenvectors
    for u in np.asarray(far).T:
        u = u / np.linalg.norm(u)
        rq = float(u @ (S @ u))
        if rq < lam - resid:
            lam, resid = rq, 0.0
    return lam, resid
