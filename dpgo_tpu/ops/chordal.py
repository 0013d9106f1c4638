"""Chordal and odometry initialization.

Equivalent of reference ``chordalInitialization`` /
``recoverTranslations`` / ``odometryInitialization``
(``src/DPGO_utils.cpp:377-476``).  The reference solves two sparse
least-squares problems with SuiteSparse SPQR.  Two implementations here:

* ``chordal_initialization_host``: host numpy/SciPy in float64, the
  normal equations of each problem factored once by a symmetric sparse
  LDL^T (SuperLU).  The centralized init of a solve built from host
  ``Measurements`` (``rbcd.centralized_chordal_init``).
* ``chordal_initialization``: on the device, both solves as
  Jacobi-preconditioned conjugate gradients on the normal equations, with
  the graph operators applied edge-wise (gather / scatter-add) — the same
  technique as ``ops.quadratic``.  For edge sets that live on the device
  or are traced: padded serving buckets, per-agent local inits.

Stage 1 (rotations): minimize  sum_e kappa_e ||R_j - R_i Rtilde_e||_F^2
over unconstrained d x d blocks with R_0 = I pinned (the reference drops the
first block column of B3, ``DPGO_utils.cpp:390``), then project each block
to SO(d).

Stage 2 (translations): with rotations fixed, minimize
sum_e tau_e ||t_j - t_i - R_i ttilde_e||^2 with t_0 = 0 pinned.

Both systems are graph-Laplacian-like: SPD on the pinned subspace when every
pose is connected to pose 0, diagonal blocks = (weighted) vertex degrees, so
Jacobi scaling is a natural preconditioner for the CG.  On a 10,000-pose
chain with closures the CG needs ~1,650 iterations per stage.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..types import EdgeSet, Measurements
from ..utils.lie import project_to_rotation, project_to_rotation_np


def _pin0(x: jax.Array) -> jax.Array:
    """Zero the slot-0 block (the pinned/anchored pose)."""
    return x.at[0].set(0.0)


def _cg(matvec, b, precond, maxiter: int, tol: float):
    """Standard preconditioned CG (jax.scipy's cg with explicit M)."""
    x, _ = jax.scipy.sparse.linalg.cg(matvec, b, M=precond, maxiter=maxiter, tol=tol)
    return x


@partial(jax.jit, static_argnames=("n", "maxiter", "tol"))
def chordal_rotations(edges: EdgeSet, n: int, maxiter: int = 2000,
                      tol: float = 1e-10) -> jax.Array:
    """Solve the chordal rotation relaxation; returns [n, d, d] in SO(d).

    Equivalent to the reference's B3 SPQR solve + per-block SO(d) projection
    (``DPGO_utils.cpp:388-410``).
    """
    d = edges.d
    dtype = edges.R.dtype
    wk = edges.mask * edges.weight * edges.kappa

    def residual_op(Rs):
        # A: [n, d, d] -> per-edge sqrt(kappa)-weighted residual (R fixed at
        # identity handled by caller via constant split).
        Ri = Rs[edges.i]
        Rj = Rs[edges.j]
        return Rj - jnp.einsum("eab,ebc->eac", Ri, edges.R)

    def residual_adjoint(res):
        # A^T: per-edge residuals -> per-vertex accumulation.
        out = jnp.zeros((n, d, d), dtype)
        contrib_j = wk[:, None, None] * res
        contrib_i = -jnp.einsum("eab,ecb->eac", wk[:, None, None] * res, edges.R)
        return out.at[edges.j].add(contrib_j).at[edges.i].add(contrib_i)

    def H(Rs):  # normal operator restricted to the pinned subspace
        return _pin0(residual_adjoint(residual_op(_pin0(Rs))))

    # Constant part: pose 0 fixed at identity.
    R_fixed = jnp.zeros((n, d, d), dtype).at[0].set(jnp.eye(d, dtype=dtype))
    b = _pin0(-residual_adjoint(residual_op(R_fixed)))

    # Jacobi preconditioner: weighted degree per vertex (diagonal blocks of
    # the rotation connection Laplacian are kappa-degree * I).
    deg = jnp.zeros((n,), dtype).at[edges.i].add(wk).at[edges.j].add(wk)
    deg = jnp.maximum(deg, 1e-12)

    def precond(Rs):
        return _pin0(Rs / deg[:, None, None])

    sol = _cg(H, b, precond, maxiter, tol)
    Rs = sol.at[0].set(jnp.eye(d, dtype=dtype))
    return project_to_rotation(Rs)


@partial(jax.jit, static_argnames=("n", "maxiter", "tol"))
def recover_translations(edges: EdgeSet, Rs: jax.Array, n: int,
                         maxiter: int = 2000, tol: float = 1e-10) -> jax.Array:
    """Least-squares translations given rotations; returns [n, d], t_0 = 0.

    Equivalent to the reference's B1/B2 SPQR solve
    (``recoverTranslations``, ``DPGO_utils.cpp:449-476``).
    """
    d = edges.d
    dtype = Rs.dtype
    wt = edges.mask * edges.weight * edges.tau

    def residual_op(ts):
        return ts[edges.j] - ts[edges.i]

    def residual_adjoint(res):
        out = jnp.zeros((n, d), dtype)
        wres = wt[:, None] * res
        return out.at[edges.j].add(wres).at[edges.i].add(-wres)

    def H(ts):
        return _pin0(residual_adjoint(residual_op(_pin0(ts))))

    # Constant: measured offsets R_i ttilde_e (and the pinned t_0 = 0).
    offs = jnp.einsum("eab,eb->ea", Rs[edges.i], edges.t)
    b = _pin0(residual_adjoint(offs))

    deg = jnp.zeros((n,), dtype).at[edges.i].add(wt).at[edges.j].add(wt)
    deg = jnp.maximum(deg, 1e-12)

    def precond(ts):
        return _pin0(ts / deg[:, None])

    return _cg(H, b, precond, maxiter, tol)


@partial(jax.jit, static_argnames=("n", "maxiter", "tol"))
def chordal_initialization(edges: EdgeSet, n: int, maxiter: int = 2000,
                           tol: float = 1e-10) -> jax.Array:
    """Full chordal init; returns T [n, d, d+1] = [R_i | t_i] per pose.

    Matches the output convention of reference ``chordalInitialization``
    (``DPGO_utils.cpp:377-424``), reshaped pose-major.
    """
    Rs = chordal_rotations(edges, n, maxiter, tol)
    ts = recover_translations(edges, Rs, n, maxiter, tol)
    return jnp.concatenate([Rs, ts[..., None]], axis=-1)


class SingularChordalSystem(ValueError):
    """The pinned normal equations are not positive definite: some pose is
    not tied to pose 0 by edges of positive weight."""


def _factor_pinned(rows, cols, vals, size: int, pin: int):
    """Symmetric SuperLU factor of the ``size x size`` normal matrix given
    as COO triplets (duplicates summed), with its first ``pin`` rows and
    columns (pose 0) dropped: minimum-degree order on ``A + A^T``, diagonal
    pivots, so the factor is ``P A P^T = L D L^T`` (``D = diag(U)``).

    Raises ``SingularChordalSystem`` where that is not the factor of a
    positive definite matrix: SuperLU finds the matrix singular, a pivot
    leaves the diagonal, or a pivot is at most ``sqrt(eps)`` of the largest
    (the round-off of a zero pivot, which a component not tied to pose 0
    leaves; a tied pose's pivot is an effective conductance to pose 0,
    orders above that on any graph the factor is tried on)."""
    from scipy import sparse
    from scipy.sparse.linalg import splu

    A = sparse.coo_matrix((vals, (rows, cols)), shape=(size, size)).tocsr()
    A = A[pin:, pin:].tocsc()
    try:
        lu = splu(A, permc_spec="MMD_AT_PLUS_A", diag_pivot_thresh=0.0,
                  options={"SymmetricMode": True})
    except RuntimeError as e:
        raise SingularChordalSystem(str(e)) from e
    piv = lu.U.diagonal()
    if not np.array_equal(lu.perm_r, lu.perm_c) \
            or piv.min() <= np.sqrt(np.finfo(np.float64).eps) * piv.max():
        raise SingularChordalSystem("pinned chordal normal equations are "
                                    "not positive definite")
    return lu


def chordal_initialization_host(meas: Measurements) -> np.ndarray:
    """Chordal init on the host in float64; returns T [n, d, d+1] with
    pose 0 = identity.

    Each stage's pinned normal equations are factored once
    (``_factor_pinned``) and solved for all ``d`` right-hand sides in one
    call, as the reference factors B3 and B1 once with SPQR.

    Rotations: the rows of the R_i decouple, so the unknowns are row
    vectors x_i and the normal matrix is the block connection Laplacian
    on them (``(n d) x (n d)``; edge e adds ``w I`` to blocks ``(i, i)``
    and ``(j, j)``, ``-w Rtilde_e`` to ``(i, j)`` and its transpose to
    ``(j, i)``); right-hand side ``a`` carries row ``a`` of ``R_0 = I``.
    Translations: the ``tau``-weighted Laplacian, with
    ``sum_e w_e R_i ttilde_e`` scattered to ``j`` (+) and ``i`` (-).  Edge
    weights are ``weight * kappa`` and ``weight * tau``, as the device path
    reads them.

    Raises ``SingularChordalSystem`` when a pose is not tied to pose 0.
    """
    n, d = int(meas.num_poses), int(meas.d)
    i = np.asarray(meas.p1, np.int64)
    j = np.asarray(meas.p2, np.int64)
    Rt = np.asarray(meas.R, np.float64)
    w = np.asarray(meas.weight, np.float64)
    wk = w * np.asarray(meas.kappa, np.float64)
    wt = w * np.asarray(meas.tau, np.float64)

    # The translation Laplacian first: it is singular exactly when a pose
    # is not tied to pose 0, where the rotation system of a component with
    # inconsistent cycles can still be definite.
    lu_t = _factor_pinned(np.concatenate([i, j, i, j]),
                          np.concatenate([i, j, j, i]),
                          np.concatenate([wt, wt, -wt, -wt]), n, 1)

    # Stage 1.  Entry [e, a, b] of block (i, j) sits at (i d + a, j d + b).
    ar = np.arange(d)
    diag_r = np.concatenate([i[:, None] * d + ar, j[:, None] * d + ar])
    diag_v = np.broadcast_to(np.concatenate([wk, wk])[:, None], diag_r.shape)
    off_r = np.broadcast_to((i[:, None] * d + ar)[:, :, None], Rt.shape)
    off_c = np.broadcast_to((j[:, None] * d + ar)[:, None, :], Rt.shape)
    off_v = -wk[:, None, None] * Rt
    rows = np.concatenate([diag_r.ravel(), off_r.ravel(), off_c.ravel()])
    cols = np.concatenate([diag_r.ravel(), off_c.ravel(), off_r.ravel()])
    vals = np.concatenate([diag_v.ravel(), off_v.ravel(), off_v.ravel()])
    lu_r = _factor_pinned(rows, cols, vals, n * d, d)
    # Right-hand side a = -H[free, 0-block] e_a: the pose-0 columns of H.
    keep = (cols < d) & (rows >= d)
    B = np.zeros(((n - 1) * d, d))
    np.add.at(B, (rows[keep] - d, cols[keep]), -vals[keep])
    X = lu_r.solve(B)  # X[(k - 1) d + b, a] = R_k[a, b]
    R = project_to_rotation_np(np.concatenate(
        [np.eye(d)[None], X.reshape(n - 1, d, d).transpose(0, 2, 1)]))

    # Stage 2.
    off = wt[:, None] * np.einsum("eab,eb->ea", R[i],
                                  np.asarray(meas.t, np.float64))
    b = np.zeros((n, d))
    np.add.at(b, j, off)
    np.add.at(b, i, -off)
    t = np.concatenate([np.zeros((1, d)), lu_t.solve(b[1:])])
    return np.concatenate([R, t[..., None]], axis=-1)


@partial(jax.jit, static_argnames=("n",))
def odometry_from_edges(edges: EdgeSet, n: int) -> jax.Array:
    """Select the odometry chain (k -> k+1) out of an arbitrary edge set and
    chain-propagate it; returns T [n, d, d+1].

    Robust to duplicates: among candidate edges with ``j == i + 1``, an edge
    flagged as odometry (``is_lc == 0``) wins over a consecutive loop
    closure, ties broken by edge order (scatter-min priority selection).  A
    pose with no incoming odometry edge gets an identity step — the chain
    continues rather than silently mis-pairing measurements.
    """
    E = edges.i.shape[0]
    d = edges.d
    dtype = edges.R.dtype
    cand = (edges.j == edges.i + 1) & (edges.mask > 0) & (edges.i < n - 1)
    big = jnp.asarray(2 * E + 1, jnp.int32)
    # priority = is_lc * E + edge_index: odometry-flagged first, then stable.
    prio = (edges.is_lc > 0).astype(jnp.int32) * E + jnp.arange(E, dtype=jnp.int32)
    prio = jnp.where(cand, prio, big)
    i_safe = jnp.where(cand, edges.i, 0)  # keep scatter indices in bounds
    best = jnp.full((n - 1,), big, jnp.int32).at[i_safe].min(prio)
    valid = best < big
    idx = jnp.where(valid, best % E, 0)
    eye = jnp.eye(d, dtype=dtype)
    R_odo = jnp.where(valid[:, None, None], edges.R[idx], eye)
    t_odo = jnp.where(valid[:, None], edges.t[idx], jnp.zeros(d, dtype))
    return odometry_initialization(R_odo, t_odo)


def odometry_initialization(R_odo: jax.Array, t_odo: jax.Array) -> jax.Array:
    """Chain-propagate odometry; returns T [n, d, d+1], pose 0 = identity.

    ``R_odo: [n-1, d, d]``, ``t_odo: [n-1, d]`` are measurements k -> k+1.
    Reference ``odometryInitialization`` (``DPGO_utils.cpp:426-447``), as an
    associative scan over SE(d) composition (log-depth on device instead of
    a sequential chain).
    """
    d = R_odo.shape[-1]
    dtype = R_odo.dtype
    eye = jnp.broadcast_to(jnp.eye(d, dtype=dtype), (1, d, d))
    zero = jnp.zeros((1, d), dtype)
    Rs = jnp.concatenate([eye, R_odo], axis=0)
    ts = jnp.concatenate([zero, t_odo], axis=0)

    def compose(a, b):
        # (Ra, ta) then relative (Rb, tb): R = Ra Rb, t = ta + Ra tb
        Ra, ta = a
        Rb, tb = b
        return Ra @ Rb, ta + jnp.einsum("...ab,...b->...a", Ra, tb)

    R_acc, t_acc = jax.lax.associative_scan(compose, (Rs, ts))
    return jnp.concatenate([R_acc, t_acc[..., None]], axis=-1)
