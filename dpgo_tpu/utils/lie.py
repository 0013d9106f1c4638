"""Rotation / Stiefel / SE(d) primitives, batched for TPU.

TPU-native equivalents of the dense-linear-algebra helpers in reference
``src/DPGO_utils.cpp:478-531`` (``projectToRotationGroup``,
``projectToStiefelManifold``, ``fixedStiefelVariable``,
``angular2ChordalSO3``) plus quaternion conversions used by the g2o reader
and CSV logger.  Everything accepts arbitrary leading batch dimensions and is
differentiable / jittable; per-pose loops in the reference (OpenMP in
``LiftedSEManifold.cpp:40-44``) become batched SVDs here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def quat_to_rotation(q: np.ndarray) -> np.ndarray:
    """Quaternion(s) [..., 4] in (x, y, z, w) order -> rotation matrices [..., 3, 3].

    Host-side (numpy) helper for the g2o reader; matches Eigen's
    ``Quaterniond(w, x, y, z).toRotationMatrix()`` used at reference
    ``DPGO_utils.cpp:182``.
    """
    q = np.asarray(q, dtype=np.float64)
    q = q / np.linalg.norm(q, axis=-1, keepdims=True)
    x, y, z, w = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    xx, yy, zz = x * x, y * y, z * z
    xy, xz, yz = x * y, x * z, y * z
    wx, wy, wz = w * x, w * y, w * z
    R = np.stack(
        [
            1 - 2 * (yy + zz), 2 * (xy - wz), 2 * (xz + wy),
            2 * (xy + wz), 1 - 2 * (xx + zz), 2 * (yz - wx),
            2 * (xz - wy), 2 * (yz + wx), 1 - 2 * (xx + yy),
        ],
        axis=-1,
    )
    return R.reshape(q.shape[:-1] + (3, 3))


def rotation_to_quat(R: np.ndarray) -> np.ndarray:
    """Rotation matrices [..., 3, 3] -> quaternions [..., 4] in (x, y, z, w).

    Host-side helper for the CSV trajectory logger (reference
    ``PGOLogger.cpp:18-45`` stores qx,qy,qz,qw).  Uses the numerically-stable
    Shepperd branch selection, vectorized over the batch.
    """
    R = np.asarray(R, dtype=np.float64)
    batch = R.shape[:-2]
    Rf = R.reshape((-1, 3, 3))
    m00, m01, m02 = Rf[:, 0, 0], Rf[:, 0, 1], Rf[:, 0, 2]
    m10, m11, m12 = Rf[:, 1, 0], Rf[:, 1, 1], Rf[:, 1, 2]
    m20, m21, m22 = Rf[:, 2, 0], Rf[:, 2, 1], Rf[:, 2, 2]
    tr = m00 + m11 + m22
    q = np.empty((Rf.shape[0], 4), dtype=np.float64)

    c0 = tr > 0
    s = np.sqrt(np.maximum(tr + 1.0, 0.0)) * 2  # s = 4w
    q[c0, 3] = 0.25 * s[c0]
    q[c0, 0] = (m21 - m12)[c0] / s[c0]
    q[c0, 1] = (m02 - m20)[c0] / s[c0]
    q[c0, 2] = (m10 - m01)[c0] / s[c0]

    c1 = (~c0) & (m00 >= m11) & (m00 >= m22)
    s = np.sqrt(np.maximum(1.0 + m00 - m11 - m22, 0.0)) * 2  # s = 4x
    q[c1, 3] = (m21 - m12)[c1] / s[c1]
    q[c1, 0] = 0.25 * s[c1]
    q[c1, 1] = (m01 + m10)[c1] / s[c1]
    q[c1, 2] = (m02 + m20)[c1] / s[c1]

    c2 = (~c0) & (~c1) & (m11 >= m22)
    s = np.sqrt(np.maximum(1.0 + m11 - m00 - m22, 0.0)) * 2  # s = 4y
    q[c2, 3] = (m02 - m20)[c2] / s[c2]
    q[c2, 0] = (m01 + m10)[c2] / s[c2]
    q[c2, 1] = 0.25 * s[c2]
    q[c2, 2] = (m12 + m21)[c2] / s[c2]

    c3 = (~c0) & (~c1) & (~c2)
    s = np.sqrt(np.maximum(1.0 + m22 - m00 - m11, 0.0)) * 2  # s = 4z
    q[c3, 3] = (m10 - m01)[c3] / s[c3]
    q[c3, 0] = (m02 + m20)[c3] / s[c3]
    q[c3, 1] = (m12 + m21)[c3] / s[c3]
    q[c3, 2] = 0.25 * s[c3]

    return q.reshape(batch + (4,))


def rotation2d(theta) -> np.ndarray:
    """Angle(s) [...] -> SO(2) matrices [..., 2, 2] (reference ``DPGO_utils.cpp:138``)."""
    theta = np.asarray(theta, dtype=np.float64)
    c, s = np.cos(theta), np.sin(theta)
    R = np.stack([c, -s, s, c], axis=-1)
    return R.reshape(theta.shape + (2, 2))


def project_to_rotation_np(M: np.ndarray) -> np.ndarray:
    """Project [..., d, d] matrices onto SO(d) in numpy (SVD with
    determinant fix).

    Pure host work on purpose: ``project_to_rotation`` would dispatch one
    tiny kernel per call to the default backend — a host dispatch each,
    which turns a 100k-pose synthesis into minutes."""
    U, _, Vh = np.linalg.svd(M)
    det = np.linalg.det(U @ Vh)
    U[det < 0, :, -1] *= -1.0
    return U @ Vh


def _project_to_rotation_batch(M: jax.Array) -> jax.Array:
    U, _, Vh = jnp.linalg.svd(M, full_matrices=False)
    det = jnp.linalg.det(U @ Vh)
    # Flip the last column of U where det(U Vh) < 0.
    d = M.shape[-1]
    flip = jnp.where(det < 0, -1.0, 1.0).astype(M.dtype)
    signs = jnp.concatenate(
        [jnp.ones(M.shape[:-2] + (d - 1,), M.dtype), flip[..., None]], axis=-1
    )
    return (U * signs[..., None, :]) @ Vh


#: Batched-SVD chunk bound: XLA:TPU stack-allocates the whole SVD batch in
#: VMEM (observed: [100000, 3, 3] wants 24 MB scoped vmem against a 16 MB
#: limit), so huge init-time projections run as a lax.map over chunks.
_SVD_CHUNK = 16384


def project_to_rotation(M: jax.Array) -> jax.Array:
    """Project [..., d, d] matrices onto SO(d) (det +1).

    Batched SVD with determinant fix, the equivalent of reference
    ``projectToRotationGroup`` (``DPGO_utils.cpp:478-492``).  Batches past
    ``_SVD_CHUNK`` are chunked (cold init path at 100k-pose scale).
    """
    d = M.shape[-1]
    flat = M.reshape((-1, d, d))
    N = flat.shape[0]
    if N <= _SVD_CHUNK:
        return _project_to_rotation_batch(M)
    pad = (-N) % _SVD_CHUNK
    flat = jnp.concatenate(
        [flat, jnp.zeros((pad, d, d), M.dtype)]) if pad else flat
    out = jax.lax.map(_project_to_rotation_batch,
                      flat.reshape((-1, _SVD_CHUNK, d, d)))
    return out.reshape((-1, d, d))[:N].reshape(M.shape)


def project_to_stiefel(M: jax.Array) -> jax.Array:
    """Project [..., r, d] matrices (r >= d) onto the Stiefel manifold St(r, d).

    The polar factor ``M (M^T M)^{-1/2}``, the equivalent of reference
    ``projectToStiefelManifold`` (``DPGO_utils.cpp:494-500``, thin-SVD
    ``U V^T`` there).  Computed by the closed-form Newton-Schulz kernel:
    XLA's batched SVD on TPU is a generic one-sided-Jacobi loop that costs
    milliseconds on the [A*n, r, d] batches of the RBCD hot path, while the
    fixed-size iteration is a handful of d x d matmuls.  Robust to
    condition(M) ~1e5-1e6 (see ``smallmat.polar_orthonormalize``); for
    potentially rank-deficient inputs use ``project_to_stiefel_svd``.
    """
    from ..ops.smallmat import polar_orthonormalize

    return polar_orthonormalize(M)


def project_to_stiefel_svd(M: jax.Array) -> jax.Array:
    """SVD form of ``project_to_stiefel`` (robust at any conditioning;
    slow on TPU — cold paths only)."""
    U, _, Vh = jnp.linalg.svd(M, full_matrices=False)
    return U @ Vh


def random_stiefel(key: jax.Array, r: int, d: int, batch=(), dtype=jnp.float32) -> jax.Array:
    """Uniform random point(s) on St(r, d) via QR of a Gaussian."""
    G = jax.random.normal(key, batch + (r, d), dtype=dtype)
    Q, R = jnp.linalg.qr(G)
    # Fix signs so the factorization is unique (diag(R) > 0).
    s = jnp.sign(jnp.diagonal(R, axis1=-2, axis2=-1))
    s = jnp.where(s == 0, 1.0, s).astype(dtype)
    return Q * s[..., None, :]


def fixed_stiefel(r: int, d: int, dtype=jnp.float32) -> jax.Array:
    """Deterministic element of St(r, d), identical across all agents/hosts.

    The shared "lifting matrix" YLift: reference ``fixedStiefelVariable``
    (``DPGO_utils.cpp:502-507``) seeds ``srand(1)``; here a fixed PRNG key
    plays that role.  Only cross-agent determinism matters, not the specific
    value.
    """
    return random_stiefel(jax.random.PRNGKey(1), r, d, dtype=jnp.float64).astype(dtype)


def lifting_matrix(rank: int, d: int, dtype=jnp.float32) -> jax.Array:
    """The shared lifting matrix YLift in St(rank, d).

    Identity for rank == d (no relaxation); the deterministic fixed Stiefel
    element otherwise (robot 0 generates and broadcasts it in the reference,
    ``PGOAgent.cpp:46``; determinism makes every agent agree without a
    broadcast).  Single source of truth for the rank-lifting policy.
    """
    if rank < d:
        raise ValueError(f"relaxation rank {rank} must be >= d = {d}")
    if rank == d:
        return jnp.eye(d, dtype=dtype)
    return fixed_stiefel(rank, d, dtype)


def check_rotation_matrix(R, tol: float = 1e-8) -> bool:
    """Validate SO(d) membership: det +1 and orthonormal within ``tol``
    (reference ``checkRotationMatrix``, ``DPGO_utils.cpp:526-531`` — an
    assert there; a boolean here so callers choose raise vs mask).
    Batched: returns an [...] bool array for [..., d, d] input."""
    R = np.asarray(R)
    d = R.shape[-1]
    det_ok = np.abs(np.linalg.det(R) - 1.0) < tol
    eye = np.eye(d)
    orth = np.linalg.norm(
        np.swapaxes(R, -1, -2) @ R - eye, axis=(-2, -1)) < tol
    out = det_ok & orth
    return bool(out) if out.ndim == 0 else out


def angular_to_chordal_so3(rad: float) -> float:
    """Angular distance (radians) -> chordal (Frobenius) distance on SO(3).

    Reference ``angular2ChordalSO3`` (``DPGO_utils.cpp:522-524``).

    Returns a Python float: a ``np.float64`` scalar is strongly typed under
    jax_enable_x64 and would promote float32 GNC arithmetic to float64.
    """
    return float(2.0 * np.sqrt(2.0) * np.sin(rad / 2.0))


def chi2inv(quantile: float, dof: int) -> float:
    """Chi-squared quantile (reference ``DPGO_utils.cpp:517-520``, Boost.math).

    Config-time host scalar; uses scipy.
    """
    from scipy.stats import chi2

    return float(chi2.ppf(quantile, dof))


def error_threshold_at_quantile(quantile: float, dof: int = 6) -> float:
    """sqrt(chi2inv(q, dof)) — GNC barc from a probabilistic quantile
    (reference ``RobustCost::computeErrorThresholdAtQuantile``)."""
    return float(np.sqrt(chi2inv(quantile, dof)))


def se_matrix(R: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Homogeneous SE(d) matrices [..., d+1, d+1] from R [..., d, d], t [..., d]."""
    R = np.asarray(R)
    t = np.asarray(t)
    d = R.shape[-1]
    T = np.zeros(R.shape[:-2] + (d + 1, d + 1), dtype=R.dtype)
    T[..., :d, :d] = R
    T[..., :d, d] = t
    T[..., d, d] = 1.0
    return T
