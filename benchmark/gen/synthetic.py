"""The benchmark's own copy of the pose-graph generators.

Copied from ``dpgo_tpu/utils/synthetic.py`` and ``dpgo_tpu/utils/lie.py``
(PR 21) so that the yardstick does not move when the program's utilities
change.  ``benchmark/tests/test_gen.py`` shows that, today, the copies
draw the same measurements as the originals for a seed.

A problem here is a plain ``Problem`` of numpy arrays; nothing of the
program is imported.  The harness converts it to the program's input
type (``drivers/common.py``), and the reference reads it as it is.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Problem:
    """Relative SE(d) measurements i -> j, globally indexed."""

    d: int
    n: int
    i: np.ndarray      # [m] int64
    j: np.ndarray      # [m] int64
    R: np.ndarray      # [m, d, d]
    t: np.ndarray      # [m, d]
    kappa: np.ndarray  # [m]
    tau: np.ndarray    # [m]

    def __len__(self) -> int:
        return int(self.i.shape[0])


# -- rotations (copies of utils/synthetic.py and utils/lie.py) ---------------

def _project_rotations_np(M: np.ndarray) -> np.ndarray:
    U, _, Vh = np.linalg.svd(M)
    det = np.linalg.det(U @ Vh)
    U[det < 0, :, -1] *= -1.0
    return U @ Vh


def random_rotation(rng, d=3):
    return _project_rotations_np(rng.standard_normal((d, d))[None])[0]


def random_trajectory(rng, n, d=3, step=1.0):
    Rs = _project_rotations_np(rng.standard_normal((n, d, d)))
    ts = np.cumsum(step * rng.standard_normal((n, d)), axis=0)
    R0inv = Rs[0].T
    ts = (ts - ts[0]) @ R0inv.T
    Rs = np.einsum("ab,nbc->nac", R0inv, Rs)
    return Rs, ts


def quat_to_rotation(q: np.ndarray) -> np.ndarray:
    """(x, y, z, w) quaternions [..., 4] -> rotations [..., 3, 3]."""
    q = np.asarray(q, dtype=np.float64)
    q = q / np.linalg.norm(q, axis=-1, keepdims=True)
    x, y, z, w = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    xx, yy, zz = x * x, y * y, z * z
    xy, xz, yz = x * y, x * z, y * z
    wx, wy, wz = w * x, w * y, w * z
    R = np.stack([1 - 2 * (yy + zz), 2 * (xy - wz), 2 * (xz + wy),
                  2 * (xy + wz), 1 - 2 * (xx + zz), 2 * (yz - wx),
                  2 * (xz - wy), 2 * (yz + wx), 1 - 2 * (xx + yy)], axis=-1)
    return R.reshape(q.shape[:-1] + (3, 3))


def rotation2d(theta) -> np.ndarray:
    theta = np.asarray(theta, dtype=np.float64)
    c, s = np.cos(theta), np.sin(theta)
    return np.stack([c, -s, s, c], axis=-1).reshape(theta.shape + (2, 2))


def relative_measurement(Rs, ts, i, j, rng=None, rot_noise=0.0,
                         trans_noise=0.0, d=3):
    R = Rs[i].T @ Rs[j]
    t = Rs[i].T @ (ts[j] - ts[i])
    if rng is not None and rot_noise > 0:
        axis = rng.standard_normal(3 if d == 3 else 1)
        if d == 3:
            axis /= np.linalg.norm(axis)
            ang = rng.normal(0, rot_noise)
            q = np.concatenate([np.sin(ang / 2) * axis, [np.cos(ang / 2)]])
            R = quat_to_rotation(q) @ R
        else:
            R = rotation2d(rng.normal(0, rot_noise)) @ R
    if rng is not None and trans_noise > 0:
        t = t + rng.normal(0, trans_noise, d)
    return R, t


def nearby_pairs(rng, ts, num: int, radius: float) -> np.ndarray:
    from scipy.spatial import cKDTree

    pairs = np.sort(cKDTree(ts).query_pairs(r=radius, output_type="ndarray"),
                    axis=1)
    pairs = pairs[pairs[:, 1] > pairs[:, 0] + 1]
    if pairs.shape[0] < num:
        raise ValueError(f"only {pairs.shape[0]} pose pairs lie within "
                         f"{radius}; {num} loop closures requested")
    pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
    return pairs[np.sort(rng.choice(pairs.shape[0], num, replace=False))]


def _problem(d, n, edges, Rm, tm, kappa, tau) -> Problem:
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    m = e.shape[0]
    return Problem(d=d, n=n, i=e[:, 0].copy(), j=e[:, 1].copy(),
                   R=np.asarray(Rm, np.float64).reshape(m, d, d),
                   t=np.asarray(tm, np.float64).reshape(m, d),
                   kappa=np.full(m, float(kappa)), tau=np.full(m, float(tau)))


def make_measurements(rng, n, d=3, num_lc=5, rot_noise=0.0, trans_noise=0.0,
                      kappa=100.0, tau=10.0, outlier_lc=0):
    """Odometry chain + random loop closures (+ gross outliers last)."""
    Rs, ts = random_trajectory(rng, n, d)
    edges = [(i, i + 1) for i in range(n - 1)]
    seen = set(edges)
    while len(edges) < (n - 1) + num_lc:
        i, j = sorted(rng.choice(n, 2, replace=False))
        if j > i + 1 and (i, j) not in seen:
            edges.append((int(i), int(j)))
            seen.add((int(i), int(j)))
    Rm, tm = [], []
    for (i, j) in edges:
        R, t = relative_measurement(Rs, ts, i, j, rng, rot_noise,
                                    trans_noise, d)
        Rm.append(R)
        tm.append(t)
    while outlier_lc > 0:
        i, j = sorted(rng.choice(n, 2, replace=False))
        if j <= i + 1:
            continue
        edges.append((int(i), int(j)))
        Rm.append(random_rotation(rng, d))
        tm.append(5.0 * rng.standard_normal(d))
        outlier_lc -= 1
    return _problem(d, n, edges, Rm, tm, kappa, tau), (Rs, ts)


def _quats_to_rotations_np(q: np.ndarray) -> np.ndarray:
    x, y, z, w = q[:, 0], q[:, 1], q[:, 2], q[:, 3]
    R = np.empty((q.shape[0], 3, 3))
    R[:, 0, 0] = 1 - 2 * (y * y + z * z)
    R[:, 0, 1] = 2 * (x * y - z * w)
    R[:, 0, 2] = 2 * (x * z + y * w)
    R[:, 1, 0] = 2 * (x * y + z * w)
    R[:, 1, 1] = 1 - 2 * (x * x + z * z)
    R[:, 1, 2] = 2 * (y * z - x * w)
    R[:, 2, 0] = 2 * (x * z - y * w)
    R[:, 2, 1] = 2 * (y * z + x * w)
    R[:, 2, 2] = 1 - 2 * (x * x + y * y)
    return R


def _random_rotations_np(rng, n: int, d: int) -> np.ndarray:
    if d == 3:
        q = rng.standard_normal((n, 4))
        q /= np.maximum(np.linalg.norm(q, axis=1, keepdims=True), 1e-12)
        return _quats_to_rotations_np(q)
    th = rng.uniform(0.0, 2.0 * np.pi, n)
    c, s = np.cos(th), np.sin(th)
    return np.stack([np.stack([c, -s], -1), np.stack([s, c], -1)], axis=1)


def rotation_noise(rng, n: int, d: int, sigma: float) -> np.ndarray:
    """n small rotations, angle ~ N(0, sigma) about a uniform axis."""
    ang = rng.normal(0.0, sigma, n)
    if d == 2:
        c, s = np.cos(ang), np.sin(ang)
        return np.stack([np.stack([c, -s], -1), np.stack([s, c], -1)],
                        axis=1)
    axis = rng.standard_normal((n, 3))
    axis /= np.maximum(np.linalg.norm(axis, axis=1, keepdims=True), 1e-12)
    q = np.concatenate([np.sin(ang / 2)[:, None] * axis,
                        np.cos(ang / 2)[:, None]], axis=1)
    return _quats_to_rotations_np(q)


def make_measurements_vectorized(rng, n, d=3, num_lc=5, rot_noise=0.0,
                                 trans_noise=0.0, kappa=100.0, tau=10.0,
                                 lc_radius=None):
    """Odometry chain + loop closures from batched numpy; with
    ``lc_radius`` the closures join poses at most that far apart."""
    Rs = _random_rotations_np(rng, n, d)
    ts = np.cumsum(rng.standard_normal((n, d)), axis=0)
    R0inv = Rs[0].T
    ts = (ts - ts[0]) @ R0inv.T
    Rs = np.einsum("ab,nbc->nac", R0inv, Rs)

    i_odo = np.arange(n - 1)
    j_odo = i_odo + 1
    if num_lc > 0 and lc_radius is not None:
        i_lc, j_lc = nearby_pairs(rng, ts, num_lc, lc_radius).T
    elif num_lc > 0:
        cand = rng.integers(0, n, (4 * num_lc + 64, 2))
        lo, hi = cand.min(1), cand.max(1)
        keep = hi > lo + 1
        pairs = np.unique(np.stack([lo[keep], hi[keep]], -1), axis=0)
        take = rng.permutation(pairs.shape[0])[:num_lc]
        i_lc, j_lc = pairs[take, 0], pairs[take, 1]
    else:
        i_lc = j_lc = np.zeros(0, np.int64)
    ei = np.concatenate([i_odo, i_lc])
    ej = np.concatenate([j_odo, j_lc])
    m = ei.shape[0]
    Ri = Rs[ei]
    Rm = np.einsum("eba,ebc->eac", Ri, Rs[ej])
    tm = np.einsum("eba,eb->ea", Ri, ts[ej] - ts[ei])
    if rot_noise > 0:
        Rm = np.einsum("eab,ebc->eac", rotation_noise(rng, m, d, rot_noise),
                       Rm)
    if trans_noise > 0:
        tm = tm + rng.normal(0.0, trans_noise, (m, d))
    return _problem(d, n, np.stack([ei, ej], -1), Rm, tm, kappa, tau), \
        (Rs, ts)


# -- what the cells draw from --seed -----------------------------------------

def with_noise(prob: Problem, rng, rot_noise: float, trans_noise: float,
               rows: np.ndarray | None = None) -> Problem:
    """``prob`` with the generators' noise model drawn afresh on ``rows``
    (default: every edge): R <- N_R R, t <- t + N(0, trans_noise)."""
    rows = np.arange(len(prob)) if rows is None else np.asarray(rows)
    R, t = prob.R.copy(), prob.t.copy()
    R[rows] = np.einsum("eab,ebc->eac",
                        rotation_noise(rng, rows.size, prob.d, rot_noise),
                        R[rows])
    t[rows] = t[rows] + rng.normal(0.0, trans_noise, (rows.size, prob.d))
    return dataclasses.replace(prob, R=R, t=t)


def with_outliers(prob: Problem, rng, rows: np.ndarray) -> Problem:
    """``prob`` with the edges ``rows`` replaced by gross outliers, drawn
    as ``make_measurements`` draws them: a uniform random rotation and a
    translation of 5 N(0, I)."""
    rows = np.asarray(rows)
    R, t = prob.R.copy(), prob.t.copy()
    R[rows] = _project_rotations_np(
        rng.standard_normal((rows.size, prob.d, prob.d)))
    t[rows] = 5.0 * rng.standard_normal((rows.size, prob.d))
    return dataclasses.replace(prob, R=R, t=t)
