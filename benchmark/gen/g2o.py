"""Minimal g2o writer for the served cells (the wire format of a request).

Follows ``dpgo_tpu/utils/g2o.write_g2o`` (PR 21): translation information
``tau I``; rotation information ``2 kappa I`` for SE(3) and ``kappa`` for
SE(2), so the precisions round-trip through the server's reader.
"""

from __future__ import annotations

import numpy as np

from .synthetic import Problem


def rotation_to_quat(R: np.ndarray) -> np.ndarray:
    """Rotations [m, 3, 3] -> (x, y, z, w) quaternions, Shepperd's rule."""
    R = np.asarray(R, np.float64)
    m00, m01, m02 = R[:, 0, 0], R[:, 0, 1], R[:, 0, 2]
    m10, m11, m12 = R[:, 1, 0], R[:, 1, 1], R[:, 1, 2]
    m20, m21, m22 = R[:, 2, 0], R[:, 2, 1], R[:, 2, 2]
    q = np.empty((R.shape[0], 4))
    with np.errstate(divide="ignore", invalid="ignore"):
        cases = [
            (m00 + m11 + m22 > 0, 1.0 + m00 + m11 + m22,
             lambda s: ((m21 - m12) / s, (m02 - m20) / s, (m10 - m01) / s,
                        0.25 * s)),
            ((m00 >= m11) & (m00 >= m22), 1.0 + m00 - m11 - m22,
             lambda s: (0.25 * s, (m01 + m10) / s, (m02 + m20) / s,
                        (m21 - m12) / s)),
            (m11 >= m22, 1.0 + m11 - m00 - m22,
             lambda s: ((m01 + m10) / s, 0.25 * s, (m12 + m21) / s,
                        (m02 - m20) / s)),
            (np.ones_like(m00, bool), 1.0 + m22 - m00 - m11,
             lambda s: ((m02 + m20) / s, (m12 + m21) / s, 0.25 * s,
                        (m10 - m01) / s)),
        ]
        done = np.zeros(R.shape[0], bool)
        for cond, arg, f in cases:
            take = cond & ~done
            s = np.sqrt(np.maximum(arg, 0.0)) * 2
            q[take] = np.stack(f(s), -1)[take]
            done |= take
    return q


def g2o_bytes(prob: Problem) -> bytes:
    """The problem as a g2o edge list (global indexing)."""
    lines = []
    if prob.d == 3:
        q = rotation_to_quat(prob.R)
        for k in range(len(prob)):
            tau, c = float(prob.tau[k]), 2.0 * float(prob.kappa[k])
            info = [tau, 0, 0, 0, 0, 0, tau, 0, 0, 0, 0, tau, 0, 0, 0,
                    c, 0, 0, c, 0, c]
            vals = [*prob.t[k], *q[k], *info]
            lines.append(f"EDGE_SE3:QUAT {int(prob.i[k])} {int(prob.j[k])} "
                         + " ".join(repr(float(v)) for v in vals))
    else:
        theta = np.arctan2(prob.R[:, 1, 0], prob.R[:, 0, 0])
        for k in range(len(prob)):
            tau, kap = float(prob.tau[k]), float(prob.kappa[k])
            vals = [*prob.t[k], theta[k], tau, 0, 0, tau, 0, kap]
            lines.append(f"EDGE_SE2 {int(prob.i[k])} {int(prob.j[k])} "
                         + " ".join(repr(float(v)) for v in vals))
    return ("\n".join(lines) + "\n").encode()
