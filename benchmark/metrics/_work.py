"""The least work of one RBCD round, counted from the problem's shapes.

One round updates every agent's block once.  Whatever implements it has
to pass at least once over each agent's real (unpadded) edges and poses:
read every edge's payload, read the agent's poses and its separator
(neighbour) poses, write its poses, and evaluate the gradient once.  The
one-hot selection matmuls of today's kernel, padding, and the further
inner iterations of truncated CG are the implementation's cost and are
not counted.  An edge between two agents belongs to both blocks.

Per edge (i -> j) and rank r, dimension d, in f32:
* payload: i and j (int32), R (d x d), t (d), the weighted kappa and tau:
  4 (d^2 + d + 4) bytes;
* FLOPs of one gradient evaluation, a multiply-add counted as 2:
  Y_i R (2 r d^2), r_R = Y_j - Y_i R (r d), Y_i t (2 r d),
  r_t = p_j - p_i - Y_i t (2 r), G_Yj += wk r_R (2 r d),
  G_Yi -= wk r_R R^T (2 r d^2 + 2 r d), G_Yi -= wt r_t t^T (3 r d),
  G_pj += wt r_t (2 r), G_pi -= wt r_t (2 r):
  4 r d^2 + 10 r d + 6 r.
Per pose: r (d + 1) f32 values, read for own and separator poses,
written for own poses.
"""

from __future__ import annotations

import numpy as np


def edge_flops(r: int, d: int) -> int:
    return 4 * r * d * d + 10 * r * d + 6 * r


def edge_bytes(d: int) -> int:
    return 4 * (d * d + d + 4)


def round_work(i: np.ndarray, j: np.ndarray, n: int, agents: int, r: int,
               d: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one round over a contiguous partition."""
    q = n // agents
    owner = np.minimum(np.arange(n) // q, agents - 1)
    ai, aj = owner[i], owner[j]
    pose_b = 4 * r * (d + 1)
    flops = bytes_ = 0.0
    for a in range(agents):
        mine = (ai == a) | (aj == a)
        ne = int(mine.sum())
        own = int((owner == a).sum())
        nbr = np.unique(np.concatenate([j[(ai == a) & (aj != a)],
                                        i[(aj == a) & (ai != a)]])).size
        flops += ne * edge_flops(r, d)
        bytes_ += ne * edge_bytes(d) + (2 * own + nbr) * pose_b
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    return max(flops / peaks["flops_per_s"], bytes_ / peaks["hbm_bytes_per_s"])
