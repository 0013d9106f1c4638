"""The kernel's least-work count against a hand count."""

import json
import os

import numpy as np

from benchmark.metrics import _work as work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_hand_count_tiny_graph():
    # 4 poses, 2 agents {0,1} {2,3}; edges 0-1, 1-2, 2-3, 0-3; r=3, d=2.
    i, j = np.array([0, 1, 2, 0]), np.array([1, 2, 3, 3])
    r, d = 3, 2
    # Per edge: Y_i R 2rd^2=24, r_R rd=6, Y_i t 2rd=12, r_t 2r=6,
    # G_Yj 2rd=12, G_Yi -= wk r_R R^T 2rd^2+2rd=36, -= wt r_t t^T 3rd=18,
    # G_pj 2r=6, G_pi 2r=6: 126 FLOPs.
    assert work.edge_flops(r, d) == 126
    # Payload: i, j, R (4), t (2), wk, wt: 10 values of 4 bytes.
    assert work.edge_bytes(d) == 40
    # Agent 0 sees edges 0-1, 1-2, 0-3 (3), owns 2 poses, neighbours
    # {2, 3}; agent 1 sees 1-2, 2-3, 0-3 (3), owns 2, neighbours {1, 0}.
    # Pose: r (d+1) = 9 values, 36 bytes; read own + neighbours, write own.
    flops, bytes_ = work.round_work(i, j, 4, 2, r, d)
    assert flops == 6 * 126
    assert bytes_ == 6 * 40 + 2 * (2 * 2 + 2) * 36


def test_least_seconds_takes_the_binding_peak():
    with open(os.path.join(HERE, "peaks.json")) as fh:
        peaks = json.load(fh)["devices"]["TPU v5 lite"]
    assert work.least_seconds(1.97e14, 0.0, peaks) == 1.0
    assert work.least_seconds(0.0, 8.19e11, peaks) == 1.0
