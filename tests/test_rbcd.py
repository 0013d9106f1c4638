"""Multi-agent sync RBCD tests (reference multi-robot-example semantics)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dpgo_tpu.config import AgentParams, Schedule, SolverParams
from dpgo_tpu.models import local_pgo, rbcd
from dpgo_tpu.ops import manifold, quadratic
from dpgo_tpu.types import edge_set_from_measurements
from dpgo_tpu.utils.partition import partition_contiguous
from synthetic import make_measurements, trajectory_error


def test_partition_contiguous(rng):
    meas, _ = make_measurements(rng, n=20, d=3, num_lc=10)
    part = partition_contiguous(meas, 4)
    assert part.n.sum() == 20
    assert part.n.tolist() == [5, 5, 5, 5]
    cls = part.classify()
    # Edge categories consistent: every shared edge crosses robots.
    shared = cls == 2
    assert np.all(part.meas.r1[shared] != part.meas.r2[shared])
    assert np.all(part.meas.r1[~shared] == part.meas.r2[~shared])
    # Round trip local -> global matches original global ids.
    g1 = part.global_index[part.meas.r1, part.meas.p1]
    assert np.array_equal(g1, part.meas_global.p1)


def test_partition_by_keys(rng):
    import dataclasses

    from dpgo_tpu.utils.partition import partition_by_keys

    # Build a 2-robot measurement set with robot-encoded, NON-dense pose ids
    # (robot 98's ids start at 10).
    meas, _ = make_measurements(rng, n=20, d=3, num_lc=10)
    robot_of = (np.arange(20) >= 10).astype(np.int32)
    keyed = dataclasses.replace(
        meas,
        r1=np.where(robot_of[meas.p1] == 0, 97, 98).astype(np.int32),
        r2=np.where(robot_of[meas.p2] == 0, 97, 98).astype(np.int32),
        p1=meas.p1,  # robot 98's local ids are 10..19: not dense from 0
        p2=meas.p2,
    )
    part = partition_by_keys(keyed)
    assert part.num_robots == 2
    assert part.n.tolist() == [10, 10]
    # Local ids densified to 0..9 per robot.
    assert part.meas.p1.max() < 10 and part.meas.p2.max() < 10
    # Global indexing is a bijection onto 0..19.
    gids = np.unique(np.concatenate([part.meas_global.p1, part.meas_global.p2]))
    assert len(gids) == 20
    # The partitioned problem still solves to the same optimum.
    params = AgentParams(d=3, r=5, num_robots=2, schedule=Schedule.JACOBI)
    res = rbcd.solve_rbcd(part.meas_global, 2, params, max_iters=100,
                          grad_norm_tol=1e-5, part=part)
    assert res.grad_norm_history[-1] < 1e-5


def test_local_problems_reproduce_global_cost_and_grad(rng):
    # Sum of per-agent private costs + half-counted shared costs == global
    # cost; per-agent block gradient == global gradient restricted to block.
    meas, _ = make_measurements(rng, n=24, d=3, num_lc=12,
                                rot_noise=0.05, trans_noise=0.05)
    part = partition_contiguous(meas, 4)
    graph, meta = rbcd.build_graph(part, rank=5, dtype=jnp.float64)

    Xg = jnp.asarray(np.random.default_rng(1).standard_normal((24, 5, 4)))
    Xa = rbcd.scatter_to_agents(Xg, graph)
    Z = rbcd.neighbor_buffer(rbcd.public_table(Xa, graph), graph)

    edges_g = edge_set_from_measurements(part.meas_global, dtype=jnp.float64)
    g_global = quadratic.egrad(Xg, edges_g)

    for a in range(4):
        buf = jnp.concatenate([Xa[a], Z[a]], axis=0)
        import jax

        g_local = quadratic.egrad(buf, jax.tree.map(lambda x: x[a], graph.edges),
                                  n_out=meta.n_max)
        na = int(graph.n[a])
        expected = g_global[part.global_index[a, :na]]
        assert np.allclose(g_local[:na], expected, atol=1e-10), f"agent {a}"


@pytest.mark.parametrize("schedule", [Schedule.JACOBI, Schedule.GREEDY])
def test_rbcd_converges_noiseless(rng, schedule):
    meas, (Rs, ts) = make_measurements(rng, n=20, d=3, num_lc=10)
    params = AgentParams(d=3, r=5, num_robots=4, schedule=schedule,
                         solver=SolverParams())
    res = rbcd.solve_rbcd(meas, 4, params, max_iters=200, grad_norm_tol=1e-6)
    assert res.grad_norm_history[-1] < 1e-6
    assert trajectory_error(res.T, Rs, ts) < 1e-4


def test_greedy_updates_exactly_one_agent_per_round(rng):
    """The gated greedy path (single dynamic-sliced solve instead of A
    masked solves) must still change exactly one agent's block per round,
    and that agent must be the argmax-gradnorm one the reference driver
    selects (MultiRobotExample.cpp:242-256)."""
    meas, _ = make_measurements(rng, n=20, d=3, num_lc=10,
                                rot_noise=0.02, trans_noise=0.02)
    params = AgentParams(d=3, r=5, num_robots=4, schedule=Schedule.GREEDY)
    part = partition_contiguous(meas, 4)
    graph, meta = rbcd.build_graph(part, params.r, jnp.float64)
    X0 = rbcd.centralized_chordal_init(part, meta, graph, jnp.float64)
    state = rbcd.init_state(graph, meta, X0, params=params)

    for _ in range(3):
        # Expected selection: per-agent Riemannian gradnorm at X.
        Z = rbcd.neighbor_buffer(rbcd.public_table(state.X, graph), graph)

        def gn_of(x, z, e, s, m):
            buf = jnp.concatenate([x, z], axis=0)
            return manifold.norm(
                manifold.rgrad(x, quadratic.egrad_ell(buf, e, s, m)))

        gn = jax.vmap(gn_of)(state.X, Z, graph.edges, graph.inc_slot,
                             graph.inc_mask)
        expect = int(jnp.argmax(gn))

        new = rbcd.rbcd_step(state, graph, meta, params)
        changed = [a for a in range(4)
                   if not np.allclose(np.asarray(new.X[a]),
                                      np.asarray(state.X[a]), atol=0)]
        assert changed == [expect]
        state = new


def test_rbcd_matches_centralized_on_noisy_graph(rng):
    meas, _ = make_measurements(rng, n=30, d=3, num_lc=15,
                                rot_noise=0.05, trans_noise=0.05)
    central = local_pgo.solve_local(meas, rank=5, grad_norm_tol=1e-6,
                                    max_iters=300)
    params = AgentParams(d=3, r=5, num_robots=5, schedule=Schedule.JACOBI)
    res = rbcd.solve_rbcd(meas, 5, params, max_iters=300, grad_norm_tol=1e-4)
    # Distributed must reach (nearly) the centralized optimum.
    assert res.cost_history[-1] <= central.cost * 1.01 + 1e-9


def test_rbcd_cost_monotone_jacobi(rng):
    meas, _ = make_measurements(rng, n=24, d=3, num_lc=10,
                                rot_noise=0.05, trans_noise=0.05)
    params = AgentParams(d=3, r=5, num_robots=4, schedule=Schedule.JACOBI)
    res = rbcd.solve_rbcd(meas, 4, params, max_iters=30, grad_norm_tol=0.0)
    c = res.cost_history
    # Jacobi RBCD on a partitioned quadratic need not be strictly monotone in
    # theory, but on these graphs it should never increase materially.
    assert all(c[k + 1] <= c[k] * (1 + 1e-6) + 1e-9 for k in range(len(c) - 1))


def test_rbcd_async_schedule_runs(rng):
    meas, _ = make_measurements(rng, n=20, d=3, num_lc=8,
                                rot_noise=0.03, trans_noise=0.03)
    params = AgentParams(d=3, r=5, num_robots=4, schedule=Schedule.ASYNC,
                         async_update_prob=0.5)
    res = rbcd.solve_rbcd(meas, 4, params, max_iters=60, grad_norm_tol=1e-3)
    assert res.cost_history[-1] <= res.cost_history[0]


def test_rbcd_se2(rng):
    meas, _ = make_measurements(rng, n=20, d=2, num_lc=8,
                                rot_noise=0.02, trans_noise=0.02)
    # Tight rel-change tol so the consensus gate (reference default 5e-3)
    # doesn't stop the solve early, and a tight local-solver gradnorm tol
    # (the reference's per-step 1e-2 floor would cap global convergence).
    params = AgentParams(d=2, r=3, num_robots=4, schedule=Schedule.JACOBI,
                         rel_change_tol=1e-10,
                         solver=SolverParams(grad_norm_tol=1e-7))
    res = rbcd.solve_rbcd(meas, 4, params, max_iters=100, grad_norm_tol=1e-4)
    assert res.grad_norm_history[-1] < 1e-4


def test_rbcd_smallgrid_vs_centralized(data_dir):
    # The reference demo config: 5 robots on smallGrid3D, r = 5
    # (README.md:31-34, MultiRobotExample gate gradnorm < 0.1).
    from dpgo_tpu.utils.g2o import read_g2o

    meas = read_g2o(f"{data_dir}/smallGrid3D.g2o")
    central = local_pgo.solve_local(meas, rank=5, grad_norm_tol=1e-3,
                                    max_iters=300)
    params = AgentParams(d=3, r=5, num_robots=5, schedule=Schedule.JACOBI)
    res = rbcd.solve_rbcd(meas, 5, params, max_iters=100, grad_norm_tol=0.1)
    # Either gate may fire first (the consensus rel-change default 5e-3 is
    # the reference's); what matters is solution quality.
    assert res.terminated_by in ("grad_norm", "consensus")
    assert res.cost_history[-1] <= central.cost * 1.05
    # Anchored output frame: pose 0 is the identity.
    T = np.asarray(res.T)
    assert np.allclose(T[0, :, :3], np.eye(3), atol=1e-8)
    assert np.allclose(T[0, :, 3], 0.0, atol=1e-8)


def test_rbcd_rgd_algorithm(rng):
    """RGD dispatch (reference QuadraticOptimizer.cpp:42-47, 124-149): the
    fixed-step gradient schedule also makes progress on a noisy graph, just
    slower than RTR.  Start from odometry so the init is far from optimal."""
    from dpgo_tpu.config import ROptAlg
    from dpgo_tpu.ops import chordal
    from dpgo_tpu.models.local_pgo import lift
    from dpgo_tpu.types import edge_set_from_measurements

    meas, _ = make_measurements(rng, n=12, d=3, num_lc=6,
                                rot_noise=0.05, trans_noise=0.05)
    params = AgentParams(
        d=3, r=5, num_robots=3, schedule=Schedule.JACOBI,
        rel_change_tol=1e-8,
        solver=SolverParams(algorithm=ROptAlg.RGD, rgd_stepsize=1e-4))
    part = partition_contiguous(meas, 3)
    graph, meta = rbcd.build_graph(part, params.r, jnp.float64)
    edges_g = edge_set_from_measurements(part.meas_global, dtype=jnp.float64)
    T0 = chordal.odometry_from_edges(edges_g, part.meas_global.num_poses)
    X0 = rbcd.scatter_to_agents(
        lift(T0, rbcd.lifting_matrix(meta, jnp.float64)), graph)
    state = rbcd.init_state(graph, meta, X0, params=params)
    seg = lambda s, k, uw, rs: rbcd.rbcd_segment(
        s, graph, k, meta, params, first_update_weights=uw,
        first_restart=rs)
    res = rbcd.run_rbcd(state, graph, meta, seg, part, max_iters=300,
                        grad_norm_tol=1e-2, params=params)
    assert res.cost_history[-1] < res.cost_history[0]
    assert res.grad_norm_history[-1] < 0.5 * res.grad_norm_history[0]


def test_package_sets_full_matmul_precision():
    """Importing dpgo_tpu must raise the default matmul precision: TPU f32
    matmuls otherwise run as bf16 MXU passes (~1e-2 error), which pushes
    iterates off the manifold (retraction stops being a no-op at zero) and
    breaks the 1e-6 suboptimality targets.  A user-chosen precision (either
    env var) wins instead."""
    import os

    import jax

    import dpgo_tpu  # noqa: F401  (import side effect under test)

    expected = (os.environ.get("DPGO_TPU_MATMUL_PRECISION")  # "" = unset
                or os.environ.get("JAX_DEFAULT_MATMUL_PRECISION")
                or "highest")
    assert jax.config.jax_default_matmul_precision == expected


def test_fused_rounds_match_sequential(rng):
    """``rbcd_steps(k)`` (the one-dispatch fori_loop) must reproduce k
    sequential ``rbcd_step`` calls exactly — same trace body, same math."""
    meas, _ = make_measurements(rng, n=20, d=3, num_lc=8,
                                rot_noise=0.03, trans_noise=0.03)
    params = AgentParams(d=3, r=5, num_robots=4, schedule=Schedule.JACOBI)
    part = partition_contiguous(meas, 4)
    graph, meta = rbcd.build_graph(part, params.r, jnp.float64)
    X0 = rbcd.centralized_chordal_init(part, meta, graph, jnp.float64)
    state0 = rbcd.init_state(graph, meta, X0, params=params)

    seq = state0
    for _ in range(5):
        seq = rbcd.rbcd_step(seq, graph, meta, params)
    fused = rbcd.rbcd_steps(state0, graph, 5, meta, params)

    assert int(fused.iteration) == int(seq.iteration) == 5
    assert np.allclose(np.asarray(fused.X), np.asarray(seq.X), atol=1e-12)
    assert np.allclose(np.asarray(fused.rel_change),
                       np.asarray(seq.rel_change), atol=1e-12)


def test_solver_uses_fused_segments(rng, monkeypatch):
    """``solve_rbcd`` with ``eval_every > 1`` must route every stretch
    through the fused segment path (one dispatch per eval stretch) and
    still converge to the same answer as per-round stepping."""
    meas, (Rs, ts) = make_measurements(rng, n=20, d=3, num_lc=10)
    params = AgentParams(d=3, r=5, num_robots=4, schedule=Schedule.JACOBI)

    calls = {"fused": 0, "per_round": 0}
    orig = rbcd.rbcd_segment

    def counting(state, graph, k, *a, **kw):
        calls["fused"] += 1
        return orig(state, graph, k, *a, **kw)

    def no_step(*a, **kw):
        calls["per_round"] += 1
        raise AssertionError("segment-driven solve must not single-step")

    monkeypatch.setattr(rbcd, "rbcd_segment", counting)
    monkeypatch.setattr(rbcd, "rbcd_step", no_step)
    res = rbcd.solve_rbcd(meas, 4, params, max_iters=60, grad_norm_tol=1e-6,
                          eval_every=10)
    assert calls["fused"] >= 1
    assert calls["per_round"] == 0
    assert res.grad_norm_history[-1] < 1e-6
    assert trajectory_error(res.T, Rs, ts) < 1e-4


def test_fused_segments_respect_gnc_and_restart_schedule(rng):
    """With acceleration + GNC active, the fused driver must fire the same
    weight-update/restart rounds as the per-round driver: identical final
    weights and iterates for eval_every 1 vs 7."""
    from dpgo_tpu.config import RobustCostParams, RobustCostType

    meas, _ = make_measurements(rng, n=20, d=3, num_lc=10, outlier_lc=3,
                                rot_noise=0.01, trans_noise=0.01)
    params = AgentParams(
        d=3, r=5, num_robots=4, schedule=Schedule.JACOBI, acceleration=True,
        robust=RobustCostParams(cost_type=RobustCostType.GNC_TLS),
        robust_opt_inner_iters=10, restart_interval=15,
        rel_change_tol=1e-14)  # keep the consensus gate out of the picture
    res_a = rbcd.solve_rbcd(meas, 4, params, max_iters=40, grad_norm_tol=0.0,
                            eval_every=1)
    res_b = rbcd.solve_rbcd(meas, 4, params, max_iters=40, grad_norm_tol=0.0,
                            eval_every=7)
    assert np.allclose(np.asarray(res_a.weights), np.asarray(res_b.weights),
                       atol=1e-12)
    assert np.allclose(np.asarray(res_a.X), np.asarray(res_b.X), atol=1e-10)


def test_rbcd_scale_20k_poses_32_agents(rng):
    """BASELINE config #5 scale smoke (the g2o100k dataset itself is
    stripped from the snapshot): a 20k-pose / 24k-edge synthetic graph over
    32 agents must build, initialize, and take fused RBCD rounds through the
    ELL formulation (the only one in budget at this size) with decreasing
    cost.  The full 100k/64 configuration runs the same code path (validated
    out-of-suite; build_graph is O(M) host work)."""
    import jax

    from dpgo_tpu.types import edge_set_from_measurements
    from dpgo_tpu.ops import quadratic

    meas, _ = make_measurements(rng, n=20_000, d=3, num_lc=4_000,
                                rot_noise=0.01, trans_noise=0.01)
    params = AgentParams(d=3, r=5, num_robots=32, schedule=Schedule.JACOBI)
    part = partition_contiguous(meas, 32)
    graph, meta = rbcd.build_graph(part, params.r, jnp.float64)
    assert rbcd._formulation(meta, params, graph, itemsize=8) == "ell"
    X0 = rbcd.centralized_chordal_init(part, meta, graph, jnp.float64)
    state = rbcd.init_state(graph, meta, X0, params=params)

    edges_g = edge_set_from_measurements(part.meas_global, dtype=jnp.float64)
    Xg0 = rbcd.gather_to_global(state.X, graph, meas.num_poses)
    f0 = float(quadratic.cost(Xg0, edges_g))

    state = rbcd.rbcd_steps(state, graph, 3, meta, params)
    assert bool(jax.numpy.isfinite(state.X).all())
    Xg = rbcd.gather_to_global(state.X, graph, meas.num_poses)
    f1 = float(quadratic.cost(Xg, edges_g))
    assert f1 < f0


def test_egrad_ell_matches_scatter(rng):
    """The gather-only ELL gradient/Hessian path must agree with the
    scatter-add reference formulation on every agent."""
    import jax

    meas, _ = make_measurements(rng, n=24, d=3, num_lc=12,
                                rot_noise=0.05, trans_noise=0.05)
    part = partition_contiguous(meas, 4)
    graph, meta = rbcd.build_graph(part, rank=5, dtype=jnp.float64)
    Xa = jnp.asarray(np.random.default_rng(3).standard_normal(
        (4, meta.n_max, 5, 4)))
    Z = rbcd.neighbor_buffer(rbcd.public_table(Xa, graph), graph)
    n_buf = meta.n_max + meta.s_max
    for a in range(4):
        e = jax.tree.map(lambda x: x[a], graph.edges)
        buf = jnp.concatenate([Xa[a], Z[a]], axis=0)
        g_ref = quadratic.egrad(buf, e, n_out=meta.n_max)
        g_ell = quadratic.egrad_ell(buf, e, graph.inc_slot[a],
                                    graph.inc_mask[a])
        assert np.allclose(g_ell, g_ref, atol=1e-12), f"agent {a}"
        V = jnp.asarray(rng.standard_normal((meta.n_max, 5, 4)))
        h_ref = quadratic.hessvec(V, e, n_buf=n_buf)
        h_ell = quadratic.hessvec_ell(V, e, graph.inc_slot[a],
                                      graph.inc_mask[a], n_buf=n_buf)
        assert np.allclose(h_ell, h_ref, atol=1e-12), f"agent {a} hessvec"


def test_colored_schedule_converges_and_matches_structure(rng):
    """Schedule.COLORED: one color class fires per round (non-adjacent
    agents only), the sweep cycles deterministically, and the solve reaches
    the same optimum as JACOBI on a well-behaved graph."""
    from dpgo_tpu.config import Schedule

    meas, _ = make_measurements(rng, n=24, d=3, num_lc=10,
                                rot_noise=0.01, trans_noise=0.01)
    part = partition_contiguous(meas, 4)
    graph, meta = rbcd.build_graph(part, 5, jnp.float64)
    assert meta.num_colors >= 2  # contiguous partitions couple neighbors
    color = np.asarray(graph.color)
    # valid coloring vs the neighbor tables
    nr, nm = np.asarray(graph.nbr_robot), np.asarray(graph.nbr_mask) > 0
    for a in range(4):
        for b in nr[a][nm[a]]:
            assert color[a] != color[b]

    params = AgentParams(d=3, r=5, num_robots=4, schedule=Schedule.COLORED,
                         rel_change_tol=0.0)
    X0 = rbcd.centralized_chordal_init(part, meta, graph, jnp.float64)
    state = rbcd.init_state(graph, meta, X0, params=params)
    prev = state.X
    # Round k must change only poses of color (k mod C).
    for k in range(meta.num_colors):
        state = rbcd.rbcd_step(state, graph, meta, params)
        changed = np.asarray(jnp.any(state.X != prev, axis=(1, 2, 3)))
        assert not np.any(changed & (color != k % meta.num_colors))
        prev = state.X
    # And the full solve converges like JACOBI does.
    res = rbcd.solve_rbcd(meas, 4, params=params, max_iters=120,
                          grad_norm_tol=0.05, eval_every=meta.num_colors,
                          dtype=jnp.float64)
    assert res.grad_norm_history[-1] < 0.05


def test_colored_fixes_jacobi_oscillation_ais2klinik(data_dir):
    """The VERDICT r2 finding: JACOBI (simultaneous updates of adjacent
    blocks) oscillates on ais2klinik even in plain L2, while the colored
    Gauss-Seidel sweep — the parallelism the RBCD theory actually licenses
    — descends monotonically and ends far below Jacobi's oscillation band.
    """
    import os
    from dpgo_tpu.config import Schedule
    from dpgo_tpu.ops import quadratic
    from dpgo_tpu.types import edge_set_from_measurements
    from dpgo_tpu.utils.g2o import read_g2o

    path = os.path.join(data_dir, "ais2klinik.g2o")
    if not os.path.exists(path):
        pytest.skip("dataset not available")
    meas = read_g2o(path)
    A = 32
    part = partition_contiguous(meas, A)
    edges_g = edge_set_from_measurements(part.meas_global,
                                         dtype=jnp.float64)
    n = meas.num_poses

    def costs_for(sched, sweeps):
        params = AgentParams(d=2, r=3, num_robots=A, schedule=sched,
                             rel_change_tol=0.0)
        graph, meta = rbcd.build_graph(part, 3, jnp.float64)
        X0 = rbcd.centralized_chordal_init(part, meta, graph, jnp.float64)
        state = rbcd.init_state(graph, meta, X0, params=params)
        per = 1 if sched == Schedule.JACOBI else meta.num_colors
        out = []
        for _ in range(sweeps):
            state = rbcd.rbcd_steps(state, graph, per, meta, params)
            out.append(float(quadratic.cost(
                rbcd.gather_to_global(state.X, graph, n), edges_g)))
        return out

    cj = costs_for(Schedule.JACOBI, 25)
    cc = costs_for(Schedule.COLORED, 25)
    inc_j = sum(1 for a, b in zip(cj, cj[1:]) if b > a + 1e-9)
    inc_c = sum(1 for a, b in zip(cc, cc[1:]) if b > a + 1e-9)
    assert inc_j >= 5          # Jacobi genuinely oscillates here
    assert inc_c == 0          # the colored sweep is monotone
    assert cc[-1] < 0.5 * cj[-1]  # and ends far below the oscillation band


def test_colored_schedule_with_acceleration(rng):
    """COLORED composes with Nesterov acceleration (deterministic lockstep
    like GREEDY, so the reference's async-only prohibition does not
    apply): the accelerated colored solve reaches the gradnorm gate.
    (Measured side-by-side during development: 20 rounds accelerated vs
    30 plain on this problem; only termination is asserted here.)"""
    from dpgo_tpu.config import Schedule

    meas, _ = make_measurements(rng, n=24, d=3, num_lc=10,
                                rot_noise=0.01, trans_noise=0.01)
    res = rbcd.solve_rbcd(meas, 4, params=AgentParams(
        d=3, r=5, num_robots=4, schedule=Schedule.COLORED,
        acceleration=True, restart_interval=30, rel_change_tol=0.0),
        max_iters=200, grad_norm_tol=0.05, eval_every=10,
        dtype=jnp.float64)
    assert res.terminated_by == "grad_norm"
    assert res.grad_norm_history[-1] < 0.05


# ---------------------------------------------------------------------------
# Device-resident verdict loop (ISSUE 9)
# ---------------------------------------------------------------------------

def _verdict_problem(rng, n=50, noise=0.05):
    meas, _ = make_measurements(rng, n=n, d=3, num_lc=n // 2,
                                rot_noise=noise, trans_noise=noise)
    return meas


def test_verdict_loop_matches_legacy_histories(rng):
    """Full-run parity: a coarse fetch cadence (K = 4 evals) reproduces
    the default cadence's (K = eval_every) cost/gradnorm histories
    bitwise, termination label, round count, and (at max_iters, where
    there is no overshoot) the iterate itself."""
    meas = _verdict_problem(rng)
    params = AgentParams(d=3, r=5, num_robots=2, rel_change_tol=0.0)
    a = rbcd.solve_rbcd(meas, 2, params=params, max_iters=24, eval_every=2,
                        grad_norm_tol=1e-9, dtype=jnp.float64)
    b = rbcd.solve_rbcd(meas, 2, params=params, max_iters=24, eval_every=2,
                        grad_norm_tol=1e-9, dtype=jnp.float64,
                        verdict_every=8)
    assert a.cost_history == b.cost_history
    assert a.grad_norm_history == b.grad_norm_history
    assert (a.iterations, a.terminated_by) == (b.iterations, b.terminated_by)
    assert np.array_equal(np.asarray(a.X), np.asarray(b.X))


def test_verdict_loop_termination_latches_mid_window(rng):
    """A gradnorm termination latched between verdict fetches reports the
    same terminal eval/round as the default cadence (K = eval_every) —
    histories truncated at the latched eval, not at the fetch
    boundary."""
    meas = _verdict_problem(rng)
    params = AgentParams(d=3, r=5, num_robots=2, rel_change_tol=0.0)
    a = rbcd.solve_rbcd(meas, 2, params=params, max_iters=200, eval_every=1,
                        grad_norm_tol=2e-2, dtype=jnp.float64)
    b = rbcd.solve_rbcd(meas, 2, params=params, max_iters=200, eval_every=1,
                        grad_norm_tol=2e-2, dtype=jnp.float64,
                        verdict_every=8)
    assert a.terminated_by == "grad_norm"
    assert (a.iterations, a.terminated_by) == (b.iterations, b.terminated_by)
    assert a.cost_history == b.cost_history
    assert a.grad_norm_history == b.grad_norm_history


@pytest.mark.parametrize("eval_every", [1, 2, 5])
def test_default_cadence_is_per_eval(rng, eval_every):
    """``verdict_every`` omitted reads the verdict word at every eval: the
    default solve equals an explicit ``verdict_every=eval_every`` one —
    rounds, termination label, histories, and the terminal iterate and
    rounded trajectory bitwise — for a gate-terminated and a
    consensus-terminated solve."""
    meas = _verdict_problem(rng, n=40)
    for rel_tol, term in ((1e-4, "grad_norm"), (3e-4, "consensus")):
        params = AgentParams(d=3, r=5, num_robots=2, rel_change_tol=rel_tol)
        kw = dict(params=params, max_iters=200, eval_every=eval_every,
                  grad_norm_tol=2e-2, dtype=jnp.float64)
        a = rbcd.solve_rbcd(meas, 2, **kw)
        b = rbcd.solve_rbcd(meas, 2, verdict_every=eval_every, **kw)
        assert a.terminated_by == term
        assert (a.iterations, a.terminated_by) == \
            (b.iterations, b.terminated_by)
        assert a.cost_history == b.cost_history
        assert a.grad_norm_history == b.grad_norm_history
        assert np.array_equal(np.asarray(a.X), np.asarray(b.X))
        assert np.array_equal(np.asarray(a.T), np.asarray(b.T))


def test_verdict_loop_fetch_cadence(rng, monkeypatch):
    """Telemetry off, the loop performs exactly rounds/K verdict-word
    fetches plus ONE fused terminal-epilogue fetch — counted through the
    ``_host_fetch`` seam (the bench's host_syncs shim technique)."""
    meas = _verdict_problem(rng)
    params = AgentParams(d=3, r=5, num_robots=2, rel_change_tol=0.0)
    count = [0]
    orig = rbcd._host_fetch
    monkeypatch.setattr(rbcd, "_host_fetch",
                        lambda x: (count.__setitem__(0, count[0] + 1),
                                   orig(x))[1])
    res = rbcd.solve_rbcd(meas, 2, params=params, max_iters=32,
                          eval_every=4, grad_norm_tol=0.0,
                          dtype=jnp.float64, verdict_every=16)
    assert res.iterations == 32
    assert count[0] == 32 // 16 + 1  # words + one fused terminal epilogue


@pytest.mark.parametrize("telemetry", [False, True])
def test_default_cadence_host_syncs(rng, monkeypatch, tmp_path, telemetry):
    """``verdict_every`` omitted reads one word per eval.  Telemetry off:
    rounds/eval_every word fetches plus ONE terminal epilogue fetch, also
    with ``certify_mode="off"``.  Telemetry on: the lazy history fetch
    rides every boundary too, so ``host_syncs_per_100_rounds`` reads
    200/eval_every."""
    from dpgo_tpu import obs
    from dpgo_tpu.obs.events import read_events

    meas = _verdict_problem(rng)
    params = AgentParams(d=3, r=5, num_robots=2, rel_change_tol=0.0)
    count = [0]
    orig = rbcd._host_fetch
    monkeypatch.setattr(rbcd, "_host_fetch",
                        lambda x: (count.__setitem__(0, count[0] + 1),
                                   orig(x))[1])
    kw = dict(params=params, max_iters=32, eval_every=4, grad_norm_tol=0.0,
              dtype=jnp.float64)
    if not telemetry:
        res = rbcd.solve_rbcd(meas, 2, **kw)
        assert res.iterations == 32 and res.certificate is None
        assert count[0] == 32 // 4 + 1  # words + one terminal epilogue
        return
    run_dir = str(tmp_path / "run")
    with obs.run_scope(run_dir):
        res = rbcd.solve_rbcd(meas, 2, **kw)
    assert res.iterations == 32
    syncs = [e["value"] for e in read_events(f"{run_dir}/events.jsonl")
             if e.get("metric") == "host_syncs_per_100_rounds"]
    assert syncs == [pytest.approx(200.0 / 4)]


def test_verdict_every_must_divide_eval_every(rng):
    meas = _verdict_problem(rng, n=20)
    params = AgentParams(d=3, r=5, num_robots=2)
    with pytest.raises(ValueError, match="verdict_every"):
        rbcd.solve_rbcd(meas, 2, params=params, max_iters=8, eval_every=3,
                        grad_norm_tol=1e-9, dtype=jnp.float64,
                        verdict_every=4)


def test_verdict_word_pack_unpack_roundtrip():
    for status in (rbcd.VERDICT_RUNNING, rbcd.VERDICT_GRAD_NORM,
                   rbcd.VERDICT_CONSENSUS):
        for anom in (rbcd.ANOMALY_NONE, rbcd.ANOMALY_STALL,
                     rbcd.ANOMALY_NON_FINITE):
            for stage in (0, 3, 97):
                w = rbcd.pack_verdict(status, anom, stage)
                dec = rbcd.unpack_verdict(w)
                assert dec["stage"] == stage
                assert dec["status"] == rbcd._VERDICT_STATUS[status]
                assert dec["anomaly"] == rbcd._VERDICT_ANOMALY[anom]


def test_verdict_loop_gnc_weight_updates_match(rng):
    """Robust (GNC) schedule parity: flagged weight-update rounds land on
    the same rounds at K = 2 evals as at the default cadence
    (host-deterministic schedule_bounds), so the mu trajectory and
    histories agree."""
    from dpgo_tpu.config import RobustCostParams, RobustCostType

    meas = _verdict_problem(rng)
    params = AgentParams(
        d=3, r=5, num_robots=2, rel_change_tol=0.0,
        robust=RobustCostParams(cost_type=RobustCostType.GNC_TLS),
        robust_opt_inner_iters=4)
    a = rbcd.solve_rbcd(meas, 2, params=params, max_iters=20, eval_every=2,
                        grad_norm_tol=1e-9, dtype=jnp.float64)
    b = rbcd.solve_rbcd(meas, 2, params=params, max_iters=20, eval_every=2,
                        grad_norm_tol=1e-9, dtype=jnp.float64,
                        verdict_every=4)
    assert a.cost_history == b.cost_history
    assert a.grad_norm_history == b.grad_norm_history
    assert np.array_equal(np.asarray(a.weights), np.asarray(b.weights))
