"""Pod-scale fast path (ISSUE 11): the device-resident verdict loop under
shard_map, halo/compute overlap, and the sharded Gauss-Newton-CG tail —
all run on the virtual 8-device CPU mesh.

The contracts pinned here mirror how PR 9 pinned the single-device verdict
loop against the per-eval path:

* the sharded metrics body (psum reductions inside shard_map) produces
  BITWISE-identical rows to the single-device ``_central_metrics_body``
  on the same state — the global-assembly psum adds one owner value to
  zeros per pose (disjoint supports), so it is exact, not merely close;
* ``solve_rbcd_sharded(verdict_every=K)`` terminates at the same round,
  for the same reason, with the same histories as the single-device
  verdict loop (to mesh reduction-order tolerance) and as the sharded
  per-eval driver;
* the overlapped fused round loop is bitwise-equal to the unpipelined
  one (the halo of round k is always ``exchange(X_k)``);
* the host reads exactly one verdict word per K rounds (counted through
  the sanctioned ``rbcd._host_fetch`` seam);
* the sharded GN-CG tail matches ``refine.gn_tail`` on the same iterate
  to f64 tolerance with zero host transfers inside the CG loop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dpgo_tpu.config import AgentParams
from dpgo_tpu.models import rbcd, refine
from dpgo_tpu.parallel import (gn_tail_sharded, make_mesh,
                               make_sharded_metrics_body,
                               make_sharded_multi_step, shard_problem,
                               solve_rbcd_sharded)
from dpgo_tpu.types import edge_set_from_measurements
from dpgo_tpu.utils.partition import partition_contiguous

from synthetic import make_measurements


def _setup(meas, num_robots, params, dtype=jnp.float64):
    part = partition_contiguous(meas, num_robots)
    graph, meta = rbcd.build_graph(part, params.r, dtype)
    X0 = rbcd.centralized_chordal_init(part, meta, graph, dtype)
    state = rbcd.init_state(graph, meta, X0, params=params)
    return part, graph, meta, state


def _noisy(rng_or_seed, n=48, num_lc=14, noise=0.01):
    rng = np.random.default_rng(rng_or_seed) \
        if isinstance(rng_or_seed, int) else rng_or_seed
    meas, _ = make_measurements(rng, n=n, d=3, num_lc=num_lc,
                                rot_noise=noise, trans_noise=noise)
    return meas


def test_sharded_divisibility_validated_up_front(rng):
    """The mesh-divisibility error fires before any graph build, naming
    both offending values and the fix."""
    meas = _noisy(rng)
    params = AgentParams(d=3, r=5, num_robots=6)
    calls = []
    orig = rbcd.build_graph

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    rbcd.build_graph = spy
    try:
        with pytest.raises(ValueError) as ei:
            solve_rbcd_sharded(meas, num_robots=6, mesh=make_mesh(4),
                               params=params, max_iters=4)
    finally:
        rbcd.build_graph = orig
    msg = str(ei.value)
    assert "num_robots=6" in msg and "4" in msg and "make_mesh" in msg
    assert not calls, "validation must precede the graph build"


def test_sharded_metrics_body_bitwise_vs_central(rng):
    """The shard_map metrics body's rows are BITWISE equal to the
    single-device ``_central_metrics_body`` on the same state, both with
    and without the telemetry extras: the global assembly / weight
    collapse psums sum disjoint (or duplicate-identical) owner
    contributions, so no reduction-order slack exists to hide behind."""
    meas = _noisy(rng)
    params = AgentParams(d=3, r=5, num_robots=8)
    part, graph, meta, state = _setup(meas, 8, params)
    mesh = make_mesh(8)
    sh_state, sh_graph = shard_problem(mesh, state, graph)
    edges_g = edge_set_from_measurements(part.meas_global,
                                         dtype=jnp.float64)
    n_total, num_meas = part.meas_global.num_poses, len(part.meas_global)
    # A couple of rounds so rel_change is finite and weights are live.
    multi = make_sharded_multi_step(mesh, meta, params)
    sh_state = multi(sh_state, sh_graph, 2)
    state = rbcd.rbcd_steps(state, graph, 2, meta, params)
    for telemetry in (False, True):
        body_c = rbcd._central_metrics_body(graph, edges_g, n_total,
                                            num_meas, telemetry)
        body_s = make_sharded_metrics_body(mesh, sh_graph, edges_g,
                                           n_total, num_meas, telemetry)
        vc = np.asarray(jax.jit(body_c)(
            state.X, state.weights, state.ready, state.mu,
            state.rel_change))
        vs = np.asarray(jax.jit(body_s)(
            sh_state.X, sh_state.weights, sh_state.ready, sh_state.mu,
            sh_state.rel_change))
        # The sharded STATE itself agrees only to reduction order, so
        # evaluate the sharded body on rows whose inputs match bitwise:
        vcs = np.asarray(jax.jit(body_s)(
            jnp.asarray(state.X), jnp.asarray(state.weights),
            jnp.asarray(state.ready), jnp.asarray(state.mu),
            jnp.asarray(state.rel_change)))
        np.testing.assert_array_equal(vcs, vc)
        np.testing.assert_allclose(vs, vc, rtol=1e-9, atol=1e-12)


def test_sharded_verdict_matches_single_device_verdict(rng):
    """ACCEPTANCE: ``solve_rbcd_sharded(verdict_every=K)`` terminates at
    the same eval, for the same reason, with the same cost/gradnorm
    histories as the single-device verdict loop."""
    meas = _noisy(rng)
    params = AgentParams(d=3, r=5, num_robots=8, rel_change_tol=0.0)
    res_sd = rbcd.solve_rbcd(meas, 8, params=params, max_iters=40,
                             grad_norm_tol=0.1, eval_every=4,
                             verdict_every=8, dtype=jnp.float64)
    res_sh = solve_rbcd_sharded(meas, 8, mesh=make_mesh(8), params=params,
                                max_iters=40, grad_norm_tol=0.1,
                                eval_every=4, verdict_every=8)
    assert res_sh.iterations == res_sd.iterations
    assert res_sh.terminated_by == res_sd.terminated_by == "grad_norm"
    np.testing.assert_allclose(res_sh.cost_history, res_sd.cost_history,
                               rtol=1e-9)
    np.testing.assert_allclose(res_sh.grad_norm_history,
                               res_sd.grad_norm_history, rtol=1e-7)
    np.testing.assert_allclose(np.asarray(res_sh.T), np.asarray(res_sd.T),
                               atol=1e-8)


def test_sharded_verdict_matches_sharded_per_eval(rng):
    """The sharded loop at a coarse cadence (K = 2 evals) vs the default
    cadence (K = eval_every) on the SAME mesh: identical termination and
    histories — the verdict-word contract carries to the mesh
    unchanged."""
    meas = _noisy(rng)
    params = AgentParams(d=3, r=5, num_robots=8, rel_change_tol=0.0)
    kw = dict(mesh=make_mesh(8), params=params, max_iters=40,
              grad_norm_tol=0.1, eval_every=4)
    res_pe = solve_rbcd_sharded(meas, 8, **kw)
    res_vw = solve_rbcd_sharded(meas, 8, verdict_every=8, **kw)
    assert res_vw.iterations == res_pe.iterations
    assert res_vw.terminated_by == res_pe.terminated_by
    np.testing.assert_allclose(res_vw.cost_history, res_pe.cost_history,
                               rtol=1e-12)
    np.testing.assert_allclose(res_vw.grad_norm_history,
                               res_pe.grad_norm_history, rtol=1e-9)


def test_sharded_verdict_host_sync_rate(rng):
    """One packed-word fetch per K rounds, counted through the sanctioned
    ``rbcd._host_fetch`` seam (telemetry off: the only other transfers
    are the 2-call terminal epilogue) — ``host_syncs_per_100_rounds ==
    100/K`` on the sharded path."""
    meas = _noisy(7, n=80, num_lc=16, noise=0.1)
    params = AgentParams(d=3, r=5, num_robots=8, rel_change_tol=0.0)
    K, rounds = 4, 16
    counted = [0]
    orig = rbcd._host_fetch

    def counting(x):
        counted[0] += 1
        return orig(x)

    rbcd._host_fetch = counting
    try:
        res = solve_rbcd_sharded(meas, 8, mesh=make_mesh(8), params=params,
                                 max_iters=rounds, grad_norm_tol=0.0,
                                 eval_every=K, verdict_every=K)
    finally:
        rbcd._host_fetch = orig
    assert res.iterations == rounds and res.terminated_by == "max_iters"
    words = rounds // K
    assert counted[0] == words + 1, counted[0]  # words + fused epilogue
    assert 100.0 * words / rounds == pytest.approx(100.0 / K)


def test_sharded_overlap_matches_unpipelined(rng):
    """The halo-pipelined fused loop is BITWISE equal to the unpipelined
    one: the halo of round k is always ``exchange(X_k)``, only its issue
    point moves."""
    meas = _noisy(rng)
    params = AgentParams(d=3, r=5, num_robots=8)
    _, graph, meta, state = _setup(meas, 8, params)
    mesh = make_mesh(8)
    sh_state, sh_graph = shard_problem(mesh, state, graph)
    on = make_sharded_multi_step(mesh, meta, params, overlap=True)
    off = make_sharded_multi_step(mesh, meta, params, overlap=False)
    a = on(sh_state, sh_graph, 5)
    b = off(sh_state, sh_graph, 5)
    np.testing.assert_array_equal(np.asarray(a.X), np.asarray(b.X))
    np.testing.assert_array_equal(np.asarray(a.rel_change),
                                  np.asarray(b.rel_change))
    assert int(a.iteration) == int(b.iteration) == 5


def test_sharded_verdict_ppermute_matches_all_gather(rng):
    """The verdict loop composes with the ppermute exchange: identical
    trace and trajectory vs the all_gather arm (the two exchanges are
    bitwise-equal by construction)."""
    meas = _noisy(rng, n=64, num_lc=20)
    params = AgentParams(d=3, r=5, num_robots=8, rel_change_tol=0.0)
    kw = dict(params=params, max_iters=40, grad_norm_tol=0.1,
              eval_every=4, verdict_every=8)
    res_a = solve_rbcd_sharded(meas, 8, mesh=make_mesh(8), **kw)
    res_p = solve_rbcd_sharded(meas, 8, mesh=make_mesh(8),
                               exchange="ppermute", **kw)
    assert res_p.iterations == res_a.iterations
    assert res_p.terminated_by == res_a.terminated_by
    np.testing.assert_array_equal(np.asarray(res_p.T), np.asarray(res_a.T))


def test_sharded_gn_tail_matches_host_gn_tail(rng):
    """ACCEPTANCE: the device-resident sharded GN-CG tail reaches the
    same final cost as the host-f64 ``refine.gn_tail`` (rel <= 1e-6) from
    the same handoff iterate, through the same gate."""
    meas = _noisy(7, n=80, num_lc=16, noise=0.1)
    params = AgentParams(d=3, r=5, num_robots=8, rel_change_tol=0.0)
    res = solve_rbcd_sharded(meas, 8, mesh=make_mesh(8), params=params,
                             max_iters=30, grad_norm_tol=0.0,
                             eval_every=10, verdict_every=10)
    part = partition_contiguous(meas, 8)
    graph, meta = rbcd.build_graph(part, 5, jnp.float64)
    e64 = refine.host_edges_f64(part.meas_global)
    Xg0 = np.asarray(rbcd.gather_to_global(res.X, graph,
                                           part.meas_global.num_poses),
                     np.float64)
    cfg = refine.GNTailConfig(max_outer=10, grad_norm_tol=1e-3,
                              cg_max_iters=200)
    host = refine.gn_tail(Xg0, e64, cfg)
    _Xa, sh = gn_tail_sharded(res.X, graph, meta, mesh=make_mesh(8),
                              cfg=cfg)
    assert host.terminated_by == "grad_norm"
    assert sh.terminated_by == "grad_norm"
    assert sh.grad_norm_history[-1] < cfg.grad_norm_tol
    rel = abs(sh.cost_history[-1] - host.cost_history[-1]) \
        / abs(host.cost_history[-1])
    assert rel <= 1e-6, rel


def test_sharded_gn_tail_zero_transfers_inside_cg(rng):
    """The CG loop and the backtracking retraction are device-resident:
    the only host fetches are the per-outer gate scalar and stats vector
    (through ``rbcd._host_fetch``), far fewer than the CG iterations they
    drive."""
    meas = _noisy(7, n=80, num_lc=16, noise=0.1)
    params = AgentParams(d=3, r=5, num_robots=8, rel_change_tol=0.0)
    res = solve_rbcd_sharded(meas, 8, mesh=make_mesh(8), params=params,
                             max_iters=20, grad_norm_tol=0.0,
                             eval_every=10, verdict_every=10)
    part = partition_contiguous(meas, 8)
    graph, meta = rbcd.build_graph(part, 5, jnp.float64)
    cfg = refine.GNTailConfig(max_outer=6, grad_norm_tol=1e-3,
                              cg_max_iters=200)
    counted = [0]
    orig = rbcd._host_fetch

    def counting(x):
        counted[0] += 1
        return orig(x)

    rbcd._host_fetch = counting
    try:
        _Xa, sh = gn_tail_sharded(res.X, graph, meta, mesh=make_mesh(8),
                                  cfg=cfg)
    finally:
        rbcd._host_fetch = orig
    # One gate fetch per loop entry + one stats fetch per executed outer.
    assert counted[0] == len(sh.grad_norm_history) + sh.outer_iterations \
        + (1 if sh.terminated_by == "no_decrease" else 0)
    assert sh.cg_iterations > counted[0], (sh.cg_iterations, counted[0])


def test_solve_sharded_with_gn_tail_extends_histories(rng):
    """``solve_rbcd_sharded(gn_tail=cfg)`` appends the tail trajectory to
    the returned histories, re-finalizes T from the polished iterate, and
    reports the tail's termination when it converges through the gate."""
    meas = _noisy(7, n=80, num_lc=16, noise=0.1)
    params = AgentParams(d=3, r=5, num_robots=8, rel_change_tol=0.0)
    cfg = refine.GNTailConfig(max_outer=8, grad_norm_tol=1e-3,
                              cg_max_iters=200)
    res = solve_rbcd_sharded(meas, 8, mesh=make_mesh(8), params=params,
                             max_iters=20, grad_norm_tol=0.0,
                             eval_every=10, verdict_every=10,
                             gn_tail=cfg)
    res_no = solve_rbcd_sharded(meas, 8, mesh=make_mesh(8), params=params,
                                max_iters=20, grad_norm_tol=0.0,
                                eval_every=10, verdict_every=10)
    assert res.terminated_by == "grad_norm"
    assert len(res.cost_history) > len(res_no.cost_history)
    assert res.grad_norm_history[-1] < cfg.grad_norm_tol
    assert res.cost_history[-1] <= res_no.cost_history[-1] + 1e-12
    assert res.T.shape == (meas.num_poses, 3, 4)
    assert np.isfinite(np.asarray(res.T)).all()


def test_sharded_verdict_telemetry_and_report(rng, tmp_path):
    """Telemetry on: the sharded verdict solve emits the same event
    stream schema as the single-device loop (solve_end with the verdict
    word, host_syncs_per_100_rounds == 100/K), the sharded_solve setup
    event carries the overlap/verdict fields, and the report CLI renders
    the 'sharded' section."""
    from dpgo_tpu import obs
    from dpgo_tpu.obs.events import read_events
    from dpgo_tpu.obs.report import render_report

    meas = _noisy(rng)
    params = AgentParams(d=3, r=5, num_robots=8, rel_change_tol=0.0)
    run_dir = str(tmp_path / "run")
    with obs.run_scope(run_dir):
        solve_rbcd_sharded(meas, 8, mesh=make_mesh(8), params=params,
                           max_iters=24, grad_norm_tol=0.0, eval_every=4,
                           verdict_every=8)
    events = read_events(f"{run_dir}/events.jsonl")
    setup = [e for e in events if e.get("event") == "sharded_solve"]
    assert setup and setup[0]["mesh_size"] == 8
    assert setup[0]["overlap"] is True
    assert setup[0]["verdict_every"] == 8
    ends = [e for e in events if e.get("event") == "solve_end"]
    assert ends and ends[0]["verdict_every"] == 8
    syncs = [e for e in events if e.get("event") == "metric"
             and e.get("metric") == "host_syncs_per_100_rounds"]
    # Telemetry on: one word + one lazy history fetch per K-round
    # boundary (the single-device verdict loop's accounting too).
    assert syncs and syncs[0]["value"] == pytest.approx(2 * 100.0 / 8)
    txt = render_report(run_dir)
    assert "sharded:" in txt
    assert "verdict sync" in txt


def test_regress_gates_sharded_host_sync_rate(tmp_path):
    """A sharded record whose host-sync rate grows regresses under
    ``report --compare`` exactly like a single-device one — the
    readback-kill gate covers the mesh path."""
    from dpgo_tpu import obs
    from dpgo_tpu.obs.regress import compare_runs

    def fake_run(d, syncs):
        with obs.run_scope(str(d)):
            run = obs.get_run()
            run.set_fingerprint(solver="solve_rbcd_sharded", mesh_size=8,
                                exchange="all_gather", num_robots=8)
            run.metric("solver_cost", 1.0, phase="eval", iteration=8)
            run.metric("solver_grad_norm", 0.05, phase="eval", iteration=8)
            run.metric("host_syncs_per_100_rounds", syncs, phase="solve",
                       fetches=int(syncs), rounds=100)

    fake_run(tmp_path / "a", 0.2)
    fake_run(tmp_path / "b", 12.5)  # someone reopened the readback
    cmp = compare_runs(str(tmp_path / "a"), str(tmp_path / "b"))
    assert cmp["rc"] == 2
    assert "host_syncs_per_100_rounds" in cmp["regressions"]
    fake_run(tmp_path / "c", 0.2)
    assert compare_runs(str(tmp_path / "a"), str(tmp_path / "c"))["rc"] == 0
