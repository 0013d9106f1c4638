"""The per-solve programs are keyed on shapes, not on the problem's arrays.

``make_verdict_program``, ``make_terminal_epilogue`` and
``_make_central_metrics`` bind the problem and call module-level jits that
take ``graph``/``edges_g`` as arguments, so a second solve of the same
padded shape (new measurements, same graph) traces, lowers and builds
nothing; a problem of another shape compiles its own executables."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dpgo_tpu import obs
from dpgo_tpu.config import AgentParams
from dpgo_tpu.models import rbcd
from dpgo_tpu.types import edge_set_from_measurements
from dpgo_tpu.utils.partition import partition_contiguous
from dpgo_tpu.utils.synthetic import make_measurements_vectorized


def _city(n: int, seed: int = 1):
    meas, _ = make_measurements_vectorized(
        np.random.default_rng(seed), n=n, d=2, num_lc=n // 2, rot_noise=0.01,
        trans_noise=0.01, lc_radius=1.0)
    return meas


def _redraw(meas, seed: int):
    """The same graph with a fresh translation-noise draw."""
    rng = np.random.default_rng(seed)
    return dataclasses.replace(
        meas, t=meas.t + 0.01 * rng.standard_normal(meas.t.shape))


def _solve(meas, agents: int = 2):
    params = AgentParams(d=2, r=3, num_robots=agents, rel_change_tol=0.0,
                         certify_mode="device")
    prob = rbcd.prepare_problem(meas, agents, params=params,
                                dtype=jnp.float32, init="chordal")
    return rbcd.dispatch_prepared(prob, max_iters=40, grad_norm_tol=0.1,
                                  eval_every=5, verdict_every=20)


def _total(snap, family, **match):
    fam = snap.get(family)
    return sum(s["value"] for s in (fam["series"] if fam else [])
               if all(s["labels"].get(k) == v for k, v in match.items()))


def _programs(snap):
    fam = snap.get("jax_programs_total")
    return {s["labels"]["program"] for s in (fam["series"] if fam else [])}


@pytest.fixture(scope="module")
def three_solves(tmp_path_factory):
    """Registry snapshots after a solve, a second noise draw of the same
    graph, and a graph with more poses, all under one telemetry run."""
    obs.end_run()
    d = str(tmp_path_factory.mktemp("program_keys") / "run")
    meas = _city(200)
    snaps = []
    with obs.run_scope(d) as run:
        for m in (meas, _redraw(meas, 7), _city(232)):
            res = _solve(m)
            assert res.certificate is not None
            snaps.append(run.registry.snapshot())
    return snaps


def test_same_shape_solve_compiles_nothing(three_solves):
    first, second, _ = three_solves
    assert _total(first, "jax_programs_total") > 0
    assert _total(second, "jax_programs_total") == \
        _total(first, "jax_programs_total")
    enq = dict(span="rbcd.enqueue")
    assert _total(first, "jax_compile_seconds_total", **enq) > 0
    assert _total(second, "jax_compile_seconds_total", **enq) == \
        _total(first, "jax_compile_seconds_total", **enq)


def test_new_pose_count_compiles_its_own_programs(three_solves):
    """The cache is keyed on shape, not disabled: a graph with another
    pose count builds the verdict and epilogue executables anew."""
    _, second, third = three_solves
    assert _total(third, "jax_programs_total") > \
        _total(second, "jax_programs_total")
    grown = {p for p in _programs(third)
             if _total(third, "jax_programs_total", program=p)
             > _total(second, "jax_programs_total", program=p)}
    assert any("_verdict_step_jit" in p for p in grown), grown
    assert any("_epilogue_jit" in p for p in grown), grown


def test_every_solve_inits_by_host_factor(three_solves):
    """In the certified cell's settings each solve's chordal init is
    factored on the host: one ``host_factor`` count per solve, no device
    CG."""
    for k, snap in enumerate(three_solves, start=1):
        assert _total(snap, "chordal_init_total", path="host_factor") == k
        assert _total(snap, "chordal_init_total", path="device_cg") == 0


def test_same_shape_init_builds_no_program(tmp_path):
    """``prepare_problem(init="chordal")`` builds one program, the cast,
    lift and scatter keyed on shapes; a second noise draw of the graph
    builds none."""
    obs.end_run()
    meas = _city(184, seed=11)
    params = AgentParams(d=2, r=3, num_robots=2)
    with obs.run_scope(str(tmp_path / "run")) as run:
        built = []
        for m in (meas, _redraw(meas, 5)):
            before = run.registry.snapshot()
            rbcd.prepare_problem(m, 2, params=params, dtype=jnp.float32,
                                 init="chordal")
            after = run.registry.snapshot()
            built.append({p for p in _programs(after)
                          if _total(after, "jax_programs_total", program=p)
                          > _total(before, "jax_programs_total", program=p)})
    assert any("_lift_scatter_jit" in p for p in built[0]), built[0]
    assert not any("_global_init_jit" in p for p in built[0]), built[0]
    assert built[1] == set()


@pytest.mark.parametrize("path", ["closure", "batched"])
def test_verdict_update_shared_by_both_paths(monkeypatch, path):
    """The single-device program, the ``metrics_body=`` closure and the
    serving plane's batched program all trace the one
    ``_verdict_update``: the closure gives bitwise the library's states,
    and ``run_bucket``'s history rows are bitwise the library program's
    on the same states."""
    calls = []
    real = rbcd._verdict_update

    def spy(*args, **kw):
        calls.append(kw["gnc"])
        return real(*args, **kw)

    monkeypatch.setattr(rbcd, "_verdict_update", spy)
    jax.clear_caches()
    meas = _city(60, seed=3)
    params = AgentParams(d=2, r=3, num_robots=3, rel_change_tol=0.0)
    if path == "batched":
        _check_batched_rows(meas, params, calls)
        return
    part = partition_contiguous(meas, 3)
    graph, meta = rbcd.build_graph(part, params.r, jnp.float64,
                                   sel_mode=rbcd.resolved_sel_mode(params))
    state = rbcd.init_state(
        graph, meta, rbcd.centralized_chordal_init(part, meta, graph,
                                                   jnp.float64),
        params=params)
    n_total, num_meas = part.meas_global.num_poses, len(part.meas_global)
    edges_g = edge_set_from_measurements(part.meas_global,
                                         dtype=jnp.float64)
    kw = dict(grad_norm_tol=1e-12, robust_params=params.robust,
              max_evals=3)
    single = rbcd.make_verdict_program(graph, edges_g, n_total, num_meas,
                                       False, **kw)
    body = rbcd._central_metrics_body(graph, edges_g, n_total, num_meas,
                                      False)
    closure = rbcd.make_verdict_program(graph, edges_g, n_total, num_meas,
                                        False, metrics_body=body, **kw)
    vs_a = vs_b = rbcd.init_verdict_state(3, meta.num_robots, jnp.float64,
                                          False)
    for _ in range(3):
        state = rbcd.rbcd_segment(state, graph, 2, meta, params)
        args = (state.X, state.weights, state.ready, state.mu,
                state.rel_change, state.iteration)
        vs_a = single(*args, vs_a)
        vs_b = closure(*args, vs_b)
    assert len(calls) == 2
    for a, b in zip(jax.tree.leaves(vs_a), jax.tree.leaves(vs_b)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert int(np.asarray(vs_a.eval_idx)) == 3


def _check_batched_rows(meas, params, calls):
    from dpgo_tpu.serve import (ExecutableCache, bucket_shape_of,
                                pad_problem, run_bucket)

    prob = rbcd.prepare_problem(meas, 3, params=params, dtype=jnp.float64,
                                init=None, pallas_sel=False)
    shape = bucket_shape_of(prob, 64)
    p = pad_problem(prob, shape)
    states = []
    (res,), _ = run_bucket(
        [p], ExecutableCache(), max_iters=6, grad_norm_tol=1e-12,
        eval_every=2, session_cb=lambda _it, sts: states.append(sts[0]))
    assert calls == [None]  # traced once, with the batched statics
    single = rbcd.make_verdict_program(
        p.graph, p.edges_g, shape.n_total, shape.num_meas, False,
        grad_norm_tol=1e-12, robust_params=None, max_evals=3)
    vs = rbcd.init_verdict_state(3, p.meta.num_robots, jnp.float64, False)
    for st in states:
        vs = single(st.X, st.weights, st.ready, st.mu, st.rel_change,
                    st.iteration, vs)
    hist = np.asarray(vs.hist)
    assert len(states) == len(res.cost_history) == 3
    assert hist[:, 0].tobytes() == np.asarray(res.cost_history).tobytes()
    assert hist[:, 1].tobytes() == \
        np.asarray(res.grad_norm_history).tobytes()


def test_gradient_tolerance_is_traced():
    """Another gate (the benchmark's control loosens it tenfold) runs the
    same verdict executable: the tolerance is an argument, not a static."""
    meas = _city(48, seed=5)
    params = AgentParams(d=2, r=3, num_robots=2, rel_change_tol=0.0)
    part = partition_contiguous(meas, 2)
    graph, meta = rbcd.build_graph(part, params.r, jnp.float32,
                                   sel_mode=rbcd.resolved_sel_mode(params))
    X0 = rbcd.centralized_chordal_init(part, meta, graph, jnp.float32)
    state = rbcd.init_state(graph, meta, X0, params=params)
    edges_g = edge_set_from_measurements(part.meas_global,
                                         dtype=jnp.float32)
    n_total, num_meas = part.meas_global.num_poses, len(part.meas_global)
    args = (state.X, state.weights, state.ready, state.mu, state.rel_change,
            state.iteration)
    words = []
    before = rbcd._verdict_step_jit._cache_size()
    for tol in (1e-9, 1e9):
        step = rbcd.make_verdict_program(
            graph, edges_g, n_total, num_meas, False, grad_norm_tol=tol,
            robust_params=None, max_evals=1)
        vs = rbcd.init_verdict_state(1, meta.num_robots, jnp.float32, False)
        words.append(rbcd.unpack_verdict(int(step(*args, vs).word)))
    assert rbcd._verdict_step_jit._cache_size() == before + 1
    assert [w["status"] for w in words] == ["running", "grad_norm"]
