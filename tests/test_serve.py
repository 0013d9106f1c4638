"""Serving plane (``dpgo_tpu.serve``): bucketing, executable cache,
batched-vs-sequential parity, admission control, warm pools, SLO
telemetry, and the zero-overhead fence."""

import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from dpgo_tpu import obs
from dpgo_tpu.config import AgentParams, Schedule
from dpgo_tpu.models import rbcd
from dpgo_tpu.serve import (BucketShape, ExecutableCache, OverCapacityError,
                            SolveRequest, SolveServer, bucket_shape_of,
                            pad_problem, problem_fingerprint, run_bucket)
from dpgo_tpu.serve.cache import fingerprint_key
from dpgo_tpu.serve.server import SolveTicket  # noqa: F401 (API surface)
from dpgo_tpu.utils.synthetic import make_measurements

PARAMS = AgentParams(d=3, r=5, num_robots=2)


def _problem(n=24, seed=0, num_lc=5):
    meas, _ = make_measurements(np.random.default_rng(seed), n=n, d=3,
                                num_lc=num_lc, rot_noise=0.01,
                                trans_noise=0.01)
    return meas


def _request(meas, **kw):
    kw.setdefault("params", PARAMS)
    kw.setdefault("max_iters", 4)
    kw.setdefault("grad_norm_tol", 1e-12)
    kw.setdefault("eval_every", 2)
    return SolveRequest(meas=meas, num_robots=2, **kw)


# ---------------------------------------------------------------------------
# The prepare/dispatch split (the run_rbcd refactor)
# ---------------------------------------------------------------------------

def test_prepare_dispatch_matches_solve_rbcd():
    meas = _problem()
    a = rbcd.solve_rbcd(meas, 2, params=PARAMS, max_iters=4,
                        grad_norm_tol=1e-12, eval_every=2)
    prob = rbcd.prepare_problem(meas, 2, params=PARAMS)
    b = rbcd.dispatch_prepared(prob, max_iters=4, grad_norm_tol=1e-12,
                               eval_every=2)
    assert a.cost_history == b.cost_history
    assert a.grad_norm_history == b.grad_norm_history
    np.testing.assert_array_equal(np.asarray(a.T), np.asarray(b.T))


def test_prepared_problem_is_reusable():
    prob = rbcd.prepare_problem(_problem(), 2, params=PARAMS)
    r1 = rbcd.dispatch_prepared(prob, max_iters=2, grad_norm_tol=1e-12)
    r2 = rbcd.dispatch_prepared(prob, max_iters=2, grad_norm_tol=1e-12)
    assert r1.cost_history == r2.cost_history


def test_dispatch_without_init_raises():
    prob = rbcd.prepare_problem(_problem(), 2, params=PARAMS, init=None)
    with pytest.raises(ValueError, match="no initial state"):
        rbcd.dispatch_prepared(prob, max_iters=2)


# ---------------------------------------------------------------------------
# Bucketing and padding
# ---------------------------------------------------------------------------

def test_bucket_shapes_coalesce_nearby_and_split_far_sizes():
    pa = rbcd.prepare_problem(_problem(n=24, seed=0), 2, params=PARAMS,
                              init=None, pallas_sel=False)
    pb = rbcd.prepare_problem(_problem(n=28, seed=1), 2, params=PARAMS,
                              init=None, pallas_sel=False)
    pc = rbcd.prepare_problem(_problem(n=200, seed=2, num_lc=40), 2,
                              params=PARAMS, init=None, pallas_sel=False)
    sa, sb = bucket_shape_of(pa, 64), bucket_shape_of(pb, 64)
    sc = bucket_shape_of(pc, 64)
    assert sa == sb  # within one quantum: same bucket
    assert sa != sc  # far apart: different bucket
    assert isinstance(sa, BucketShape)


def test_padded_batched_solve_matches_sequential():
    """A batch of mixed-size problems padded into one bucket must agree
    with per-problem solve_rbcd on costs and trajectories — padding is
    masking, not new math."""
    metas = [_problem(n=24, seed=0), _problem(n=27, seed=1, num_lc=6)]
    seq = [rbcd.solve_rbcd(m, 2, params=PARAMS, max_iters=4,
                           grad_norm_tol=1e-12, eval_every=2)
           for m in metas]
    probs = [rbcd.prepare_problem(m, 2, params=PARAMS, init=None,
                                  pallas_sel=False) for m in metas]
    shapes = [bucket_shape_of(p, 64) for p in probs]
    assert shapes[0] == shapes[1]
    padded = [pad_problem(p, shapes[0]) for p in probs]
    cache = ExecutableCache()
    results, info = run_bucket(padded, cache, max_iters=4,
                               grad_norm_tol=1e-12, eval_every=2)
    assert info["size"] == 2 and info["batch"] == 2
    for a, b in zip(seq, results):
        ra = np.asarray(a.cost_history)
        rb = np.asarray(b.cost_history)
        np.testing.assert_allclose(ra, rb, rtol=1e-8, atol=1e-12)
        np.testing.assert_allclose(np.asarray(a.T), np.asarray(b.T),
                                   atol=1e-7)
        assert a.T.shape == b.T.shape  # sliced back to the real pose count
        np.testing.assert_allclose(np.asarray(a.weights),
                                   np.asarray(b.weights), atol=1e-8)


def test_run_bucket_refuses_mixed_shapes():
    pa = rbcd.prepare_problem(_problem(n=24, seed=0), 2, params=PARAMS,
                              init=None, pallas_sel=False)
    pb = rbcd.prepare_problem(_problem(n=24, seed=1), 2, params=PARAMS,
                              init=None, pallas_sel=False)
    padded_a = pad_problem(pa, bucket_shape_of(pa, 32))
    padded_b = pad_problem(pb, bucket_shape_of(pb, 128))
    with pytest.raises(ValueError, match="never mix incompatible shapes"):
        run_bucket([padded_a, padded_b], ExecutableCache(), max_iters=1)


def test_pad_problem_rejects_too_small_bucket():
    p = rbcd.prepare_problem(_problem(n=40, seed=0), 2, params=PARAMS,
                             init=None, pallas_sel=False)
    tiny = BucketShape(n_max=1, e_max=1, s_max=1, p_max=1, k_inc=1,
                       n_total=1, num_meas=1)
    with pytest.raises(ValueError, match="smaller than problem"):
        pad_problem(p, tiny)


# ---------------------------------------------------------------------------
# The fingerprint-keyed executable cache
# ---------------------------------------------------------------------------

def test_executable_cache_identical_fingerprints_reuse():
    meta = rbcd.GraphMeta(num_robots=2, n_max=32, e_max=64, s_max=8,
                          p_max=8, d=3, rank=5)
    shape = BucketShape(32, 64, 8, 8, 8, 64, 64)
    cache = ExecutableCache()
    builds = []
    fp = problem_fingerprint(meta, PARAMS, jnp.float64, shape, 2, "segment")
    for _ in range(3):
        cache.get(problem_fingerprint(meta, PARAMS, jnp.float64, shape, 2,
                                      "segment"),
                  lambda: builds.append(1) or "exe")
    assert cache.compiles == 1 and len(builds) == 1
    assert cache.hits == 2
    # The key IS the canonical fingerprint: same content, same key.
    assert fingerprint_key(fp) == fingerprint_key(
        problem_fingerprint(meta, PARAMS, jnp.float64, shape, 2, "segment"))


def test_executable_cache_rank_dtype_schedule_miss():
    meta = rbcd.GraphMeta(num_robots=2, n_max=32, e_max=64, s_max=8,
                          p_max=8, d=3, rank=5)
    shape = BucketShape(32, 64, 8, 8, 8, 64, 64)
    cache = ExecutableCache()
    base = problem_fingerprint(meta, PARAMS, jnp.float64, shape, 2, "segment")
    cache.get(base, lambda: "exe")
    # Differing rank
    meta_r6 = rbcd.GraphMeta(num_robots=2, n_max=32, e_max=64, s_max=8,
                             p_max=8, d=3, rank=6)
    cache.get(problem_fingerprint(meta_r6, PARAMS, jnp.float64, shape, 2,
                                  "segment"), lambda: "exe-r6")
    # Differing dtype
    cache.get(problem_fingerprint(meta, PARAMS, jnp.float32, shape, 2,
                                  "segment"), lambda: "exe-f32")
    # Differing schedule
    greedy = AgentParams(d=3, r=5, num_robots=2, schedule=Schedule.GREEDY)
    cache.get(problem_fingerprint(meta, greedy, jnp.float64, shape, 2,
                                  "segment"), lambda: "exe-greedy")
    assert cache.compiles == 4 and cache.hits == 0
    # And every one of those keys is distinct.
    assert len(cache) == 4


def test_warm_pool_precompiles_bucket_executables():
    with SolveServer(max_batch=2, batch_window_s=0.005, quantum=64) as srv:
        warm_req = _request(_problem(n=24, seed=3))
        assert srv.warm([warm_req]) == 1
        compiles_after_warm = srv.cache.compiles
        assert compiles_after_warm >= 3  # segment + verdict + epilogue
        res = srv.solve(_request(_problem(n=25, seed=4)), timeout=300)
        assert np.isfinite(res.cost_history[-1])
        # Same bucket, same batch width: the live request reused the
        # warmed executables — the compile counter stayed flat.
        assert srv.cache.compiles == compiles_after_warm
        assert srv.cache.hits >= 3


# ---------------------------------------------------------------------------
# Server: batching, admission control, deadlines
# ---------------------------------------------------------------------------

def test_server_concurrent_mixed_sizes_match_sequential():
    metas = [_problem(n=24 + k, seed=k) for k in range(4)]
    seq = [rbcd.solve_rbcd(m, 2, params=PARAMS, max_iters=4,
                           grad_norm_tol=1e-12, eval_every=2)
           for m in metas]
    with SolveServer(max_batch=4, batch_window_s=0.05, quantum=64) as srv:
        tickets = [srv.submit(_request(m, tenant=f"t{k % 2}"))
                   for k, m in enumerate(metas)]
        results = [t.result(timeout=300) for t in tickets]
    for a, b in zip(seq, results):
        assert abs(a.cost_history[-1] - b.cost_history[-1]) <= \
            1e-8 * max(1.0, abs(a.cost_history[-1]))
        assert np.isfinite(b.cost_history[-1])


def test_admission_queue_full_and_tenant_quota(monkeypatch):
    # Pin the worker so the queue fills deterministically.
    monkeypatch.setattr(SolveServer, "_dispatch_once",
                        lambda self: time.sleep(0.01))
    srv = SolveServer(max_batch=2, max_queue=2, tenant_quota=2,
                      batch_window_s=0.0)
    try:
        m = _problem()
        srv.submit(_request(m, tenant="a"))
        srv.submit(_request(m, tenant="b"))
        with pytest.raises(OverCapacityError) as exc:
            srv.submit(_request(m, tenant="c"))
        assert exc.value.reason == "queue"
    finally:
        srv.close()
    # Per-tenant quota, queue not full.
    monkeypatch.setattr(SolveServer, "_dispatch_once",
                        lambda self: time.sleep(0.01))
    srv = SolveServer(max_batch=2, max_queue=16, tenant_quota=1,
                      batch_window_s=0.0)
    try:
        t1 = srv.submit(_request(m, tenant="a"))
        with pytest.raises(OverCapacityError) as exc:
            srv.submit(_request(m, tenant="a"))
        assert exc.value.reason == "tenant_quota"
        srv.submit(_request(m, tenant="b"))  # other tenants unaffected
    finally:
        srv.close()
    # Close sheds whatever was still queued, with a clean reason.
    with pytest.raises(OverCapacityError) as exc:
        t1.result(timeout=5)
    assert exc.value.reason == "closed"


def test_deadline_expired_request_is_shed():
    with SolveServer(max_batch=2, batch_window_s=0.0) as srv:
        t = srv.submit(_request(_problem(), deadline_s=0.0))
        with pytest.raises(OverCapacityError) as exc:
            t.result(timeout=30)
        assert exc.value.reason == "deadline"


def test_bad_request_reports_instead_of_killing_worker():
    with SolveServer(max_batch=2, batch_window_s=0.0) as srv:
        bad = _problem()
        t = srv.submit(SolveRequest(meas=bad, num_robots=0, params=PARAMS))
        with pytest.raises(Exception):
            t.result(timeout=60)
        # The worker survived: a good request still completes.
        res = srv.solve(_request(_problem(n=24, seed=9)), timeout=300)
        assert np.isfinite(res.cost_history[-1])


# ---------------------------------------------------------------------------
# SLO telemetry and the zero-overhead fence
# ---------------------------------------------------------------------------

def test_serving_slo_metrics_and_report_section(tmp_path):
    run_dir = str(tmp_path / "serve_run")
    with obs.run_scope(run_dir):
        with SolveServer(max_batch=4, batch_window_s=0.05,
                         quantum=64) as srv:
            tickets = [srv.submit(_request(_problem(n=24 + k, seed=k),
                                           tenant=f"t{k % 2}"))
                       for k in range(3)]
            for t in tickets:
                t.result(timeout=300)
            # A shed lands in the same run.
            shed = srv.submit(_request(_problem(), deadline_s=0.0))
            with pytest.raises(OverCapacityError):
                shed.result(timeout=30)
    from dpgo_tpu.obs.report import render_report, report_data

    text = render_report(run_dir)
    assert "serving:" in text
    assert "tenant t0" in text and "latency p50" in text
    assert "shed:" in text
    data = report_data(run_dir)
    srv_stats = data["serving"]
    assert srv_stats["tenants"]["t0"]["requests"] >= 1
    assert srv_stats["tenants"]["t0"]["latency_p50_s"] is not None
    assert srv_stats["tenants"]["t0"]["latency_p99_s"] is not None
    assert srv_stats["batches"]["count"] >= 1
    assert srv_stats["batches"]["mean_occupancy"] is not None
    assert any(s["reason"] == "deadline" for s in srv_stats["shed"])
    # Histograms landed in the metrics snapshot with tenant labels.
    assert "serve_solve_latency_seconds" in data["metrics"]
    assert "serve_requests_total" in data["metrics"]


def test_telemetry_off_serving_constructs_no_obs_objects(monkeypatch, tmp_path):
    """The zero-overhead acceptance gate, extended to the serve plane:
    with no ambient run, a full submit -> batch -> result cycle must
    construct no obs objects and emit nothing — no spans, no HTTP
    sidecar threads (even with metrics_port set), no device profiler
    (even with profile_dir set), no SLO trackers, no AOT/cost_analysis
    profiling wrappers."""
    import dpgo_tpu.obs.events as events_mod
    import dpgo_tpu.obs.health as health_mod
    import dpgo_tpu.obs.metrics as metrics_mod
    import dpgo_tpu.obs.profile as profile_mod
    import dpgo_tpu.obs.run as run_mod
    import dpgo_tpu.obs.trace as trace_mod
    import dpgo_tpu.serve.server as server_mod
    import dpgo_tpu.serve.statusz as statusz_mod

    assert obs.get_run() is None

    def boom(*a, **kw):
        raise AssertionError("obs touched with telemetry off")

    monkeypatch.setattr(events_mod.EventStream, "emit", boom)
    monkeypatch.setattr(run_mod, "materialize", boom)
    monkeypatch.setattr(obs, "materialize", boom)
    monkeypatch.setattr(run_mod.TelemetryRun, "set_fingerprint", boom)
    monkeypatch.setattr(metrics_mod.MetricsRegistry, "counter", boom)
    monkeypatch.setattr(metrics_mod.MetricsRegistry, "gauge", boom)
    monkeypatch.setattr(metrics_mod.MetricsRegistry, "histogram", boom)
    monkeypatch.setattr(metrics_mod.Counter, "inc", boom)
    monkeypatch.setattr(metrics_mod.Gauge, "set", boom)
    monkeypatch.setattr(metrics_mod.Histogram, "observe_many", boom)
    monkeypatch.setattr(trace_mod.Span, "__init__", boom)
    monkeypatch.setattr(trace_mod, "emit_span", boom)
    monkeypatch.setattr(health_mod.HealthMonitor, "__init__", boom)
    monkeypatch.setattr(statusz_mod.MetricsSidecar, "__init__", boom)
    monkeypatch.setattr(profile_mod.ProfiledExecutable, "__init__", boom)
    monkeypatch.setattr(profile_mod.ProfilerWindow, "__init__", boom)
    monkeypatch.setattr(profile_mod, "aot_compile_profile", boom)
    monkeypatch.setattr(server_mod._SloTracker, "__init__", boom)

    from dpgo_tpu.serve import ServeSLO

    with SolveServer(max_batch=2, batch_window_s=0.005, quantum=64,
                     metrics_port=0, profile_dir=str(tmp_path / "prof"),
                     slo=ServeSLO(latency_s=1e-9)) as srv:
        assert srv.sidecar is None
        assert srv._profiler is None
        res = srv.solve(_request(_problem(n=24, seed=11)), timeout=300)
        # Shed paths are fenced too.
        t = srv.submit(_request(_problem(), deadline_s=0.0))
        with pytest.raises(OverCapacityError):
            t.result(timeout=30)
        assert srv._slo_state == {}
    assert np.isfinite(res.cost_history[-1])


def test_submissions_from_many_threads_are_safe():
    metas = [_problem(n=24, seed=k) for k in range(4)]
    results = [None] * 4
    with SolveServer(max_batch=4, batch_window_s=0.05, quantum=64) as srv:
        def go(k):
            results[k] = srv.solve(_request(metas[k]), timeout=300)

        threads = [threading.Thread(target=go, args=(k,)) for k in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert all(r is not None and np.isfinite(r.cost_history[-1])
               for r in results)


def test_run_bucket_verdict_mode_matches_legacy():
    """Batched verdict vector: a coarse cadence
    (``verdict_every=4`` at ``eval_every=2``) reproduces the default
    cadence's (K = eval_every) histories, per-problem termination labels,
    and round counts — including members that latch termination at
    different evals — with one [B] word fetch per K rounds."""
    metas = [_problem(n=24, seed=0), _problem(n=27, seed=1, num_lc=6)]
    probs = [rbcd.prepare_problem(m, 2, params=PARAMS, init=None,
                                  pallas_sel=False) for m in metas]
    shapes = [bucket_shape_of(p, 64) for p in probs]
    padded = [pad_problem(p, shapes[0]) for p in probs]
    res_a, info_a = run_bucket(padded, ExecutableCache(), max_iters=8,
                               grad_norm_tol=1e-3, eval_every=2)
    res_b, info_b = run_bucket(padded, ExecutableCache(), max_iters=8,
                               grad_norm_tol=1e-3, eval_every=2,
                               verdict_every=4)
    # info["rounds"] may include the verdict window's polish overshoot
    # (the host learns of termination at the K boundary); the REPORTED
    # per-problem results must be identical.
    assert info_b["rounds"] >= info_a["rounds"]
    for a, b in zip(res_a, res_b):
        assert (a.iterations, a.terminated_by) == \
            (b.iterations, b.terminated_by)
        assert a.cost_history == b.cost_history
        assert a.grad_norm_history == b.grad_norm_history
    with pytest.raises(ValueError, match="verdict_every"):
        run_bucket(padded, ExecutableCache(), max_iters=4,
                   grad_norm_tol=1e-3, eval_every=3, verdict_every=4)


def test_batched_verdict_compiles_once_across_gates():
    """The batched verdict program takes the gradient gate as an
    argument: two buckets at gates 1e-3 and 1e-2 share one verdict cache
    entry (and one executable), and each gate still decides its own
    termination."""
    params = AgentParams(d=3, r=5, num_robots=2, rel_change_tol=0.0)
    prob = rbcd.prepare_problem(_problem(n=24, seed=0), 2, params=params,
                                init=None, pallas_sel=False)
    padded = [pad_problem(prob, bucket_shape_of(prob, 64))]
    cache = ExecutableCache()
    gates = (1e-3, 1e-2)
    res = [run_bucket(padded, cache, max_iters=200, grad_norm_tol=tol,
                      eval_every=2)[0][0] for tol in gates]
    (vex,) = [e for k, e in cache._entries.items()
              if json.loads(k)["kind"].startswith("verdict")]
    assert cache.compiles == len(cache) == 3  # segment, verdict, epilogue
    assert vex._cache_size() == 1
    for r, tol in zip(res, gates):
        assert r.terminated_by == "grad_norm"
        assert r.grad_norm_history[-1] < tol <= r.grad_norm_history[-2]
    assert res[1].iterations <= res[0].iterations


def test_server_verdict_every_plumbs_to_dispatch():
    """SolveServer(verdict_every=K) solves through the batched verdict
    loop and returns the same result as a default server; a request
    whose eval_every does not divide K runs at K = eval_every rather
    than erroring, and matches a default server's solve."""
    meas = _problem()
    with SolveServer(max_batch=4, verdict_every=4) as srv:
        t = srv.submit(_request(meas, eval_every=2))
        r_v = t.result(timeout=60)
        t2 = srv.submit(_request(meas, eval_every=3))  # runs at K = 3
        r_l = t2.result(timeout=60)
    with SolveServer(max_batch=4) as srv:
        r_ref = srv.submit(_request(meas, eval_every=2)).result(timeout=60)
        r_ref3 = srv.submit(_request(meas, eval_every=3)).result(timeout=60)
    assert r_v.cost_history == r_ref.cost_history
    assert r_l.cost_history == r_ref3.cost_history
    assert r_l.grad_norm_history == r_ref3.grad_norm_history
