"""Spans and compile counters on the library solve path.

With a telemetry run installed, ``prepare_problem`` + ``dispatch_prepared``
time their host work from inside the program (``obs.trace.span``): each
span observes ``span_seconds{span=<name>}``, nests under its phase by
``parent``, opens a ``dpgo.<name>`` annotation on the profiler's host
plane, and every compile lands in ``jax_compile_seconds_total`` labelled
with the innermost open span."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dpgo_tpu import obs
from dpgo_tpu.config import AgentParams
from dpgo_tpu.models import certify, rbcd
from dpgo_tpu.obs import compile as compile_mod
from dpgo_tpu.obs.events import read_events
from dpgo_tpu.types import Measurements, edge_set_from_measurements
from dpgo_tpu.utils.synthetic import make_measurements_vectorized

DISPATCH_CHILDREN = {"rbcd.programs", "rbcd.enqueue", "rbcd.fetch",
                     "cert.decide"}


def _city(n: int, agents: int, seed: int = 0):
    """A small certified city problem: SE(2), closures between nearby
    poses (the benchmark's city10000 cell, cut to ``n`` poses)."""
    meas, _ = make_measurements_vectorized(
        np.random.default_rng(seed), n=n, d=2, num_lc=n, rot_noise=0.01,
        trans_noise=0.01, lc_radius=1.0)
    params = AgentParams(d=2, r=3, num_robots=agents, rel_change_tol=0.0,
                         certify_mode="device")
    return meas, params


def _solve(meas, params, agents, max_iters):
    prob = rbcd.prepare_problem(meas, agents, params=params,
                                dtype=jnp.float32, init="chordal")
    return rbcd.dispatch_prepared(prob, max_iters=max_iters,
                                  grad_norm_tol=0.1, eval_every=5,
                                  verdict_every=20)


@pytest.fixture(scope="module")
def traced_solve(tmp_path_factory):
    """One certified solve of a 1,000-pose, 4-agent city problem with a
    telemetry run installed: its events and final registry snapshot."""
    obs.end_run()
    d = str(tmp_path_factory.mktemp("solve_spans") / "run")
    meas, params = _city(1000, 4)
    with obs.run_scope(d) as run:
        res = _solve(meas, params, 4, max_iters=200)
        snap = run.registry.snapshot()
    return {"res": res, "snap": snap,
            "events": read_events(os.path.join(d, "events.jsonl"))}


def _series(snap, family, **match):
    fam = snap.get(family)
    return [s for s in (fam["series"] if fam else [])
            if all(s["labels"].get(k) == v for k, v in match.items())]


def test_solve_span_seconds_series(traced_solve):
    snap = traced_solve["snap"]
    assert traced_solve["res"].certificate is not None
    for name in ("rbcd.prepare", "rbcd.partition", "rbcd.build_graph",
                 "rbcd.init", "rbcd.dispatch", "rbcd.programs",
                 "rbcd.enqueue", "cert.decide"):
        got = _series(snap, "span_seconds", span=name)
        assert got and all(s["count"] > 0 and s["sum"] > 0 for s in got), \
            name
    for what in ("word", "epilogue"):
        (s,) = _series(snap, "span_seconds", span="rbcd.fetch", what=what)
        assert s["count"] > 0
    # One word fetch per verdict boundary, one epilogue fetch per solve.
    (ep,) = _series(snap, "span_seconds", span="rbcd.fetch",
                    what="epilogue")
    (word,) = _series(snap, "span_seconds", span="rbcd.fetch", what="word")
    assert ep["count"] == 1 and word["count"] >= 1


def test_solve_spans_nest_by_parent(traced_solve):
    spans = [e for e in traced_solve["events"] if e["event"] == "span"]
    (disp,) = [e for e in spans if e["name"] == "rbcd.dispatch"]
    (prep,) = [e for e in spans if e["name"] == "rbcd.prepare"]
    children = {e["name"] for e in spans if e.get("parent") == disp["span"]}
    assert children == DISPATCH_CHILDREN
    assert {e["name"] for e in spans if e.get("parent") == prep["span"]} \
        == {"rbcd.partition", "rbcd.build_graph", "rbcd.init"}
    # The fetch events carry their label; the dispatch children fit in it.
    assert {e["what"] for e in spans if e["name"] == "rbcd.fetch"} \
        >= {"word", "epilogue"}
    inner = sum(e["dur_s"] for e in spans if e.get("parent") == disp["span"])
    assert inner <= disp["dur_s"]


def test_first_solve_compiles_inside_enqueue(traced_solve):
    snap = traced_solve["snap"]
    enq = _series(snap, "jax_compile_seconds_total", span="rbcd.enqueue")
    assert enq and sum(s["value"] for s in enq) > 0
    assert {s["labels"]["stage"] for s in enq} >= {"trace", "backend"}
    built = _series(snap, "jax_programs_total", source="compiled")
    assert built and sum(s["value"] for s in built) > 0


def test_compile_counter_classifies_cache_loads(tmp_path):
    """A build that a persistent-cache hit served counts as ``cache``; a
    build without one as ``compiled``; both outside any span as
    ``span=none``."""
    obs.end_run()
    backend = "/jax/core/compile/backend_compile_duration"
    with obs.run_scope(str(tmp_path / "run")) as run:
        compile_mod._on_event("/jax/compilation_cache/cache_hits")
        compile_mod._on_duration(backend, 0.25, fun_name="jit_a")
        compile_mod._on_duration(backend, 1.5, fun_name="jit_b")
        with obs.trace.span("outer"):
            compile_mod._on_duration(
                "/jax/core/compile/jaxpr_trace_duration", 0.5,
                fun_name="b")
        snap = run.registry.snapshot()
    src = {(s["labels"]["source"], s["labels"]["program"]): s["value"]
           for s in snap["jax_programs_total"]["series"]}
    assert src == {("cache", "jit_a"): 1.0, ("compiled", "jit_b"): 1.0}
    secs = {tuple(s["labels"][k] for k in ("stage", "program", "span")):
            s["value"] for s in snap["jax_compile_seconds_total"]["series"]}
    assert secs == {("backend", "jit_a", "none"): 0.25,
                    ("backend", "jit_b", "none"): 1.5,
                    ("trace", "b", "outer"): 0.5}


def test_compile_listener_idle_without_run():
    obs.end_run()
    compile_mod._on_event("/jax/compilation_cache/cache_hits")
    compile_mod._on_duration("/jax/core/compile/backend_compile_duration",
                             1.0, fun_name="x")
    assert obs.get_run() is None


def test_fetch_spans_on_the_xplane_host_plane(tmp_path):
    """Under ``jax.profiler`` the solve's spans are ``dpgo.*`` events on
    the host plane, the plane the device ops' clock is shared with."""
    from jax.profiler import ProfileData

    obs.end_run()
    meas, params = _city(200, 2, seed=1)
    logdir = str(tmp_path / "trace")
    with obs.run_scope(str(tmp_path / "run")):
        jax.profiler.start_trace(logdir)
        try:
            _solve(meas, params, 2, max_iters=40)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"dpgo.rbcd.fetch", "dpgo.rbcd.enqueue",
            "dpgo.rbcd.dispatch"} <= names


def test_f64_refuse_records_certificate_spans(rng, tmp_path):
    """A payload in the f32 REFUSE band sends the decision to the host
    f64 recheck: ``cert.f64`` and its ``cert.f64.assemble`` stage are
    recorded, the stage within its parent, and ``cert.f64`` is the
    fallback's own seconds (``cert_f64_fallback_seconds_total``) plus at
    most 10 ms of bookkeeping."""
    obs.end_run()
    from synthetic import make_measurements

    meas, _ = make_measurements(rng, n=40, d=3, num_lc=20,
                                rot_noise=0.05, trans_noise=0.05)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    X = _lifted_chain(meas)
    n, dh = X.shape[0], X.shape[2]
    # Undecidable in f32 (10 ulps of sigma dwarf tol) and not decisively
    # negative: the REFUSE band.
    payload = {"lam_min": np.float32(0.0), "sigma": np.float32(1e6),
               "rq": np.float32(0.0), "wscale": np.float32(1.0),
               "defl_resid": np.float32(0.0), "stat": np.float32(0.0),
               "direction": np.zeros((n, dh), np.float32)}
    tol = 1e-5

    def f64_solve(t):
        return certify.lambda_min_f64_shift_invert(X, edges, tol)

    with obs.run_scope(str(tmp_path / "run")) as run:
        cert = certify.decide_device_certificate(
            payload, 1e-5, float(jnp.finfo(jnp.float32).eps),
            f64_solve=f64_solve)
        snap = run.registry.snapshot()
    assert cert.device_verdict == certify.CERT_REFUSE
    assert cert.lambda_min_f64 is not None
    (f64,) = _series(snap, "span_seconds", span="cert.f64")
    (asm,) = _series(snap, "span_seconds", span="cert.f64.assemble")
    assert f64["count"] == 1 and asm["count"] == 1
    assert asm["sum"] <= f64["sum"]
    (fb,) = snap["cert_f64_fallback_seconds_total"]["series"]
    assert fb["value"] <= f64["sum"] <= fb["value"] + 0.010


def _lifted_chain(meas: Measurements) -> np.ndarray:
    """A rank-5 lifted iterate [n, 5, d+1] from the measurements'
    odometry chain — any point at which S can be assembled will do."""
    d, n = meas.d, meas.num_poses
    Rs = [np.eye(d)]
    ts = [np.zeros(d)]
    odo = {(int(a), int(b)): k for k, (a, b) in
           enumerate(zip(meas.p1, meas.p2)) if b == a + 1}
    for i in range(n - 1):
        k = odo[(i, i + 1)]
        Rs.append(Rs[-1] @ meas.R[k])
        ts.append(ts[-1] + Rs[-2] @ meas.t[k])
    pose = np.concatenate([np.stack(Rs), np.stack(ts)[:, :, None]], axis=2)
    Y = np.linalg.qr(np.random.default_rng(0).standard_normal((5, d)))[0]
    return np.einsum("rd,nde->nre", Y, pose)


@pytest.mark.parametrize("outcome", ["pass", "fail", "pivoted",
                                     "guarded"])
def test_f64_recheck_counts_inertia_outcome(outcome, tmp_path, monkeypatch):
    """Each host f64 recheck adds one to ``cert_inertia_total`` under the
    outcome of its inertia pass, and the factorization's
    ``cert.f64.inertia`` span nests inside ``cert.f64.shift_invert``."""
    import scipy.sparse.linalg

    from dpgo_tpu.utils.synthetic import make_stitched_winding

    obs.end_run()
    meas, Xw = make_stitched_winding(2, 12)
    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    X = np.asarray(Xw, np.float64)
    tol = 1e-4
    if outcome == "pass":
        # A wound saddle's S shifted up past its bottom eigenvalue.
        S = certify.sparse_certificate(X, edges)
        lam0 = float(np.linalg.eigvalsh(S.toarray())[0])
        S_up = (S - 2.0 * lam0 * scipy.sparse.eye(S.shape[0])).tocsr()
        monkeypatch.setattr(certify, "sparse_certificate",
                            lambda X64, e: S_up)
    elif outcome == "pivoted":
        def singular(*_, **__):
            raise RuntimeError("Factor is exactly singular")
        monkeypatch.setattr(scipy.sparse.linalg, "splu", singular)
    elif outcome == "guarded":
        monkeypatch.setattr(certify, "_expander_guard", lambda *_: True)
    with obs.run_scope(str(tmp_path / "run")) as run:
        for _ in range(2):
            certify.f64_recheck(
                lambda t: certify.lambda_min_f64_shift_invert(X, edges, tol),
                tol)
        snap = run.registry.snapshot()
    (c,) = snap["cert_inertia_total"]["series"]
    assert c["labels"] == {"outcome": outcome} and c["value"] == 2.0
    spans = [e for e in read_events(str(tmp_path / "run" / "events.jsonl"))
             if e["event"] == "span"]
    inertia = [e for e in spans if e["name"] == "cert.f64.inertia"]
    assert len(inertia) == (0 if outcome == "guarded" else 2)
    parents = {e["span"]: e for e in spans
               if e["name"] == "cert.f64.shift_invert"}
    for e in inertia:
        assert e["parent"] in parents
        assert e["dur_s"] <= parents[e["parent"]]["dur_s"]
    sa = _series(snap, "span_seconds", span="cert.f64.sa")
    assert bool(sa) == (outcome != "pass")
