"""Flight recorder (``dpgo_tpu.obs.recorder``): ring/snapshot bookkeeping,
black-box dumps, and the ACCEPTANCE scenario — a seeded NaN injection into
one agent's neighbor frame produces an anomaly event + ``blackbox.npz``,
and ``--replay`` reproduces the recorded trajectory from the last good
snapshot bit-for-bit on CPU."""

import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from dpgo_tpu import obs
from dpgo_tpu.config import (AgentParams, RobustCostParams, RobustCostType,
                             Schedule, SolverParams)
from dpgo_tpu.obs.events import read_events
from dpgo_tpu.obs.recorder import (FlightRecorder, decode_config,
                                   encode_config, inject_nan, load_blackbox,
                                   main as recorder_main, replay)


@pytest.fixture(autouse=True)
def _no_leaked_ambient_run():
    obs.end_run()
    yield
    obs.end_run()


def _tiny_problem(n=40, num_lc=20, seed=0):
    from dpgo_tpu.utils.synthetic import make_measurements

    meas, _ = make_measurements(np.random.default_rng(seed), n=n, d=3,
                                num_lc=num_lc, rot_noise=0.01,
                                trans_noise=0.01)
    return meas


def _params(**kw):
    return AgentParams(
        d=3, r=5, num_robots=2, rel_change_tol=1e-16,
        robust=RobustCostParams(cost_type=RobustCostType.GNC_TLS),
        robust_opt_inner_iters=4, **kw)


def _run_recorded_solve(run, params, meas, max_iters=10, eval_every=2,
                        fault=None, crash_at=None, snapshot_every=1,
                        verdict_every=None):
    """Drive ``run_rbcd`` the way ``solve_rbcd`` does, with a segment
    wrapper that injects the canonical NaN fault (``inject_nan``) the
    first time the cumulative round count crosses ``fault['iteration']``
    — the recorded-input model of a fault injector corrupting one agent's
    neighbor frame (the poisoned block is exactly what neighbors consume
    on the next exchange)."""
    from dpgo_tpu.models import rbcd
    from dpgo_tpu.utils.partition import partition_contiguous

    rec = FlightRecorder.attach(run, snapshot_every=snapshot_every)
    if fault is not None:
        rec.set_context(fault=fault)

    part = partition_contiguous(meas, params.num_robots)
    graph, meta = rbcd.build_graph(part, params.r, jnp.float64,
                                   sel_mode=rbcd.resolved_sel_mode(params))
    X0 = rbcd.centralized_chordal_init(part, meta, graph, jnp.float64)
    state = rbcd.init_state(graph, meta, X0, params=params)
    rounds = {"n": 0}
    applied = {"v": False}

    def seg(s, k, uw, rs):
        s = rbcd.rbcd_segment(s, graph, k, meta, params,
                              first_update_weights=uw, first_restart=rs)
        rounds["n"] += k
        if crash_at is not None and rounds["n"] >= crash_at:
            raise RuntimeError("synthetic driver crash")
        if fault is not None and not applied["v"] \
                and rounds["n"] >= fault["iteration"]:
            s = inject_nan(s, fault["agent"], fault["pose"])
            applied["v"] = True
        return s

    res = rbcd.run_rbcd(state, graph, meta, seg, part, max_iters,
                        grad_norm_tol=1e-12, eval_every=eval_every,
                        dtype=jnp.float64, params=params,
                        verdict_every=verdict_every)
    return res, rec


# ---------------------------------------------------------------------------
# Bookkeeping
# ---------------------------------------------------------------------------

def test_config_roundtrip():
    p = _params(schedule=Schedule.COLORED, acceleration=False,
                solver=SolverParams(pallas_tcg=False, max_inner_iters=7))
    enc = encode_config(p)
    json.dumps(enc)  # JSON-safe end to end
    assert decode_config(enc) == p


def test_ring_is_bounded_and_snapshots_rotate(tmp_path):
    with obs.run_scope(str(tmp_path / "r")) as run:
        rec = FlightRecorder(run, capacity=4, snapshot_every=2,
                             max_snapshots=2)
        for i in range(10):
            rec.record_eval(i, {"cost": float(i), "grad_norm": 1.0})
        assert len(rec.ring) == 4
        assert [r["iteration"] for r in rec.ring] == [6, 7, 8, 9]
        assert rec.snapshots.maxlen == 2


def test_dump_writes_npz_and_jsonl(tmp_path):
    d = str(tmp_path / "r")
    with obs.run_scope(d) as run:
        run.set_fingerprint(dataset="synthetic-tiny")
        rec = FlightRecorder.attach(run)
        rec.record_eval(2, {"cost": 1.5, "grad_norm": 0.5,
                            "rel_change": np.array([0.1, float("nan")])})
        path = rec.dump("unit-test")
        assert rec.dump("second-call") == path  # first dump wins
        assert rec._dumped == "unit-test"
    arrays = dict(np.load(path))
    assert arrays["ring_cost"].tolist() == [1.5]
    assert not arrays["ring_healthy"][0]  # NaN rel_change -> unhealthy
    with open(os.path.join(d, "blackbox.jsonl")) as fh:
        lines = [json.loads(ln) for ln in fh]
    assert lines[0]["kind"] == "context"
    assert lines[0]["reason"] == "unit-test"
    assert lines[0]["fingerprint"]["dataset"] == "synthetic-tiny"
    assert lines[1]["kind"] == "round" and lines[1]["iteration"] == 2
    (ev,) = [e for e in read_events(os.path.join(d, "events.jsonl"))
             if e["event"] == "blackbox_dump"]
    assert ev["reason"] == "unit-test"


def test_replay_refuses_problemless_blackbox(tmp_path):
    d = str(tmp_path / "r")
    with obs.run_scope(d) as run:
        rec = FlightRecorder.attach(run)
        rec.record_eval(1, {"cost": 1.0, "grad_norm": 1.0})
        path = rec.dump("no-problem")
    with pytest.raises(ValueError, match="not replayable"):
        replay(path)
    assert recorder_main(["--replay", path]) == 2


# ---------------------------------------------------------------------------
# Clean-run replay (no fault): trajectory reproduces bit-for-bit
# ---------------------------------------------------------------------------

def test_clean_run_replays_bit_for_bit(tmp_path):
    d = str(tmp_path / "run")
    with obs.run_scope(d) as run:
        params = _params()
        _res, rec = _run_recorded_solve(run, params, _tiny_problem(),
                                        max_iters=10, snapshot_every=2)
        path = rec.dump("manual")
    rep = replay(path)
    assert rep.match, rep.mismatches
    assert rep.iterations  # at least one eval replayed
    for a, b in zip(rep.cost, rep.recorded_cost):
        assert a == b  # bitwise
    assert recorder_main(["--replay", path]) == 0


# ---------------------------------------------------------------------------
# ACCEPTANCE: seeded NaN injection -> anomaly + blackbox + exact replay
# ---------------------------------------------------------------------------

def test_nan_injection_anomaly_blackbox_and_exact_replay(tmp_path, capsys):
    d = str(tmp_path / "run")
    fault = {"iteration": 6, "agent": 1, "pose": 0}
    with obs.run_scope(d) as run:
        params = _params()
        res, rec = _run_recorded_solve(run, params, _tiny_problem(),
                                       max_iters=10, fault=fault)
        # The solve ran through the NaN to max_iters (no abort policy).
        assert res.iterations == 10
        assert math.isnan(res.cost_history[-1])

    evs = read_events(os.path.join(d, "events.jsonl"))
    # 1) the anomaly event: the NaN surfaced at the eval after injection.
    anomalies = [e for e in evs if e["event"] == "anomaly"]
    assert anomalies and anomalies[0]["kind"] == "non_finite"
    assert anomalies[0]["severity"] == "critical"
    assert anomalies[0]["iteration"] == fault["iteration"]

    # 2) the black box dumped on the anomaly, not at run end.
    (dump,) = [e for e in evs if e["event"] == "blackbox_dump"]
    assert dump["reason"] == "anomaly:non_finite"
    npz = os.path.join(d, "blackbox.npz")
    assert os.path.exists(npz)
    context, arrays = load_blackbox(npz)
    assert context["fault"] == fault
    # The recorded trajectory went NaN exactly at the fault eval.
    it_col = arrays["ring_iteration"].tolist()
    nan_mask = [math.isnan(c) for c in arrays["ring_cost"].tolist()]
    assert nan_mask == [it >= fault["iteration"] for it in it_col]

    # 3) replay resumes from the last GOOD snapshot (iteration 4 — the
    # snapshot at the fault eval is already poisoned) and reproduces the
    # recorded trajectory bit-for-bit, NaNs included.
    rep = replay(npz)
    assert rep.snapshot_iteration == 4
    assert rep.match, rep.mismatches
    # The dump fired AT the anomaly (first-write-wins), so the failure
    # eval is the recorded frontier; the replay reproduces it exactly.
    assert rep.iterations == [6]
    assert [math.isnan(c) for c in rep.cost] == [True]

    # The CLI agrees (exit 0 = reproduced).
    assert recorder_main(["--replay", npz]) == 0
    out = capsys.readouterr().out
    assert "REPRODUCED bit-for-bit" in out

    # 4) a tampered recording is caught: replace the recorded failure
    # value with a finite one.
    arrays2 = dict(np.load(npz))
    arrays2["ring_cost"] = arrays2["ring_cost"].copy()
    arrays2["ring_cost"][-1] = 123.0
    with open(npz, "wb") as fh:
        np.savez_compressed(fh, **arrays2)
    rep2 = replay(npz)
    assert not rep2.match
    assert recorder_main(["--replay", npz]) == 1


def test_crash_dumps_blackbox(tmp_path):
    d = str(tmp_path / "run")
    with obs.run_scope(d) as run:
        with pytest.raises(RuntimeError, match="synthetic driver crash"):
            _run_recorded_solve(run, _params(), _tiny_problem(),
                                max_iters=10, crash_at=5)
    evs = read_events(os.path.join(d, "events.jsonl"))
    (dump,) = [e for e in evs if e["event"] == "blackbox_dump"]
    assert dump["reason"] == "crash"
    assert os.path.exists(os.path.join(d, "blackbox.npz"))


def test_report_renders_health_and_blackbox(tmp_path, capsys):
    """The report CLI surfaces the anomaly + black-box story."""
    from dpgo_tpu.obs.report import main as report_main

    d = str(tmp_path / "run")
    fault = {"iteration": 6, "agent": 0, "pose": 1}
    with obs.run_scope(d):
        _run_recorded_solve(obs.get_run(), _params(), _tiny_problem(),
                            max_iters=8, fault=fault)
    assert report_main([d]) == 0
    out = capsys.readouterr().out
    assert "numerical health:" in out
    assert "non_finite" in out
    assert "blackbox:" in out and "anomaly:non_finite" in out


# ---------------------------------------------------------------------------
# Verdict-word loop compatibility (ISSUE 9): the fused program and the
# replay path stay on the byte-identical metrics computation
# ---------------------------------------------------------------------------

def test_verdict_history_rows_bitwise_match_central_metrics():
    """The verdict program's device-side history rows must equal the
    standalone ``_make_central_metrics`` program's output BITWISE on the
    same states — the ``_central_metrics_body`` extraction contract that
    lets ``--replay`` (which evaluates through ``_make_central_metrics``)
    verify a verdict-mode recording bit-for-bit."""
    from dpgo_tpu.models import rbcd
    from dpgo_tpu.types import edge_set_from_measurements
    from dpgo_tpu.utils.partition import partition_contiguous

    meas = _tiny_problem()
    params = _params()
    part = partition_contiguous(meas, params.num_robots)
    graph, meta = rbcd.build_graph(part, params.r, jnp.float64,
                                   sel_mode=rbcd.resolved_sel_mode(params))
    X0 = rbcd.centralized_chordal_init(part, meta, graph, jnp.float64)
    state = rbcd.init_state(graph, meta, X0, params=params)
    n_total = part.meas_global.num_poses
    num_meas = len(part.meas_global)
    edges_g = edge_set_from_measurements(part.meas_global,
                                         dtype=jnp.float64)
    central = rbcd._make_central_metrics(graph, edges_g, n_total,
                                         num_meas, telemetry=True)
    vstep = rbcd.make_verdict_program(
        graph, edges_g, n_total, num_meas, telemetry=True,
        grad_norm_tol=1e-12, robust_params=params.robust, max_evals=4)
    vs = rbcd.init_verdict_state(4, meta.num_robots, jnp.float64,
                                 telemetry=True)
    for k in range(4):
        state = rbcd.rbcd_segment(state, graph, 2, meta, params)
        vs = vstep(state.X, state.weights, state.ready, state.mu,
                   state.rel_change, state.iteration, vs)
        ref = np.asarray(central(state.X, state.weights, state.ready,
                                 state.mu, state.rel_change))
        row = np.asarray(vs.hist)[k]
        assert row.tobytes() == ref.tobytes(), (k, row, ref)


def test_verdict_mode_replay_crosses_boundary_bit_for_bit(tmp_path):
    """ACCEPTANCE (ISSUE 9 satellite): a verdict-mode recorded run with a
    seeded NaN fault dumps a black box whose ``--replay`` resumes from a
    K-boundary snapshot, crosses subsequent verdict boundaries, and
    reproduces the recorded trajectory bit-for-bit (rc 0)."""
    meas = _tiny_problem()
    params = _params()
    fault = {"iteration": 9, "agent": 1, "pose": 3}
    d = str(tmp_path / "run")
    with obs.run_scope(d) as run:
        res, rec = _run_recorded_solve(run, params, meas, max_iters=16,
                                       eval_every=2, fault=fault,
                                       verdict_every=4)
        # The on-device non-finite predicate latched into the verdict
        # word (in-band signal) AND the host monitor re-judged the same
        # rows into the standard anomaly event (stream parity).
        npz = os.path.join(d, "blackbox.npz")
        assert os.path.exists(npz)
        # Snapshots were taken at verdict boundaries by snapshot_state.
        ctx, _arrays = load_blackbox(npz)
        snaps = ctx["snapshots"]
        assert snaps and all(s["iteration"] % 4 == 0 for s in snaps)
        assert any(s["healthy"] for s in snaps)
    evs = read_events(os.path.join(d, "events.jsonl"))
    kinds = {e.get("kind") for e in evs if e.get("event") == "anomaly"}
    assert "non_finite" in kinds
    ends = [e for e in evs if e.get("event") == "solve_end"]
    assert ends and ends[0].get("verdict", {}).get("anomaly") == "non_finite"
    # Exact replay across the verdict boundary: the ring rows came from
    # the fused verdict program's history; the replay recomputes them
    # through _make_central_metrics — bitwise agreement required.
    rep = replay(npz)
    assert rep.match, rep.mismatches
    assert recorder_main(["--replay", npz]) == 0


def test_verdict_mode_emits_identical_event_stream(tmp_path):
    """ACCEPTANCE: with telemetry on, a coarse fetch cadence
    (K = 4 evals) must emit the SAME health/anomaly event stream and the
    same solver-metric trajectory as the default cadence (K = eval_every)
    on a seeded NaN-injection run — the K-round fetch coarsens the
    transfer cadence, never the observable events."""
    meas = _tiny_problem()
    params = _params()
    fault = {"iteration": 9, "agent": 1, "pose": 3}
    streams = {}
    for mode, k in (("per_eval", None), ("verdict", 8)):
        d = str(tmp_path / mode)
        with obs.run_scope(d) as run:
            _run_recorded_solve(run, params, meas, max_iters=16,
                                eval_every=2, fault=fault,
                                verdict_every=k)
        streams[mode] = read_events(os.path.join(d, "events.jsonl"))

    def anomalies(evs):
        return [(e["kind"], e["severity"], e["iteration"])
                for e in evs if e.get("event") == "anomaly"]

    def metrics(evs, name):
        # repr round-trips NaN equality (math.nan != math.nan).
        return [(e["iteration"], repr(e["value"])) for e in evs
                if e.get("event") == "metric" and e.get("metric") == name
                and e.get("phase") == "eval"]

    assert anomalies(streams["verdict"]) == anomalies(streams["per_eval"])
    assert anomalies(streams["verdict"]), "fault must surface as anomaly"
    for name in ("solver_cost", "solver_grad_norm", "gnc_mu",
                 "gnc_inlier_fraction"):
        assert metrics(streams["verdict"], name) == \
            metrics(streams["per_eval"], name), name
    assert metrics(streams["verdict"], "solver_cost"), "evals must emit"
    # Identical terminal accounting (iterations, terminated_by).
    (end_v,) = [e for e in streams["verdict"]
                if e.get("event") == "solve_end"]
    (end_p,) = [e for e in streams["per_eval"]
                if e.get("event") == "solve_end"]
    assert (end_v["iterations"], end_v["terminated_by"]) == \
        (end_p["iterations"], end_p["terminated_by"])
