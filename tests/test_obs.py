"""Run-scoped telemetry subsystem (``dpgo_tpu.obs``): metrics registry,
JSONL event stream, exporters, report CLI, and the instrumented solver /
agent hot paths — including the zero-overhead telemetry-off contract."""

import json
import os
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from dpgo_tpu import obs
from dpgo_tpu.obs import run as run_mod
from dpgo_tpu.obs.events import EventStream, metric_record, read_events
from dpgo_tpu.obs.exporters import (to_prometheus_text,
                                    write_tensorboard_scalars)
from dpgo_tpu.obs.metrics import MetricsRegistry
from dpgo_tpu.obs.report import main as report_main


@pytest.fixture(autouse=True)
def _no_leaked_ambient_run():
    """Every test starts and ends with telemetry off."""
    obs.end_run()
    yield
    obs.end_run()


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_with_labels():
    reg = MetricsRegistry()
    c = reg.counter("msgs", "messages", unit="1")
    c.inc(robot=0)
    c.inc(2, robot=0)
    c.inc(5, robot=1, neighbor=2)
    assert c.value(robot=0) == 3
    assert c.value(robot=1, neighbor=2) == 5
    assert c.value(robot=9) == 0
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("mu")
    g.set(1e-4)
    g.inc(1e-4)
    assert g.value() == pytest.approx(2e-4)

    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    h.observe(0.05, phase="solve")
    h.observe_many([0.5, 5.0, 50.0], phase="solve")
    s = h.snapshot_series(phase="solve")
    assert s["count"] == 4
    assert s["counts"] == [1, 1, 1, 1]  # one per bucket + one overflow
    assert s["sum"] == pytest.approx(55.55)

    # Same name returns the same family; a kind change raises.
    assert reg.counter("msgs") is c
    with pytest.raises(ValueError):
        reg.gauge("msgs")

    snap = reg.snapshot()
    assert snap["msgs"]["kind"] == "counter"
    assert {"labels": {"robot": "0"}, "value": 3.0} in snap["msgs"]["series"]
    assert snap["lat"]["buckets"] == [0.1, 1.0, 10.0]
    json.dumps(snap)  # JSON-serializable end to end


def test_registry_thread_safety():
    """Concurrent increments from many threads lose nothing — the registry
    must be callable from the agent's background optimization thread."""
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("v", buckets=(0.5,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8000
    assert h.snapshot_series()["count"] == 8000


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("dpgo_msgs", "messages sent").inc(3, robot=1)
    reg.gauge("dpgo_mu").set(2.5e-4)
    h = reg.histogram("dpgo_lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(7.0)
    text = to_prometheus_text(reg)
    assert "# TYPE dpgo_msgs counter" in text
    assert '# HELP dpgo_msgs messages sent' in text
    assert 'dpgo_msgs{robot="1"} 3.0' in text
    # Label-value escaping per the text exposition format: backslash,
    # newline (previously unescaped — it split the sample line), quote.
    reg.counter("dpgo_esc").inc(1, path='a\\b\n"c"')
    esc = to_prometheus_text(reg)
    assert 'dpgo_esc{path="a\\\\b\\n\\"c\\""} 1.0' in esc
    assert "\na" not in esc.split("dpgo_esc", 1)[1].split("\n")[0]
    assert "# TYPE dpgo_lat histogram" in text
    # Cumulative buckets and the +Inf tail.
    assert 'dpgo_lat_bucket{le="0.1"} 1' in text
    assert 'dpgo_lat_bucket{le="1.0"} 2' in text
    assert 'dpgo_lat_bucket{le="+Inf"} 3' in text
    assert "dpgo_lat_count 3" in text
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# Event stream + shared metric schema
# ---------------------------------------------------------------------------

def test_event_stream_correlation_fields(tmp_path):
    path = str(tmp_path / "events.jsonl")
    es = EventStream(path, run_id="runabc")
    es.emit("alpha", phase="solve", iteration=3)
    es.metric("cost", 1.5, "1", phase="eval", iteration=4)
    es.close()
    evs = read_events(path)
    assert [e["event"] for e in evs] == ["alpha", "metric"]
    for e in evs:
        assert e["run"] == "runabc"
        assert isinstance(e["t_wall"], float)
        assert isinstance(e["t_mono"], float)
    assert [e["seq"] for e in evs] == [0, 1]
    m = evs[1]
    # The in-stream metric event carries the shared schema keys.
    assert (m["metric"], m["value"], m["unit"]) == ("cost", 1.5, "1")
    # Closed stream: emit is a no-op, not a crash.
    es.emit("late")
    assert len(read_events(path)) == 2


def test_metric_record_matches_bench_schema():
    """``bench.py``'s final line and telemetry metric events share one
    record shape: ``metric``/``value``/``unit`` leading keys — the same
    key set BENCH_r0*.json archives."""
    rec = metric_record("rbcd_rounds_per_sec", 1146.2, "rounds/s",
                        vs_baseline=33.4)
    assert list(rec)[:3] == ["metric", "value", "unit"]
    assert rec["vs_baseline"] == 33.4
    # Non-finite floats and numpy scalars serialize cleanly, on the one
    # canonical (Prometheus-style) spelling.
    rec2 = metric_record("m", np.float64(2.0), extra=float("inf"))
    assert rec2["value"] == 2.0 and rec2["extra"] == "+Inf"
    json.dumps(rec2)


def test_event_payloads_coerce_numpy(tmp_path):
    es = EventStream(str(tmp_path / "e.jsonl"), "r")
    es.emit("x", arr=np.arange(3), scalar=np.float32(1.5),
            nested={"a": np.int64(2)}, nan=float("nan"),
            pinf=float("inf"), ninf=float("-inf"))
    es.close()
    # On disk: the canonical non-finite strings (valid JSON).
    raw = json.loads(open(str(tmp_path / "e.jsonl")).readline())
    assert raw["nan"] == "NaN"
    assert raw["pinf"] == "+Inf" and raw["ninf"] == "-Inf"
    # Through read_events: restored to real floats (the round-trip).
    (ev,) = read_events(str(tmp_path / "e.jsonl"))
    assert ev["arr"] == [0, 1, 2]
    assert ev["scalar"] == 1.5
    assert ev["nested"] == {"a": 2}
    import math

    assert math.isnan(ev["nan"])
    assert ev["pinf"] == float("inf") and ev["ninf"] == float("-inf")


def test_nonfinite_convention_unified_across_snapshot_and_prometheus():
    """The metrics snapshot and the Prometheus exposition spell non-finite
    values identically (the satellite: metrics.py stringified str(float)
    while the exporter emitted NaN/+Inf)."""
    reg = MetricsRegistry()
    reg.gauge("g_nan").set(float("nan"))
    reg.gauge("g_inf").set(float("inf"))
    snap = reg.snapshot()
    assert snap["g_nan"]["series"][0]["value"] == "NaN"
    assert snap["g_inf"]["series"][0]["value"] == "+Inf"
    text = to_prometheus_text(reg)
    assert "g_nan NaN" in text
    assert "g_inf +Inf" in text
    json.dumps(snap)


# ---------------------------------------------------------------------------
# Run scoping + artifacts
# ---------------------------------------------------------------------------

def test_run_scope_writes_artifacts(tmp_path):
    d = str(tmp_path / "run")
    with obs.run_scope(d) as run:
        assert obs.get_run() is run
        run.counter("things").inc(7)
        run.event("hello", phase="setup")
    assert obs.get_run() is None
    assert run.closed
    evs = read_events(os.path.join(d, "events.jsonl"))
    assert [e["event"] for e in evs] == ["run_start", "hello", "run_end"]
    snap = json.load(open(os.path.join(d, "metrics.json")))
    assert snap["run"] == run.run_id
    assert snap["metrics"]["things"]["series"][0]["value"] == 7.0
    prom = open(os.path.join(d, "metrics.prom")).read()
    assert "things 7.0" in prom
    meta = json.load(open(os.path.join(d, "run.json")))
    assert meta["run"] == run.run_id


def test_start_run_refuses_overlap(tmp_path):
    obs.start_run(str(tmp_path / "a"))
    with pytest.raises(RuntimeError, match="already active"):
        obs.start_run(str(tmp_path / "b"))
    obs.end_run()
    assert obs.get_run() is None
    obs.end_run()  # idempotent


def test_report_cli(tmp_path, capsys):
    d = str(tmp_path / "run")
    with obs.run_scope(d) as run:
        run.metric("solver_cost", 10.0, phase="eval", iteration=1)
        run.metric("solver_cost", 2.0, phase="eval", iteration=5)
        run.event("phase_timings", timings={
            "solve": {"total_s": 1.0, "count": 4, "avg_ms": 250.0}})
        run.histogram("round_latency_seconds").observe(0.01)
    rc = report_main([d])
    out = capsys.readouterr().out
    assert rc == 0
    assert "solver_cost: 2 points, first 10, last 2" in out
    assert "solve: 1.0000s / 4 (250.00 ms avg)" in out
    assert "round_latency_seconds" in out
    assert report_main([str(tmp_path / "missing")]) == 2


def test_tensorboard_export_is_optional(tmp_path):
    """No TensorBoard writer in the environment => graceful None (and if
    one exists, a logdir comes back) — never an ImportError."""
    events = [metric_record("m", 1.0) | {"event": "metric", "seq": 0}]
    out = write_tensorboard_scalars(str(tmp_path), events)
    assert out is None or os.path.isdir(out)


# ---------------------------------------------------------------------------
# Instrumented hot paths
# ---------------------------------------------------------------------------

def _tiny_problem(n=40, num_lc=20, seed=0):
    from dpgo_tpu.utils.synthetic import make_measurements

    meas, _ = make_measurements(np.random.default_rng(seed), n=n, d=3,
                                num_lc=num_lc, rot_noise=0.01,
                                trans_noise=0.01)
    return meas


def test_solve_rbcd_telemetry_stream(tmp_path):
    """A telemetry-on multi-agent solve yields the full acceptance signal
    set: per-iteration cost/grad-norm events, GNC mu trajectory, per-agent
    round latency + relative change, and round counters."""
    from dpgo_tpu.config import (AgentParams, RobustCostParams,
                                 RobustCostType)
    from dpgo_tpu.models import rbcd

    meas = _tiny_problem()
    params = AgentParams(
        d=3, r=5, num_robots=2,
        robust=RobustCostParams(cost_type=RobustCostType.GNC_TLS),
        robust_opt_inner_iters=4)
    d = str(tmp_path / "run")
    with obs.run_scope(d):
        res = rbcd.solve_rbcd(meas, 2, params=params, max_iters=8,
                              eval_every=2, grad_norm_tol=1e-9,
                              dtype=jnp.float64)
    evs = read_events(os.path.join(d, "events.jsonl"))
    kinds = {e["event"] for e in evs}
    assert {"run_start", "solve_start", "metric", "solve_end",
            "run_end"} <= kinds

    costs = [e for e in evs if e.get("metric") == "solver_cost"]
    gns = [e for e in evs if e.get("metric") == "solver_grad_norm"]
    mus = [e for e in evs if e.get("metric") == "gnc_mu"]
    assert len(costs) == len(res.cost_history)
    assert [e["value"] for e in costs] == pytest.approx(res.cost_history)
    assert [e["value"] for e in gns] == pytest.approx(
        res.grad_norm_history)
    assert mus and all(m["value"] > 0 for m in mus)
    assert all("iteration" in e for e in costs)
    # mu anneals across the weight-update schedule (strictly increasing).
    mu_vals = [m["value"] for m in mus]
    assert mu_vals == sorted(mu_vals)

    (end,) = [e for e in evs if e["event"] == "solve_end"]
    assert end["iterations"] == res.iterations
    assert end["terminated_by"] == res.terminated_by

    snap = json.load(open(os.path.join(d, "metrics.json")))["metrics"]
    assert snap["solver_rounds"]["series"][0]["value"] == res.iterations
    (lat,) = snap["round_latency_seconds"]["series"]
    assert lat["count"] == len(res.cost_history) and lat["sum"] > 0
    assert len(snap["agent_rel_change"]["series"]) == 2
    assert snap["round_latency_seconds"]["kind"] == "histogram"


def test_agent_telemetry_comms_gnc_and_lifecycle(tmp_path):
    """The deployment surface: per-neighbor message/byte counters, iterate
    latency + events, GNC weight histogram, and lifecycle transitions."""
    from test_agent import exchange, make_agents
    from dpgo_tpu.config import RobustCostParams, RobustCostType

    d = str(tmp_path / "run")
    with obs.run_scope(d) as run:
        agents, part, _ = make_agents(
            2, n=12, num_lc=6,
            robust=RobustCostParams(cost_type=RobustCostType.GNC_TLS),
            robust_opt_inner_iters=2)
        for _ in range(4):
            exchange(agents)
            for ag in agents:
                ag.iterate()
        snap = run.registry.snapshot()
    evs = read_events(os.path.join(d, "events.jsonl"))

    # Lifecycle: both agents reached INITIALIZED (robot 1 via frame
    # alignment after the first pose message).
    states = [(e["robot"], e["state"]) for e in evs
              if e["event"] == "agent_state"]
    assert (0, "INITIALIZED") in states and (1, "INITIALIZED") in states

    # Comms: bytes + messages per direction, receives labeled by neighbor.
    rx = {tuple(sorted(s["labels"].items())): s["value"]
          for s in snap["comms_bytes_received"]["series"]}
    assert (("neighbor", "0"), ("robot", "1")) in rx
    assert (("neighbor", "1"), ("robot", "0")) in rx
    assert all(v > 0 for v in rx.values())
    sent = snap["comms_bytes_sent"]["series"]
    assert len(sent) == 2 and all(s["value"] > 0 for s in sent)
    n_pub = len(agents[0].get_shared_pose_dict())
    r, dd = agents[0].r, agents[0].d
    per_msg = n_pub * r * (dd + 1) * 8  # float64 pose blocks
    got = next(s["value"] for s in sent
               if s["labels"] == {"robot": "0"})
    assert got % per_msg == 0

    # Iterate: latency histogram + per-robot events with iteration numbers.
    its = [e for e in evs if e["event"] == "agent_iterate"]
    assert {e["robot"] for e in its} == {0, 1}
    assert all(e["latency_s"] > 0 for e in its)
    assert snap["agent_iterate_seconds"]["series"]

    # GNC: a weight update happened (inner_iters=2 over 4 rounds) and the
    # weight histogram saw every updatable loop closure.
    gnc = [e for e in evs if e["event"] == "metric"
           and e["metric"] == "gnc_mu"]
    assert gnc and all(e["inlier_fraction"] >= 0 for e in gnc)
    wh = snap["gnc_weight"]["series"]
    assert wh and all(s["count"] > 0 for s in wh)


def test_certificate_telemetry(tmp_path):
    from dpgo_tpu.models import certify, local_pgo

    meas = _tiny_problem(n=20, num_lc=8)
    from dpgo_tpu.types import edge_set_from_measurements

    edges = edge_set_from_measurements(meas, dtype=jnp.float64)
    res = local_pgo.solve_local(meas, rank=5)
    d = str(tmp_path / "run")
    with obs.run_scope(d):
        cert = certify.certify_solution(res.X, edges)
    evs = read_events(os.path.join(d, "events.jsonl"))
    (ev,) = [e for e in evs if e["event"] == "certificate"]
    assert ev["certified"] == cert.certified
    assert ev["eigenvalue_gap"] == pytest.approx(
        (cert.lambda_min_f64 if cert.lambda_min_f64 is not None
         else cert.lambda_min) + cert.tol)
    assert ev["duration_s"] > 0


def test_sharded_solve_telemetry(tmp_path):
    import jax

    from dpgo_tpu.config import AgentParams
    from dpgo_tpu.parallel import sharded

    if not hasattr(jax, "shard_map"):
        # The sharded path itself is broken on this jax build (the seed's
        # test_sharded.py failures have the same cause); the telemetry
        # hooks can only be exercised where the solver runs.
        pytest.skip("jax.shard_map unavailable in this jax build")
    meas = _tiny_problem()
    mesh = sharded.make_mesh(2)
    params = AgentParams(d=3, r=5, num_robots=2)
    d = str(tmp_path / "run")
    with obs.run_scope(d):
        res = sharded.solve_rbcd_sharded(meas, 2, mesh=mesh, params=params,
                                         max_iters=4, eval_every=2,
                                         grad_norm_tol=1e-9,
                                         dtype=jnp.float64)
    assert res.iterations > 0
    evs = read_events(os.path.join(d, "events.jsonl"))
    (sh,) = [e for e in evs if e["event"] == "sharded_solve"]
    assert sh["mesh_size"] == 2
    assert sh["comm_bytes_per_round"] > 0
    (pt,) = [e for e in evs if e["event"] == "phase_timings"]
    assert {"build_graph", "init", "shard"} <= set(pt["timings"])
    assert all(row["count"] == 1 for row in pt["timings"].values())


# ---------------------------------------------------------------------------
# The zero-overhead contract (satellite: telemetry-off smoke test)
# ---------------------------------------------------------------------------

def test_telemetry_off_is_zero_overhead(monkeypatch):
    """With no ambient run, an instrumented solve emits ZERO events, makes
    ZERO registry calls, performs ZERO obs-owned device->host transfers in
    the RBCD round loop, constructs ZERO tracing spans, opens ZERO
    profiler annotations, and builds ZERO health detectors and ZERO
    flight-recorder buffers — the instrumentation's only cost is the
    ``get_run() is None`` guard.  Driven on the per-eval loop and on the
    certified library path (``prepare_problem`` + ``dispatch_prepared``,
    verdict loop, device certificate and its host decision)."""
    import jax

    from dpgo_tpu.config import AgentParams
    from dpgo_tpu.models import rbcd
    from dpgo_tpu.obs import health as health_mod
    from dpgo_tpu.obs import metrics as metrics_mod
    from dpgo_tpu.obs import recorder as recorder_mod
    from dpgo_tpu.obs import trace as trace_mod

    def boom(*a, **kw):
        raise AssertionError("telemetry path taken while disabled")

    # Any event emission, any registry mutation, any obs-owned transfer,
    # any span/detector/recorder construction trips the failure.
    monkeypatch.setattr(EventStream, "emit", boom)
    monkeypatch.setattr(run_mod, "materialize", boom)
    monkeypatch.setattr(obs, "materialize", boom)
    monkeypatch.setattr(metrics_mod.Counter, "inc", boom)
    monkeypatch.setattr(metrics_mod.Gauge, "set", boom)
    monkeypatch.setattr(metrics_mod.Histogram, "observe_many", boom)
    monkeypatch.setattr(trace_mod.Span, "__init__", boom)
    monkeypatch.setattr(trace_mod, "emit_span", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(MetricsRegistry, "_family", boom)
    monkeypatch.setattr(health_mod.HealthMonitor, "__init__", boom)
    monkeypatch.setattr(health_mod.HealthMonitor, "observe_solver", boom)
    monkeypatch.setattr(recorder_mod.FlightRecorder, "__init__", boom)
    monkeypatch.setattr(recorder_mod.FlightRecorder, "record_eval", boom)
    # ISSUE 16: the device-profiling layer sits behind the same fence.
    from dpgo_tpu.obs import devprof as devprof_mod
    from dpgo_tpu.obs import ledger as ledger_mod
    monkeypatch.setattr(devprof_mod.DeviceTraceWindow, "__init__", boom)
    monkeypatch.setattr(devprof_mod, "profiled_program", boom)
    monkeypatch.setattr(ledger_mod.PerfLedger, "__init__", boom)
    # ISSUE 20: the fleet-observability layer too — no sampler thread,
    # no fleet HTTP sidecar, no harvest work with telemetry off.
    from dpgo_tpu.obs import fleetobs as fleetobs_mod
    monkeypatch.setattr(fleetobs_mod.ResourceSampler, "__init__", boom)
    monkeypatch.setattr(fleetobs_mod.FleetSidecar, "__init__", boom)
    assert fleetobs_mod.start_resource_sampler() is None
    assert fleetobs_mod.attach_fleet_sidecar(
        fleetobs_mod.ServersFleetSource([])) is None
    assert fleetobs_mod.harvest_generation(None, 0, {}) is None

    assert obs.get_run() is None
    meas = _tiny_problem()
    res = rbcd.solve_rbcd(meas, 2, params=AgentParams(d=3, r=5,
                                                      num_robots=2),
                          max_iters=4, eval_every=2, grad_norm_tol=1e-9,
                          dtype=jnp.float64)
    # Consensus may terminate early on this tiny, well-conditioned problem;
    # what matters is that the solve ran and no telemetry path fired.
    assert res.iterations > 0
    assert res.cost_history

    params = AgentParams(d=3, r=5, num_robots=2, rel_change_tol=0.0,
                         certify_mode="device")
    prob = rbcd.prepare_problem(meas, 2, params=params, dtype=jnp.float32)
    res = rbcd.dispatch_prepared(prob, max_iters=20, grad_norm_tol=0.1,
                                 eval_every=5, verdict_every=10)
    assert res.iterations > 0
    assert res.certificate is not None


def test_telemetry_off_agent_paths(monkeypatch):
    from test_agent import exchange, make_agents
    from dpgo_tpu.obs import health as health_mod
    from dpgo_tpu.obs import trace as trace_mod

    def boom(*a, **kw):
        raise AssertionError("telemetry path taken while disabled")

    monkeypatch.setattr(EventStream, "emit", boom)
    monkeypatch.setattr(run_mod, "materialize", boom)
    monkeypatch.setattr(trace_mod.Span, "__init__", boom)
    monkeypatch.setattr(trace_mod, "emit_span", boom)
    monkeypatch.setattr(health_mod.HealthMonitor, "__init__", boom)
    monkeypatch.setattr(health_mod, "monitor_for", boom)

    agents, _part, _ = make_agents(2, n=10, num_lc=4)
    for _ in range(2):
        exchange(agents)
        for ag in agents:
            ag.iterate()
    assert all(ag.get_status().iteration_number == 2 for ag in agents)
