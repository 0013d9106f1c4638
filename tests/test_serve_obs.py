"""Serving-plane & device observability: end-to-end request tracing,
live /metrics + /statusz endpoints, compile/device profiling, SLO
burn-rate alerting, exporter unit-suffixing, and the single-flight
executable cache."""

import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from dpgo_tpu import obs
from dpgo_tpu.config import AgentParams
from dpgo_tpu.obs.exporters import exposition_name, to_prometheus_text
from dpgo_tpu.obs.metrics import MetricsRegistry
from dpgo_tpu.obs.report import (live_report, render_report, render_statusz,
                                 serving_stats)
from dpgo_tpu.serve import (ExecutableCache, OverCapacityError, ServeSLO,
                            SolveRequest, SolveServer)
from dpgo_tpu.utils.synthetic import make_measurements

PARAMS = AgentParams(d=3, r=5, num_robots=2)

#: Prometheus text-format sample line (after HELP/TYPE comments): name,
#: optional label set, value, no trailing garbage.
_PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z0-9_]+="(?:[^"\\]|\\.)*")*\})? '
    r'(-?\d+(\.\d+)?([eE][-+]?\d+)?|NaN|\+Inf|-Inf)$')


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


def _get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read().decode("utf-8")


def _spans(events):
    return [e for e in events if e.get("event") == "span"]


# ---------------------------------------------------------------------------
# The acceptance scenario: one traced, scraped, SLO'd serving run
# ---------------------------------------------------------------------------

def test_serving_observability_end_to_end(tmp_path):
    """ACCEPTANCE: a traced serving run exports a valid Chrome trace where
    every completed request shows admission -> queue_wait -> dispatch ->
    reply spans with a flow arrow into its shared batch ``dispatch``
    span; the live ``/metrics`` endpoint returns parseable Prometheus
    text mid-flight including cache compile/hit counters and per-tenant
    SLO burn gauges; ``/statusz`` and ``report --live`` agree."""
    run_dir = str(tmp_path / "run")
    n_req = 4
    with obs.run_scope(run_dir):
        with SolveServer(max_batch=2, batch_window_s=0.05, quantum=64,
                         slo=ServeSLO(latency_s=1e-9, window_s=60.0),
                         metrics_port=0) as srv:
            assert srv.sidecar is not None and srv.sidecar.port > 0
            # Two waves of two: wave 2 re-dispatches wave 1's bucket at
            # the same pow2 batch width, so it must HIT the executable
            # cache (the counter the /metrics assertion below pins).
            tickets = []
            for wave in range(2):
                wave_tickets = [
                    srv.submit(_request(_problem(n=24 + k, seed=2 * wave + k),
                                        tenant=f"t{k % 2}"))
                    for k in range(2)]
                for t in wave_tickets:
                    t.result(timeout=600)
                tickets.extend(wave_tickets)
            # One shed rides the same run (reason-tagged span below).
            shed = srv.submit(_request(_problem(), deadline_s=0.0))
            with pytest.raises(OverCapacityError):
                shed.result(timeout=60)

            base = f"http://{srv.sidecar.host}:{srv.sidecar.port}"
            code, prom = _get(base + "/metrics")
            assert code == 200
            code, hz = _get(base + "/healthz")
            assert code == 200 and json.loads(hz)["ok"] is True
            code, st = _get(base + "/statusz")
            assert code == 200
            status = json.loads(st)
            rc = live_report(f"{srv.sidecar.host}:{srv.sidecar.port}")
            assert rc == 0

    # --- live scrape: well-formed Prometheus text with the counters ----
    for line in prom.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _PROM_SAMPLE.match(line), f"bad exposition line: {line!r}"
    assert 'serve_cache_requests_total{outcome="compile"}' in prom
    assert 'serve_cache_requests_total{outcome="hit"}' in prom
    assert "serve_slo_burn_rate" in prom and 'tenant="t0"' in prom
    assert "serve_compile_seconds_total" in prom
    assert "serve_device_time_seconds_total" in prom

    # --- statusz payload ----------------------------------------------
    assert status["queue_depth"] == 0
    assert status["requests_served"] == n_req
    assert status["cache"]["compiles"] >= 1
    assert status["last_batch"]["occupancy"] > 0
    assert status["slo"]["t0"]["latency_burn"] > 1.0
    assert render_statusz(status)  # renders without exploding

    # --- the span graph ------------------------------------------------
    events = obs.read_events(f"{run_dir}/events.jsonl")
    spans = _spans(events)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    for name in ("admission", "prepare", "queue_wait", "dispatch",
                 "batch_member", "reply", "stack", "device_dispatch",
                 "slice", "shed"):
        assert name in by_name, f"missing span {name!r}"
    dispatch_ids = {s["span"] for s in by_name["dispatch"]}
    dispatch_traces = {s["trace"] for s in by_name["dispatch"]}
    # Every completed request: one trace holding admission -> queue_wait
    # -> reply, a batch_member flow arrow into a dispatch span's trace,
    # and a reply linked back from its dispatch span.
    req_traces = {s["trace"] for s in by_name["admission"]
                  if s.get("outcome") == "queued"}
    assert len(req_traces) == n_req + 1  # + the shed request
    completed = {s["trace"] for s in by_name["reply"]}
    assert len(completed) == n_req and completed <= req_traces
    for tr in completed:
        mine = [s for s in spans if s["trace"] == tr]
        assert {"admission", "queue_wait", "reply"} <= \
            {s["name"] for s in mine}
    member_links = {s["link_trace"] for s in by_name["batch_member"]}
    assert member_links == completed
    assert all(s["trace"] in dispatch_traces
               for s in by_name["batch_member"])
    for s in by_name["reply"]:
        assert s["link_span"] in dispatch_ids
    # The shed request's trace closes with a reason-tagged span.
    shed_span = by_name["shed"][0]
    assert shed_span["reason"] == "deadline"
    assert shed_span["trace"] in req_traces - completed
    # Runner spans nest under the shared dispatch.
    assert all(s["parent"] in dispatch_ids for s in by_name["stack"])

    # --- compile & device profiling ------------------------------------
    compiles = [e for e in events if e.get("event") == "compile_profile"]
    # "finalize" became the fused terminal epilogue (certify-aware key).
    labels = {c["label"] for c in compiles}
    assert labels >= {"segment", "epilogue:off"}
    assert any(lb.startswith("verdict:") for lb in labels), labels
    for c in compiles:
        assert c["total_s"] > 0 and "key" in c

    # --- SLO burn events through the health machinery ------------------
    burns = [e for e in events if e.get("event") == "anomaly"
             and e.get("kind") == "slo_burn"]
    lat_burns = [b for b in burns if b["slo"] == "latency"]
    assert {b["tenant"] for b in lat_burns} == {"t0", "t1"}
    assert all(b["burn_rate"] > 1.0 for b in burns)

    # --- Chrome trace round-trip ---------------------------------------
    from dpgo_tpu.obs import timeline

    path = timeline.write_chrome_trace(str(tmp_path / "trace.json"),
                                       timeline.merge([run_dir]))
    checks = timeline.validate_chrome_trace(path)
    assert checks["spans"] >= len(spans)
    obj = json.load(open(path))
    arrows = [e for e in obj["traceEvents"] if e.get("ph") == "s"]
    # One arrow per batch mate into dispatch + one per reply out of it.
    assert len(arrows) >= 2 * n_req

    # --- report: serving section carries the SLO story -----------------
    text = render_report(run_dir)
    assert "serving:" in text and "slo burn: tenant" in text
    stats = serving_stats(events)
    assert stats["slo"]["t0"]["alerts"] >= 1
    assert stats["no_traffic"] is False


def test_shed_only_run_reports_no_traffic(tmp_path, capsys):
    """Zero completed requests must not divide by an empty serving
    window: the section renders an explicit no-traffic line and the CLI
    exits 0."""
    run_dir = str(tmp_path / "run")
    with obs.run_scope(run_dir):
        with SolveServer(max_batch=2, batch_window_s=0.0,
                         quantum=64) as srv:
            t = srv.submit(_request(_problem(), deadline_s=0.0))
            with pytest.raises(OverCapacityError):
                t.result(timeout=60)
    events = obs.read_events(f"{run_dir}/events.jsonl")
    stats = serving_stats(events)
    assert stats is not None and stats["no_traffic"] is True
    assert stats["tenants"] == {}
    text = render_report(run_dir)
    assert "no completed requests (no traffic)" in text
    assert "shed: tenant default x1 (deadline)" in text
    from dpgo_tpu.obs.report import main as report_main

    assert report_main([run_dir]) == 0
    assert report_main([run_dir, "--json"]) == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(out)["serving"]["no_traffic"] is True


def test_live_report_unreachable_is_clean(capsys):
    rc = live_report("127.0.0.1:9")  # discard port: nothing listens
    assert rc == 2
    assert "cannot scrape" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Executable cache: single-flight under concurrency
# ---------------------------------------------------------------------------

def test_executable_cache_single_flight():
    """Parallel get() on one fingerprint must invoke the builder once;
    everyone else blocks on that build and counts as a hit."""
    cache = ExecutableCache()
    fp = {"solver": "x", "rank": 5}
    n = 8
    started = threading.Barrier(n)
    build_entered = threading.Event()
    release_build = threading.Event()
    builds = []

    def builder():
        builds.append(threading.get_ident())
        build_entered.set()
        assert release_build.wait(30)
        return object()

    results = [None] * n

    def go(k):
        started.wait()
        results[k] = cache.get(fp, builder)

    threads = [threading.Thread(target=go, args=(k,)) for k in range(n)]
    for th in threads:
        th.start()
    assert build_entered.wait(30)
    release_build.set()
    for th in threads:
        th.join(30)
    assert len(builds) == 1, "single-flight violated"
    assert all(r is results[0] and r is not None for r in results)
    assert cache.compiles == 1
    assert cache.hits == n - 1
    assert cache.stats() == {"entries": 1, "compiles": 1, "hits": n - 1}


def test_executable_cache_failed_build_retries():
    cache = ExecutableCache()
    fp = {"solver": "y"}
    calls = []

    def bad():
        calls.append(1)
        raise RuntimeError("compile exploded")

    with pytest.raises(RuntimeError):
        cache.get(fp, bad)
    # The in-flight marker is cleared: a retry builds (no deadlock).
    sentinel = object()
    assert cache.get(fp, lambda: sentinel) is sentinel
    assert cache.compiles == 1 and len(calls) == 1


# ---------------------------------------------------------------------------
# Exporter: unit suffixes + HELP hygiene
# ---------------------------------------------------------------------------

def test_exposition_name_unit_suffixing():
    assert exposition_name("queue_wait", "s") == "queue_wait_seconds"
    assert exposition_name("serve_queue_wait_seconds", "s") == \
        "serve_queue_wait_seconds"
    assert exposition_name("payload", "bytes") == "payload_bytes"
    assert exposition_name("comms_bytes_sent", "bytes") == "comms_bytes_sent"
    assert exposition_name("device_time_total", "s") == \
        "device_time_seconds_total"
    assert exposition_name("plain_counter", "") == "plain_counter"
    assert exposition_name("weird", "furlongs") == "weird"


def test_exporter_emits_help_type_and_suffixed_names():
    reg = MetricsRegistry()
    reg.histogram("wait", "queue\nwait", unit="s",
                  buckets=(0.1, 1.0)).observe(0.5)
    reg.counter("unhelped").inc()
    text = to_prometheus_text(reg)
    # Unit suffix lands on every sample and on the HELP/TYPE headers.
    assert "# TYPE wait_seconds histogram" in text
    assert "# HELP wait_seconds queue\\nwait" in text
    assert 'wait_seconds_bucket{le="0.1"}' in text
    assert "wait_seconds_sum" in text and "wait_seconds_count" in text
    assert "wait{" not in text
    # HELP falls back to the family name so every family is documented.
    assert "# HELP unhelped unhelped" in text
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert _PROM_SAMPLE.match(line), line


# ---------------------------------------------------------------------------
# Trace plumbing: explicit-trace emit_span
# ---------------------------------------------------------------------------

def test_emit_span_explicit_trace_and_parent(tmp_path):
    from dpgo_tpu.obs import trace

    with obs.run_scope(str(tmp_path / "run")) as run:
        trace.emit_span(run, "pinned", 1.0, 2.0, 0.5, phase="serve",
                        trace_id=0xabc, parent_id=0xdef, tenant="t9")
        with trace.span("outer"):
            trace.emit_span(run, "inherits", 1.0, 2.0, 0.1)
    events = obs.read_events(str(tmp_path / "run" / "events.jsonl"))
    spans = {e["name"]: e for e in _spans(events)}
    assert spans["pinned"]["trace"] == f"{0xabc:016x}"
    assert spans["pinned"]["parent"] == f"{0xdef:016x}"
    assert spans["pinned"]["tenant"] == "t9"
    assert spans["inherits"]["trace"] == spans["outer"]["trace"]
    assert spans["inherits"]["parent"] == spans["outer"]["span"]


def test_wire_trace_context_joins_server_trace(tmp_path):
    """A client-stamped wire trace context (pack_trace_entries) makes the
    server's ``frontend`` span join the CLIENT's trace id and link back
    to the client's span — one trace from TCP accept to reply."""
    from dpgo_tpu.comms.protocol import (ORIGIN_SERVE_CLIENT,
                                         pack_trace_entries)
    from dpgo_tpu.serve.frontend import _pack_str, handle_request

    with SolveServer(max_batch=2, batch_window_s=0.0, quantum=64) as srv:
        # Telemetry off: the context is popped and dropped, no span.
        frame = {"op": _pack_str("ping")}
        frame.update(pack_trace_entries(0x1234, 0x5678,
                                        ORIGIN_SERVE_CLIENT))
        assert int(handle_request(srv, frame)["ok"]) == 1
        assert "_trace" not in frame  # popped before parsing

        with obs.run_scope(str(tmp_path / "run")):
            frame = {"op": _pack_str("ping")}
            frame.update(pack_trace_entries(0x1234, 0x5678,
                                            ORIGIN_SERVE_CLIENT))
            assert int(handle_request(srv, frame)["ok"]) == 1
    events = obs.read_events(str(tmp_path / "run" / "events.jsonl"))
    fr = [e for e in _spans(events) if e["name"] == "frontend"]
    assert len(fr) == 1
    assert fr[0]["trace"] == f"{0x1234:016x}"
    assert fr[0]["link_span"] == f"{0x5678:016x}"
    assert fr[0]["link_robot"] == ORIGIN_SERVE_CLIENT


# ---------------------------------------------------------------------------
# Profiling plumbing
# ---------------------------------------------------------------------------

def test_profiled_executable_compiles_once_per_static_combo(tmp_path):
    import jax
    import jax.numpy as jnp

    from dpgo_tpu.obs.profile import ProfiledExecutable

    @jax.jit
    def f(x):
        return x * 2.0

    x = jnp.arange(4.0)
    # Telemetry off: plain jit passthrough, no AOT machinery.
    prof = ProfiledExecutable(f, key="k", label="test")
    np.testing.assert_allclose(np.asarray(prof(x)), np.asarray(x) * 2.0)

    g = jax.jit(lambda x, scale: x * (2.0 if scale else 1.0),
                static_argnames=("scale",))
    with obs.run_scope(str(tmp_path / "run")) as run:
        prof = ProfiledExecutable(g, key="k2", label="test",
                                  static_names=("scale",))
        for _ in range(3):
            np.testing.assert_allclose(np.asarray(prof(x, scale=True)),
                                       np.asarray(x) * 2.0)
        np.testing.assert_allclose(np.asarray(prof(x, scale=False)),
                                   np.asarray(x))
        run.events.close()
    events = obs.read_events(str(tmp_path / "run" / "events.jsonl"))
    compiles = [e for e in events if e.get("event") == "compile_profile"]
    # One AOT compile per static combo, NOT per call.
    assert len(compiles) == 2
    assert {json.dumps(c.get("static")) for c in compiles} == \
        {'{"scale": true}', '{"scale": false}'}
    assert all(c["label"] == "test" and c["total_s"] > 0 for c in compiles)
