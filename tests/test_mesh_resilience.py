"""Pod-scale resilience (ISSUE 14): mesh-elastic checkpoints, the
deterministic collective fault injector, and the anomaly-triggered
rewind supervisor behind ``solve_rbcd_sharded(resilience=...)``.

The contracts pinned here:

* **Kill-a-device acceptance** — a device lost mid-solve on the
  8-virtual-device mesh loses at most K rounds of progress: the
  supervisor resumes from the last verdict-boundary checkpoint on a
  4-device mesh, the final cost matches the undisturbed run within
  rtol 1e-6, and the resumed history is a numerically-pinned suffix of
  the undisturbed one.
* **Anomaly rewind** — an injected NaN halo trips the verdict word's
  latched ``non_finite`` anomaly, the supervisor rewinds, and the solve
  converges within 1% of fault-free (exact on the virtual mesh).
* **Zero new steady-state syncs** — ``host_syncs_per_100_rounds ==
  100/K`` is unchanged with resilience enabled, counted through the
  sanctioned ``rbcd._host_fetch`` seam; the checkpoint gather rides its
  own ``resilience._host_fetch`` seam instead.
* **Fail-open storage** — corrupt checkpoints (truncated / bit-flipped
  / wrong-schema) quarantine and recovery falls back to the previous
  boundary, mirroring PR 10's session-store matrix; a global-index
  mismatch degrades to a cold restart.
* **Watchdog** — a hung fetch surfaces as a phase-naming, structured
  ``MeshFaultError`` instead of a silent hang, and the supervisor
  recovers from it like any other mesh fault.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from dpgo_tpu import obs
from dpgo_tpu.config import AgentParams
from dpgo_tpu.models import rbcd
from dpgo_tpu.parallel import (CollectiveFaultInjector, DeviceLostError,
                               MeshFaultError, MeshFaultSpec,
                               ResilienceConfig, Watchdog, make_mesh,
                               shrink_mesh_size, solve_rbcd_sharded)
from dpgo_tpu.parallel import resilience as resilience_mod
from dpgo_tpu.parallel import sharded as sharded_mod
from dpgo_tpu.serve.session import SessionStore
from dpgo_tpu.utils.partition import partition_contiguous

from synthetic import make_measurements


@pytest.fixture(autouse=True)
def _no_ambient_run():
    obs.end_run()
    yield
    obs.end_run()


def _noisy(seed, n=80, num_lc=16, noise=0.1):
    rng = np.random.default_rng(seed)
    meas, _ = make_measurements(rng, n=n, d=3, num_lc=num_lc,
                                rot_noise=noise, trans_noise=noise)
    return meas


_PARAMS = AgentParams(d=3, r=5, num_robots=8, rel_change_tol=0.0)
_K, _ROUNDS = 4, 24
_REF = {}


def _solve(meas, mesh_size=8, resilience=None, **kw):
    return solve_rbcd_sharded(
        meas, num_robots=8, mesh=make_mesh(mesh_size), params=_PARAMS,
        max_iters=_ROUNDS, verdict_every=_K, grad_norm_tol=0.0,
        eval_every=_K, resilience=resilience, **kw)


def _ref(meas):
    """The undisturbed reference run, computed once per process."""
    if "res" not in _REF:
        _REF["res"] = _solve(meas)
    return _REF["res"]


def _graph_for(meas, num_robots=8):
    part = partition_contiguous(meas, num_robots)
    graph, meta = rbcd.build_graph(part, _PARAMS.r, jnp.float64)
    X0 = rbcd.centralized_chordal_init(part, meta, graph, jnp.float64)
    state = rbcd.init_state(graph, meta, X0, params=_PARAMS)
    return graph, meta, state


# ---------------------------------------------------------------------------
# Config + small-piece contracts (fast, tier-1)
# ---------------------------------------------------------------------------

def test_resilience_config_validation(tmp_path):
    with pytest.raises(ValueError, match="checkpoint_dir"):
        ResilienceConfig()
    with pytest.raises(ValueError, match="rewind_on"):
        ResilienceConfig(checkpoint_dir=str(tmp_path),
                         rewind_on=("non_finite", "flux_capacitor"))
    with pytest.raises(ValueError, match="checkpoint_every"):
        ResilienceConfig(checkpoint_dir=str(tmp_path), checkpoint_every=0)
    with pytest.raises(ValueError, match="fetch_deadline_s"):
        ResilienceConfig(checkpoint_dir=str(tmp_path),
                         fetch_deadline_s=0.0)
    with pytest.raises(ValueError, match="max_rewinds"):
        ResilienceConfig(checkpoint_dir=str(tmp_path), max_rewinds=-1)
    cfg = ResilienceConfig(checkpoint_dir=str(tmp_path / "ck"), keep=4)
    assert cfg.resolve_store().keep == 4


def test_resilience_runs_at_the_default_cadence(tmp_path):
    """Resilience rides the verdict boundaries, which the default cadence
    puts at every eval: with ``verdict_every`` omitted the supervisor
    checkpoints at each non-terminal eval boundary, and the solve equals
    an explicit ``verdict_every=eval_every`` one."""
    meas = _noisy(3, n=24, num_lc=6, noise=0.01)
    kw = dict(num_robots=8, mesh=make_mesh(1), params=_PARAMS, max_iters=8,
              eval_every=2, grad_norm_tol=0.0)
    seen = []
    res = solve_rbcd_sharded(
        meas, boundary_cb=lambda it, *_: seen.append(it),
        resilience=ResilienceConfig(checkpoint_dir=str(tmp_path / "a")),
        **kw)
    ref = solve_rbcd_sharded(
        meas, verdict_every=2,
        resilience=ResilienceConfig(checkpoint_dir=str(tmp_path / "b")),
        **kw)
    assert seen == [2, 4, 6, 8]
    assert res.resilience["checkpoints"] == 3
    snap = SessionStore(str(tmp_path / "a")).load_newest("sharded-solve")
    assert int(snap.iteration) == 6
    assert (res.iterations, res.terminated_by) == \
        (ref.iterations, ref.terminated_by)
    assert res.cost_history == ref.cost_history
    assert np.array_equal(np.asarray(res.X), np.asarray(ref.X))


def test_shrink_mesh_size_respects_divisibility():
    assert shrink_mesh_size(8, 8) == 4
    assert shrink_mesh_size(4, 8) == 2
    assert shrink_mesh_size(2, 8) == 1
    assert shrink_mesh_size(1, 8) == 1       # nowhere left: same mesh
    assert shrink_mesh_size(4, 12) == 3      # next divisor, not half
    assert shrink_mesh_size(8, 8, min_size=4) == 4
    assert shrink_mesh_size(4, 8, min_size=4) == 4  # floor reached


def test_watchdog_deadline_names_phase():
    """A fetch that exceeds the deadline raises a structured, phase-naming
    MeshFaultError (mirroring RoundTimer.stop's open-phase guard), and
    the watchdog stays usable for the post-rewind fetch."""
    wd = Watchdog(0.15)
    release = threading.Event()
    try:
        with pytest.raises(MeshFaultError) as ei:
            wd.fetch(lambda x: release.wait(30.0), None, "sharded_verdict")
        assert ei.value.kind == "fetch_timeout"
        assert ei.value.phase == "sharded_verdict"
        assert "sharded_verdict" in str(ei.value)
        assert "watchdog deadline" in str(ei.value)
        # The stuck worker was abandoned: a fresh fetch works immediately.
        assert wd.fetch(lambda x: x + 1, 41, "gn_tail") == 42
    finally:
        release.set()
        wd.close()
    with pytest.raises(ValueError, match="deadline"):
        Watchdog(0.0)


def test_fetch_guard_composes_with_counting_shim():
    """The guard wraps whatever rbcd._host_fetch currently is, so a
    test's counting shim installed first keeps counting; the seam is
    restored on exit."""
    counted = [0]
    orig = rbcd._host_fetch

    def shim(x):
        counted[0] += 1
        return orig(x)

    rbcd._host_fetch = shim
    try:
        with resilience_mod.fetch_guard(Watchdog(5.0), None,
                                        ["sharded_verdict"], close=True):
            assert rbcd._host_fetch is not shim
            out = rbcd._host_fetch(jnp.asarray([1.0, 2.0]))
            np.testing.assert_array_equal(out, [1.0, 2.0])
        assert rbcd._host_fetch is shim
    finally:
        rbcd._host_fetch = orig
    assert counted[0] == 1


def test_injector_dispatch_poison_is_seeded_and_counted():
    """Same seed -> same poisoned (agent, pose); different seed moves it.
    The poison lands on a PUBLIC pose so the next exchange carries it."""
    _graph, _meta, state = _graph_for(_noisy(3, n=24, num_lc=6,
                                             noise=0.01))

    def poisoned(seed):
        inj = CollectiveFaultInjector(
            MeshFaultSpec(nan_halo_rounds=(2,)), seed=seed)
        inj.arm(_graph)
        st = state
        for _ in range(3):
            st = inj.before_dispatch(st, 1)
        assert inj.stats["rounds_dispatched"] == 3
        assert inj.stats["halo_nan"] == 1
        bad = np.argwhere(~np.isfinite(np.asarray(st.X)))
        assert bad.size, "no NaN landed"
        a, p = int(bad[0][0]), int(bad[0][1])
        assert p in set(np.asarray(_graph.pub_idx)[a].tolist())
        return a, p

    assert poisoned(11) == poisoned(11)
    assert poisoned(11) != poisoned(12)


def test_injector_wrap_exchange_and_installed_hooks():
    """wrap_exchange corrupts one seeded neighbor-buffer slot at trace
    level (a no-op while disabled); installed() sets and restores both
    module hooks."""
    inj = CollectiveFaultInjector(MeshFaultSpec(nan_halo_rounds=(0,)),
                                  seed=2)
    Z0 = jnp.zeros((4, 6), jnp.float64)
    wrapped = inj.wrap_exchange(lambda Xl: Z0)
    out = np.asarray(wrapped(None))
    assert np.isnan(out).sum() == 1
    assert inj.stats["links_wrapped"] == 1
    inj.enabled = False
    np.testing.assert_array_equal(np.asarray(wrapped(None)), np.asarray(Z0))
    inj.enabled = True

    assert rbcd._exchange_wrap is None and sharded_mod._gather_wrap is None
    with inj.installed():
        # Bound methods compare equal (never `is`): check the target.
        assert rbcd._exchange_wrap.__self__ is inj
        assert sharded_mod._gather_wrap.__self__ is inj
    assert rbcd._exchange_wrap is None and sharded_mod._gather_wrap is None


def test_injector_fetch_side_device_loss_and_hang():
    inj = CollectiveFaultInjector(
        MeshFaultSpec(device_loss_rounds=(0,), lost_device=5), seed=1)
    with pytest.raises(DeviceLostError) as ei:
        inj.on_fetch("sharded_verdict")
    assert ei.value.device == 5 and ei.value.kind == "device_loss"
    assert ei.value.phase == "sharded_verdict"
    assert inj.stats["device_loss"] == 1
    inj.on_fetch("sharded_verdict")  # fires once, then clean

    hang = CollectiveFaultInjector(
        MeshFaultSpec(hang_rounds=(0,), hang_s=0.05), seed=1)
    t0 = time.perf_counter()
    hang.on_fetch("gn_tail")
    assert time.perf_counter() - t0 >= 0.04
    assert hang.stats["hung_fetches"] == 1
    hang.release_hangs()


def test_boundary_cb_checkpoints_clean_and_rewinds_anomalous(tmp_path):
    """The supervisor's boundary hook: clean boundaries checkpoint (mesh
    tags included), anomalous words raise AnomalyRewind — even terminal
    ones (a solve that latched non_finite 'converged' on garbage) —
    and anomalies outside the policy pass through un-rewound."""
    graph, _meta, state = _graph_for(_noisy(3, n=24, num_lc=6,
                                            noise=0.01))
    cfg = ResilienceConfig(checkpoint_dir=str(tmp_path), rewind_on=(
        "non_finite",))
    sup = resilience_mod.CheckpointSupervisor(cfg, cfg.resolve_store(),
                                              graph, session_id="s")
    sup.attach_mesh(8)
    clean = rbcd.pack_verdict(rbcd.VERDICT_RUNNING)
    sup.boundary_cb(4, 1, state, clean, False)
    assert sup.checkpoints == 1
    snap = sup.store.load_newest("s")
    assert snap.iteration == 4 and snap.mesh_shape == (8,)
    np.testing.assert_array_equal(snap.global_index,
                                  np.asarray(graph.global_index))

    bad = rbcd.pack_verdict(rbcd.VERDICT_RUNNING, rbcd.ANOMALY_NON_FINITE)
    with pytest.raises(resilience_mod.AnomalyRewind) as ei:
        sup.boundary_cb(8, 2, state, bad, False)
    assert ei.value.anomaly == "non_finite" and ei.value.iteration == 8
    with pytest.raises(resilience_mod.AnomalyRewind):
        sup.boundary_cb(8, 2, state, bad, True)  # terminal, still garbage
    # A latched stall is outside this policy's rewind_on: no rewind, and
    # the anomalous state is never checkpointed either.
    stall = rbcd.pack_verdict(rbcd.VERDICT_RUNNING, rbcd.ANOMALY_STALL)
    sup.boundary_cb(8, 2, state, stall, False)
    assert sup.checkpoints == 1


@pytest.mark.parametrize("corrupt", ["truncate", "bitflip", "schema"])
def test_corrupt_checkpoint_falls_back_a_boundary(tmp_path, corrupt):
    """The 3-way corruption matrix (PR 10's session-store test) on the
    resilience save path: a corrupt newest checkpoint quarantines and
    recovery resumes from the boundary before it."""
    graph, _meta, state = _graph_for(_noisy(3, n=24, num_lc=6,
                                            noise=0.01))
    cfg = ResilienceConfig(checkpoint_dir=str(tmp_path))
    sup = resilience_mod.CheckpointSupervisor(cfg, cfg.resolve_store(),
                                              graph)
    sup.attach_mesh(8)
    # Boundary writes land off-thread (async_checkpoint) with
    # last-writer-wins coalescing; drain between saves so both
    # boundaries land (as they would with K rounds of compute between
    # them) and before poking at the files directly.
    sup.save(state, 4, 1)
    sup.store.flush()
    sup.save(state, 8, 2)
    sup.store.flush()
    sdir = tmp_path / cfg.session_id
    path = sdir / "snap-00000008.npz"
    if corrupt == "schema":
        blob = dict(np.load(path, allow_pickle=False))
        blob["__schema__"] = np.asarray(99, np.int64)
        with open(path, "wb") as fh:
            np.savez_compressed(fh, **blob)
    elif corrupt == "truncate":
        raw = path.read_bytes()
        path.write_bytes(raw[: len(raw) // 3])
    else:
        raw = bytearray(path.read_bytes())
        raw[len(raw) // 2] ^= 0xFF
        path.write_bytes(bytes(raw))
    fault = DeviceLostError("boom", phase="sharded_verdict", device=7)
    new_size, host_state, it, nwu = sup.recover(fault, 8, 8)
    assert (new_size, it, nwu) == (4, 4, 1)
    assert host_state is not None
    names = sorted(p.name for p in sdir.iterdir())
    assert "snap-00000008.npz.quarantined" in names
    assert sup.fault_kinds == ["device_loss"]


def test_global_index_mismatch_degrades_to_cold_restart(tmp_path):
    """A snapshot keyed to a DIFFERENT agent->pose layout is unusable:
    recovery fails open to a cold restart instead of mis-resuming."""
    graph, _meta, state = _graph_for(_noisy(3, n=24, num_lc=6,
                                            noise=0.01))
    cfg = ResilienceConfig(checkpoint_dir=str(tmp_path))
    store = cfg.resolve_store()
    sup = resilience_mod.CheckpointSupervisor(cfg, store, graph)
    sup.attach_mesh(8)
    host = resilience_mod.checkpoint_arrays(state)
    store.save(cfg.session_id, resilience_mod._host_state(host),
               iteration=4, mesh_shape=(8,),
               global_index=np.asarray(graph.global_index) + 1)
    new_size, host_state, it, nwu = sup.recover(
        MeshFaultError("hang", phase="gn_tail", kind="fetch_timeout"),
        8, 8)
    assert host_state is None and (it, nwu) == (0, 0)
    assert sup.cold_restarts == 1 and new_size == 4


def test_rewind_budget_exhaustion_is_structured(tmp_path):
    graph, _meta, _state = _graph_for(_noisy(3, n=24, num_lc=6,
                                             noise=0.01))
    cfg = ResilienceConfig(checkpoint_dir=str(tmp_path), max_rewinds=1)
    sup = resilience_mod.CheckpointSupervisor(cfg, cfg.resolve_store(),
                                              graph)
    sup.recover(DeviceLostError("x", phase="p", device=0), 8, 8)
    with pytest.raises(MeshFaultError) as ei:
        sup.recover(DeviceLostError("x", phase="p", device=1), 4, 8)
    assert ei.value.kind == "rewind_budget"
    assert "budget exhausted" in str(ei.value)


def test_checkpoint_gather_has_its_own_seam(tmp_path, monkeypatch):
    """The checkpoint gather must route through resilience._host_fetch,
    NOT rbcd._host_fetch — that separation is WHY the driver's sync-rate
    contract holds with resilience enabled."""
    _graph, _meta, state = _graph_for(_noisy(3, n=24, num_lc=6,
                                             noise=0.01))
    rbcd_counted, rz_counted = [], []
    orig = rbcd._host_fetch
    monkeypatch.setattr(rbcd, "_host_fetch",
                        lambda x: (rbcd_counted.append(0), orig(x))[1])
    orig_rz = resilience_mod._host_fetch
    monkeypatch.setattr(resilience_mod, "_host_fetch",
                        lambda x: (rz_counted.append(0), orig_rz(x))[1])
    host = resilience_mod.checkpoint_arrays(state)
    assert len(rz_counted) == len(host) > 0
    assert not rbcd_counted


# ---------------------------------------------------------------------------
# End-to-end chaos on the virtual 8-device mesh (slow; the CI mesh-chaos
# suite runs these unfiltered)
# ---------------------------------------------------------------------------

def test_device_loss_resumes_on_smaller_mesh(tmp_path):
    """Kill-a-device acceptance: at most K rounds lost, resume on a
    4-device mesh, final cost within rtol 1e-6 of the undisturbed run,
    history a numerically-pinned suffix — plus the telemetry/report
    surface for the whole fault story."""
    from dpgo_tpu.obs.events import read_events
    from dpgo_tpu.obs.report import render_report

    meas = _noisy(7)
    ref = _ref(meas)
    fault_round = 9
    inj = CollectiveFaultInjector(
        MeshFaultSpec(device_loss_rounds=(fault_round,), lost_device=3),
        seed=5)
    run_dir = str(tmp_path / "run")
    with obs.run_scope(run_dir):
        res = _solve(meas, resilience=ResilienceConfig(
            checkpoint_dir=str(tmp_path / "ck"), injector=inj))
    assert res.recovered
    rz = res.resilience
    assert rz["recoveries"] == 1 and rz["cold_restarts"] == 0
    assert rz["mesh_sizes"] == [8, 4]
    assert rz["fault_kinds"] == ["device_loss"]
    assert rz["injector"]["device_loss"] == 1
    # Final-cost parity and the pinned suffix.
    np.testing.assert_allclose(res.cost_history[-1], ref.cost_history[-1],
                               rtol=1e-6)
    nsuf = len(res.cost_history)
    np.testing.assert_allclose(res.cost_history,
                               ref.cost_history[-nsuf:], rtol=1e-6)
    assert res.iterations == ref.iterations
    # At most K rounds of verdict-CONFIRMED progress lost: the resume
    # point is exactly the last checkpoint taken before the fault, and
    # checkpoints land every K rounds.  In dispatched rounds the rewind
    # spans < 2K — the word fetch for boundary b runs after the
    # speculative b..b+K segment is dispatched, so a loss injected at
    # dispatch round r is observed at boundary b* >= r - K and resumes
    # from b* - K.
    events = read_events(f"{run_dir}/events.jsonl")
    rewinds = [e for e in events if e.get("event") == "mesh_rewind"]
    assert len(rewinds) == 1 and rewinds[0]["cold"] is False
    assert rewinds[0]["mesh_from"] == 8 and rewinds[0]["mesh_to"] == 4
    ri = events.index(rewinds[0])
    cps_before = [e["iteration"] for e in events[:ri]
                  if e.get("event") == "mesh_checkpoint"]
    assert cps_before
    assert rewinds[0]["resume_iteration"] == cps_before[-1]
    assert fault_round - rewinds[0]["resume_iteration"] < 2 * _K
    assert [e for e in events if e.get("event") == "mesh_fault"
            and e.get("kind") == "device_loss"]
    overhead = [e for e in events if e.get("event") == "metric"
                and e.get("metric") == "mesh_recovery_overhead_s"]
    assert overhead and overhead[0]["value"] > 0
    txt = render_report(run_dir)
    assert "resilience:" in txt and "rewind [device_loss]" in txt
    assert "mesh 8 -> 4 devices" in txt


def test_nan_halo_trips_anomaly_rewind(tmp_path):
    """An injected NaN halo payload trips the verdict anomaly latch
    (non_finite), rewinds on the SAME mesh (anomalies are numerical, not
    topological), and converges within 1% of fault-free."""
    meas = _noisy(7)
    ref = _ref(meas)
    inj = CollectiveFaultInjector(MeshFaultSpec(nan_halo_rounds=(10,)),
                                  seed=3)
    res = _solve(meas, resilience=ResilienceConfig(
        checkpoint_dir=str(tmp_path / "ck"), injector=inj))
    assert res.recovered
    rz = res.resilience
    assert rz["fault_kinds"] == ["anomaly:non_finite"]
    assert rz["mesh_sizes"] == [8, 8]
    assert rz["injector"]["halo_nan"] == 1
    rel = abs(res.cost_history[-1] - ref.cost_history[-1]) \
        / abs(ref.cost_history[-1])
    assert rel < 0.01
    assert np.isfinite(np.asarray(res.X)).all()


def test_double_device_loss_reshards_8_4_2(tmp_path):
    """Two device losses: 8 -> 4 -> 2 devices, the history suffix still
    pinned against the undisturbed run within rtol 1e-6 — the
    checkpoint layout is genuinely mesh-shape-independent."""
    meas = _noisy(7)
    ref = _ref(meas)
    inj = CollectiveFaultInjector(
        MeshFaultSpec(device_loss_rounds=(9, 17), lost_device=0), seed=5)
    res = _solve(meas, resilience=ResilienceConfig(
        checkpoint_dir=str(tmp_path / "ck"), injector=inj))
    rz = res.resilience
    assert rz["recoveries"] == 2
    assert rz["mesh_sizes"] == [8, 4, 2]
    nsuf = len(res.cost_history)
    np.testing.assert_allclose(res.cost_history,
                               ref.cost_history[-nsuf:], rtol=1e-6)
    np.testing.assert_allclose(res.cost_history[-1], ref.cost_history[-1],
                               rtol=1e-6)


def test_resilience_sync_rate_unchanged(tmp_path):
    """host_syncs_per_100_rounds == 100/K with resilience ENABLED: the
    checkpoint gathers ride already-paid verdict boundaries through the
    resilience plane's own seam, adding zero fetches to the sanctioned
    rbcd._host_fetch count (words + one fused terminal epilogue)."""
    meas = _noisy(7)
    counted = [0]
    orig = rbcd._host_fetch

    def shim(x):
        counted[0] += 1
        return orig(x)

    rbcd._host_fetch = shim
    try:
        res = _solve(meas, resilience=ResilienceConfig(
            checkpoint_dir=str(tmp_path / "ck")))
    finally:
        rbcd._host_fetch = orig
    words = _ROUNDS // _K
    assert counted[0] == words + 1
    assert res.resilience["checkpoints"] >= words - 1


def test_hung_fetch_watchdog_rewind(tmp_path):
    """A hung collective (simulated at the fetch seam) exceeds the
    watchdog deadline, surfaces as MeshFaultError(kind=fetch_timeout),
    and the supervisor rewinds and finishes the solve — no silent hang,
    no leaked watchdog threads (leakcheck covers this file in CI)."""
    meas = _noisy(7)
    ref = _ref(meas)
    inj = CollectiveFaultInjector(
        MeshFaultSpec(hang_rounds=(9,), hang_s=120.0), seed=3)
    res = _solve(meas, resilience=ResilienceConfig(
        checkpoint_dir=str(tmp_path / "ck"), injector=inj,
        fetch_deadline_s=2.0))
    rz = res.resilience
    assert rz["fault_kinds"] == ["fetch_timeout"]
    assert rz["injector"]["hung_fetches"] == 1
    assert rz["mesh_sizes"] == [8, 4]  # timeouts reshard like losses
    np.testing.assert_allclose(res.cost_history[-1], ref.cost_history[-1],
                               rtol=1e-6)
