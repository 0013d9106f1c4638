"""Pod-scale sharded bench (ISSUE 11): weak scaling of the sharded
verdict loop, halo-overlap A/B, comm-model validation, the sharded GN-CG
tail parity arm, and the large-scale functional solve.

Arms (each skippable):

* **weak scaling** — poses/s of the device-resident sharded verdict loop
  (``solve_rbcd_sharded(verdict_every=K)``'s driver machinery) at a
  constant per-device problem size as the mesh grows 1 -> N devices.
  Host syncs during the timed trials are counted through the sanctioned
  ``rbcd._host_fetch`` seam, exactly like ``bench.py``.
* **overlap A/B** — the halo-pipelined fused round loop vs the lockstep
  one at the largest arm; ``efficiency = 1 - t_overlap/t_lockstep``.
* **comm model** — modeled per-device interconnect bytes per round
  (``comm_bytes_per_round``) vs the bytes moved by the collectives XLA
  actually compiled (parsed from partitioned HLO).
* **GN tail** — the sharded device-resident Gauss-Newton-CG tail vs the
  host-f64 ``refine.gn_tail`` from the same handoff iterate on the noisy
  probe (final-cost parity, transfer count).
* **scale test** — a synthetic large solve (the 1M-pose / 256-agent
  configuration) driven end to end through the sharded verdict loop.
* **resilience (chaos)** — a device is killed mid-solve under the
  ``parallel.resilience`` supervisor: the solve must recover from the
  last verdict-boundary checkpoint on a halved mesh and land within
  rtol of the fault-free reference (``tools/check_bench_floor.py``
  enforces recoveries >= 1 and bounded recovery overhead).

Runs FUNCTIONALLY on CPU via the virtual device mesh
(``--xla_force_host_platform_device_count``); absolute TPU readings are
recorded as deferred when no TPU is attached.  Prints exactly one JSON
line — the MULTICHIP record (``tools/check_bench_floor.py`` validates the
schema).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma-separated mesh sizes for the weak-scaling "
                         "arm (default 1,2,4,8)")
    ap.add_argument("--poses-per-dev", type=int, default=256)
    ap.add_argument("--agents-per-dev", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=64,
                    help="rounds per timed weak-scaling trial")
    ap.add_argument("--verdict-k", type=int, default=16)
    ap.add_argument("--gn-poses", type=int, default=2000,
                    help="noisy-probe size for the GN-tail parity arm "
                         "(0 skips the arm)")
    ap.add_argument("--gn-handoff-rounds", type=int, default=60)
    ap.add_argument("--scale-poses", type=int, default=0,
                    help="pose count for the functional scale test "
                         "(0 skips; the record run uses 1000000)")
    ap.add_argument("--scale-robots", type=int, default=256)
    ap.add_argument("--scale-rounds", type=int, default=8)
    ap.add_argument("--scale-verdict-k", type=int, default=4)
    ap.add_argument("--chaos-poses", type=int, default=0,
                    help="pose count for the resilience chaos arm "
                         "(0 skips; kills a device mid-solve and gates "
                         "the recovery)")
    ap.add_argument("--chaos-rounds", type=int, default=24)
    ap.add_argument("--chaos-verdict-k", type=int, default=4)
    ap.add_argument("--telemetry", metavar="RUN_DIR", default=None,
                    help="also emit the obs event stream (sharded report "
                         "section) into RUN_DIR")
    return ap.parse_args(argv)


ARGS = parse_args()

# Backend pinning must precede the jax import.  The TPU readings of this
# bench are explicitly deferred to a TPU-attached round
# (BENCH_SHARDED_TPU=1 leaves the default platform alone); the default
# run is the functional CPU arm on the virtual device mesh.
_MAX_DEV = max(int(x) for x in ARGS.devices.split(","))
if os.environ.get("BENCH_SHARDED_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={_MAX_DEV}"
        ).strip()

import jax  # noqa: E402

if os.environ.get("BENCH_SHARDED_TPU") != "1":
    # The GN parity arm is an f64 contract.
    jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def compiled_collective_bytes(txt: str, n_dev: int):
    """Per-device cross-device bytes of a compiled program's collectives
    (partitioned HLO): an all-gather sends all but its own shard on the
    ring; a collective-permute forwards its operand block once.  The
    measured side of the comm-model check (tests/test_sharded.py pins the
    same parse against ``comm_bytes_per_round``)."""
    total = 0
    for line in txt.splitlines():
        m = re.search(r"= (f64|f32|s32|u32|pred)\[([\d,]*)\][^ ]* "
                      r"(all-gather|collective-permute)\(", line)
        if not m:
            continue
        ty, dims, op = m.groups()
        size = 1
        for x in dims.split(","):
            if x:
                size *= int(x)
        nbytes = size * {"f64": 8, "f32": 4, "s32": 4, "u32": 4,
                         "pred": 1}[ty]
        total += nbytes * (n_dev - 1) // n_dev if op == "all-gather" \
            else nbytes
    return total


def build_problem(n, robots, dtype, seed=0, noise=0.01, lc_frac=0.3,
                  init="chordal"):
    from dpgo_tpu.config import AgentParams
    from dpgo_tpu.models import rbcd
    from dpgo_tpu.utils.partition import partition_contiguous
    from dpgo_tpu.utils.synthetic import make_measurements_vectorized

    meas, _ = make_measurements_vectorized(
        np.random.default_rng(seed), n=n, d=3,
        num_lc=max(4, int(lc_frac * n)), rot_noise=noise,
        trans_noise=noise)
    params = AgentParams(d=3, r=5, num_robots=robots, rel_change_tol=0.0)
    part = partition_contiguous(meas, robots)
    graph, meta = rbcd.build_graph(part, params.r, dtype)
    X0 = rbcd.initial_state_for(init, part, meta, graph, params, dtype)
    state = rbcd.init_state(graph, meta, X0, params=params)
    return meas, params, part, graph, meta, state


def sharded_driver(mesh, part, graph, meta, state, params, dtype, k):
    """The solve_rbcd_sharded machinery with the build hoisted out, so
    repeated drives reuse the compiled step/segment programs (the same
    structure bench.py's ``time_verdict_loop`` uses)."""
    from dpgo_tpu.models import rbcd
    from dpgo_tpu.parallel import (make_sharded_metrics_body,
                                   make_sharded_multi_step,
                                   make_sharded_segment, shard_problem)
    from dpgo_tpu.types import edge_set_from_measurements

    state, graph_s = shard_problem(mesh, state, graph)
    sh_multi = make_sharded_multi_step(mesh, meta, params)
    sh_seg = make_sharded_segment(mesh, meta, params)
    seg = lambda s, kk, uw, rs: sh_seg(s, graph_s, kk, update_weights=uw,
                                       restart=rs)
    edges_g = edge_set_from_measurements(part.meas_global, dtype=dtype)
    factory = lambda tel: make_sharded_metrics_body(
        mesh, graph_s, edges_g, part.meas_global.num_poses,
        len(part.meas_global), tel)

    def drive(rounds):
        return rbcd.run_rbcd(state, graph_s, meta, seg, part, rounds,
                             grad_norm_tol=0.0, eval_every=k, dtype=dtype,
                             params=params, verdict_every=k,
                             metrics_body_factory=factory)

    return drive, state, graph_s, sh_multi


def weak_scaling_arm(dev_list, dtype):
    from dpgo_tpu.models import rbcd
    from dpgo_tpu.parallel import make_mesh

    arms = []
    syncs_last = None
    for n_dev in dev_list:
        n = ARGS.poses_per_dev * n_dev
        robots = ARGS.agents_per_dev * n_dev
        rounds, k = ARGS.rounds, ARGS.verdict_k
        _meas, params, part, graph, meta, state = build_problem(
            n, robots, dtype, seed=n_dev)
        mesh = make_mesh(n_dev)
        drive, *_ = sharded_driver(mesh, part, graph, meta, state, params,
                                   dtype, k)
        t0 = time.perf_counter()
        res = drive(k)
        log(f"  [{n_dev} dev] compile+first block: "
            f"{time.perf_counter() - t0:.1f}s "
            f"({n} poses / {robots} agents)")
        assert res.iterations == k

        counted = [0]
        orig = rbcd._host_fetch

        def counting(x):
            counted[0] += 1
            return orig(x)

        rates = []
        rbcd._host_fetch = counting
        try:
            for _ in range(2):
                counted[0] = 0
                t0 = time.perf_counter()
                res = drive(rounds)
                dt = time.perf_counter() - t0
                assert res.iterations == rounds, res.iterations
                rates.append(rounds / dt)
        finally:
            rbcd._host_fetch = orig
        rps = float(np.median(rates))
        # The one fused terminal-epilogue fetch excluded, as in bench.py.
        syncs_last = 100.0 * max(counted[0] - 1, 0) / rounds
        arms.append({"devices": n_dev, "num_robots": robots, "n_poses": n,
                     "rounds_per_s": round(rps, 3),
                     "poses_per_s": round(rps * n, 1),
                     "host_syncs_per_100_rounds": round(syncs_last, 4)})
        log(f"  [{n_dev} dev] {rps:.2f} rounds/s = "
            f"{rps * n:.0f} poses/s, {syncs_last:.3g} syncs/100 rounds")
    return arms, syncs_last


def overlap_arm(dtype, obs_run=None):
    """Halo-pipelined vs lockstep fused rounds at the largest mesh."""
    from dpgo_tpu.parallel import (make_mesh, make_sharded_multi_step,
                                   shard_problem)

    n_dev = _MAX_DEV
    n = ARGS.poses_per_dev * n_dev
    robots = ARGS.agents_per_dev * n_dev
    _meas, params, _part, graph, meta, state = build_problem(
        n, robots, dtype, seed=99)
    mesh = make_mesh(n_dev)
    state, graph_s = shard_problem(mesh, state, graph)
    rates, multis = {}, {}
    for name, overlap in (("lockstep", False), ("overlap", True)):
        multi = make_sharded_multi_step(mesh, meta, params, overlap=overlap)
        multis[name] = multi
        _ = np.asarray(multi(state, graph_s, 2).X)  # compile + warm
        t0 = time.perf_counter()
        out = multi(state, graph_s, ARGS.rounds)
        _ = np.asarray(out.X)
        rates[name] = ARGS.rounds / (time.perf_counter() - t0)
        log(f"  [overlap A/B] {name}: {rates[name]:.2f} rounds/s")
    eff = 1.0 - rates["lockstep"] / max(rates["overlap"], 1e-9)
    rec = {"efficiency": round(eff, 4),
           "overlap_rounds_per_s": round(rates["overlap"], 3),
           "lockstep_rounds_per_s": round(rates["lockstep"], 3)}
    if obs_run is not None:
        # Device-time attribution per arm (ISSUE 16): a separate traced
        # segment AFTER the clean A/B walls above (the profiler slows
        # execution, so it must never touch the timed arms).  The
        # measured split says WHERE the A/B delta comes from.
        from dpgo_tpu.obs import devprof

        calib = max(4, min(ARGS.rounds, 16))
        for name in ("lockstep", "overlap"):
            win = devprof.DeviceTraceWindow(
                os.path.join(obs_run.run_dir, f"devprof_ab_{name}"),
                plane="sharded").start()
            _ = np.asarray(multis[name](state, graph_s, calib).X)
            att = win.stop(num_rounds=calib, label=f"ab_{name}")
            if att is not None:
                rec[f"{name}_measured_overlap"] = round(
                    att["overlap_efficiency_measured"], 4)
                rec[f"{name}_collective_s_per_round"] = round(
                    att["per_round"]["collective_s"], 6)
                log(f"  [overlap A/B] {name} attribution: "
                    f"{att['overlap_efficiency_measured'] * 100:.1f}% of "
                    f"collective time hidden")
        obs_run.metric("sharded_overlap_efficiency", rec["efficiency"],
                       phase="bench",
                       overlap_rounds_per_s=rec["overlap_rounds_per_s"],
                       lockstep_rounds_per_s=rec["lockstep_rounds_per_s"])
    return rec


def comm_arm(dtype, obs_run=None):
    """Modeled vs compiled interconnect bytes for one sharded round."""
    from dpgo_tpu.parallel import (comm_bytes_per_round, make_mesh,
                                   make_sharded_step, shard_problem)

    n_dev = _MAX_DEV
    if n_dev < 2:
        return {"skipped": "single-device mesh has no collectives"}
    n = ARGS.poses_per_dev * n_dev
    robots = ARGS.agents_per_dev * n_dev
    _meas, params, _part, graph, meta, state = build_problem(
        n, robots, dtype, seed=5)
    mesh = make_mesh(n_dev)
    state, graph_s = shard_problem(mesh, state, graph)
    step = make_sharded_step(mesh, meta, params)
    txt = step.lower(state, graph_s, update_weights=False,
                     restart=False).compile().as_text()
    measured = compiled_collective_bytes(txt, n_dev)
    modeled = comm_bytes_per_round(meta, n_dev,
                                   itemsize=np.dtype(dtype).itemsize)
    log(f"  [comm] modeled {modeled} vs compiled {measured} bytes/round")
    if obs_run is not None:
        obs_run.metric("sharded_comm_bytes_measured", measured,
                       phase="bench", modeled=modeled)
    return {"modeled_bytes_per_round": modeled,
            "measured_bytes_per_round": measured,
            "match": bool(measured == modeled)}


def gn_tail_arm(dtype):
    """Sharded device-resident GN-CG tail vs host refine.gn_tail on the
    noisy probe, from the same sharded handoff iterate."""
    from dpgo_tpu.models import rbcd, refine
    from dpgo_tpu.parallel import gn_tail_sharded, make_mesh

    if ARGS.gn_poses <= 0:
        return {"skipped": "disabled (--gn-poses 0)"}
    n = ARGS.gn_poses
    robots = ARGS.agents_per_dev * _MAX_DEV
    _meas, params, part, graph, meta, state = build_problem(
        n, robots, dtype, seed=7, noise=0.1, lc_frac=0.2)
    mesh = make_mesh(_MAX_DEV)
    drive, state_s, graph_s, _ = sharded_driver(
        mesh, part, graph, meta, state, params, dtype,
        max(ARGS.gn_handoff_rounds // 4, 1))
    t0 = time.perf_counter()
    res = drive(ARGS.gn_handoff_rounds)
    log(f"  [gn] handoff after {res.iterations} BCD rounds "
        f"({time.perf_counter() - t0:.1f}s)")

    cfg = refine.GNTailConfig()
    e64 = refine.host_edges_f64(part.meas_global)
    Xg0 = np.asarray(rbcd.gather_to_global(res.X, graph,
                                           part.meas_global.num_poses),
                     np.float64)
    t0 = time.perf_counter()
    host = refine.gn_tail(Xg0, e64, cfg)
    t_host = time.perf_counter() - t0

    counted = [0]
    orig = rbcd._host_fetch

    def counting(x):
        counted[0] += 1
        return orig(x)

    rbcd._host_fetch = counting
    try:
        t0 = time.perf_counter()
        _Xa, sh = gn_tail_sharded(res.X, graph, meta, mesh=mesh, cfg=cfg)
        t_sh = time.perf_counter() - t0
    finally:
        rbcd._host_fetch = orig
    parity = abs(sh.cost_history[-1] - host.cost_history[-1]) \
        / max(abs(host.cost_history[-1]), 1e-300)
    log(f"  [gn] host: {host.terminated_by} cost {host.cost_history[-1]:.6g} "
        f"gn {host.grad_norm_history[-1]:.3g} ({t_host:.1f}s)  "
        f"sharded: {sh.terminated_by} cost {sh.cost_history[-1]:.6g} "
        f"gn {sh.grad_norm_history[-1]:.3g} ({t_sh:.1f}s, "
        f"{counted[0]} host fetches / {sh.cg_iterations} CG iters)  "
        f"parity {parity:.2e}")
    return {"n_poses": n, "num_robots": robots,
            "handoff_rounds": int(res.iterations),
            "host": {"terminated_by": host.terminated_by,
                     "final_cost": host.cost_history[-1],
                     "final_gn": host.grad_norm_history[-1],
                     "outer": host.outer_iterations,
                     "wall_s": round(t_host, 2)},
            "sharded": {"terminated_by": sh.terminated_by,
                        "final_cost": sh.cost_history[-1],
                        "final_gn": sh.grad_norm_history[-1],
                        "outer": sh.outer_iterations,
                        "cg_iterations": sh.cg_iterations,
                        "host_fetches": counted[0],
                        "wall_s": round(t_sh, 2)},
            "parity_rel": parity}


def scale_arm(dtype=jnp.float32):
    """The functional large-scale solve, end to end through the sharded
    verdict loop (odometry init — chordal at this scale is a bench of the
    init, not the loop), then the CERTIFIED row: the terminal iterate
    polished by the sharded GN-CG tail and judged by the fused device
    certificate (``rbcd.make_terminal_epilogue(certify_mode="device")``)
    — a true dual certificate at the 1M-pose scale, not a proxy.  The
    host-f64 REFUSE fallback is deliberately NOT run here (a sparse
    million-pose eigensolve on the bench host is its own benchmark); a
    REFUSE is recorded as refused."""
    from dpgo_tpu.models import certify as certify_mod
    from dpgo_tpu.models import rbcd, refine
    from dpgo_tpu.parallel import gn_tail_sharded, make_mesh
    from dpgo_tpu.types import edge_set_from_measurements

    if ARGS.scale_poses <= 0:
        return {"skipped": "disabled (--scale-poses 0)"}
    n, robots = ARGS.scale_poses, ARGS.scale_robots
    t_build0 = time.perf_counter()
    _meas, params, part, graph, meta, state = build_problem(
        n, robots, dtype, seed=11, noise=0.05, lc_frac=0.2,
        init="odometry")
    t_build = time.perf_counter() - t_build0
    log(f"  [scale] built {n} poses / {robots} agents in {t_build:.1f}s")
    mesh = make_mesh(_MAX_DEV)
    drive, _state_s, graph_s, _ = sharded_driver(
        mesh, part, graph, meta, state, params, dtype,
        ARGS.scale_verdict_k)
    t0 = time.perf_counter()
    res = drive(ARGS.scale_rounds)
    wall = time.perf_counter() - t0
    ok = res.iterations == ARGS.scale_rounds \
        and all(np.isfinite(c) for c in res.cost_history) \
        and bool(np.isfinite(np.asarray(res.X)).all())
    log(f"  [scale] {res.iterations} rounds through the sharded verdict "
        f"loop in {wall:.1f}s; cost {res.cost_history[0]:.4g} -> "
        f"{res.cost_history[-1]:.4g}")

    # Certified row: GN-CG polish + device certificate, one terminal
    # fetch through the fused epilogue.
    t_c0 = time.perf_counter()
    Xa, tail = gn_tail_sharded(res.state.X, graph_s, meta, mesh=mesh,
                               cfg=refine.GNTailConfig(max_outer=4),
                               weights=res.state.weights)
    edges_g = edge_set_from_measurements(part.meas_global, dtype=dtype)
    epilogue = rbcd.make_terminal_epilogue(
        graph_s, edges_g, part.meas_global.num_poses,
        len(part.meas_global), meta, certify_mode="device")
    eta = 1e-3 if np.dtype(dtype) == np.float32 else 1e-5
    fin = rbcd._host_fetch(epilogue(Xa, res.state.weights, {}))
    cert = certify_mod.decide_device_certificate(
        fin["cert"], eta, float(np.finfo(np.dtype(dtype)).eps),
        f64_solve=None, source="bench_scale")
    t_cert = time.perf_counter() - t_c0
    log(f"  [scale] certificate: "
        f"{certify_mod.CERT_STATUS[cert.device_verdict]} "
        f"(lam_min {cert.lambda_min:.3g}, tol {cert.tol:.3g}) "
        f"in {t_cert:.1f}s")
    return {"n_poses": n, "num_robots": robots,
            "devices": _MAX_DEV, "rounds": int(res.iterations),
            "verdict_every": ARGS.scale_verdict_k,
            "completed": bool(ok), "build_s": round(t_build, 1),
            "solve_s": round(wall, 1),
            "rounds_per_s": round(res.iterations / wall, 4),
            "poses_per_s": round(n * res.iterations / wall, 1),
            "cost_first_eval": res.cost_history[0],
            "cost_last_eval": res.cost_history[-1],
            "certified": bool(cert.certified),
            "cert_status": certify_mod.CERT_STATUS[cert.device_verdict],
            "cert_lambda_min": float(cert.lambda_min),
            "cert_tol": float(cert.tol),
            "cert_eta": eta,
            "gn_tail_terminated_by": tail.terminated_by,
            "certify_s": round(t_cert, 1),
            "dtype": str(np.dtype(dtype))}


def resilience_arm(dtype):
    """Chaos arm (ISSUE 14): kill a device mid-solve under the rewind
    supervisor and gate the recovery against the fault-free run."""
    import tempfile

    from dpgo_tpu.parallel import (CollectiveFaultInjector, MeshFaultSpec,
                                   ResilienceConfig, make_mesh,
                                   solve_rbcd_sharded)

    if ARGS.chaos_poses <= 0:
        return {"skipped": "disabled (--chaos-poses 0)"}
    n = ARGS.chaos_poses
    robots = ARGS.agents_per_dev * _MAX_DEV
    k, rounds = ARGS.chaos_verdict_k, ARGS.chaos_rounds
    meas, params, part, *_ = build_problem(n, robots, dtype, seed=13,
                                           noise=0.1, lc_frac=0.2)
    common = dict(num_robots=robots, part=part, params=params,
                  max_iters=rounds, verdict_every=k, grad_norm_tol=0.0,
                  eval_every=k, dtype=dtype)
    t0 = time.perf_counter()
    ref = solve_rbcd_sharded(meas, mesh=make_mesh(_MAX_DEV), **common)
    t_ref = time.perf_counter() - t0
    # Kill a device just past the midpoint so at least one checkpoint
    # exists; the supervisor resumes on a halved mesh.
    inj = CollectiveFaultInjector(
        MeshFaultSpec(device_loss_rounds=(rounds // 2 + 1,),
                      lost_device=_MAX_DEV - 1), seed=13)
    with tempfile.TemporaryDirectory() as td:
        t0 = time.perf_counter()
        res = solve_rbcd_sharded(
            meas, mesh=make_mesh(_MAX_DEV),
            resilience=ResilienceConfig(checkpoint_dir=td, injector=inj),
            **common)
        t_chaos = time.perf_counter() - t0
    rz = res.resilience
    rel = abs(res.cost_history[-1] - ref.cost_history[-1]) \
        / max(abs(ref.cost_history[-1]), 1e-300)
    log(f"  [chaos] device {_MAX_DEV - 1} killed after "
        f"{rounds // 2 + 1} rounds: {rz['recoveries']} recoveries, "
        f"mesh {rz['mesh_sizes']}, overhead "
        f"{rz['recovery_overhead_s']:.2f}s, final-cost rel err {rel:.2e} "
        f"({t_ref:.1f}s fault-free vs {t_chaos:.1f}s chaos)")
    return {"n_poses": n, "num_robots": robots, "devices": _MAX_DEV,
            "rounds": rounds, "verdict_every": k,
            "recoveries": rz["recoveries"],
            "checkpoints": rz["checkpoints"],
            "cold_restarts": rz["cold_restarts"],
            "mesh_sizes": rz["mesh_sizes"],
            "fault_kinds": rz["fault_kinds"],
            "recovery_overhead_s": rz["recovery_overhead_s"],
            "final_cost_rel_err": rel,
            "fault_free_s": round(t_ref, 2),
            "chaos_s": round(t_chaos, 2)}


def main():
    from dpgo_tpu import obs

    backend = jax.default_backend()
    avail = len(jax.devices())
    dev_list = [int(x) for x in ARGS.devices.split(",") if int(x) <= avail]
    log(f"bench_sharded: backend {backend}, {avail} devices, "
        f"weak-scaling arms {dev_list}")
    dtype = jnp.float64 if backend == "cpu" else jnp.float32

    scope = obs.run_scope(ARGS.telemetry) if ARGS.telemetry \
        else None
    run = None
    if scope is not None:
        scope.__enter__()
        run = obs.get_run()
    try:
        ws, syncs = weak_scaling_arm(dev_list, dtype)
        ov = overlap_arm(dtype, obs_run=run)
        comm = comm_arm(dtype, obs_run=run)
        gn = gn_tail_arm(dtype)
        scale = scale_arm()
        rz = resilience_arm(dtype)
    finally:
        if scope is not None:
            scope.__exit__(None, None, None)

    rec = {
        "record": "MULTICHIP",
        "metric": "sharded_verdict_poses_per_sec",
        "value": ws[-1]["poses_per_s"],
        "unit": "poses/s",
        "n_devices": _MAX_DEV,
        "rc": 0, "ok": True, "skipped": False,
        "backend": backend,
        "tpu_attached": backend == "tpu",
        "verdict_every": ARGS.verdict_k,
        "host_syncs_per_100_rounds": round(syncs, 4),
        "weak_scaling": ws,
        "overlap": ov,
        "comm": comm,
        "gn_tail": gn,
        "scale_test": scale,
        "resilience": rz,
    }
    if backend != "tpu":
        rec["notes"] = ("functional CPU run on the virtual device mesh; "
                        "TPU absolute readings deferred to a TPU-attached "
                        "round (single-core CPU: virtual shards share one "
                        "core, so weak-scaling poses/s is a correctness "
                        "arm here, not a throughput claim)")
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
