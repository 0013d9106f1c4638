"""Benchmark: synchronous RBCD throughput on sphere2500 with 8 agents, r=5
(BASELINE.md north-star config #2).

Measures full RBCD rounds/sec — each round = public-pose exchange + one RTR
(truncated-CG) step for every agent — on the default JAX backend (TPU when
present), and the same problem on the CPU backend in float64 as the
stand-in for the reference's SuiteSparse/ROPTLIB CPU implementation (the
reference publishes no numbers and its ROPTLIB dependency is git-fetched at
configure time, unavailable offline — see BASELINE.md).

Since round 6 the accelerator arm times the PRODUCTION solve loop — the
device-resident verdict-word driver (``run_rbcd(verdict_every=K)``): all
rounds, the fused eval program, and termination run on device, and the
host reads one packed word per K rounds.  The raw fused-segment loop (the
pre-round-6 measurement: one trailing readback per trial) is still
measured and recorded as ``fused_rounds_per_s`` for cross-round
continuity.  Host syncs during the timed verdict trials are COUNTED via a
shim on the driver's one sanctioned fetch seam (``rbcd._host_fetch`` —
the same patch-the-seam technique as the zero-overhead telemetry smoke)
and reported as ``host_syncs_per_100_rounds``; the CPU f64 arm's
methodology (fused loop, spaced windows, contention guard) is unchanged.

Prints exactly one JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

DATASET = "/root/reference/data/sphere2500.g2o"
NUM_ROBOTS = 8
RANK = 5
#: Rounds per verdict-loop trial (the headline arm).  Large enough that
#: the per-K-round word fetches and the one-per-solve epilogue amortize:
#: at ~0.3-0.5 ms/round on the TPU the loop is device-bound, not
#: RTT-bound.
ROUNDS = int(os.environ.get("BENCH_ROUNDS", "2048"))
#: Verdict cadence K for the headline arm (one word readback per K
#: rounds; host_syncs_per_100_rounds = 100/K).
VERDICT_K = int(os.environ.get("BENCH_VERDICT_K", "512"))
#: Rounds per raw fused-loop trial (the pre-round-6 continuity arm).
FUSED_ROUNDS = int(os.environ.get("BENCH_FUSED_ROUNDS", "200"))
# 25 rounds/trial: the 1-core host's scheduling variance dominates short
# trials (observed 22.6-33.4 rounds/s across runs at 15), and ~1 s
# trials steady the median at negligible total cost.
CPU_ROUNDS = int(os.environ.get("BENCH_CPU_ROUNDS", "25"))
# Kernel selection-matmul mode for the TPU arm: bf16x3 (3-pass hi/mid/lo
# split; covers the full 24-bit f32 mantissa, so accuracy is f32-grade —
# per-round kernel-vs-XLA drift ~3e-5 vs the HIGHEST path's ~8e-6, both far
# inside the 5e-4 parity bound asserted below) at ~1.2x the HIGHEST-
# emulation round rate on this shape.  Recorded in the output JSON.
SEL_MODE = os.environ.get("BENCH_SEL_MODE", "bf16x3")
# CPU f64 arm: number of time-spaced measurement windows and their spacing.
# The 1-core host's effective f64 throughput swings up to 2x across thermal
# / scheduling windows (BASELINE.md round-4 caveat), so a single window can
# silently cherry-pick the headline; >=3 spaced windows give a min/median/
# max band and vs_baseline is computed from the MEDIAN (VERDICT r4 item 7).
CPU_WINDOWS = int(os.environ.get("BENCH_CPU_WINDOWS", "3"))
CPU_WINDOW_SPACING_S = float(os.environ.get("BENCH_CPU_SPACING_S", "45"))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def build(dtype, never_terminate: bool = False):
    from dpgo_tpu.config import AgentParams, SolverParams
    from dpgo_tpu.models import rbcd
    from dpgo_tpu.utils.partition import partition_contiguous

    if os.path.exists(DATASET):
        from dpgo_tpu.utils.g2o import read_g2o
        meas = read_g2o(DATASET)
    else:  # fall back to a same-order synthetic problem
        from dpgo_tpu.utils.synthetic import make_measurements
        meas, _ = make_measurements(np.random.default_rng(0), n=2500, d=3,
                                    num_lc=2449, rot_noise=0.01,
                                    trans_noise=0.01)
    # never_terminate (verdict-loop arm): zero the consensus tolerance so
    # the on-device termination test can never cut a timed trial short —
    # every trial runs exactly its configured round count.
    params = AgentParams(d=3, r=RANK, num_robots=NUM_ROBOTS,
                         solver=SolverParams(pallas_sel_mode=SEL_MODE),
                         rel_change_tol=0.0 if never_terminate else 5e-3)
    part = partition_contiguous(meas, NUM_ROBOTS)
    graph, meta = rbcd.build_graph(part, RANK, dtype, sel_mode=SEL_MODE)
    X0 = rbcd.centralized_chordal_init(part, meta, graph, dtype)
    state = rbcd.init_state(graph, meta, X0, params=params)
    return state, graph, meta, params, part


def time_rounds(device, dtype, rounds):
    import jax
    from dpgo_tpu.models import rbcd

    state, graph, meta, params, _part = build(dtype)
    state = jax.device_put(state, device)
    graph = jax.device_put(graph, device)

    # Fused stepping (rbcd.rbcd_steps): the whole trial runs as one on-device
    # fori_loop of full rounds — pose exchange + per-agent RTR each — so the
    # measurement excludes host dispatch, which otherwise dominates.
    steps = lambda s, k: rbcd.rbcd_steps(s, graph, k, meta, params)
    t0 = time.perf_counter()
    state = steps(state, 1)
    _ = np.asarray(state.X)
    log(f"  [{device.platform}] compile+first round: "
        f"{time.perf_counter() - t0:.1f}s")
    # Steady-state warm-up: the first fused call after compile measures
    # slower (device ramp) — an accelerator effect, so skip the extra
    # rounds on the CPU baseline.
    if device.platform != "cpu":
        _ = np.asarray(steps(state, min(50, rounds)).X)

    # Median of several trials: the median is robust to interfered trials
    # without reporting the lucky peak.
    rates = []
    state0 = state
    for _ in range(5 if device.platform != "cpu" else 3):
        t0 = time.perf_counter()
        state = steps(state0, rounds)
        # The readback fences the trial (and feeds the finiteness check).
        Xh = np.asarray(state.X)
        dt = time.perf_counter() - t0
        assert bool(np.isfinite(Xh).all()), "non-finite state"
        assert int(state.iteration) == int(state0.iteration) + rounds
        rates.append(rounds / dt)
        log(f"  [{device.platform}] trial: {rounds / dt:.1f} rounds/s")
    return float(np.median(rates))


def profile_fused_rounds(device, dtype, profile_dir, rounds=8):
    """Device-time attribution of the fused single-device loop (ISSUE
    16, opt-in via ``BENCH_DEVPROF=<dir>``): one traced segment run
    AFTER the timed trials — tracing slows the loop, so it must never
    touch a measured window.  Returns the attribution dict (or None when
    the profiler produced no trace)."""
    import jax
    from dpgo_tpu.models import rbcd
    from dpgo_tpu.obs import devprof

    state, graph, meta, params, _part = build(dtype)
    state = jax.device_put(state, device)
    graph = jax.device_put(graph, device)
    steps = lambda s, k: rbcd.rbcd_steps(s, graph, k, meta, params)
    _ = np.asarray(steps(state, 1).X)  # compile outside the window
    win = devprof.DeviceTraceWindow(profile_dir, plane="solve").start()
    _ = np.asarray(steps(state, rounds).X)
    att = win.stop(num_rounds=rounds, label="fused_loop")
    if att is not None:
        pr = att["per_round"]
        log(f"  [devprof] fused loop: {pr['compute_s'] * 1e3:.2f} ms "
            f"compute + {pr['collective_s'] * 1e3:.2f} ms collective + "
            f"{pr['idle_s'] * 1e3:.2f} ms idle per round "
            f"({att['lanes']} lanes; trace in {profile_dir})")
    return att


def time_verdict_loop(device, dtype, rounds, k):
    """Time the production device-resident solve loop: ``run_rbcd`` in
    verdict mode — schedule segments + fused eval/verdict program on
    device, ONE packed-word readback per ``k`` rounds, tolerances zeroed
    so every trial executes exactly ``rounds`` rounds.  Host syncs are
    counted through the ``rbcd._host_fetch`` seam; the per-solve terminal
    epilogue (history + latched-index fetch, 2 calls) is excluded from
    the recurring rate, matching the driver's own metric accounting.

    Returns ``(rounds_per_s_median, syncs_per_100_rounds, fetches)``."""
    import jax
    from dpgo_tpu.models import rbcd

    state0, graph, meta, params, part = build(dtype, never_terminate=True)
    state0 = jax.device_put(state0, device)
    graph = jax.device_put(graph, device)
    seg = lambda s, kk, uw, rs: rbcd.rbcd_segment(s, graph, kk, meta,
                                                  params,
                                                  first_update_weights=uw,
                                                  first_restart=rs)

    def drive(n_rounds):
        return rbcd.run_rbcd(state0, graph, meta, seg, part, n_rounds,
                             grad_norm_tol=0.0, eval_every=k, dtype=dtype,
                             params=params, verdict_every=k)

    # Warm-up compiles the segment, verdict, and finalize programs with
    # the exact call pattern of the timed trials (a structurally
    # different warm-up re-traces inside the clock — verify SKILL.md).
    t0 = time.perf_counter()
    res = drive(k)
    assert res.iterations == k
    log(f"  [{device.platform}] verdict loop compile+first block: "
        f"{time.perf_counter() - t0:.1f}s")
    drive(min(2 * k, rounds))

    counted = [0]
    orig_fetch = rbcd._host_fetch

    def counting_fetch(x):
        counted[0] += 1
        return orig_fetch(x)

    rates, sync_rates = [], []
    fetches = 0
    rbcd._host_fetch = counting_fetch
    try:
        for _ in range(3 if device.platform != "cpu" else 2):
            counted[0] = 0
            t0 = time.perf_counter()
            res = drive(rounds)
            dt = time.perf_counter() - t0
            assert res.iterations == rounds, res.iterations
            assert res.terminated_by == "max_iters", res.terminated_by
            assert all(np.isfinite(c) for c in res.cost_history), \
                "non-finite cost in verdict history"
            fetches = counted[0]
            # The single fused terminal-epilogue fetch is once-per-solve,
            # like _finalize — excluded from the rate.
            sync_rates.append(100.0 * max(fetches - 1, 0) / rounds)
            rates.append(rounds / dt)
            log(f"  [{device.platform}] verdict trial: "
                f"{rounds / dt:.1f} rounds/s, {fetches} host fetches")
    finally:
        rbcd._host_fetch = orig_fetch
    return (float(np.median(rates)), float(np.median(sync_rates)),
            int(fetches))


def kernel_parity_check(device) -> float:
    """On-device Pallas-vs-XLA drift guard: one full RBCD round through
    the compiled Mosaic kernel and through the ELL formulation on the
    bench device (``rbcd.kernel_parity``, shared with chip_smoke.py).
    Caller asserts ``rbcd.KERNEL_PARITY_BOUND`` and records the number."""
    import jax
    import jax.numpy as jnp
    from dpgo_tpu.models import rbcd

    state, graph, meta, params, _part = build(jnp.float32)
    state = jax.device_put(state, device)
    graph = jax.device_put(graph, device)
    return rbcd.kernel_parity(state, graph, meta, params)


def _busy_core_seconds() -> float:
    """System-wide non-idle CPU time in core-seconds (all cores summed)."""
    with open("/proc/stat") as f:
        vals = [int(x) for x in f.readline().split()[1:]]
    idle = vals[3] + (vals[4] if len(vals) > 4 else 0)  # idle + iowait
    # guest/guest_nice (fields 9-10) are already counted in user/nice.
    guest = sum(vals[8:10]) if len(vals) > 9 else 0
    return (sum(vals) - idle - guest) / os.sysconf("SC_CLK_TCK")


def other_cpu_during(fn):
    """Run ``fn()`` and return ``(result, other_busy)`` where ``other_busy``
    is the CPU time used by OTHER processes during the call, in core-seconds
    per wall-second (system-wide ``/proc/stat`` busy delta minus this
    process's own ``os.times`` delta).

    The CPU f64 arm under-measures when anything else loads the host (a
    concurrent pytest run halved it once — which would silently DOUBLE the
    reported speedup), so contention is measured over the TIMED WINDOW
    ITSELF — pre/post sampling misses a competitor that lives exactly as
    long as the trial, and instantaneous runnable-count sampling misses
    bursty ones (measured: a competing f64 solve dropped the arm
    28.5 -> 22 rounds/s while 5 runnable-count samples all read 0).
    Core-seconds-per-second is core-count independent: one compute-bound
    competitor reads ~1.0 on any machine."""
    try:
        b0 = _busy_core_seconds()
    except (OSError, ValueError, IndexError):  # non-Linux: no guard
        return fn(), 0.0
    s0 = sum(os.times()[:4])  # self user+sys, incl. reaped children
    t0 = time.perf_counter()
    result = fn()
    dt = max(time.perf_counter() - t0, 1e-9)
    other = max(0.0, (_busy_core_seconds() - b0) - (sum(os.times()[:4]) - s0))
    return result, other / dt


#: Other-process core-seconds/s above which the f64 CPU arm is considered
#: contended: a clean host reads ~0, a single compute-bound competitor ~1.
CONTENTION_OTHER_CORES = 0.2


def cpu_baseline_subprocess() -> dict:
    """Measure the f64 CPU baseline in a clean subprocess pinned to the
    CPU: this process holds the chip (one process per chip), and the arm
    runs with x64 on for a true double-precision run.  Returns {"ips",
    "contended", ...}."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="1",
               BENCH_MODE="cpu")
    out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                         env=env, capture_output=True, text=True, timeout=1800)
    sys.stderr.write(out.stderr)
    if out.returncode != 0:
        raise RuntimeError(f"cpu baseline failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    import jax
    import jax.numpy as jnp

    if os.environ.get("BENCH_MODE") == "cpu":
        cpu = jax.devices("cpu")[0]
        # Pre-check (this process sleeps, so all measured busy is others'):
        # wait once for a clean window before paying for the trials.
        _, pre = other_cpu_during(lambda: time.sleep(1.0))
        if pre > CONTENTION_OTHER_CORES:
            log(f"  [cpu] host contended ({pre:.2f} other core-s/s) — "
                f"waiting 20 s for a clean window")
            time.sleep(20.0)
        # The guard that counts is measured over the timed window itself.
        ips, other = other_cpu_during(
            lambda: time_rounds(cpu, jnp.float64, CPU_ROUNDS))
        try:
            with open("/proc/loadavg") as f:
                load1 = float(f.read().split()[0])
        except (OSError, ValueError):
            load1 = 0.0
        log(f"  cpu baseline: {ips:.2f} rounds/s (float64); "
            f"other-process CPU during trials {other:.2f} core-s/s, "
            f"load1 {load1:.2f}")
        print(json.dumps({"ips": ips,
                          "contended": other > CONTENTION_OTHER_CORES,
                          "other_busy_cores": round(other, 3),
                          "load1": load1}))
        return

    dev = jax.devices()[0]
    log(f"benchmark device: {dev.platform} ({dev.device_kind})")
    bench_dtype = "float32" if dev.platform != "cpu" else "float64"
    if bench_dtype == "float64":
        # CPU-only host: enable double precision.  (On the chip the arm
        # runs in f32: v5e has no hardware f64.)
        jax.config.update("jax_enable_x64", True)

    parity = None
    if dev.platform != "cpu":
        # Drift guard BEFORE timing: the compiled Mosaic kernel must match
        # the XLA formulation on this device.
        parity = kernel_parity_check(dev)
        from dpgo_tpu.models.rbcd import KERNEL_PARITY_BOUND
        log(f"  on-device kernel-vs-XLA parity: max-abs-diff {parity:.2e} "
            f"(bound {KERNEL_PARITY_BOUND:.0e})")
        assert parity < KERNEL_PARITY_BOUND, (
            f"Mosaic kernel drifted from the XLA formulation: "
            f"{parity:.3e} >= {KERNEL_PARITY_BOUND}")

    if dev.platform == "cpu":
        # CPU-only fallback: the raw fused loop, as in every prior round.
        ips = time_rounds(dev, getattr(jnp, bench_dtype), FUSED_ROUNDS)
        fused_ips, syncs, fetches = ips, None, None
        log(f"  {ips:.2f} RBCD rounds/s ({bench_dtype}, fused loop)")
    else:
        # Continuity arm first (the pre-round-6 measurement), then the
        # headline: the device-resident verdict-word solve loop.
        fused_ips = time_rounds(dev, getattr(jnp, bench_dtype),
                                FUSED_ROUNDS)
        log(f"  {fused_ips:.2f} RBCD rounds/s ({bench_dtype}, fused loop)")
        ips, syncs, fetches = time_verdict_loop(
            dev, getattr(jnp, bench_dtype), ROUNDS, VERDICT_K)
        log(f"  {ips:.2f} RBCD rounds/s ({bench_dtype}, verdict loop "
            f"K={VERDICT_K}; {syncs:.3g} host syncs/100 rounds)")

    # Optional device-time attribution of the fused loop (ISSUE 16):
    # a separate traced segment AFTER the timed arms above, so the
    # profiler overhead never contaminates the measured rates.
    attribution = None
    if os.environ.get("BENCH_DEVPROF"):
        attribution = profile_fused_rounds(
            dev, getattr(jnp, bench_dtype), os.environ["BENCH_DEVPROF"])

    if dev.platform == "cpu":
        windows = [{"ips": ips, "contended": False}]
    else:
        # >=3 time-spaced windows of the f64 arm (VERDICT r4 item 7): the
        # band makes the 2x thermal swing visible instead of letting one
        # lucky window set the headline.
        windows = []
        for wi in range(max(CPU_WINDOWS, 1)):
            if wi:
                log(f"  [cpu] window spacing: sleeping "
                    f"{CPU_WINDOW_SPACING_S:.0f}s")
                time.sleep(CPU_WINDOW_SPACING_S)
            windows.append(cpu_baseline_subprocess())
            log(f"  [cpu] window {wi + 1}/{CPU_WINDOWS}: "
                f"{windows[-1]['ips']:.2f} rounds/s"
                + (" (CONTENDED)" if windows[-1].get("contended") else ""))
    rates_all = [w["ips"] for w in windows]
    # Contended windows under-measure the arm (inflating vs_baseline), so
    # the band prefers clean windows and falls back to all only when no
    # clean window exists — in which case the output is flagged.
    clean = [w["ips"] for w in windows if not w.get("contended")] or rates_all
    cpu_med = float(np.median(clean))

    # The final line goes through the obs event schema (same leading
    # metric/value/unit keys as BENCH_r0*.json and the telemetry stream's
    # metric events), so bench records and run telemetry parse with one
    # reader (dpgo_tpu.obs.events.metric_record).
    from dpgo_tpu.obs.events import metric_record

    out = metric_record(
        "rbcd_rounds_per_sec_sphere2500_8agents_r5",
        round(ips, 3),
        "rounds/s",
        vs_baseline=round(ips / cpu_med, 3),
        sel_mode=SEL_MODE,
        cpu_arm_band={"min": round(min(rates_all), 2),
                      "median": round(cpu_med, 2),
                      "max": round(max(rates_all), 2),
                      "windows": [round(r, 2) for r in rates_all],
                      "spacing_s": CPU_WINDOW_SPACING_S},
        vs_baseline_band={"min": round(ips / max(rates_all), 2),
                          "max": round(ips / min(rates_all), 2)},
        loop="fused" if dev.platform == "cpu" else "verdict_word",
        fused_rounds_per_s=round(fused_ips, 3),
    )
    if syncs is not None:
        out["verdict_every"] = VERDICT_K
        out["verdict_rounds_per_trial"] = ROUNDS
        out["host_syncs_per_100_rounds"] = round(syncs, 4)
        out["host_fetches_per_trial"] = fetches
    if parity is not None:
        out["kernel_parity_max_abs_diff"] = parity
    if attribution is not None:
        out["device_attribution"] = {
            k: attribution[k]
            for k in ("lanes", "window_s", "compute_s", "collective_s",
                      "idle_s", "overlap_efficiency_measured")}
    if any(w.get("contended") for w in windows):
        # At least one f64 window ran on a loaded host; if ALL were
        # contended the median itself is inflated — flag loudest then.
        out["cpu_arm_contended_windows"] = sum(
            1 for w in windows if w.get("contended"))
        out["cpu_arm_all_contended"] = all(
            w.get("contended") for w in windows)
        out["cpu_arm_other_busy_cores"] = max(
            w.get("other_busy_cores") or 0.0 for w in windows)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
