"""Batched dispatch: many problems per device call.

``run_bucket`` stacks shape-identical padded problems (``bucketing``)
along a leading batch axis and drives ONE compiled program per schedule
segment — ``vmap`` of the fused RBCD segment (``models.rbcd._rbcd_segment``)
over the problem axis — instead of one driver loop per problem.  The
device amortizes dispatch and compilation across the batch; the math per
problem is the single-problem ELL formulation unchanged (vmap is
semantically per-example), so batched results match sequential solves
within kernel tolerance.

The batch axis is padded to the next power of two by replicating the last
problem, so one executable per (bucket, pow2-width) serves every
occupancy instead of one per exact batch size.

Executables come from the caller's ``ExecutableCache`` keyed by the
config fingerprint (``cache.problem_fingerprint``): segment, verdict, and
terminal-epilogue programs are each cached independently; with
``params.certify_mode="device"`` the epilogue program also computes the
per-member dual-certificate payload so the certificate rides the batch's
single terminal fetch.  With telemetry on, the
cached entries are ``obs.profile.ProfiledExecutable``\\ s (AOT compile
wall-time + XLA cost/memory analysis recorded per fingerprint key), each
dispatch window times itself into ``serve_dispatch_device_seconds``, and
the stack/dispatch/slice stages emit spans under the server's per-batch
``dispatch`` span; with telemetry off none of that machinery exists.

Termination is ``run_rbcd``'s, decided on device by the same
``rbcd._verdict_update`` (the verdict program is a ``vmap`` of the
library's): per problem, the centralized gradient norm against
``grad_norm_tol`` or all-agents consensus; the batch keeps stepping until
every member has terminated (a converged member's extra rounds only
polish its iterate — cost is monotone under the plain schedule), with
each member's history truncated at its own termination eval.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp

from .. import obs
from ..config import RobustCostType
from ..models import rbcd
from ..obs.trace import span
from .bucketing import PaddedProblem
from .cache import ExecutableCache, fingerprint_key, problem_fingerprint


def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _slice_states(state_b, n: int):
    """Per-problem views of the stacked batch state (device slices; the
    session store materializes them on save)."""
    return [jax.tree.map(lambda a: a[b], state_b) for b in range(n)]


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _make_segment_exec(meta: rbcd.GraphMeta, params):
    def seg(state_b, graph_b, k, uw, rs):
        one = lambda s, g: rbcd._rbcd_segment(
            s, g, k, meta, params, first_update_weights=uw, first_restart=rs)
        return jax.vmap(one)(state_b, graph_b)

    return jax.jit(seg, static_argnames=("uw", "rs"))


def _make_epilogue_exec(meta: rbcd.GraphMeta, n_total: int, num_meas: int,
                        certify_mode: str = "off", certify_seed: int = 0):
    """Batched fused terminal epilogue (the vmap analog of
    ``rbcd.make_terminal_epilogue``): rounding/anchoring + the weight
    collapse, plus — with ``certify_mode="device"`` — the gauge-deflated
    device-certificate eigensolve per batch member.  Padded members are
    benign: a padded pose contributes zero rows to the dual operator,
    whose zero eigenvalue is clamped by the payload's ``min(lam, 0)``."""
    device_cert = certify_mode == "device"
    want_xg = certify_mode in ("device", "host")
    if device_cert:
        from ..models import certify as certify_mod

    def one(Xa, weights, graph, eg):
        Xg = rbcd.gather_to_global(Xa, graph, n_total)
        w = rbcd.global_weights(weights, graph, num_meas)
        out = {"T": rbcd.round_global(Xg, rbcd.lifting_matrix(meta,
                                                              Xg.dtype)),
               "w": w}
        if want_xg:
            out["Xg"] = Xg
        if device_cert:
            out["cert"] = certify_mod.device_certificate_payload(
                Xg, eg._replace(weight=w),
                jax.random.PRNGKey(certify_seed))
        return out

    return jax.jit(jax.vmap(one))


def _cached_exec(cache: ExecutableCache, fp: dict, make,
                 static_names: tuple = ()):
    """Cache lookup with the compile-profiling wrap applied behind the
    telemetry fence: with a run live, the cached entry is a
    ``ProfiledExecutable`` (AOT compile + cost/memory analysis recorded
    per fingerprint key); with telemetry off the bare jit wrapper is
    stored and no profiling object ever exists.

    A cache carrying a persistent disk tier stores ``AOTExecutable``
    entries instead, on BOTH telemetry paths: the disk tier is a
    durability feature (replica restarts must skip XLA with telemetry
    off too), and the wrapper keeps its own obs emission behind the
    fence."""
    if cache.disk is not None:
        from .fleet.aotcache import AOTExecutable

        return cache.get(fp, lambda: AOTExecutable(
            make(), cache.disk, key=fingerprint_key(fp),
            label=fp.get("kind", "?"), static_names=static_names,
            bucket=fp.get("bucket_shape"), batch=fp.get("batch")))
    run = obs.get_run()
    if run is None:
        return cache.get(fp, make)
    from ..obs.profile import ProfiledExecutable

    return cache.get(fp, lambda: ProfiledExecutable(
        make(), key=fingerprint_key(fp), label=fp.get("kind", "?"),
        static_names=static_names,
        bucket=fp.get("bucket_shape"), batch=fp.get("batch")))


def run_bucket(padded: list[PaddedProblem], cache: ExecutableCache,
               max_iters: int | None = None, grad_norm_tol: float = 0.1,
               eval_every: int = 1, verdict_every: int | None = None,
               session_cb=None, session_every: int = 1,
               should_stop=None):
    """Solve a list of same-bucket padded problems as one batched program.

    Returns ``(results, info)``: per-problem ``RBCDResult`` (trajectories
    and weights sliced back to the problem's real pose/measurement
    counts), and a dict of batch statistics (rounds, evals, batch width,
    occupancy) for the serving metrics.

    Termination is the library's: per problem, the batched verdict
    program — a ``vmap`` of ``rbcd._verdict_step_jit``, so the one
    ``rbcd._verdict_update`` — latches the terminal eval on device, and
    the host reads back one packed ``[B]`` int32 verdict vector every
    ``verdict_every`` rounds (K, a positive multiple of ``eval_every``;
    None reads it at every eval), with the per-eval histories fetched
    once at the end.  A member that terminates mid-window runs up to
    ``K - eval_every`` extra polish rounds (monotone under the plain
    schedule, like waiting for the rest of the batch); its reported
    history and round count are truncated at its latched terminal eval.

    ``session_cb(iteration, states)`` — the crash-recovery hook
    (``serve.session``): called every ``session_every`` verdict
    boundaries with the per-problem sliced solver states, so a server
    can persist resumable snapshots while the batch is in flight.  A
    member problem carrying ``state0`` resumes from that exact state
    instead of its ``X0`` init.

    ``should_stop()`` — the live-migration hook (``serve.fleet``):
    polled at every verdict boundary.  A True return forces that
    boundary's ``session_cb`` snapshot (so a stopping batch always leaves
    a resume point) and breaks the loop early; the partial results
    return as usual and ``info`` carries ``interrupted=True`` so the
    server can evacuate instead of replying."""
    if not padded:
        return [], {"rounds": 0, "evals": 0, "batch": 0, "occupancy": 0.0,
                    "interrupted": False}
    first = padded[0]
    meta, params, dtype = first.meta, first.prob.params, first.prob.dtype
    shape = first.shape
    for p in padded[1:]:
        if p.shape != shape or p.meta != meta or p.prob.params != params \
                or p.prob.dtype != dtype:
            raise ValueError(
                "run_bucket requires shape/config-identical problems — "
                "bucketing must never mix incompatible shapes "
                f"({p.shape} vs {shape})")
    max_iters = params.max_num_iters if max_iters is None else max_iters
    verdict_every = eval_every if verdict_every is None else verdict_every
    if verdict_every <= 0 or verdict_every % eval_every != 0:
        raise ValueError(
            f"verdict_every={verdict_every} must be a positive "
            f"multiple of eval_every={eval_every}")

    B_real = len(padded)
    B = _next_pow2(B_real)

    def _initial_state(p: PaddedProblem):
        if p.state0 is not None:
            st = p.state0
            # Persisted snapshots drop the recomputable factors; restore
            # them from the carried weights (bit-identical refresh).
            if st.chol is None:
                st = rbcd.refresh_problem(st, p.graph, meta, params)
            return st
        return rbcd.init_state(p.graph, meta, p.X0, params=params)

    with span("stack", phase="serve", batch=B, size=B_real):
        states = [_initial_state(p) for p in padded]
        graphs = [p.graph for p in padded]
        edges_g = [p.edges_g for p in padded]
        while len(states) < B:  # replicate the tail to the pow2 width
            states.append(states[B_real - 1])
            graphs.append(graphs[B_real - 1])
            edges_g.append(edges_g[B_real - 1])
        state_b = _tree_stack(states)
        graph_b = _tree_stack(graphs)
        eg_b = _tree_stack(edges_g)

    seg = _cached_exec(
        cache, problem_fingerprint(meta, params, dtype, shape, B, "segment"),
        lambda: _make_segment_exec(meta, params),
        static_names=("uw", "rs"))
    # The library's verdict step per member; the gate is an argument, so
    # every tolerance shares one executable.  The history holds a pow2
    # number of eval rows, so resumed budgets mostly reuse one program.
    max_evals = _next_pow2(-(-max_iters // eval_every))
    gnc, health = rbcd._verdict_statics(None, None)
    one_verdict = partial(rbcd._verdict_step_jit, n_total=shape.n_total,
                          num_meas=shape.num_meas, telemetry=False,
                          gnc=gnc, health=health)
    vex = _cached_exec(
        cache, problem_fingerprint(meta, params, dtype, shape, B,
                                   f"verdict:{max_evals}"),
        lambda: jax.jit(jax.vmap(one_verdict, in_axes=(0,) * 9 + (None,))))
    certify_mode = getattr(params, "certify_mode", "off")
    fin = _cached_exec(
        cache, problem_fingerprint(meta, params, dtype, shape, B,
                                   f"epilogue:{certify_mode}"),
        lambda: _make_epilogue_exec(meta, shape.n_total, shape.num_meas,
                                    certify_mode))

    robust_on = params.robust.cost_type != RobustCostType.L2
    accel_on = params.acceleration
    vs = jax.tree.map(lambda a: jnp.broadcast_to(a, (B,) + a.shape),
                      rbcd.init_verdict_state(max_evals, meta.num_robots,
                                              dtype, False))
    tol = jnp.asarray(grad_norm_tol)
    run = obs.get_run()

    it = 0
    nwu = 0
    evals = boundaries = 0
    interrupted = False
    while True:
        vtarget = min(((it // verdict_every) + 1) * verdict_every, max_iters)
        t_d0 = time.monotonic() if run is not None else 0.0
        with span("device_dispatch", phase="serve", batch=B, verdict=True):
            while it < vtarget:
                target = min(((it // eval_every) + 1) * eval_every, vtarget)
                while it < target:
                    uw, rs, end = rbcd.schedule_bounds(
                        it, nwu, max_iters=max_iters, eval_every=eval_every,
                        params=params, robust_on=robust_on,
                        accel_on=accel_on)
                    nwu += int(uw)
                    state_b = seg(state_b, graph_b, end - it, uw=uw, rs=rs)
                    it = end
                vs = vex(graph_b, eg_b, state_b.X, state_b.weights,
                         state_b.ready, state_b.mu, state_b.rel_change,
                         state_b.iteration, vs, tol)
                evals += 1
            # The batch's one readback per K rounds: the packed
            # per-problem verdict vector.
            # dpgolint: disable=DPG003 -- sanctioned verdict fetch
            wv = rbcd._host_fetch(vs.word)
        if run is not None:
            dt = time.monotonic() - t_d0
            run.gauge("serve_dispatch_device_seconds",
                      "wall-clock of the last batched dispatch window "
                      "(segment launches through verdict readback)",
                      unit="s").set(dt)
            run.counter("serve_device_time_seconds_total",
                        "cumulative batched-dispatch wall-clock",
                        unit="s").inc(dt)
        boundaries += 1
        stop = should_stop is not None and should_stop()
        if session_cb is not None and (
                stop or boundaries % max(int(session_every), 1) == 0):
            # A stopping batch forces the boundary snapshot even when the
            # cadence would skip it — migration needs the resume point.
            session_cb(it, _slice_states(state_b, B_real))
        if stop:
            interrupted = True
            break
        all_terminal = ((wv & 7) != rbcd.VERDICT_RUNNING).all()
        if it >= max_iters or bool(all_terminal):
            break

    with span("slice", phase="serve", batch=B, certify=certify_mode):
        # The batch's ONE terminal blocking read: rounded trajectories,
        # collapsed weights, the raw batch iterate, the device-side
        # histories + latched indices, and (certify on) the per-member
        # certificate payload — a single fused pytree fetch through the
        # sanctioned seam.
        ep = {"fin": fin(state_b.X, state_b.weights, graph_b, eg_b),
              "X": state_b.X, "hist": vs.hist,
              "te": jnp.stack([vs.term_eval, vs.term_it])}
        # dpgolint: disable=DPG003 -- sanctioned terminal epilogue fetch
        ep = rbcd._host_fetch(ep)
    hist_h, te_h = ep["hist"], ep["te"]
    T_b, w_b, X_b = ep["fin"]["T"], ep["fin"]["w"], ep["X"]
    results = []
    for b, p in enumerate(padded):
        te, ti = int(te_h[0, b]), int(te_h[1, b])
        if te >= 0:
            n_keep, iters = te + 1, ti
            term = rbcd._VERDICT_STATUS.get(int(wv[b]) & 7, "max_iters")
        else:
            n_keep, iters, term = evals, it, "max_iters"
        certificate = None
        if certify_mode != "off":
            # Host decision per member on the already-fetched payload —
            # the f64 REFUSE fallback reads the fetched Xg, never the
            # device.
            with span("certify_decide", phase="serve", member=b):
                fin_b = jax.tree.map(lambda a: a[b], ep["fin"])
                fin_b["w_glob"] = fin_b.pop("w")
                certificate = rbcd._epilogue_certificate(
                    fin_b, p.edges_g, params, dtype)
        results.append(rbcd.RBCDResult(
            T=jnp.asarray(T_b[b, :p.prob.n_total]),
            X=jnp.asarray(X_b[b, :, :p.prob.meta.n_max]),
            cost_history=[float(hist_h[b, r, 0]) for r in range(n_keep)],
            grad_norm_history=[float(hist_h[b, r, 1])
                               for r in range(n_keep)],
            iterations=iters,
            terminated_by=term,
            weights=jnp.asarray(w_b[b, :p.prob.num_meas]),
            certificate=certificate,
        ))
    info = {"rounds": it, "evals": evals, "batch": B,
            "size": B_real, "occupancy": B_real / float(B),
            "interrupted": interrupted}
    return results, info
