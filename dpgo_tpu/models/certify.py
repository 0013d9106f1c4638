"""Solution certification and the Riemannian staircase — beyond-reference.

The reference implements the RBCD solver of Tian, Khosoussi, Rosen, How
(T-RO 2021) but NOT the certification half of "Distributed Certifiably
Correct Pose-Graph Optimization" (no certificate code exists anywhere in
``/root/reference/src``); SURVEY.md section 7 (M6) scopes it from the paper.
This module provides the centralized version operating on the assembled
lifted solution (the same place the framework already evaluates its
centralized monitoring metrics):

* **Dual certificate.**  A first-order critical point ``X`` of the rank-r
  relaxation yields block-diagonal dual multipliers
  ``Lambda_i = sym(Y_i^T (XQ)_i)`` on the rotation blocks (translations are
  unconstrained, their multiplier is zero).  ``X`` is a global optimum of
  the underlying SDP — and the rounded trajectory certifiably optimal —
  iff ``S = Q - Lambda`` is positive semidefinite (SE-Sync / T-RO 2021
  Prop. "exactness").  ``S`` always annihilates the global-translation
  gauge directions, so the test is ``lambda_min(S) >= -eta``.
* **Minimum eigenvalue.**  ``S`` is only ever applied as an operator: the
  edge-list connection-Laplacian matvec of ``ops.quadratic`` minus a
  per-pose block multiply — no (d+1)n x (d+1)n matrix is assembled.
  ``lambda_min`` comes from LOBPCG on the spectrally shifted operator
  ``sigma I - S`` (sigma from a short power iteration), all jittable.
* **Staircase.**  If ``lambda_min < -eta``, the eigenvector ``v`` is a
  second-order descent direction after lifting to rank r+1
  (``X+ = [[X], [alpha v^T]]``); re-solving and re-certifying ascends the
  rank staircase until certification or ``r_max`` (SE-Sync Algorithm 1
  adapted to the lifted SE(d) manifold).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp

from .. import obs
from ..config import SolverParams
from ..types import EdgeSet, Measurements, edge_set_from_measurements
from ..utils.lie import lifting_matrix
from ..ops import manifold, quadratic, solver
from .local_pgo import make_problem, round_solution


# ---------------------------------------------------------------------------
# Dual certificate operator
# ---------------------------------------------------------------------------

# Latched verdict codes of the DEVICE certificate stage (the f32
# eigensolve fused into the solve's terminal epilogue).  The f32-vs-f64
# disagreement band is an explicit verdict — CERT_REFUSE — not a silent
# recheck: a REFUSE hands the decision to the host sparse/f64 path, and
# no solve is ever certified by f32 alone inside the band.
CERT_NONE = 0      # certify_mode off / certificate not evaluated
CERT_ACCEPT = 1    # f32 verdict decisive and PSD within tolerance
CERT_REFUSE = 2    # disagreement band: host f64 must decide
CERT_FAIL = 3      # decisively negative (sound without f64)

CERT_STATUS = {CERT_NONE: "none", CERT_ACCEPT: "accept",
               CERT_REFUSE: "refuse", CERT_FAIL: "fail"}


def dual_blocks(X: jax.Array, edges: EdgeSet) -> jax.Array:
    """Block-diagonal dual multipliers Lambda [n, d, d] at a critical point.

    ``Lambda_i = sym(Y_i^T G_i)`` with ``G = X Q`` (the Euclidean gradient)
    restricted to the rotation columns.  At exact first-order criticality
    ``G_i = [Y_i Lambda_i | 0]``.
    """
    G = quadratic.egrad(X, edges)
    Y = X[..., :-1]     # [n, r, d]
    GY = G[..., :-1]
    return manifold.sym(jnp.einsum("nra,nrb->nab", Y, GY))


def certificate_matvec(V: jax.Array, edges: EdgeSet, lam: jax.Array) -> jax.Array:
    """Apply ``S = Q - Lambda`` to ``V [n, k, d+1]`` (k probe vectors).

    ``Q V`` reuses the edge-list gradient map (linear in its argument);
    ``Lambda V`` multiplies each pose's rotation columns by ``Lambda_i``
    (translation column untouched by Lambda).
    """
    QV = quadratic.egrad(V, edges)
    LV_rot = jnp.einsum("nka,nab->nkb", V[..., :-1], lam)
    LV = jnp.concatenate([LV_rot, jnp.zeros_like(V[..., -1:])], axis=-1)
    return QV - LV


@dataclasses.dataclass
class CertificateResult:
    certified: bool
    lambda_min: float           # minimum eigenvalue of S
    direction: jax.Array        # [n, d+1] eigenvector of lambda_min
    stationarity_gap: float     # ||X S|| — sanity check, ~0 at criticality
    sigma: float                # spectral shift used
    # Round-5 honesty fields (VERDICT r4 item 3): the PSD tolerance that
    # was actually applied, the measurement-weight scale it derives from,
    # whether the eigensolve's own dtype error could decide at that
    # tolerance, and the host-f64 lambda_min when a verification ran.
    tol: float = float("nan")
    weight_scale: float = float("nan")
    decidable: bool = True
    lambda_min_f64: float | None = None
    # Device-epilogue verdict (CERT_* code) when the certificate rode the
    # fused terminal fetch; CERT_NONE for the legacy post-hoc paths.
    device_verdict: int = CERT_NONE


def weight_scale(edges: EdgeSet) -> float:
    """Per-edge curvature scale of the problem: the median weighted
    concentration over valid edges (rotation and translation channels).

    This is the natural yardstick for the PSD test: S's blocks are sums
    of O(w*kappa)-sized per-edge terms, so an eigenvalue deficit far
    below this scale is physically meaningless gauge/solver noise, while
    one at or above it is a real descent direction.  Contrast the
    round-4 tolerance ``eta * sigma``: sigma is the SPECTRAL RADIUS,
    which grows with graph size and conditioning, so at the 100k-pose
    scale (sigma ~ 1.6e7) it certified a lambda_min of -2.45 against a
    tolerance of ~160 — a vacuous claim (VERDICT r4 item 3).
    """
    import numpy as np

    m = np.asarray(edges.mask, np.float64) > 0
    w = np.asarray(edges.weight, np.float64)[m] * np.asarray(
        edges.mask, np.float64)[m]
    k = np.asarray(edges.kappa, np.float64)[m]
    t = np.asarray(edges.tau, np.float64)[m]
    if k.size == 0:
        return 1.0
    return float(max(np.median(w * k), np.median(w * t), 1.0))


def weight_scale_device(edges: EdgeSet) -> jax.Array:
    """Device twin of ``weight_scale``: same median-of-weighted-
    concentrations yardstick, computed with jnp so it can ride the fused
    terminal epilogue (masked-out edges become NaN and ``nanmedian``
    skips them; an all-masked edge set degrades to the same 1.0 floor)."""
    m = edges.mask > 0
    w = edges.weight * edges.mask
    med_k = jnp.nanmedian(jnp.where(m, w * edges.kappa, jnp.nan))
    med_t = jnp.nanmedian(jnp.where(m, w * edges.tau, jnp.nan))
    scale = jnp.maximum(jnp.maximum(med_k, med_t), 1.0)
    return jnp.where(jnp.isnan(scale), 1.0, scale)


@partial(jax.jit, static_argnames=("num_probe", "power_iters", "lobpcg_iters"))
def _min_eig_jit(X, edges: EdgeSet, key, num_probe: int = 4,
                 power_iters: int = 30, lobpcg_iters: int = 300):
    from jax.experimental.sparse.linalg import lobpcg_standard

    n, _, dh = X.shape
    dtype = X.dtype
    lam = dual_blocks(X, edges)

    def S(V):  # [n, k, d+1] -> [n, k, d+1]
        return certificate_matvec(V, edges, lam)

    # Spectral upper bound: power iteration on S (symmetric, so dominant
    # |eigenvalue|); sigma slightly above max(|lambda|_max, 0).
    def power_body(_, v):
        w = S(v)
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v0 = jax.random.normal(key, (n, 1, dh), dtype)
    v0 = v0 / jnp.linalg.norm(v0)
    v = jax.lax.fori_loop(0, power_iters, power_body, v0)
    lam_dom = jnp.sum(v * S(v))  # Rayleigh quotient, |.| ~ spectral radius
    sigma = 1.1 * jnp.abs(lam_dom) + 1e-3

    # LOBPCG on sigma I - S (PSD): largest eigenvalue = sigma - lambda_min(S).
    def A_flat(Vf):  # [n(d+1), k]
        k = Vf.shape[1]
        V = Vf.T.reshape(k, n, dh).transpose(1, 0, 2)
        W = sigma * V - S(V)
        return W.transpose(1, 0, 2).reshape(k, n * dh).T

    key2 = jax.random.fold_in(key, 1)
    V0 = jax.random.normal(key2, (n * dh, num_probe), dtype)
    theta, U, iters = lobpcg_standard(A_flat, V0, m=lobpcg_iters)
    lam_min = sigma - theta[0]
    vec = U[:, 0].reshape(n, dh)

    # Stationarity residual ||X S|| = ||XQ - X Lambda|| for diagnostics.
    XS = certificate_matvec(X, edges, lam)
    stat = jnp.sqrt(jnp.sum(XS * XS))
    return lam_min, vec, stat, sigma


def _timed_f64(fn, sink: list):
    """Wrap the host f64 REFUSE-band fallback so its wall seconds land
    in ``sink`` — installed only when telemetry is live (the off path
    keeps the bare closure)."""
    def wrapped(t):
        t_f = time.perf_counter()
        try:
            return fn(t)
        finally:
            sink.append(time.perf_counter() - t_f)
    return wrapped


def _tally_cert(run, certified: bool, decidable: bool, f64_secs: list,
                source: str) -> None:
    """ACCEPT/FAIL/REFUSE decision tallies plus the f64-fallback wall —
    the per-status counters the f32 ACCEPT-band sweep (ROADMAP item 3)
    reads to see how often the expensive host eigensolve fires."""
    status = "accept" if certified else ("fail" if decidable else "refuse")
    run.counter("cert_status_total",
                "certificate decisions by final status").inc(
        status=status, source=source)
    if f64_secs:
        run.counter("cert_f64_fallback_seconds_total",
                    "wall-clock spent in the host f64 REFUSE-band "
                    "eigensolve fallback",
                    unit="s").inc(sum(f64_secs), source=source)


def certify_solution(
    X: jax.Array,
    edges: EdgeSet,
    eta: float = 1e-5,
    seed: int = 0,
    num_probe: int = 4,
    lobpcg_iters: int = 300,
    f64_verify: str = "auto",
) -> CertificateResult:
    """Certify a first-order critical point of the rank-r relaxation.

    ``certified`` means ``lambda_min(S) >= -tol`` with
    ``tol = eta * weight_scale(edges)`` — a threshold at the per-edge
    curvature scale, NOT the spectral radius (the round-4 ``eta * sigma``
    rule was near-vacuous at large sigma; VERDICT r4 item 3).  The gauge
    nullspace of S makes exact zeros expected; ``eta`` absorbs them.

    The eigensolve runs in ``X.dtype``; its error scales with
    ``eps(dtype) * sigma``.  When that error cannot resolve ``tol``
    (an f32 solve on a large/ill-conditioned graph), the f32 verdict is
    NOT trusted: with ``f64_verify="auto"`` the minimum eigenvalue is
    re-computed on the host in float64 (``lambda_min_f64``, warm-started
    from the f32 eigenvector) and THAT value decides; with
    ``f64_verify="never"`` the result reports ``decidable=False`` and
    refuses to certify.
    """
    run = obs.get_run()
    t0 = time.perf_counter() if run is not None else 0.0
    key = jax.random.PRNGKey(seed)
    # lobpcg_standard requires 5*k < dim; clamp the probe count so tiny
    # graphs (triangle/line test fixtures) certify instead of crashing.
    dim = X.shape[0] * X.shape[2]
    num_probe = max(1, min(num_probe, (dim - 1) // 5))
    lam_min, vec, stat, sigma = _min_eig_jit(
        X, edges, key, num_probe=num_probe, lobpcg_iters=lobpcg_iters)
    lam_min_f = float(lam_min)
    sigma_f = float(sigma)
    wscale = weight_scale(edges)
    tol = eta * wscale

    import numpy as np

    def f64_solve(t):
        return lambda_min_f64(np.asarray(X, np.float64), edges,
                              warm=np.asarray(vec, np.float64), tol=t,
                              tol_cert=tol)

    f64_secs: list = []
    chosen_f64 = f64_solve if f64_verify == "auto" else None
    if run is not None and chosen_f64 is not None:
        chosen_f64 = _timed_f64(chosen_f64, f64_secs)
    certified, decidable, lam_used, lam_f64, vec64 = decide_certificate(
        lam_min_f, sigma_f, tol, float(jnp.finfo(X.dtype).eps),
        chosen_f64)
    if vec64 is not None:
        vec = jnp.asarray(vec64, X.dtype)
    if run is not None:
        # The eigenvalue gap is how far the decisive minimum eigenvalue
        # clears the certification threshold -tol: positive = certified
        # margin, negative = descent-direction depth the staircase escapes
        # along.  ``float(lam_min)`` above already materialized the
        # eigensolve, so the timing fence is the existing readback.
        gap = lam_used + tol
        run.gauge("certificate_eigenvalue_gap",
                  "lambda_min + tol of the dual certificate").set(gap)
        run.gauge("certificate_lambda_min",
                  "minimum eigenvalue of the certificate operator").set(
            lam_used)
        run.counter("certificates_evaluated",
                    "certify_solution calls").inc()
        _tally_cert(run, certified, decidable, f64_secs,
                    source="certify_solution")
        run.event("certificate", phase="certify",
                  certified=certified, decidable=decidable,
                  lambda_min=lam_min_f, lambda_min_f64=lam_f64,
                  eigenvalue_gap=gap, tol=tol, sigma=sigma_f,
                  stationarity_gap=float(stat), dim=dim,
                  f64_fallback_s=sum(f64_secs) if f64_secs else None,
                  duration_s=time.perf_counter() - t0)
        # Verdict timeline -> numerical health: a streak of undecidable
        # verdicts (REFUSE loop) is an anomaly the staircase driver would
        # otherwise spin on silently.
        from ..obs.health import monitor_for as _monitor_for

        _monitor_for(run).observe_certificate(
            certified=certified, decidable=decidable, lambda_min=lam_used,
            source="certify_solution")
    return CertificateResult(
        certified=certified,
        lambda_min=lam_min_f,
        direction=vec,
        stationarity_gap=float(stat),
        sigma=sigma_f,
        tol=tol,
        weight_scale=wscale,
        decidable=decidable,
        lambda_min_f64=lam_f64,
    )


def decide_certificate(lam_eig: float, sigma: float, tol: float,
                       dtype_eps: float, f64_solve=None):
    """The post-eigensolve certificate decision, shared by
    ``certify_solution`` and ``parallel.certify.certify_sharded`` so the
    two paths cannot desynchronize (round-5 review).

    Semantics (VERDICT r4 item 3): the eigensolve's error is ~10 ulps of
    the shifted operator (the LOBPCG works on ``sigma I - S``); when that
    cannot resolve ``tol`` the dtype verdict is NOT trusted — the caller's
    ``f64_solve(tol_f64) -> (lam_f64, vec64_or_None, resid)`` host
    verification decides instead, and an UNCONVERGED f64 eigensolve
    (``resid > tol/2``) refuses: Ritz values approach lambda_min from
    above, so accepting one could only ever over-certify.

    Returns ``(certified, decidable, lam_used, lam_f64, vec64)``.
    """
    err_est = 10.0 * dtype_eps * sigma
    decidable = err_est <= 0.5 * tol
    lam_f64 = vec64 = None
    if not decidable and lam_eig + 50.0 * err_est < -tol:
        # Decisively negative FAIL without the (expensive) f64
        # verification.  Asymmetric on purpose — skipping f64 here can
        # only ever UNDER-certify, never over-certify, and it saves a
        # multi-minute host eigensolve per failing staircase rank at
        # 100k.  The 50x safety factor is empirical (round 5): err_est
        # models ROUNDING (~10 ulps of the shifted operator), but an
        # f32 LOBPCG at 300k dims reported lambda ~ -4e-4 at a
        # POLISHED gn-4e-7 optimum — ~20 ulps of sigma of
        # accumulation/non-convergence error.  A wound saddle
        # (lambda ~ -1.5e-2 at sigma 170) still shortcuts; anything
        # within 50 ulps of the tolerance goes to f64.
        return False, True, lam_eig, None, None
    if not decidable and f64_solve is not None:
        certified, decidable, lam_f64, vec64 = f64_recheck(f64_solve, tol)
        return certified, decidable, lam_f64, lam_f64, vec64
    lam_used = lam_eig
    return (bool(decidable and lam_used >= -tol), bool(decidable),
            lam_used, lam_f64, vec64)


def f64_recheck(f64_solve, tol: float):
    """REFUSE-band fallback: the host f64 eigensolve decides.

    Two-sided interval decision on the f64 eigenpair (shared by
    ``decide_certificate`` and the device-epilogue path): the residual
    places a true eigenvalue within ``resid`` of ``lam_f64``, so
      lam_f64 + resid < -tol  => an eigenvalue below -tol exists
                                 (sound FAIL), and
      lam_f64 - resid >= -tol => the targeted bottom eigenvalue
                                 clears -tol (PASS).
    Anything in between is refused.  This replaces the round-5 draft
    rule ``resid <= tol/2`` which refused a CONVERGED-to-0 eigenvalue
    whose residual (2e-4) merely missed an arbitrary threshold while
    the verdict itself was unambiguous.

    Where ``lambda_min_f64_shift_invert`` decides by the inertia of
    ``S + tol I`` (pose graphs whose sparse factorization is viable), the
    PASS is exact: no eigenvalue lies below -tol.  On its fall-through
    path, and on the small-problem LOBPCG path, the PASS rests on the
    trust assumption every Krylov certificate makes: that the
    warm-started, gauge-deflated solve targeted the minimal subspace.

    Returns ``(certified, decidable, lam_f64, vec64)``.
    """
    with obs.trace.span("cert.f64"):
        lam_f64, vec64, resid = f64_solve(0.25 * tol)
    certified = lam_f64 - resid >= -tol
    decidable = certified or (lam_f64 + resid < -tol)
    return bool(certified), bool(decidable), lam_f64, vec64


# ---------------------------------------------------------------------------
# Device-resident certificate (fused terminal epilogue, ROADMAP item 3)
# ---------------------------------------------------------------------------

def device_certificate_payload(X: jax.Array, edges: EdgeSet, key,
                               num_probe: int = 4, power_iters: int = 30,
                               lobpcg_iters: int = 300) -> dict:
    """Everything the HOST needs to decide the certificate, computed as
    one traceable program so it can ride the solve's fused terminal
    epilogue (a single blocking fetch).

    Unlike ``_min_eig_jit`` this eigensolve is GAUGE-DEFLATED on device:
    at a stationary point the r rows of X span exact zero-eigenvalue
    directions of S, a cluster that stalls LOBPCG's convergence to the
    bottom of the spectrum.  The probes are constrained to the
    complement via the projector ``P = I - Yc Yc^T`` (Yc = orthonormal
    basis of the rows), the LOBPCG runs on ``P (sigma I - S) P``, and
    the full-space minimum is ``min(lambda_complement, 0)`` since the
    deflated directions contribute exact zeros.

    The payload also carries the two soundness probes the host decision
    needs (``decide_device_certificate``):

    * ``defl_resid`` — max column norm of ``S Yc``: the deflation is
      only valid near stationarity; a PASS with an invalid deflation
      basis is unsound and must be refused (same ``0.1 * tol`` bound as
      ``lambda_min_f64_shift_invert``).
    * ``rq`` — the explicit Rayleigh quotient of the returned unit
      direction on S: ``RQ(v) >= lambda_min`` for ANY v, so a decisively
      negative RQ is an unconditional FAIL even if the eigensolve itself
      did not converge.

    All outputs are scalars (plus the ``[n, d+1]`` direction), cheap to
    fetch; no decision happens here — f32 never certifies alone.
    """
    from jax.experimental.sparse.linalg import lobpcg_standard

    n, r, dh = X.shape
    dtype = X.dtype
    dim = n * dh
    # lobpcg_standard requires 5*k < dim; shapes are static at trace
    # time, so the tiny-problem clamp is Python int math.
    num_probe = max(1, min(num_probe, (dim - 1) // 5))
    lam = dual_blocks(X, edges)

    def S(V):  # [n, k, d+1] -> [n, k, d+1]
        return certificate_matvec(V, edges, lam)

    def S_flat(Vf):  # [n(d+1), k]
        k = Vf.shape[1]
        V = Vf.T.reshape(k, n, dh).transpose(1, 0, 2)
        return S(V).transpose(1, 0, 2).reshape(k, dim).T

    # Spectral upper bound: power iteration on S (symmetric, so dominant
    # |eigenvalue|); sigma slightly above max(|lambda|_max, 0).
    def power_body(_, v):
        w = S(v)
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v0 = jax.random.normal(key, (n, 1, dh), dtype)
    v0 = v0 / jnp.linalg.norm(v0)
    v = jax.lax.fori_loop(0, power_iters, power_body, v0)
    lam_dom = jnp.sum(v * S(v))
    sigma = 1.1 * jnp.abs(lam_dom) + 1e-3

    # Gauge basis: the SIGNIFICANT left-singular directions of X's rows.
    # At (near-)optimality X itself is low-rank (rank ~ d+1 < r), and a
    # plain QR of the rank-deficient row basis manufactures arbitrary
    # complement directions that are NOT near-kernel — deflating along
    # them would blind the eigensolve, and the defl_resid guard below
    # would (correctly) veto every ACCEPT.  Insignificant directions are
    # instead left in the complement where the LOBPCG sees them like any
    # other; the soundness guard only needs the directions we actually
    # remove to be near-kernel.
    Yf = X.transpose(1, 0, 2).reshape(r, dim).T           # [dim, r]
    U_g, sv, _ = jnp.linalg.svd(Yf, full_matrices=False)
    keep = (sv > jnp.max(sv) * jnp.sqrt(jnp.finfo(dtype).eps)
            ).astype(dtype)                               # [r]
    Yc = U_g * keep[None, :]
    SYc = S_flat(U_g)
    defl_resid = jnp.max(jnp.linalg.norm(SYc, axis=0) * keep)

    def project(Vf):
        return Vf - Yc @ (Yc.T @ Vf)

    def A_flat(Vf):  # P (sigma I - S) P
        Pv = project(Vf)
        return project(sigma * Pv - S_flat(Pv))

    key2 = jax.random.fold_in(key, 1)
    V0 = project(jax.random.normal(key2, (dim, num_probe), dtype))
    theta, U, _ = lobpcg_standard(A_flat, V0, m=lobpcg_iters)
    lam_comp = sigma - theta[0]
    # Gauge zeros complete the spectrum: full-space minimum.
    lam_min = jnp.minimum(lam_comp, 0.0)

    vec_f = U[:, 0]
    vec_f = vec_f / jnp.maximum(jnp.linalg.norm(vec_f), 1e-30)
    # Explicit Rayleigh quotient of the unit direction on the TRUE
    # operator — the sound one-sided FAIL bound.
    rq = jnp.sum(vec_f * S_flat(vec_f[:, None])[:, 0])
    vec = vec_f.reshape(n, dh)

    XS = certificate_matvec(X, edges, lam)
    stat = jnp.sqrt(jnp.sum(XS * XS))
    return {
        "lam_min": lam_min,
        "sigma": sigma,
        "stat": stat,
        "wscale": weight_scale_device(edges),
        "defl_resid": defl_resid,
        "rq": rq,
        "direction": vec,
    }


def decide_device_certificate(payload: dict, eta: float, dtype_eps: float,
                              f64_solve=None,
                              source: str = "device_epilogue",
                              ) -> CertificateResult:
    """HOST decision on an already-fetched device certificate payload.

    Mirrors ``decide_certificate``'s ladder exactly, with the deflation
    validity bound gating only the ACCEPT side (a FAIL via the Rayleigh
    quotient is sound regardless of deflation):

    * decidable (``10 ulps of sigma`` resolves tol) and lam >= -tol and
      the deflation basis is near-kernel  -> CERT_ACCEPT;
    * decidable and lam < -tol            -> CERT_FAIL (f32 decides);
    * undecidable but lam or rq is below ``-tol`` by 50x the error
      band                                 -> CERT_FAIL (sound shortcut,
      same asymmetric rule as ``decide_certificate``);
    * anything else                        -> CERT_REFUSE, and the host
      f64 path (``f64_solve``) decides via ``f64_recheck`` when
      provided — never the f32 value.

    The payload values arrive as 0-d arrays from the fused terminal
    fetch; everything here is host float math (no device sync).
    """
    run = obs.get_run()
    t0 = time.perf_counter() if run is not None else 0.0
    f64_secs: list = []
    if run is not None and f64_solve is not None:
        f64_solve = _timed_f64(f64_solve, f64_secs)
    lam = float(payload["lam_min"])
    sigma = float(payload["sigma"])
    rq = float(payload["rq"])
    wscale = float(payload["wscale"])
    defl_resid = float(payload["defl_resid"])
    stat = float(payload["stat"])
    direction = payload["direction"]
    tol = eta * wscale
    err_est = 10.0 * dtype_eps * sigma
    defl_ok = defl_resid <= 0.1 * tol
    decidable = err_est <= 0.5 * tol

    verdict = CERT_REFUSE
    certified = False
    lam_used = lam
    lam_f64 = None
    if decidable and lam < -tol:
        verdict, decidable = CERT_FAIL, True
    elif decidable and defl_ok and lam >= -tol:
        verdict, certified = CERT_ACCEPT, True
    elif min(lam, rq) + 50.0 * err_est < -tol:
        # Decisively negative even through the undecidable band — the
        # RQ bound makes this sound without f64 (under-certify only).
        verdict, decidable, lam_used = CERT_FAIL, True, min(lam, rq)
    elif f64_solve is not None:
        certified, decidable, lam_f64, vec64 = f64_recheck(f64_solve, tol)
        lam_used = lam_f64
        if vec64 is not None:
            direction = jnp.asarray(vec64, payload["direction"].dtype)
    else:
        decidable = False
    if run is not None:
        gap = lam_used + tol
        run.gauge("certificate_eigenvalue_gap",
                  "lambda_min + tol of the dual certificate").set(gap)
        run.gauge("certificate_lambda_min",
                  "minimum eigenvalue of the certificate operator").set(
            lam_used)
        run.counter("certificates_evaluated",
                    "certify_solution calls").inc()
        _tally_cert(run, certified, decidable, f64_secs, source=source)
        run.event("certificate", phase="certify",
                  certified=certified, decidable=decidable,
                  lambda_min=lam, lambda_min_f64=lam_f64,
                  eigenvalue_gap=gap, tol=tol, sigma=sigma,
                  stationarity_gap=stat,
                  device_verdict=CERT_STATUS[verdict], source=source,
                  f64_fallback_s=sum(f64_secs) if f64_secs else None,
                  duration_s=time.perf_counter() - t0)
        from ..obs.health import monitor_for as _monitor_for

        _monitor_for(run).observe_certificate(
            certified=certified, decidable=decidable, lambda_min=lam_used,
            source=source)
    return CertificateResult(
        certified=bool(certified),
        lambda_min=lam,
        direction=direction,
        stationarity_gap=stat,
        sigma=sigma,
        tol=tol,
        weight_scale=wscale,
        decidable=bool(decidable),
        lambda_min_f64=lam_f64,
        device_verdict=verdict,
    )


def host_f64_solve(X, edges: EdgeSet, tol_cert: float, warm=None):
    """Closure adapting ``lambda_min_f64`` to the
    ``f64_solve(t) -> (lam, vec, resid)`` shape the decision ladders
    consume — the REFUSE fallback of both the post-hoc and the
    device-epilogue certificate paths."""
    import numpy as np

    def f64_solve(t):
        return lambda_min_f64(
            np.asarray(X, np.float64), edges,
            warm=None if warm is None else np.asarray(warm, np.float64),
            tol=t, tol_cert=tol_cert)
    return f64_solve


def sparse_certificate(X64, edges: EdgeSet):
    """Assemble the certificate operator ``S = Q - Lambda`` as a scipy
    CSR matrix over the ``[n * (d+1)]`` column space (f64, host).

    Mirrors ``certificate_matvec``'s quadratic form edge-by-edge: with
    ``rR = Y_j - Y_i R`` and ``rt = p_j - p_i - Y_i t`` (the
    ``quadratic._edge_terms`` convention), each edge contributes the
    (d+1)x(d+1) pose blocks

      H_jj = diag(wk I_d, wt)
      H_ii = [[wk I_d + wt t t^T, wt t], [wt t^T, wt]]
      H_ij = [[-wk R, -wt t], [0, -wt]]          (H_ji = H_ij^T)

    and ``Lambda_i = sym(Y_i^T G_i)`` is subtracted on the rotation
    coordinates.  Exists for the at-scale f64 verification: an explicit
    sparse matrix enables shift-invert Lanczos (``eigsh(sigma=-tol)``),
    which converges tightly even inside the dense near-zero clusters
    (gauge + cycle bands) where plain LOBPCG's eigenVECTOR residual
    never resolves (measured round 5 at 300k dims).
    """
    import numpy as np
    from scipy import sparse

    X64 = np.asarray(X64, np.float64)
    n, r, dh = X64.shape
    d = dh - 1
    i = np.asarray(edges.i)
    j = np.asarray(edges.j)
    R = np.asarray(edges.R, np.float64)
    t = np.asarray(edges.t, np.float64)
    w = np.asarray(edges.weight, np.float64) \
        * np.asarray(edges.mask, np.float64)
    wk = w * np.asarray(edges.kappa, np.float64)
    wt = w * np.asarray(edges.tau, np.float64)
    m = i.shape[0]
    valid = w != 0.0

    Hjj = np.zeros((m, dh, dh))
    Hii = np.zeros((m, dh, dh))
    Hij = np.zeros((m, dh, dh))
    eye = np.eye(d)
    Hjj[:, :d, :d] = wk[:, None, None] * eye
    Hjj[:, d, d] = wt
    Hii[:, :d, :d] = wk[:, None, None] * eye \
        + wt[:, None, None] * t[:, :, None] * t[:, None, :]
    Hii[:, :d, d] = wt[:, None] * t
    Hii[:, d, :d] = wt[:, None] * t
    Hii[:, d, d] = wt
    Hij[:, :d, :d] = -wk[:, None, None] * R
    Hij[:, :d, d] = -wt[:, None] * t
    Hij[:, d, d] = -wt

    def coo(blocks, rows_of, cols_of):
        rr = (rows_of[:, None] * dh + np.arange(dh))[:, :, None]
        cc = (cols_of[:, None] * dh + np.arange(dh))[:, None, :]
        rr = np.broadcast_to(rr, (m, dh, dh))
        cc = np.broadcast_to(cc, (m, dh, dh))
        v = np.where(valid[:, None, None], blocks, 0.0)
        return rr.ravel(), cc.ravel(), v.ravel()

    parts = [coo(Hii, i, i), coo(Hjj, j, j), coo(Hij, i, j),
             coo(np.swapaxes(Hij, -1, -2), j, i)]
    rows = np.concatenate([p[0] for p in parts])
    cols = np.concatenate([p[1] for p in parts])
    vals = np.concatenate([p[2] for p in parts])
    Q = sparse.coo_matrix((vals, (rows, cols)),
                          shape=(n * dh, n * dh)).tocsr()

    # Lambda from the assembled Q: G = X Q per probe row.
    Xf = X64.transpose(1, 0, 2).reshape(r, n * dh)
    G = (Q @ Xf.T).T.reshape(r, n, dh).transpose(1, 0, 2)
    lam = np.einsum("nra,nrb->nab", X64[..., :d], G[..., :d])
    lam = 0.5 * (lam + np.swapaxes(lam, -1, -2))
    lr = np.broadcast_to(np.arange(n)[:, None, None] * dh
                         + np.arange(d)[None, :, None], (n, d, d))
    lc = np.broadcast_to(np.arange(n)[:, None, None] * dh
                         + np.arange(d)[None, None, :], (n, d, d))
    L = sparse.coo_matrix((lam.ravel(), (lr.ravel(), lc.ravel())),
                          shape=(n * dh, n * dh)).tocsr()
    return Q - L


def _inertia_factor(S, tol_cert: float):
    """Symmetric SuperLU factor of ``S + tol_cert I`` (minimum-degree order
    on ``A + A^T``, diagonal pivots) and what its pivots decide.

    With the row order equal to the column order the factor is
    ``P A P^T = L D L^T`` (``D = diag(U)``), so by Sylvester's law of
    inertia the pivots <= 0 count the eigenvalues of S at or below
    ``-tol_cert`` exactly.  Returns ``(lu or None, outcome)``: ``"pass"``
    (every pivot > 0, so ``lambda_min(S) > -tol_cert``), ``"fail"`` (a
    pivot <= 0), ``"pivoted"`` (a pivot left the diagonal, or SuperLU
    found the matrix singular).  Only a ``"pass"`` factor is positive
    definite, hence stable to solve with."""
    import numpy as np
    from scipy import sparse
    from scipy.sparse.linalg import splu

    A = (S + tol_cert * sparse.eye(S.shape[0], format="csr")).tocsc()
    try:
        with obs.trace.span("cert.f64.inertia"):
            lu = splu(A, permc_spec="MMD_AT_PLUS_A", diag_pivot_thresh=0.0,
                      options={"SymmetricMode": True})
    except RuntimeError:
        return None, "pivoted"
    if not np.array_equal(lu.perm_r, lu.perm_c):
        return lu, "pivoted"
    return lu, "pass" if bool((lu.U.diagonal() > 0).all()) else "fail"


def _shift_invert_window(S, k: int, tol_cert: float, maxiter: int,
                         lu=None):
    """``eigsh(S, sigma=-tol_cert)``: the ``k`` eigenpairs nearest the
    threshold, through ``lu`` (a factor of ``S + tol_cert I``) when given,
    else through eigsh's own sparse LU.  Non-convergence salvages partial
    pairs; ``(None, None)`` when there are none or the LU is singular."""
    import numpy as np
    from scipy.sparse.linalg import (ArpackNoConvergence, LinearOperator,
                                     eigsh)

    opinv = None if lu is None else LinearOperator(
        S.shape, matvec=lu.solve, dtype=np.float64)
    try:
        return eigsh(S, k=k, sigma=-tol_cert, which="LM", maxiter=maxiter,
                     tol=1e-10, OPinv=opinv)
    except ArpackNoConvergence as e:
        if e.eigenvalues is None or not len(e.eigenvalues):
            return None, None
        return e.eigenvalues, e.eigenvectors
    except RuntimeError:
        return None, None


def _min_pair(S, vals, vecs):
    """The smallest Ritz pair as a unit vector, with its explicit
    residual ``||S v - lam v||``."""
    import numpy as np

    idx = int(np.argmin(vals))
    lam, v = float(vals[idx]), vecs[:, idx]
    v = v / max(np.linalg.norm(v), 1e-300)
    return lam, v, float(np.linalg.norm(S @ v - lam * v))


def _rq_veto(S, v):
    """(Rayleigh quotient, normalized v), or None for a degenerate v —
    RQ < -tol_cert is a sound FAIL certificate for any v."""
    import numpy as np

    v = np.asarray(v, np.float64).reshape(-1)
    nv = np.linalg.norm(v)
    if not np.isfinite(nv) or nv < 1e-300:
        return None
    v = v / nv
    return float(v @ (S @ v)), v


def _refusal(S):
    """A pair the interval rule can never pass: a residual of at least
    the spectral radius, and no vector, so the caller KEEPS its own (f32)
    direction — a zero direction would silently no-op the staircase's
    saddle escape."""
    import numpy as np

    return 0.0, None, float(np.abs(S).sum(axis=1).max())


def _expander_guard(edges: EdgeSet, n: int, dh: int) -> bool:
    """FILL GUARD: sparse LU is only viable on chain/planar-ish graphs.
    A high fraction of long-range edges (random loop closures) makes the
    graph an expander whose LU fill is near-dense — measured round 5:
    >25 min and ~7 GB at 400k dims, twice, on the noisy 100k synthetic
    (17% random LCs), vs seconds on the stitched-winding chain (1%
    long-range bridges).  Such graphs never factor: when the guard trips
    and the Krylov passes are inconclusive, the honest outcome is
    REFUSAL, not an unbounded factorization."""
    import numpy as np

    i_np = np.asarray(edges.i)
    j_np = np.asarray(edges.j)
    msk = (np.asarray(edges.mask) > 0) if hasattr(edges, "mask") \
        else np.ones_like(i_np, bool)
    span = np.abs(i_np[msk] - j_np[msk])
    long_frac = float(np.mean(span > max(64, n // 100))) if span.size \
        else 0.0
    return n * dh > 100_000 and long_frac > 0.05


def lambda_min_f64_shift_invert(X64, edges: EdgeSet, tol_cert: float,
                                k: int = 12, maxiter: int = 2000,
                                warm=None):
    """Minimum eigenvalue of S near the certification threshold, on the
    explicit sparse operator.

    Returns ``(lam_min, eigenvector [n, d+1] or None, resid)`` with
    ``resid`` the explicit eigenpair residual of the reported pair on S —
    ``f64_recheck``'s two-sided interval rule consumes it.  The vector is
    ``None`` when no eigenpair could be computed (the caller must then
    keep its own direction estimate, e.g. the f32 one).

    Order of work:

    1. The caller's ``warm`` direction (the f32 eigensolve's) is screened
       by its RAYLEIGH QUOTIENT on S: RQ(v) >= lambda_min for ANY v, so
       RQ(v) < -tol_cert is an unconditional FAIL, residual 0.
    2. Inertia.  Unless ``_expander_guard`` trips, one symmetric
       factorization of ``S + tol_cert I`` decides (``_inertia_factor``).
       Every pivot > 0 proves ``lambda_min(S) > -tol_cert`` by Sylvester's
       law, exact up to the factorization's rounding (with positive pivots
       it is a Cholesky factor of a matrix within ~k eps max(S_ii) of
       ``S + tol I``, k the fill of a row of L — orders below the
       weight-scale tolerance).  The shift-invert window
       (``eigsh(sigma=-tol_cert)`` through the SAME factor) then reports
       the pair: with nothing below the threshold, the eigenvalues nearest
       it are the bottom of the spectrum.  No SA or LOBPCG pass runs.
    3. Otherwise (the guard tripped, a pivot <= 0, pivoting off the
       diagonal, a singular factor, or a window that converged to nothing)
       the Krylov passes of ``_krylov_passes`` run: SA Lanczos,
       gauge-deflated LOBPCG, shift-invert through eigsh's own
       partially pivoted LU (a symmetric factor of an indefinite matrix
       has unbounded growth, so it is not reused).  Their PASS rests on
       the trust assumption that some screened direction tracks the
       minimal subspace — except after a pivot <= 0: the inertia has then
       counted an eigenvalue at or below -tol_cert, and a pair that would
       pass is reported as a refusal instead.

    ``cert_inertia_total{outcome=pass|fail|pivoted|guarded}`` counts,
    with a telemetry run, how the inertia decided each recheck that got
    past the warm veto.
    """
    import numpy as np

    n, _, dh = np.asarray(X64).shape
    with obs.trace.span("cert.f64.assemble"):
        S = sparse_certificate(X64, edges)

    # A veto returns resid 0.0: the RQ bound is ONE-SIDED for free
    # (lambda_min <= RQ < -tol needs no eigenpair residual), and
    # decide_certificate's FAIL branch (lam + resid < -tol) then draws
    # exactly the sound conclusion.  The reported value is an upper
    # bound on lambda_min, which only ever understates the deficit.
    if warm is not None:
        r_w = _rq_veto(S, warm)
        if r_w is not None and r_w[0] < -tol_cert:
            return r_w[0], r_w[1].reshape(n, dh), 0.0

    guarded = _expander_guard(edges, n, dh)
    vals = vecs = None
    if guarded:
        outcome = "guarded"
    else:
        with obs.trace.span("cert.f64.shift_invert"):
            lu, outcome = _inertia_factor(S, tol_cert)
            if outcome == "pass":
                vals, vecs = _shift_invert_window(S, k, tol_cert, maxiter,
                                                  lu)
    run = obs.get_run()
    if run is not None:
        run.counter("cert_inertia_total",
                    "host f64 rechecks by what the inertia of S + tol I "
                    "decided").inc(outcome=outcome)
    if outcome == "pass" and vals is not None:
        lam, v, resid = _min_pair(S, vals, vecs)
        return lam, v.reshape(n, dh), resid

    lam, v, resid = _krylov_passes(S, X64, tol_cert, k, maxiter, warm,
                                   guarded)
    if outcome == "fail" and lam - resid >= -tol_cert:
        # The inertia counted an eigenvalue at or below -tol that no
        # screened direction found: the window missed the bottom.
        # Refuse (lam - resid < -tol, lam + resid >= -tol), with no
        # vector: the window's has RQ >= -tol, no descent direction, so
        # the caller keeps its own.
        return lam, None, lam + 2.0 * tol_cert
    return lam, None if v is None else v.reshape(n, dh), resid


def _krylov_passes(S, X64, tol_cert: float, k: int, maxiter: int, warm,
                   guarded: bool):
    """The recheck's passes where the inertia did not decide: SA Lanczos,
    gauge-deflated LOBPCG, then shift-invert at the threshold (never
    where ``guarded``).  Returns ``(lam, flat vector or None, resid)``.

    ``eigsh(sigma)`` returns the eigenvalues NEAREST the shift, so a
    near-zero cluster larger than ``k`` could crowd a genuinely negative
    lambda_min out of the window, and the window pair alone would falsely
    PASS (``k`` should comfortably exceed the gauge dimension, r rows +
    slack).  Every available direction is therefore screened by its
    Rayleigh quotient — the SA pass's Ritz vectors (converged or not) and
    the LOBPCG direction — so any RQ < -tol_cert is a sound FAIL."""
    import numpy as np
    from scipy.sparse.linalg import ArpackNoConvergence, eigsh

    n, r, dh = np.asarray(X64).shape

    # Pass 1 — plain smallest-algebraic Lanczos: converges fast exactly
    # when lambda_min is a SEPARATED negative outlier (the case the
    # shift-invert pass below can rank beneath the gauge cluster in its
    # transformed spectrum).  Its Ritz values are Rayleigh quotients of
    # the Ritz vectors, so ANY Ritz value < -tol is a sound FAIL even
    # unconverged; an inconclusive pass (all Ritz >= -tol) falls through
    # to shift-invert.
    try:
        with obs.trace.span("cert.f64.sa"):
            vals, vecs = eigsh(S, k=4, which="SA", maxiter=60, tol=1e-7)
        lam_sa, v_sa, r_sa = _min_pair(S, vals, vecs)
    except ArpackNoConvergence as e:
        lam_sa = v_sa = r_sa = None
        if getattr(e, "eigenvalues", None) is not None \
                and len(e.eigenvalues):
            lam_sa, v_sa, r_sa = _min_pair(S, e.eigenvalues, e.eigenvectors)
    if lam_sa is not None and lam_sa < -tol_cert:
        # Recompute the RQ explicitly: a salvaged unconverged ARPACK
        # Ritz value can deviate from the true RQ of its vector (lost
        # orthogonality ~ eps * sigma); the SOUND bound is the explicit
        # v @ S v of the actual unit vector, not the reported value.
        r_sa_rq = _rq_veto(S, v_sa)
        if r_sa_rq is not None and r_sa_rq[0] < -tol_cert:
            return r_sa_rq[0], r_sa_rq[1], 0.0

    # Pass 2 — gauge-deflated LOBPCG on the SPARSE operator.  Complement
    # of the shift-invert pass below: on well-connected graphs (random
    # long-range loop closures — e.g. the 100k synthetic) the spectrum
    # has a healthy gap above the gauge kernel, so deflated LOBPCG with
    # ~10 ms sparse matvecs converges in seconds, while the sparse LU of
    # the SAME graph explodes (expander fill-in: measured round 5, >25
    # min and ~7 GB at 400k dims before being killed).  On chain/planar
    # graphs the roles flip (tiny fill, clustered bottom) — which is
    # exactly the case pass 3 handles.
    from scipy.sparse.linalg import lobpcg as _lobpcg

    Yc = np.stack([np.asarray(X64[:, a, :], np.float64).reshape(n * dh)
                   for a in range(r)], axis=1)
    Yc, _ = np.linalg.qr(Yc)
    rng = np.random.default_rng(0)
    V0 = rng.standard_normal((n * dh, 4))
    if warm is not None:
        w = np.asarray(warm, np.float64).reshape(n * dh)
        if np.isfinite(w).all() and np.linalg.norm(w) > 1e-300:
            V0[:, 0] = w
    # Deflation-validity bound for the PASS direction: the constrained
    # search cannot see eigenvalue content INSIDE span(Yc), so a PASS is
    # only sound if Yc really is near-kernel.  With ||S yc|| <= delta, a
    # missing direction u (lambda_u < -tol) satisfies
    # |<u, yc>| <= delta / |lambda_u| <= delta / tol, so delta <=
    # 0.1 * tol leaves >= 99% of u's mass in the complement where the
    # LOBPCG sees it.  An iterate stopped far from stationarity (gauge
    # columns not near-kernel) therefore falls through instead of
    # certifying blind.  The sound-FAIL RQ veto needs no such guard.
    SYc = S @ Yc
    defl_ok = float(np.linalg.norm(SYc, axis=0).max()) <= 0.1 * tol_cert
    try:
        with obs.trace.span("cert.f64.lobpcg"):
            vals_l, vecs_l = _lobpcg(S, V0, Y=Yc, largest=False, maxiter=300,
                                     tol=min(1e-8, 0.1 * tol_cert),
                                     verbosityLevel=0)
        lam_l, v_l, r_l = _min_pair(S, vals_l, vecs_l)
        rq_l = float(v_l @ (S @ v_l))  # explicit RQ of the unit vector
        lam_l_full = min(lam_l, 0.0)  # gauge zeros complete the spectrum
        if rq_l < -tol_cert:
            # Rayleigh quotient of a genuine unit vector: sound FAIL.
            return rq_l, v_l, 0.0
        if defl_ok and lam_l_full - r_l >= -tol_cert:
            return lam_l_full, v_l, r_l
    except (np.linalg.LinAlgError, ValueError) as e:
        # The EXPECTED numerical failures of deflated LOBPCG (singular
        # Gram/basis breakdown -> LinAlgError; degenerate block shapes ->
        # ValueError) fall through to shift-invert.  Anything else (a
        # programming error, keyboard interrupt, OOM) propagates — the
        # old blanket ``except Exception: pass`` hid those too.
        import warnings
        warnings.warn(
            f"gauge-deflated LOBPCG pass failed with {type(e).__name__}: "
            f"{e}; falling through to shift-invert", RuntimeWarning)

    # Pass 3 — shift-invert at the threshold: the sparse LU of
    # S + tol I separates the near-zero clusters (gauge + graph bands)
    # where plain Krylov eigenvector residuals never resolve; the
    # eigenvalues NEAREST the threshold are exactly the ones that
    # decide certification.  Non-convergence (or a singular LU when the
    # shift lands on an eigenvalue) must REFUSE, not crash a multi-hour
    # staircase: salvage partial eigenpairs when present, else return a
    # pair whose residual can never pass the interval rule.
    if guarded:
        if lam_sa is not None:
            return lam_sa, v_sa, r_sa
        return _refusal(S)
    with obs.trace.span("cert.f64.shift_invert"):
        vals, vecs = _shift_invert_window(S, k, tol_cert, maxiter)
    if vals is None:
        if lam_sa is not None:
            return lam_sa, v_sa, r_sa
        return _refusal(S)
    lam, v, resid = _min_pair(S, vals, vecs)
    if lam_sa is not None and lam_sa + r_sa < lam - resid:
        # The SA interval proves a true eigenvalue strictly below
        # everything the shift-invert window saw — the window missed
        # the bottom, so its PASS would be unsound; report the more
        # pessimistic SA pair (refusal) instead.
        return lam_sa, v_sa, r_sa
    # The window's Ritz values are RQs too: _min_pair took the argmin, so
    # a window member below -tol decides FAIL through the interval rule
    # with its (tiny) residual.  At this point every screened direction
    # (warm, SA Ritz, window) has RQ >= -tol; the PASS still rests on
    # the documented trust assumption that SOME screened direction
    # tracks the minimal subspace — the assumption the inertia pass
    # removes wherever the guard lets it factor.
    return lam, v, resid


def lambda_min_f64(X64, edges: EdgeSet, warm=None, num_probe: int = 4,
                   maxiter: int = 4000, tol: float | None = None,
                   deflate: bool = False, tol_cert: float | None = None):
    """HOST float64 minimum eigenvalue of the certificate operator S.

    The device eigensolve cannot resolve a weight-scale tolerance when
    ``eps32 * sigma`` exceeds it (e.g. the 100k-pose synthetic: sigma
    ~1.6e7 makes f32 blind below ~16); this scipy LOBPCG runs the same
    operator in f64 via the numpy edge-gradient (``refine._np_egrad``),
    warm-started from the f32 eigenvector so it polishes rather than
    searches.  Returns ``(lambda_min, eigenvector [n, d+1], resid)`` —
    ``resid`` is the eigenpair residual ``||S v - lambda v||``, and it is
    load-bearing: an unconverged Ritz value approaches lambda_min from
    ABOVE, so callers MUST refuse certification unless ``resid`` resolves
    their tolerance (see the refusal gates in ``certify_solution`` /
    ``parallel.certify.certify_sharded``).
    """
    import numpy as np
    from scipy.sparse.linalg import LinearOperator, lobpcg

    from .refine import _np_egrad, _np_sym, np_edges_batched

    n, r, dh = X64.shape
    d = dh - 1
    if tol_cert is not None and n * dh >= 10_000:
        # Large problems route to shift-invert Lanczos on the explicit
        # sparse operator: the near-zero clusters (gauge + graph bands)
        # that stall LOBPCG's eigenvector residual at this scale are
        # exactly what the spectral transformation separates.  Measured
        # on a 5,000-pose SE(2) map with closures between nearby poses
        # (15,000 dims, PR 21): LOBPCG 50 s, residual 2e-3 against a
        # 1e-3 tolerance (refused); shift-invert 1.8 s, residual 3e-14.
        # At 30,000 dims (city10000 shape) LOBPCG refused on the chip's
        # host too, which the old 50,000-dim threshold sent it to.
        # ``tol_cert`` is the CERTIFICATION threshold (the certify
        # callers pass their -tol decision point explicitly); ``tol``
        # remains the LOBPCG convergence tolerance of the small path.
        return lambda_min_f64_shift_invert(X64, edges, tol_cert,
                                           warm=warm)
    e64 = np_edges_batched(edges)

    G, _, _, _ = _np_egrad(X64[None], e64, n)
    lam = _np_sym(np.swapaxes(X64[..., :d], -1, -2) @ G[0][..., :d])

    def S_apply(Vf):
        # Vf [n*dh, k] -> S V; probes ride the r axis of the egrad map.
        k = Vf.shape[1]
        V = Vf.T.reshape(k, n, dh).transpose(1, 0, 2)      # [n, k, dh]
        QV, _, _, _ = _np_egrad(V[None], e64, n)
        QV = QV[0]
        LV = np.einsum("nka,nab->nkb", V[..., :d], lam)
        SV = QV.copy()
        SV[..., :d] -= LV
        return SV.transpose(1, 0, 2).reshape(k, n * dh).T

    op = LinearOperator((n * dh, n * dh), matvec=lambda v: S_apply(
        v.reshape(-1, 1)).ravel(), matmat=S_apply, dtype=np.float64)

    rng = np.random.default_rng(0)
    V0 = rng.standard_normal((n * dh, num_probe))
    if warm is not None:
        V0[:, 0] = np.asarray(warm, np.float64).reshape(n * dh)
    # Deflate the GAUGE kernel: at a stationary point X S = 0 exactly, so
    # the r rows of X span known zero-eigenvalue directions — an exact
    # zero CLUSTER that stalls LOBPCG's convergence to the smallest
    # eigenvalue at large n (measured round 5: 300k dims never reached
    # tol 2.5e-5, so every 100k certificate was refused).  Constraining
    # the probes to the complement (scipy's Y) removes the cluster; the
    # gauge directions themselves have lambda = 0 >= -tol by
    # construction, so lambda_min over the full space is
    # min(lambda_complement, 0) and certification is decided by the
    # complement eigenvalue alone.  At a NON-stationary X the deflation
    # vectors are only approximate — harmless: the eigenpair residual
    # below is computed on the TRUE operator, so a poisoned result still
    # refuses (and the stationarity gap is reported separately).
    # OPT-IN only: scipy's constrained LOBPCG is unstable at small dims
    # (measured: resid 50.8 on a 60-dim test that converges
    # unconstrained), and the production large-scale route is the
    # shift-invert path above (which supersedes deflation — the sparse
    # LU separates the zero cluster structurally); deflation remains for
    # matrix-free use where assembling S is not an option.
    Yc = None
    if deflate:
        Yc = np.stack([np.asarray(X64[:, a, :], np.float64).reshape(n * dh)
                       for a in range(r)], axis=1)
        Yc, _ = np.linalg.qr(Yc)
    with obs.trace.span("cert.f64.lobpcg"):
        vals, vecs = lobpcg(op, V0, Y=Yc, largest=False, maxiter=maxiter,
                            tol=tol, verbosityLevel=0)
    i = int(np.argmin(vals))
    lam_min, v = float(vals[i]), vecs[:, i]
    # Eigenpair residual ||S v - lam v||: an UNCONVERGED Ritz value
    # approaches lambda_min from ABOVE, so accepting it would
    # over-certify — exactly the failure this f64 path exists to stop.
    # Callers must refuse certification unless the residual resolves
    # their tolerance.  (The residual of a DEFLATED eigenpair carries a
    # component along the approximate-kernel directions when X is not
    # exactly stationary; that component is bounded by the stationarity
    # gap, which certification already requires to be small.)
    v = v / max(np.linalg.norm(v), 1e-300)
    resid = float(np.linalg.norm(S_apply(v.reshape(-1, 1)).ravel()
                                 - lam_min * v))
    if deflate:
        # Full-space lambda_min = min(complement value, gauge zeros).
        lam_min = min(lam_min, 0.0)
    return lam_min, v.reshape(n, dh), resid


# ---------------------------------------------------------------------------
# Riemannian staircase
# ---------------------------------------------------------------------------

def escape_rank(X: jax.Array, direction: jax.Array, edges: EdgeSet,
                alpha0: float = 1e-2, max_halvings: int = 20) -> jax.Array:
    """Lift ``X`` to rank r+1 along the negative-curvature direction.

    ``X+ = [[X], [alpha v^T]]`` projected to the rank-(r+1) manifold: since
    ``v^T S v < 0``, the cost strictly decreases for small alpha (SE-Sync
    saddle escape).  Backtracks alpha until the projected point improves.
    """
    n, r, dh = X.shape
    f0 = quadratic.cost(X, edges)

    def lifted(alpha):
        row = alpha * direction[:, None, :]  # [n, 1, d+1]
        return manifold.project(jnp.concatenate([X, row], axis=1))

    def cond(s):
        alpha, k, ok = s
        return (~ok) & (k < max_halvings)

    def body(s):
        alpha, k, _ = s
        ok = quadratic.cost(lifted(alpha), edges) < f0
        return jnp.where(ok, alpha, alpha * 0.5), k + 1, ok

    alpha, _, ok = jax.lax.while_loop(
        cond, body, (jnp.asarray(alpha0, X.dtype), jnp.array(0), jnp.array(False)))
    # If no improving step was found (flat direction), keep the zero row:
    # the re-solve at rank r+1 can still escape via its own Hessian steps.
    return lifted(jnp.where(ok, alpha, 0.0))


@dataclasses.dataclass
class StaircaseResult:
    T: jax.Array                # [n, d, d+1] rounded trajectory
    X: jax.Array                # [n, r_final, d+1]
    cost: float
    rank: int                   # rank at which the staircase stopped
    certificate: CertificateResult
    history: list               # [(rank, cost, lambda_min)]


def solve_staircase(
    meas: Measurements,
    r_min: int | None = None,
    r_max: int = 10,
    params: SolverParams | None = None,
    max_iters: int = 300,
    grad_norm_tol: float = 1e-6,
    eta: float = 1e-5,
    init: str = "chordal",
    dtype=jnp.float64,
    verbose: bool = False,
) -> StaircaseResult:
    """Certifiably correct centralized PGO: solve the rank-r relaxation,
    certify, and climb the staircase r -> r+1 on failure (SE-Sync
    Algorithm 1 on the lifted SE(d) manifold; BASELINE config #5 scope).
    """
    from ..ops import chordal as chordal_ops

    d = meas.d
    n = meas.num_poses
    r_min = d + 1 if r_min is None else r_min
    params = params or SolverParams(initial_radius=1e1, max_inner_iters=50)
    edges = edge_set_from_measurements(meas, dtype=dtype)

    if init == "chordal":
        T0 = chordal_ops.chordal_initialization(edges, n)
    elif init == "odometry":
        T0 = chordal_ops.odometry_from_edges(edges, n)
    else:
        raise ValueError(f"Unknown init {init!r}")
    from .local_pgo import lift
    X = lift(T0, lifting_matrix(r_min, d, dtype))

    history = []
    problem = make_problem(edges, n, params.precond_shift)
    for r in range(r_min, r_max + 1):
        out = solver.rtr_solve(problem, X, params, max_iters=max_iters,
                               grad_norm_tol=grad_norm_tol)
        X = out.X
        cert = certify_solution(X, edges, eta=eta, seed=r)
        history.append((r, float(out.f), cert.lambda_min))
        if verbose:
            print(f"[staircase] rank {r}: cost {float(out.f):.6f}, "
                  f"lambda_min {cert.lambda_min:.3e}, "
                  f"certified={cert.certified}")
        if cert.certified or r == r_max:
            ylift = _recover_rounding_basis(X, d)
            T = round_solution(X, ylift)
            return StaircaseResult(T=T, X=X, cost=float(out.f), rank=r,
                                   certificate=cert, history=history)
        X = escape_rank(X, cert.direction, edges)
    raise AssertionError("unreachable")


def _recover_rounding_basis(X: jax.Array, d: int) -> jax.Array:
    """Rank-r -> SE(d) rounding basis via thin SVD of the stacked rotation
    factor (SE-Sync's rounding): project onto the dominant d left singular
    directions rather than a fixed lifting matrix, since the staircase may
    have rotated the solution out of the initial lifted subspace."""
    n, r, dh = X.shape
    Y = X[..., :d].transpose(1, 0, 2).reshape(r, n * d)
    U, _, _ = jnp.linalg.svd(Y, full_matrices=False)
    return U[:, :d]
