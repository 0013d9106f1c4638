"""Library cells: solves back to back through ``rbcd.prepare_problem`` and
``rbcd.dispatch_prepared``, the calls a SLAM back-end makes.

The traffic's pool holds a few problems of one graph (fixed by the
configuration) that differ in their noise; ``--seed`` draws the order in
which the window solves them, in whole cycles, so every seed does the
same work.  Every solve starts from scratch (problem build, initial
state, solve, certificate), and all run on one compiled shape.  A cache
in the program keyed on the problem's content would read here as a gain
that users do not get; it does not count (``PERF.md``).
"""

from __future__ import annotations

import hashlib
import time

import numpy as np

from . import common


def agent_params(config: dict, traffic: dict, d: int):
    from dpgo_tpu.config import (AgentParams, RobustCostParams,
                                 RobustCostType, SolverParams)

    kw = dict(traffic.get("params", {}))
    if "solver" in kw:
        kw["solver"] = SolverParams(**kw["solver"])
    if "robust" in kw:
        rb = dict(kw["robust"])
        rb["cost_type"] = RobustCostType[rb["cost_type"]]
        kw["robust"] = RobustCostParams(**rb)
    return AgentParams(d=d, r=config["rank"], num_robots=config["agents"],
                       **kw)


def global_iterate(Xa: np.ndarray, n: int) -> np.ndarray:
    """Agent blocks [A, n_max, r, d+1] -> poses [n, r, d+1] by the
    contiguous partition the configuration states: agent a holds poses
    [a q, (a+1) q), q = n // A, the last agent the remainder."""
    A = Xa.shape[0]
    q = n // A
    owner = np.minimum(np.arange(n) // q, A - 1)
    return Xa[owner, np.arange(n) - owner * q]


class Cell:
    """One library cell's run: ``set_up``, ``window``, ``answers``."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 spans: common.Spans):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed = seed
        self.spans = spans
        self.tol_scale = 1.0   # the control loosens the gate by this factor
        self.warm = True
        self.noise_seeds = None  # readings over other noise realizations
        self.solves: list[dict] = []
        self.failures: list[str] = []

    # -- set-up ---------------------------------------------------------------

    def set_up(self) -> None:
        import jax.numpy as jnp

        cfg, tr = self.config, self.traffic
        n_out = int(round(tr.get("outlier_fraction", 0.0)
                          * cfg["loop_closures"]))
        pool = common.pool(cfg, tr["pool"], n_out,
                           noise_seeds=self.noise_seeds)
        self.problems = [p for p, _ in pool]
        self.outlier_rows = pool[0][1]
        self.meas = [common.to_measurements(p) for p in self.problems]
        self.order = np.random.default_rng(self.seed).permutation(len(pool))
        self.params = agent_params(cfg, tr, cfg["d"])
        self.dtype = jnp.float32
        # A whole solve of each problem: it passes through every program
        # the window's solves run (segments, verdicts, terminal epilogue,
        # certificate), whichever way the certificate decides.
        if self.warm:
            for k in self.order:
                self.solve_once(int(k), record=False)

    def apply_control(self) -> None:
        """The control of ``check.py``: the solve stops at a gate loosened
        by the traffic's ``control.grad_norm_tol_scale``."""
        self.tol_scale = float(self.traffic["control"]["grad_norm_tol_scale"])

    # -- the timed path ---------------------------------------------------------

    def solve_once(self, k: int, record: bool = True):
        import jax
        from dpgo_tpu.models import rbcd

        tr = self.traffic
        with self.spans("bench.build"):
            prob = rbcd.prepare_problem(self.meas[k], self.config["agents"],
                                        params=self.params, dtype=self.dtype,
                                        init=tr["init"])
        with self.spans("bench.dispatch"):
            res = rbcd.dispatch_prepared(
                prob, max_iters=tr["max_iters"],
                grad_norm_tol=tr["grad_norm_tol"] * self.tol_scale,
                eval_every=tr["eval_every"],
                verdict_every=tr["verdict_every"])
            jax.block_until_ready((res.T, res.X, res.weights))
        t_end = time.perf_counter()
        if record:
            self.solves.append(self._answer(k, res, t_end))
        return res

    def _answer(self, k: int, res, t_end: float) -> dict:
        X = np.asarray(res.X)
        cert = res.certificate
        return {
            "k": k, "t_end": t_end,
            "X": X, "T": np.asarray(res.T),
            "w": None if res.weights is None else np.asarray(res.weights),
            "key": hashlib.sha1(X.tobytes()).hexdigest(),
            "rounds": int(res.iterations),
            "terminated_by": res.terminated_by,
            "certified": None if cert is None else bool(cert.certified),
        }

    def window(self, seconds: float, tracer=None) -> dict:
        """Whole cycles over the pool in the seed's order, back to back; no
        cycle starts after ``seconds``.  With a ``tracer`` the first solve
        runs under the profiler."""
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds and not self.failures:
            for k in self.order:
                try:
                    if tracer is not None and not self.solves:
                        with tracer():
                            self.solve_once(int(k))
                    else:
                        self.solve_once(int(k))
                except Exception as e:  # a failed solve counts in ``failed``
                    self.failures.append(f"{type(e).__name__}: {e}")
                    break
        done = self.solves
        return {
            "attempted": len(done) + len(self.failures),
            "e2e": {"solve_s": (done[-1]["t_end"] - t0) / len(done)}
            if done else {},
        }

    def drop_program_state(self) -> None:
        self.meas = None

    # -- what the per-layer readers get ----------------------------------------

    def readings(self) -> dict:
        n = len(self.solves)
        return {
            "solves": n,
            "rounds": [s["rounds"] for s in self.solves],
            "traced_rounds": self.solves[0]["rounds"] if n else None,
            "terminated_by": sorted({s["terminated_by"]
                                     for s in self.solves}),
        }
